"""PDT004 — observability-catalog drift.

Repo law (PR 2/5): docs/observability.md is the catalog of record —
its metric table must equal the set of registered ``pdt_*``
instruments, and every span/event name the code emits must appear in
its trace-model section. Formerly a regex-plus-import scan in
tests/test_observability_slo.py that only covered the metric table;
the AST pass needs no imports (so it also covers modules the old
test's import list forgot) and extends to span/event names — which
immediately caught four undocumented ``checkpoint.*`` events.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Set, Tuple

from .._astutil import call_name, import_aliases, literal_str
from ..core import Checker, Finding, Project

__all__ = ["CatalogDriftChecker", "collect_instruments",
           "collect_span_events", "documented_metrics"]

_METRIC_ROW_RE = re.compile(r"`(pdt_[a-z_]*[a-z])`")
_BACKTICK_NAME_RE = re.compile(r"`([a-z_]+\.[a-z_]+)`")
# backticked dotted tokens that are filenames/artifacts, not trace names
_NON_TRACE_SUFFIXES = {"py", "md", "json", "jsonl", "prom", "txt",
                       "cc", "log", "tmp", "hb"}

_REGISTRATION_TAILS = ("counter", "gauge", "histogram")
_SPAN_TAILS = ("span", "event", "interval", "telemetry_span",
               "telemetry_event")


def collect_instruments(project: Project, scope, exclude,
                        ) -> Dict[str, List[Tuple[str, ast.Call]]]:
    """Literal ``pdt_*`` names passed to counter()/gauge()/histogram()
    registrations, mapped to their call sites."""
    out: Dict[str, List[Tuple[str, ast.Call]]] = {}
    for sf in project.match(scope, exclude=exclude):
        if sf.tree is None:
            continue
        aliases = import_aliases(sf.tree)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node, aliases)
            if name is None \
                    or name.split(".")[-1] not in _REGISTRATION_TAILS:
                continue
            lit = literal_str(node.args[0]) if node.args else None
            if lit is not None and lit.startswith("pdt_"):
                out.setdefault(lit, []).append((sf.relpath, node))
    return out


def collect_span_events(project: Project, scope, exclude,
                        ) -> Dict[str, List[Tuple[str, ast.Call]]]:
    """Literal dotted span/event/trace-root names the code emits."""
    out: Dict[str, List[Tuple[str, ast.Call]]] = {}

    def add(lit, sf, node):
        if lit is not None and re.fullmatch(r"[a-z_]+\.[a-z_]+", lit):
            out.setdefault(lit, []).append((sf.relpath, node))

    for sf in project.match(scope, exclude=exclude):
        if sf.tree is None:
            continue
        aliases = import_aliases(sf.tree)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node, aliases)
            if name is None:
                continue
            tail = name.split(".")[-1]
            if tail in _SPAN_TAILS:
                add(literal_str(node.args[0]) if node.args else None,
                    sf, node)
            elif tail == "start_trace":
                kw = next((k.value for k in node.keywords
                           if k.arg == "name"), None)
                add(literal_str(kw), sf, node)
    return out


def documented_metrics(doc_text: str) -> Set[str]:
    """``pdt_*`` names in the metric-catalog table rows."""
    out: Set[str] = set()
    for ln in doc_text.splitlines():
        if ln.lstrip().startswith("|"):
            out |= set(_METRIC_ROW_RE.findall(ln))
    return out


class CatalogDriftChecker(Checker):
    code = "PDT004"
    name = "catalog-drift"
    rationale = ("docs/observability.md is the catalog of record for "
                 "pdt_* instruments and span/event names (PR 2/5)")

    DEFAULT_SCOPE = ("paddle_tpu/*.py", "paddle_tpu/**/*.py")
    # the substrate defines counter()/gauge()/histogram() and uses
    # docstring examples; it registers nothing of its own
    DEFAULT_EXCLUDE = ("paddle_tpu/observability/registry.py",
                       "paddle_tpu/analysis/*.py",
                       "paddle_tpu/analysis/**/*.py")
    DEFAULT_DOC = "docs/observability.md"

    def __init__(self, scope=DEFAULT_SCOPE, exclude=DEFAULT_EXCLUDE,
                 doc=DEFAULT_DOC):
        self.scope = scope
        self.exclude = exclude
        self.doc = doc

    def _doc_finding(self, doc_text: str, needle: str,
                     message: str, detail: str) -> Finding:
        line = 0
        for i, ln in enumerate(doc_text.splitlines(), start=1):
            if needle in ln:
                line = i
                break
        return Finding(self.code, self.doc, line, message,
                       symbol="<doc>", detail=detail, checker=self.name)

    def check(self, project: Project) -> Iterable[Finding]:
        doc_text = project.read_text(self.doc)
        if doc_text is None:
            yield Finding(self.code, self.doc, 0,
                          f"{self.doc} is missing — the observability "
                          "catalog of record must exist",
                          detail="missing-doc", checker=self.name)
            return
        # -- metric table vs registrations ------------------------------
        registered = collect_instruments(project, self.scope,
                                         self.exclude)
        documented = documented_metrics(doc_text)
        for name in sorted(set(registered) - documented):
            path, node = registered[name][0]
            sf = project.file(path)
            yield self.finding(
                sf, node,
                f"instrument \"{name}\" is registered but has no row "
                f"in the {self.doc} metric catalog — add one",
                detail=name, project=project)
        for name in sorted(documented - set(registered)):
            yield self._doc_finding(
                doc_text, name,
                f"metric-catalog row \"{name}\" matches no registered "
                "instrument — remove the row or restore the metric",
                detail=name)
        # -- span/event names vs the trace-model prose -------------------
        emitted = collect_span_events(project, self.scope, self.exclude)
        for name in sorted(emitted):
            if name not in doc_text:
                path, node = emitted[name][0]
                sf = project.file(path)
                yield self.finding(
                    sf, node,
                    f"span/event \"{name}\" is emitted but not named "
                    f"in {self.doc} — the trace model section lists "
                    "every instrumented span and point event",
                    detail=name, project=project)
        prefixes = {n.split(".")[0] for n in emitted}
        fault_sites = self._fault_sites(project)
        for name in sorted(set(_BACKTICK_NAME_RE.findall(doc_text))):
            head, tail = name.split(".", 1)
            if head not in prefixes or tail in _NON_TRACE_SUFFIXES:
                continue                 # not a trace-name reference
            if name in emitted or name in fault_sites:
                continue
            yield self._doc_finding(
                doc_text, f"`{name}`",
                f"documented span/event \"{name}\" is never emitted — "
                "remove the doc reference or restore the "
                "instrumentation",
                detail=name)

    def _fault_sites(self, project: Project) -> Set[str]:
        # fault sites share the dotted namespace (`transfer.serialize`
        # is both a span and a site); the doc may reference either
        from .faultsites import FaultSiteDriftChecker, collect_doc_sites
        return collect_doc_sites(
            project, FaultSiteDriftChecker.DEFAULT_FAULTS_FILE)
