"""Test env: 8 virtual CPU devices (SURVEY.md §4 fake-backend strategy).

≙ the reference's fake custom_cpu device plugin («test/custom_runtime/»):
every parallelism test must pass on this fake 8-device mesh. Set
PDT_TEST_PLATFORM=tpu to run the suite natively on the attached chip
instead (distributed >1-device tests will skip there).

XLA_FLAGS must be set before the (lazy) CPU client is created, which is
why this happens at conftest import and not in a fixture.
"""
import os

if os.environ.get("PDT_TEST_PLATFORM", "cpu") == "cpu":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_ENABLE_X64", "0")
    import jax

    jax.config.update("jax_platforms", "cpu")
else:
    import jax

# this jaxlib's CPU matmul defaults to fast (bf16-ish) passes; tests compare
# against NumPy, so force exact fp32 matmuls in the test env only
jax.config.update("jax_default_matmul_precision", "highest")


# -- test tiers (SURVEY.md §4 CI plumbing; VERDICT r3 #9) --------------
# Default run = the FAST tier (target < 10 min on the 8-dev CPU mesh).
# Heavy tests carry @pytest.mark.slow (module-level pytestmark in the
# heavy files) and run only with PDT_RUN_SLOW=1 or `-m slow` /
# `--run-slow`. `pytest tests/` stays the quick regression gate;
# `PDT_RUN_SLOW=1 pytest tests/` is the full tier.
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--run-slow", action="store_true", default=False,
                     help="include the slow tier")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy tier (HF parity, multi-process, "
        "e2e recipes) — run with --run-slow / PDT_RUN_SLOW=1")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests "
        "(utils.faults) — CPU-mesh fast tier, runs in tier-1")
    config.addinivalue_line(
        "markers", "telemetry: observability-subsystem tests "
        "(paddle_tpu.observability) — CPU-mesh fast tier, runs in "
        "tier-1")


# serving/chaos/telemetry suites run with telemetry RECORDING on, each
# test from a zeroed registry/ring, so (a) the instrumentation paths are
# exercised by the whole engine suite for free and (b) a failing test's
# report carries a telemetry snapshot for post-mortem debugging
_TELEMETRY_FILES = ("test_serving.py", "test_chaos.py",
                    "test_telemetry.py", "test_elastic_robustness.py",
                    "test_router.py", "test_observability_slo.py",
                    "test_ragged_attention.py", "test_disagg.py",
                    "test_spec_decode.py", "test_admission.py",
                    "test_loadgen.py", "test_tp_serving.py",
                    "test_journal.py", "test_sentry.py",
                    "test_quant_serving.py", "test_autoscaler.py",
                    "test_multimodel.py", "test_async_pipeline.py",
                    "test_profile.py")

# failing fleet-drill tests additionally attach a Chrome-trace export
# of the telemetry ring: the failover timeline that produced the
# failure is then directly loadable in chrome://tracing / Perfetto
_CHROME_TRACE_FILES = ("test_chaos.py", "test_router.py")

# failing perf-sensitive tests additionally attach the performance-
# attribution report (span self-time waterfall + compile table +
# memory ledger): a hang or throughput collapse then arrives with its
# own waterfall instead of needing a rerun under a profiler
_PROFILE_REPORT_FILES = ("test_async_pipeline.py", "test_tp_serving.py",
                         "test_quant_serving.py", "test_profile.py")


@pytest.fixture(autouse=True)
def _telemetry_enabled(request, monkeypatch):
    if os.path.basename(str(request.fspath)) in _TELEMETRY_FILES:
        import paddle_tpu.observability as telemetry
        monkeypatch.setenv("PDT_TELEMETRY", "1")
        telemetry.reset()
        telemetry.clear_events()
    yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed:
        base = os.path.basename(str(item.fspath))
        if base in _TELEMETRY_FILES:
            try:
                import json
                import paddle_tpu.observability as telemetry
                rep.sections.append(
                    ("telemetry snapshot",
                     json.dumps(telemetry.snapshot(), indent=1,
                                sort_keys=True, default=str)))
            except Exception:
                pass    # a broken dump must never mask the real failure
        if base in _CHROME_TRACE_FILES:
            try:
                import json
                import paddle_tpu.observability as telemetry
                rep.sections.append(
                    ("chrome trace (save as .json, load in "
                     "chrome://tracing or ui.perfetto.dev)",
                     json.dumps(telemetry.export_chrome_trace(),
                                default=str)))
            except Exception:
                pass
        if base in _PROFILE_REPORT_FILES:
            try:
                from paddle_tpu.observability import profile
                rep.sections.append(
                    ("profile report", profile.snapshot_report()))
            except Exception:
                pass


@pytest.fixture(autouse=True)
def _serving_invariant_checks(request, monkeypatch):
    """Every serving/chaos test runs with the engine invariant checker
    on: page-accounting violations surface as EngineInvariantError in
    whatever test created them, for free."""
    if os.path.basename(str(request.fspath)) in (
            "test_serving.py", "test_chaos.py", "test_router.py",
            "test_ragged_attention.py", "test_disagg.py",
            "test_spec_decode.py", "test_admission.py",
            "test_loadgen.py", "test_tp_serving.py",
            "test_journal.py", "test_sentry.py",
            "test_quant_serving.py", "test_autoscaler.py",
            "test_multimodel.py", "test_async_pipeline.py",
            "test_profile.py"):
        monkeypatch.setenv("PDT_CHECK_INVARIANTS", "1")
    yield


def pytest_collection_modifyitems(config, items):
    if (config.getoption("--run-slow")
            or os.environ.get("PDT_RUN_SLOW") == "1"
            or "slow" in config.getoption("-m", "")):
        return
    skip = pytest.mark.skip(
        reason="slow tier: enable with --run-slow or PDT_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
