"""`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q` from the root
of the repo. Not part of the repo's tier-1 tests (those collect
`tests/` only); a later PR should add a tier-1 test that runs
`benchmark/run.py --dry` (PERF.md, for the tracing issue)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
