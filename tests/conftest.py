import importlib.util
import os
import sys

# The benchmark regression tests import the `benchmarks` namespace package
# from the repo root (tests usually run with only PYTHONPATH=src).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Keep tests on the single real device (the dry-run sets 512 host devices
# ONLY inside repro.launch.dryrun, never here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# The property-based tests import `hypothesis`; the container may not ship
# it (tier-1 must not pip install).  Fall back to the deterministic shim so
# those modules still collect AND run — see tests/_hypothesis_shim.py and
# requirements-dev.txt.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    _spec = importlib.util.spec_from_file_location(
        "hypothesis",
        os.path.join(os.path.dirname(__file__), "_hypothesis_shim.py"))
    _shim = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_shim)
    sys.modules["hypothesis"] = _shim
    sys.modules["hypothesis.strategies"] = _shim.strategies
