"""The benchmark's own tests.  They run on the CPU and are not among the
repository's tier-1 tests (``testpaths`` is ``tests``); run them with

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import atexit
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the CPU runs compile into a cache of their own, removed at exit, so the
# checkout's cache holds only what the chip compiled
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _CACHE = tempfile.mkdtemp(prefix="chipbench-tests-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE
    atexit.register(shutil.rmtree, _CACHE, ignore_errors=True)
