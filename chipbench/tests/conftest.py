"""Tests of the benchmark's own code, on the CPU:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests`` from the checkout."""
import atexit
import os
import shutil
import sys
import tempfile
from pathlib import Path

# a compile cache of the tests' own, removed when they end: entries from
# runs on other machines or chips in the checkout's cache are never read here
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _cache = tempfile.mkdtemp(prefix="chipbench_tests_cache_")
    atexit.register(shutil.rmtree, _cache, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
