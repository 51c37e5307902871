"""CPU tests of the benchmark's own code.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# compiled CPU programs go to a scratch cache, not the checkout's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", tempfile.mkdtemp(prefix="bench-test-cache-"))
