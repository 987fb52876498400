"""The chip benchmark of the serving program (``BENCHMARK.json``)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def prepare_process():
    """Set up a benchmark process before JAX is imported: the compile
    cache at one fixed path inside the checkout, with eviction off (an
    entry another process left without its access-time file would
    otherwise fail every later write), and the program's ``src`` on the
    import path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
