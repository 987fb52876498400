"""The benchmark's own tests run on the CPU at smoke sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They sit outside the repository's test paths.  The checkout's root and
``src`` go on the import path here, as ``bench/run.py`` puts them."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
