"""CPU rehearsals of the chip benchmark: ``python -m pytest bench/tests``.

The benchmark's own modules live in ``bench/`` and import each other by
plain name, as ``bench/run.py`` runs them.  Tests run on the CPU with the
Pallas kernels interpreted; no test here measures a time.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
