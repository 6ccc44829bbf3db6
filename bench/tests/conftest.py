"""The benchmark's own tests: ``python -m pytest bench/tests -q`` (not part
of the repo's tier-1 run).  ``bench/`` is no package (``bench.py`` owns the
name), so its directory goes on the path as ``run.py`` puts it there."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
