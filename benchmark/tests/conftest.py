"""``python -m pytest benchmark/tests`` — the benchmark's own tests; they are not
part of ``tests/``. Everything runs on the CPU at tiny widths."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
