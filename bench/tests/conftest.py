import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_BENCH, os.path.join(os.path.dirname(_BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
