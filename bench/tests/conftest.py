"""The benchmark's tests run on the CPU at small sizes: make the harness
(the ``bench`` package) and the program (``src/``) importable."""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
