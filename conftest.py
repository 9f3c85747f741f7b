# Ensures `import benchmarks` and `import repro` work from pytest (adds
# repo root + src/ to sys.path).
import os
import sys

_ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
