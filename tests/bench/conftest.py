"""The benchmark's own tests: CPU only, small sizes."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, os.path.join(ROOT, "src"), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
