"""The benchmark's own tests: CPU only, tiny sizes, under a minute in all.

Run with ``python -m pytest benchmark/tests -q`` from the root of the repo.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
