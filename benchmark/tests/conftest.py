"""`pytest benchmark/tests` runs on the CPU: toy sizes, the program's disk
caches off (as tests/conftest.py does for the program's own tests)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_PROGRAM_CACHE_DIR", "")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
