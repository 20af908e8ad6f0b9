"""Shared launch shim for the scripts in this directory.

`python scripts/x.py` puts scripts/ (not the repo root) on sys.path, so
each script's first import is `import _bootstrap`, which inserts the
repo root so `paddle_tpu` resolves regardless of cwd. To keep a script
off the chip, set JAX_PLATFORMS=cpu in its environment.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
