"""CPU tests of the benchmark's pieces: ``python -m pytest bench/tests``.

They run on the CPU at tiny sizes (Pallas kernels in interpret mode); the
refusal of any platform but the TPU lives in ``bench/run.py`` alone.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
