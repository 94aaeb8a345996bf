"""The benchmark's own tests run on the CPU, at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

from the root of the repository (which puts `bench` and `src` on the path)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

FIXTURE = Path(__file__).resolve().parent / "fixture"
