"""The benchmark's own tests run on the CPU at small sizes:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are not collected by the repository's tier-1 run (`testpaths =
tests`)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
