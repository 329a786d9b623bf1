"""Import paths for the benchmark's CPU tests: the program under ``src``, the
benchmark's modules and this directory."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP.parents[1] / "src", CHIP, CHIP / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
