import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHIP.parent.parent / "src"), str(CHIP), str(CHIP / "tests")]
