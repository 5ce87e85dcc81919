import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
# the Pallas kernel runs in the interpreter off the chip
os.environ.setdefault("REPRO_KERNEL_MODE", "interpret")
