"""Puts the benchmark's own directory on the import path of its tests."""
import sys
from pathlib import Path

PERFBENCH = Path(__file__).resolve().parents[2] / "perfbench"
if str(PERFBENCH) not in sys.path:
    sys.path.insert(0, str(PERFBENCH))
