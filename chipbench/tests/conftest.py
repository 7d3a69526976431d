"""CPU tests of the chip benchmark's harness (not part of the tier-1 run).

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
