"""Shared set-up for the benchmark's own tests: its modules on the path,
and the CPU in place of the chip."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(BENCH, "lib"), BENCH]
