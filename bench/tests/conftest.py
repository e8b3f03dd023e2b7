"""The benchmark's modules are plain files under bench/, imported by
name, as bench/run.py imports them."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.append(BENCH)
