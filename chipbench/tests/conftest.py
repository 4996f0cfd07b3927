"""The benchmark's CPU tests import its modules by their plain names, as
``run.py`` does, and the program from ``src``."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
