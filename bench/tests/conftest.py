# The benchmark's CPU tests import ``bench`` from the checkout root and the
# program from ``src``; multi-device cases run in a child process with their
# own XLA_FLAGS (never set here).
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
