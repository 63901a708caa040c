"""The benchmark's own tests: run with `python3 -m pytest bench/tests`.

The repo's pytest.ini sets `testpaths = tests`, so the tier-1 run does not
collect them.  They run on the CPU, at sizes a test run can hold.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
