"""Run the benchmark harness with a fault planted in the program, for
the tests that must see ``correct`` come out false.

    python bench/tests/fault_driver.py <fault> <bench/run.py arguments>

The faults are those of ``bench/harness/faults.py``.
"""
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

if __name__ == "__main__":
    t0 = time.perf_counter()
    from harness import faults

    faults.plant(sys.argv[1])
    from harness.runner import main

    sys.exit(main(sys.argv[2:], t_process=t0))
