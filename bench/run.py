"""Benchmark entry point: run one cell of ``BENCHMARK.json`` once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It builds the cell's inputs from ``--seed``, warms up, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON result line last on stdout.  It exits
non-zero, with no result line, when jax finds no TPU or fewer chips
than the cell asks for.  ``--rehearse`` runs a tiny version on the CPU
(never a measurement).
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

if __name__ == "__main__":
    from harness.runner import main

    sys.exit(main(t_process=T_PROCESS))
