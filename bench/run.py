"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cells are the ``workloads`` of
``BENCHMARK.json``.  The run needs a TPU with as many chips as the cell asks
for: without one it exits 2 and prints no result.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``); the last lines of standard error are the numbers compared, each
beside its limit.
"""
import os
import sys
import time

STARTED = time.perf_counter()

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], STARTED))
