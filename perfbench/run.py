"""Run one cell of the benchmark once and print its result line.

    python3 perfbench/run.py --workload yi-6b.decode --seed 7 \
        --seconds 51 --trace 0

The cells, configurations, mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line on standard
output is one JSON object; the numbers compared to decide ``correct`` are
the last lines on standard error.  Exits non-zero, printing no result,
where JAX finds no accelerator or fewer chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

from pb import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
