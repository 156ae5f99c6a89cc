"""Run one benchmark cell once, on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted`` and ``failed`` (rounds),
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, the numbers that decided ``correct`` beside their limits.
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

if __name__ == "__main__":
    sys.path.insert(0, BENCH_DIR)
    import harness
    sys.exit(harness.main(sys.argv[1:], T_START,
                          os.path.dirname(BENCH_DIR)))
