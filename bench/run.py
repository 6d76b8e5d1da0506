#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  It exits non-zero, and prints no result, when
JAX finds no TPU or fewer chips than the cell asks for.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` a
``breakdown``), then ``checks``, each number compared beside its limit.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
