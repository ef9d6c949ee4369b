#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the TPU chips this machine holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result line last (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and ``checks``:
each number compared beside its limit). Exits 2, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.

``--control`` puts the control (the reference in the next narrower
precision) in the program's place in the comparison, so that the result
reads ``correct: false`` (see PERF.md on how each limit was set).
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    sys.exit(harness.main(t_start=T_START))
