#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``--trace 1`` adds ``breakdown``). Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits non-zero;
``--rehearsal`` walks the same control flow on the CPU backend at tiny
widths and reports no metric value."""

import os
import sys
import time

T_PROCESS_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import harness
    sys.exit(harness.main(t_process_start=T_PROCESS_START))
