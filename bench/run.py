#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload speed3d_r2c_f32.n512.1x1 --seed 7 \
        --seconds 10 --trace 0

``--trace 0`` prints the cell's end-to-end metrics (``step_ms``,
``setup_s``); ``--trace 1`` prints its per-layer metrics from a profiler
trace of a few steps. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each compared
number beside its limit). Without a TPU, or with fewer chips than the cell
asks for, it exits with code 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
