#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload tgv_re1600.n256.1x1 \
        --seeds 11,12,13 --seconds 10

For each seed, in one process: set the cell up as a benchmark run does,
run its closed loop for ``--seconds``, and print one JSON line with the
program's compared numbers (``program``) and the same numbers with the
plain reference computed in bfloat16 put in the program's place
(``control``), each beside the configuration's limit. A limit is sound
when every program reading is below it and the control reading above it.
Benchmark runs never run the control.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(workload, seeds, seconds, require_tpu=True, root=None):
    """Yield one ``{seed, steps, program, control, limits}`` per seed."""
    import importlib

    from bench import harness

    spec = harness.load_cell(workload, root or harness.ROOT)
    config, traffic = spec["config"], spec["traffic"]
    devices = harness.devices_for(spec["cell"]["chips"], require_tpu)
    if devices[0].platform == "tpu":
        harness.use_compile_cache()
    mesh = harness.make_mesh(traffic["mesh"], devices)
    kind = importlib.import_module(f"bench.kinds.{config['kind']}")
    for seed in seeds:
        case = kind.Case(config, traffic, mesh, seed)
        case.setup()
        steps, _ = harness.closed_loop(case, seconds=seconds,
                                       in_flight=int(traffic["in_flight"]))
        host = case.collect()
        yield {"seed": seed, "steps": steps, "program": case.compare(host),
               "control": case.control(host), "limits": config["limits"]}


def main(argv=None) -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for row in readings(args.workload, seeds, args.seconds):
            print(json.dumps(row), flush=True)
    except harness.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    t0 = time.perf_counter()
    rc = main()
    print(f"control: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    sys.exit(rc)
