"""The one harness loop: a cell of ``BENCHMARK.json`` from set-up to result.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name:

* ``configs[].file`` — the configuration; its ``kind`` names the module
  ``bench/kinds/<kind>.py`` that runs it;
* ``bench/traffic/<traffic>.json`` — grid size ``n``, pencil mesh,
  the closed loop's ``in_flight`` depth and the traced run's ``trace_steps``;
* ``bench/metrics/<name>.py`` — one reader per per-layer metric;
* ``bench/optables/<class>/*.json`` — the op tables of the trace reduction;
* ``bench/peaks.json`` — peaks per ``device_kind``.

A run: check the devices, build and warm the cell (``setup_s`` ends at the
first timed step), then either time a closed loop for ``--seconds``
(``step_ms`` = window ÷ steps, the window ending in ``block_until_ready``)
or trace ``trace_steps`` steps for the per-layer metrics; then read the
memory peak, free the program's state and compare with the reference.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, traffic and
    metric entries, all read from files named in ``BENCHMARK.json``."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    mesh = tuple(traffic["mesh"])
    if math.prod(mesh) != cell["chips"]:
        raise SystemExit(f"{workload}: mesh {mesh} is not {cell['chips']} chips")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
            "per_layer": [m for m in manifest["per_layer"] if applies(m)]}


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX has {len(devices)}")
    return devices


def use_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def make_mesh(shape, devices):
    import numpy as np
    from jax.sharding import Mesh

    pu, pv = shape
    return Mesh(np.array(devices[:pu * pv]).reshape(pu, pv), ("data", "model"))


def closed_loop(case, *, seconds=None, steps=None, in_flight=2, spans=False):
    """Dispatch steps back to back, keeping at most ``in_flight`` in flight;
    stop after ``seconds`` or ``steps``; end in ``block_until_ready``.
    Returns ``(steps, seconds)`` of the window."""
    import jax

    span = jax.profiler.TraceAnnotation if spans else (
        lambda _name: contextlib.nullcontext())
    pending = collections.deque()
    n = 0
    t0 = time.perf_counter()
    while True:
        with span("bench.dispatch"):
            pending.append(case.step())
        n += 1
        if len(pending) > in_flight:
            with span("bench.wait"):
                jax.block_until_ready(pending.popleft())
        if steps is not None and n >= steps:
            break
        if seconds is not None and time.perf_counter() - t0 >= seconds:
            break
    with span("bench.wait"):
        jax.block_until_ready(list(pending))
    return n, time.perf_counter() - t0


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, root: Path = ROOT) -> dict:
    """One run of a cell of ``root/BENCHMARK.json``; returns the result."""
    import jax

    spec = load_cell(workload, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    devices = devices_for(cell["chips"], require_tpu)
    if devices[0].platform == "tpu":
        use_compile_cache()
    mesh = make_mesh(traffic["mesh"], devices)
    used = list(mesh.devices.flat)
    kind = importlib.import_module(f"bench.kinds.{config['kind']}")
    case = kind.Case(config, traffic, mesh, seed)
    case.setup()
    in_flight = int(traffic["in_flight"])
    metrics, result_device, breakdown = {}, {}, None
    if not trace:
        setup_s = time.perf_counter() - t_start
        steps, window = closed_loop(case, seconds=seconds, in_flight=in_flight)
        values = {"step_ms": 1e3 * window / steps, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from bench import tracing, work

        steps = int(traffic["trace_steps"])

        def window():
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                return closed_loop(case, steps=steps, in_flight=in_flight,
                                   spans=True)

        _, cap = tracing.capture(window)
        scopes = {}
        for fn, args in case.programs():
            text = fn.lower(*args).compile().as_text()
            scopes[tracing.module_name(text)] = tracing.op_names(text)
        peaks_doc = json.loads((BENCH / "peaks.json").read_text())
        kind_name = devices[0].device_kind
        if kind_name not in peaks_doc["devices"]:
            raise KeyError(f"no peaks for device_kind {kind_name!r} in "
                           f"bench/peaks.json")
        fft_work = work.fft_step_work(traffic["n"], config["fft_per_step"],
                                      len(used), config["dtype"])
        summary = tracing.reduce(cap, [d.id for d in used], steps,
                                 tracing.load_tables(), scopes, fft_work,
                                 peaks_doc["devices"][kind_name])
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(summary)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result_device = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        breakdown = tracing.breakdown(summary)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(used),
              **result_device}
    values = case.compare(case.collect())
    checks = {k: (v, config["limits"][k]) for k, v in values.items()}
    failed = sum(1 for v, lim in checks.values() if not v <= lim)
    result = {"correct": failed == 0, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     t_start)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


def emit(result: dict) -> None:
    """Compared numbers as the last lines of stderr; the result as the last
    line of stdout."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
