"""Benchmark suite — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Analytic (model-derived) rows
report ``us_per_call=0``; measured rows time real executions on this host.
``--json PATH`` additionally writes the machine-readable
``{"schema": "bench-fft/v2", "meta": {...}, "rows": [{name, us_per_call,
p50_us, p95_us, model_predicted_us, model_err, config}]}`` document that
CI uploads as the perf-trajectory artifact (measured rows carry the tail
percentiles and the perf model's prediction; ``meta`` pins the substrate
and active calibration). ``--trace PATH`` writes a Chrome-trace JSON of
the run — auto-derived as ``<json>.trace.json`` when ``--json`` is given;
``--trace ''`` disables.

    PYTHONPATH=src python -m benchmarks.run [--only a,b,c] [--json BENCH_fft.json]
    PYTHONPATH=src python -m benchmarks.run --only solvers,serving \\
        --json BENCH_fft.json --trace bench.trace.json

``--list`` prints the known ``--only`` workload names (one per line) and
exits — the discovery aid for the exit-2 unknown-name path.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

_ROWS: list[dict] = []


def _row(name, us, derived, config=None, stats=None, model_us=None):
    print(f"{name},{us:.3f},{derived}")
    if config is None:
        config = {"derived": derived} if derived != "" else {}
    row = {"name": name, "us_per_call": round(us, 3), "config": config}
    if stats is not None:
        row["p50_us"] = round(stats["p50_us"], 3)
        row["p95_us"] = round(stats["p95_us"], 3)
    if model_us is not None and model_us > 0 and us > 0:
        # signed relative model error: measured/predicted − 1. The absolute
        # prediction is nominal-substrate seconds, so the gate in compare.py
        # tracks the *drift* of this error vs a baseline, not its size.
        row["model_predicted_us"] = round(model_us, 3)
        row["model_err"] = round(us / model_us - 1.0, 4)
    _ROWS.append(row)


# ---------------------------------------------------------------------------
# Table 4.1 / 4.2 — architecture comparison (normalized units)
# ---------------------------------------------------------------------------

def bench_table_4_1():
    from repro.core import perfmodel as pm
    for mu in (1, 3):
        t = pm.table_4_1(mu)
        for k, v in t.items():
            _row(f"table4.1/mu{mu}/{k}/T_tot", 0.0, v["T_tot"])
            _row(f"table4.1/mu{mu}/{k}/B", 0.0, v["B"])
            _row(f"table4.1/mu{mu}/{k}/M", 0.0, v["M"])


def bench_table_4_2():
    from repro.core import perfmodel as pm
    for mu in (1, 3, 4):
        t = pm.table_4_2(mu)
        for k, v in t.items():
            _row(f"table4.2/mu{mu}/{k}/T_tot", 0.0, v["T_tot"])
            _row(f"table4.2/mu{mu}/{k}/B", 0.0, v["B"])


# ---------------------------------------------------------------------------
# Tables 5.2 / 5.4 / 5.6 — FFT engine characterization
# ---------------------------------------------------------------------------

_ENGINE_POINTS = [  # (r, n, l_op, f_mhz) — f_max values reported by the thesis
    (1, 512, 3, 250), (1, 1024, 3, 247), (1, 2048, 3, 251), (1, 4096, 3, 244),
    (1, 8192, 3, 236), (1, 2048, 9, 379),
    (2, 512, 3, 238), (2, 2048, 6, 345), (2, 8192, 9, 377),
    (4, 512, 3, 226), (4, 2048, 9, 376), (4, 4096, 9, 378),
]


def bench_engine_tables():
    from repro.core.perfmodel import EnginePoint
    for r, n, lop, f in _ENGINE_POINTS:
        pt = EnginePoint(n=n, r=r, l_op=lop, f_mhz=f)
        tbl = {1: "5.2", 2: "5.4", 4: "5.6"}[r]
        base = f"table{tbl}/R{r}/N{n}/lop{lop}"
        _row(base + "/latency_cycles", 0.0, pt.latency_cycles)
        _row(base + "/T_FFT_us", 0.0, round(pt.t_fft_us, 3))
        _row(base + "/B_FFT_GiBs", 0.0, round(pt.b_fft_gib_s, 2))
        _row(base + "/GFLOPS", 0.0, round(pt.gflops, 2))


# ---------------------------------------------------------------------------
# Table 5.7 — global 3D FFT expected times ; Table 5.8 — Xeon Phi baseline
# ---------------------------------------------------------------------------

def bench_global_fft():
    from repro.core import perfmodel as pm
    for mu in (1, 3):
        t = pm.table_5_7(mu=mu)
        for n, row in t.items():
            for p, v in row.items():
                _row(f"table5.7/mu{mu}/N{n}/P{p}", 0.0,
                     "oom" if v is None else round(v, 6))
    # Table 5.8 — measured Marconi (Xeon Phi) baselines from the thesis, the
    # strong-scaling comparison the paper draws in §5.6
    xeon = {(1024, 8): 1.20, (1024, 16): 0.67, (1024, 32): 1.61,
            (1024, 64): 0.29, (1024, 128): 0.18, (2048, 16): 48.2,
            (2048, 32): 3.75, (2048, 64): 2.26, (2048, 128): 4.90,
            (2048, 256): 0.74, (2048, 512): 0.41}
    for (n, p), v in sorted(xeon.items()):
        ours = pm.global_fft_time(n, min(p, 1024), mu=1)
        _row(f"table5.8/N{n}/P{p}/xeonphi_s", 0.0, v)
        _row(f"table5.8/N{n}/P{p}/fpga_model_speedup", 0.0, round(v / ours, 1))


# ---------------------------------------------------------------------------
# Figs 5.11 / 5.12 — network required-bandwidth curves
# ---------------------------------------------------------------------------

def bench_network_bw():
    from repro.core import topology as topo
    for topol in ("switched", "torus"):
        fig = "fig5.11" if topol == "switched" else "fig5.12"
        curves = topo.bandwidth_curves(topol)
        for (r, f), pts in sorted(curves.items()):
            for q, bw in pts:
                if q in (2, 4, 8, 16, 32):
                    _row(f"{fig}/{topol}/R{r}/f{int(f)}/sqP{q}_Gbps",
                         0.0, round(bw, 1))
    s = topo.scalability_summary(200.0)
    for (t, r, f), p in sorted(s.items()):
        _row(f"scalability/{t}/R{r}/f{int(f)}/maxP_at_200G", 0.0, p)


# ---------------------------------------------------------------------------
# Fig 1.1 — required RAM per node
# ---------------------------------------------------------------------------

def bench_fig_1_1():
    from repro.core.perfmodel import required_ram_per_node
    for n in (256, 512, 1024, 2048, 4096, 8192):
        for p in (1, 64, 1024):
            _row(f"fig1.1/N{n}/P{p}_GiB", 0.0,
                 round(required_ram_per_node(n, p) / 2 ** 30, 3))


# ---------------------------------------------------------------------------
# Measured: single-host FFT wallclock (engine vs oracle backends)
# ---------------------------------------------------------------------------

def _time(fn, *a, iters=5):
    from repro.tuning.timing import time_us
    return time_us(fn, *a, iters=iters)


def _stats(fn, *a, iters=5):
    from repro.tuning.timing import time_stats
    return time_stats(fn, *a, iters=iters)


def bench_fft_wallclock():
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    for n in (256, 1024):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, n), jnp.float32)
        xi = jnp.zeros_like(x)
        for backend in ("jnp", "ref", "pallas"):
            st = _stats(lambda a, b: kops.fft1d(a, b, backend=backend), x, xi)
            _row(f"fft1d_wallclock/{backend}/B64xN{n}", st["mean_us"], "",
                 stats=st)
    from repro.core import perfmodel as pm
    from repro.core.decomposition import PencilGrid
    from repro.core.fft3d import FFT3DPlan, fft3d_local
    for n in (32, 64):
        grid = PencilGrid(pu=1, pv=1, u_axes=(), v_axes=())
        plan = FFT3DPlan(n=(n, n, n), grid=grid, backend="jnp")
        x = jax.random.normal(jax.random.PRNGKey(1), (n, n, n), jnp.float32)
        xi = jnp.zeros_like(x)
        f = jax.jit(functools.partial(fft3d_local, plan))
        st = _stats(f, x, xi)
        model = pm.estimate_plan_seconds((n, n, n), 1, 1,
                                         spec=plan.spec()) * 1e6
        _row(f"fft3d_wallclock/jnp/N{n}", st["mean_us"], "", stats=st,
             model_us=model)
        z = np.random.randn(n, n, n).astype(np.complex64)
        t0 = time.time()
        for _ in range(5):
            np.fft.fftn(z)
        _row(f"fft3d_wallclock/numpy/N{n}", (time.time() - t0) / 5 * 1e6, "")


# ---------------------------------------------------------------------------
# Measured: distributed 3D FFT per TransposeEngine (the engine axis of the
# plan space — fft_overlap_ring rows are the perf trajectory of the fused
# compute/communication ring vs the serial fabrics)
# ---------------------------------------------------------------------------

def bench_fft_engines(n: int = 16):
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.core.comm import ENGINE_NAMES
    from repro.core.engine_spec import EngineSpec
    from repro.core.fft3d import make_fft3d

    ndev = len(jax.devices())
    rng = np.random.RandomState(0)
    xr = jnp.asarray(rng.randn(n, n, n).astype(np.float32))
    xi = jnp.zeros_like(xr)

    from repro.core import perfmodel as pm

    def _sweep(mesh, mesh_tag, u_axes, v_axes):
        for engine in ENGINE_NAMES:
            fwd, inv, plan = make_fft3d(mesh, (n, n, n),
                                        spec=EngineSpec(engine=engine),
                                        u_axes=u_axes, v_axes=v_axes)
            cfg = {"comm_engine": engine, "net": plan.net, "n": n,
                   "mesh": mesh_tag, "backend": plan.backend}
            g = plan.grid
            model = pm.estimate_plan_seconds(
                (n, n, n), g.pu, g.pv, spec=plan.spec(),
                pu_axes=g.u_sizes, pv_axes=g.v_sizes) * 1e6
            st = _stats(fwd, xr, xi)
            _row(f"fft_{engine}/N{n}/mesh{mesh_tag}/fwd", st["mean_us"], "",
                 config=cfg, stats=st, model_us=model)
            kr, ki = fwd(xr, xi)
            st = _stats(inv, kr, ki)
            _row(f"fft_{engine}/N{n}/mesh{mesh_tag}/inv", st["mean_us"], "",
                 config=cfg, stats=st, model_us=model)

    pu, pv = (4, 2) if ndev >= 8 else ((2, 1) if ndev >= 2 else (1, 1))
    mesh = compat.make_mesh((pu, pv), ("data", "model"))
    _sweep(mesh, f"{pu}x{pv}", ("data",), ("model",))
    if ndev >= 8:
        # multi-axis pencil: the u grid dimension spans two mesh axes, so
        # the ring engines run one staged per-axis ring per mesh axis
        mesh3 = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
        _sweep(mesh3, "2x2x2", ("pod", "data"), ("model",))


# ---------------------------------------------------------------------------
# Measured: per-solver step latency (the repro.solvers workloads — each row
# is one full FFT→spectral→iFFT→local cycle on the largest pencil mesh the
# host's devices allow)
# ---------------------------------------------------------------------------

def _solver_model_us(solver) -> float:
    """The perf model's time for one step of ``solver``'s plan (µs):
    diagonal-kernel solvers price the spectral roundtrip of their plan
    (fused when the plan streams it), the others the same roundtrip
    composed. A nominal-substrate estimate; the drift gate in
    ``compare.py`` tracks its error against a baseline."""
    from repro.core import perfmodel as pm
    from repro.solvers.base import SpectralSolver

    g = solver.plan.grid
    diagonal = (type(solver).spectral_kernel
                is not SpectralSolver.spectral_kernel)
    est = pm.estimate_roundtrip_seconds(
        solver.n, g.pu, g.pv, spec=solver.plan.spec(),
        fused=solver.plan.fused_roundtrip and diagonal,
        mu=max(solver.components, 1), pu_axes=g.u_sizes, pv_axes=g.v_sizes)
    return est * 1e6


def bench_solvers(n: int = 16):
    import jax

    from repro import compat
    from repro.solvers import SOLVERS, make_solver
    from repro.solvers.base import SpectralSolver

    ndev = len(jax.devices())
    pu, pv = (4, 2) if ndev >= 8 else ((2, 1) if ndev >= 2 else (1, 1))
    mesh = compat.make_mesh((pu, pv), ("data", "model"))
    # float32: benches run without x64 and timing doesn't need f64 validation
    for case in sorted(SOLVERS):
        solver = make_solver(case, mesh, (n, n, n), dtype="float32")
        state = solver.init_state()
        # time the jitted step directly: the benchmark number stays free of
        # the dispatch-span bookkeeping solver.step() adds under --trace
        st = _stats(solver._stepj, state.fields, iters=3)
        _row(f"solver_{case}/N{n}/mesh{pu}x{pv}/us_per_step", st["mean_us"],
             "", config={"case": case, "n": n, "mesh": f"{pu}x{pv}",
                         **solver.plan_config()},
             stats=st, model_us=_solver_model_us(solver))
        if SOLVERS[case].spectral_kernel is SpectralSolver.spectral_kernel:
            continue  # no diagonal spectral kernel — nothing to fuse
        fused = make_solver(case, mesh, (n, n, n), dtype="float32",
                            plan_cfg={"fused_roundtrip": True})
        fstate = fused.init_state()
        st = _stats(fused._stepj, fstate.fields, iters=3)
        _row(f"solver_{case}_fused/N{n}/mesh{pu}x{pv}/us_per_step",
             st["mean_us"], "",
             config={"case": case, "n": n, "mesh": f"{pu}x{pv}",
                     **fused.plan_config()},
             stats=st, model_us=_solver_model_us(fused))


# ---------------------------------------------------------------------------
# Measured: batched solver serving (requests/s + latency tails under a burst
# load — the repro.serving layer's rows on the perf trajectory)
# ---------------------------------------------------------------------------

def bench_serving(n: int = 16, n_requests: int = 8, steps: int = 2):
    """Load-generate against an in-process SimServer at two batch limits.

    Burst-submits ``n_requests`` same-fingerprint heat requests and drains;
    ``max_batch=1`` is the no-batching baseline, ``max_batch=4`` the batched
    path (⌈8/4⌉ = 2 sharded steps per Δt instead of 8). Rows carry the mean
    request latency as ``us_per_call`` with p50/p95 (row schema) and p99
    (serving extra) tails, plus a lower-is-better ``us_per_request``
    throughput row (``requests_per_s`` in its config). A compile warm-up
    run per batch limit keeps XLA compilation off the latency rows — the
    registry keeps engines hot, which is the layer's whole point.
    """
    import jax

    from repro import compat
    from repro.serving import SimRequest, SimServer, run_load

    ndev = len(jax.devices())
    pu, pv = (4, 2) if ndev >= 8 else ((2, 1) if ndev >= 2 else (1, 1))
    mesh = compat.make_mesh((pu, pv), ("data", "model"))
    case = "heat"

    def make_requests():
        return [SimRequest(case=case, n=n, steps=steps, dtype="float32",
                           scale=1.0 + 0.25 * i, request_id=f"req-{i}")
                for i in range(n_requests)]

    for max_batch in (1, 4):
        server = SimServer(mesh, max_batch=max_batch, use_plan_cache=False)
        run_load(server, make_requests())        # compile warm-up, untimed
        report = run_load(server, make_requests())
        st = report.stats()
        assert st["n_failed"] == 0, report.results
        cfg = {"case": case, "n": n, "mesh": f"{pu}x{pv}",
               "steps": steps, "requests": st["n_requests"],
               "max_batch": max_batch,
               "requests_per_s": st["requests_per_s"]}
        base = f"serving_{case}/N{n}/mesh{pu}x{pv}/b{max_batch}"
        _row(f"{base}/latency", st["mean_us"], "", config=cfg,
             stats={"p50_us": st["p50_us"], "p95_us": st["p95_us"]})
        _ROWS[-1]["p99_us"] = st["p99_us"]
        _row(f"{base}/us_per_request",
             st["wall_s"] * 1e6 / max(st["n_requests"], 1), "", config=cfg)


# ---------------------------------------------------------------------------
# Measured: autotuned vs default 3D-FFT plan (single device, Pu=Pv=1)
# ---------------------------------------------------------------------------

def bench_fft_autotune(n: int = 32):
    """Time the autotuner's sweep (the default plan is always in it).

    ``force=True``: a benchmark must measure *this* run, never replay the
    persistent plan cache (the entry still gets refreshed as a side effect).
    """
    from repro import compat
    from repro.tuning import autotune

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    res = autotune(mesh, n, real=True, max_candidates=4, iters=3, force=True)
    for r in res.rows:
        _row(f"fft3d_autotune/N{n}/{r['name']}", r["us_per_call"], "",
             config=r["config"])
    _row(f"fft3d_autotune/N{n}/selected", res.best_us, res.best.name,
         config=res.best_config)


BENCHES = {
    "table_4_1": bench_table_4_1,
    "table_4_2": bench_table_4_2,
    "engine_tables": bench_engine_tables,
    "global_fft": bench_global_fft,
    "network_bw": bench_network_bw,
    "fig_1_1": bench_fig_1_1,
    "fft_wallclock": bench_fft_wallclock,
    "fft_engines": bench_fft_engines,
    "fft_autotune": bench_fft_autotune,
    "solvers": bench_solvers,
    "serving": bench_serving,
}


def _trace_path_for(json_path: str) -> str:
    base = json_path[:-5] if json_path.endswith(".json") else json_path
    return base + ".trace.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help=f"comma-separated benchmark names; known: "
                         f"{','.join(sorted(BENCHES))}")
    ap.add_argument("--json", dest="json_path", default="",
                    help="also write rows as a bench-fft/v2 JSON document")
    ap.add_argument("--trace", dest="trace_path", default=None,
                    help="write a Chrome-trace JSON (Perfetto-loadable) of "
                         "the run; defaults to <json-stem>.trace.json when "
                         "--json is given, '' disables")
    ap.add_argument("--list", action="store_true",
                    help="print the known --only workload names and exit")
    args = ap.parse_args()
    if args.list:
        for name in sorted(BENCHES):
            print(name)
        return
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        # a typo'd --only must fail loudly, not emit an empty document that
        # the CI perf gate would then wave through
        ap.error(f"unknown benchmark name(s) {', '.join(unknown)}; "
                 f"known: {', '.join(sorted(BENCHES))}")
    trace_path = args.trace_path
    if trace_path is None:
        trace_path = _trace_path_for(args.json_path) if args.json_path else ""
    if trace_path:
        from repro import obs
        obs.clear()
        obs.enable()
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    meta = None
    if args.json_path or trace_path:
        import jax

        from repro.core import perfmodel as pm
        cal = pm.active_calibration()
        meta = {"jax": jax.__version__,
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "devices": len(jax.devices()),
                "benches": names,
                "calibration": {
                    "active": cal is not None,
                    "link_bytes_per_s": pm.link_bytes_per_s(),
                    **({"fingerprint": cal.get("fingerprint", {})}
                       if cal else {}),
                }}
    if args.json_path:
        from repro.tuning.cli import write_bench_json
        write_bench_json(args.json_path, _ROWS, meta)
    if trace_path:
        from repro import obs
        obs.disable()
        obs.write_chrome_trace(trace_path, obs.tracer, obs.metrics, meta=meta)
        print(f"# wrote trace {trace_path} "
              f"({len(obs.tracer.events())} spans); load in "
              f"https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
