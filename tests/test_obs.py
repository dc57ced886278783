"""``repro.obs`` — span nesting and attributes, the disabled-path no-op
guarantees the hot paths rely on, the counters/gauges registry, the
Chrome-trace-event export schema (what Perfetto loads), and the timing
helpers' percentile stats and donated-buffer guard."""

import json
import threading

import pytest

from repro import obs


@pytest.fixture(autouse=True)
def _obs_reset():
    # every test starts and ends disabled with empty global state, however
    # the test body left it
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


# ---------------------------------------------------------------------------
# disabled path: the zero-overhead contract
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop_singleton():
    assert not obs.is_enabled()
    s = obs.span("dispatch/x")
    assert s is obs.NULL_SPAN
    # attrs are accepted and dropped without recording anything
    with obs.span("dispatch/x", engine="torus") as sp:
        sp.set_attr(late=1)
    assert obs.tracer.events() == []


def test_disabled_metrics_record_nothing():
    obs.metrics.inc("comm.wire_bytes", 1024)
    obs.metrics.set_gauge("g", 3.0)
    assert obs.metrics.counters() == {}
    assert obs.metrics.gauges() == {}
    assert obs.metrics.get("comm.wire_bytes") == 0
    assert obs.metrics.get("missing", default=-1) == -1


def test_disabled_traced_call_is_transparent():
    calls = []

    def fn(a, b=0):
        calls.append((a, b))
        return a + b

    fn.custom_marker = "still-reachable"
    wrapped = obs.traced_call(fn, "dispatch/fn")
    assert wrapped(1, b=2) == 3
    assert calls == [(1, 2)]
    assert obs.tracer.events() == []
    # attribute access forwards to the wrapped function (jit surfaces like
    # .lower keep working on the wrapped object)
    assert wrapped.custom_marker == "still-reachable"


# ---------------------------------------------------------------------------
# enabled path: nesting, attributes, threads
# ---------------------------------------------------------------------------

def test_span_nesting_records_parent_and_depth():
    obs.enable()
    with obs.span("dispatch/outer", engine="torus"):
        with obs.span("tune/inner", round=3) as sp:
            sp.set_attr(bytes=64)
    events = {e["name"]: e for e in obs.tracer.events()}
    assert set(events) == {"dispatch/outer", "tune/inner"}
    outer, inner = events["dispatch/outer"], events["tune/inner"]
    assert outer["parent"] == "" and outer["depth"] == 0
    assert inner["parent"] == "dispatch/outer" and inner["depth"] == 1
    assert inner["args"] == {"round": 3, "bytes": 64}
    assert outer["args"] == {"engine": "torus"}
    # the inner interval sits inside the outer one
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_span_stacks_are_per_thread():
    obs.enable()
    ready = threading.Event()

    def worker():
        with obs.span("dispatch/worker"):
            ready.set()

    with obs.span("dispatch/main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    events = {e["name"]: e for e in obs.tracer.events()}
    # the worker's span must not see the main thread's open span as parent
    assert events["dispatch/worker"]["parent"] == ""
    assert events["dispatch/worker"]["tid"] != events["dispatch/main"]["tid"]


def test_traced_call_records_dispatch_span_with_attrs():
    obs.enable()
    wrapped = obs.traced_call(lambda x: x * 2, "dispatch/fft3d.fwd",
                              attrs={"engine": "switched"})
    assert wrapped(21) == 42
    (ev,) = obs.tracer.events()
    assert ev["name"] == "dispatch/fft3d.fwd"
    assert ev["args"] == {"engine": "switched"}
    assert ev["dur"] >= 0


def test_capture_enables_then_disables():
    with obs.capture() as (tracer, metrics):
        assert obs.is_enabled()
        with obs.span("dispatch/x"):
            metrics.inc("k", 2)
    assert not obs.is_enabled()
    # recorded state stays readable after capture exits
    assert [e["name"] for e in tracer.events()] == ["dispatch/x"]
    assert metrics.get("k") == 2


def test_metrics_counters_accumulate_and_gauges_overwrite():
    obs.enable()
    obs.metrics.inc("comm.exchanges.data")
    obs.metrics.inc("comm.exchanges.data")
    obs.metrics.inc("comm.wire_bytes", 640)
    obs.metrics.set_gauge("link_bytes_per_s", 1e9)
    obs.metrics.set_gauge("link_bytes_per_s", 2e9)
    assert obs.metrics.get("comm.exchanges.data") == 2
    assert obs.metrics.get("comm.wire_bytes") == 640
    assert obs.metrics.get("link_bytes_per_s") == 2e9
    snap = obs.metrics.snapshot()
    assert snap["counters"]["comm.wire_bytes"] == 640
    assert snap["gauges"] == {"link_bytes_per_s": 2e9}


# ---------------------------------------------------------------------------
# the profiler's clock: spans on the host plane, dispatch spans never block
# ---------------------------------------------------------------------------

def test_enabled_span_lands_on_profiler_host_plane(tmp_path):
    import glob
    import sys
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import tracing

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span(tracing.WINDOW):
            with obs.span("bench.dispatch", step=0):
                f(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    cap = tracing.load(path)
    (name, start, end), = cap.host
    assert name == "bench.dispatch"
    assert cap.window[0] <= start <= end <= cap.window[1]
    # the tracer's own events are still recorded
    assert [e["name"] for e in obs.tracer.events()] == [
        "bench.dispatch", tracing.WINDOW]


def test_disabled_span_imports_no_profiler(monkeypatch):
    import builtins

    real_import = builtins.__import__

    def guarded(name, *args, **kwargs):
        assert name != "jax.profiler", "disabled span touched the profiler"
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", guarded)
    with obs.span("dispatch/x"):
        pass


def _record_blocks(monkeypatch):
    import jax

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(x) or real(x))
    return calls


def test_traced_call_does_not_block(monkeypatch):
    import jax.numpy as jnp

    calls = _record_blocks(monkeypatch)
    obs.enable()
    wrapped = obs.traced_call(lambda x: x + 1, "dispatch/fft3d.fwd")
    out = wrapped(jnp.ones(4))
    obs.disable()
    assert calls == []
    assert float(out[0]) == 2.0
    assert [e["name"] for e in obs.tracer.events()] == ["dispatch/fft3d.fwd"]


def test_solver_step_does_not_block(monkeypatch):
    import numpy as np

    from repro import compat
    from repro.solvers import make_solver

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    solver = make_solver("heat", mesh, 8, dtype="float32")
    state = solver.init_state()
    calls = _record_blocks(monkeypatch)
    obs.enable()
    nxt = solver.step(state)
    obs.disable()
    assert calls == []
    assert nxt.n_steps == 1 and np.all(np.isfinite(np.asarray(nxt.fields)))
    (ev,) = obs.tracer.events()
    assert ev["name"] == "dispatch/solver.step"
    assert ev["args"] == {"case": "heat", "engine": solver.plan.comm_engine}


# ---------------------------------------------------------------------------
# Chrome-trace export (the document Perfetto / chrome://tracing load)
# ---------------------------------------------------------------------------

def test_chrome_trace_document_schema(tmp_path):
    obs.enable()
    with obs.span("dispatch/fft3d.fwd", engine="torus"):
        with obs.span("tune/candidate", grid_dim="u"):
            pass
    obs.metrics.inc("comm.wire_bytes", 128)
    obs.disable()

    path = str(tmp_path / "trace.json")
    obs.write_chrome_trace(path, obs.tracer, obs.metrics,
                           meta={"devices": 8})
    with open(path) as f:
        doc = json.load(f)
    assert obs.validate_chrome_trace(doc) == []
    assert doc["displayTimeUnit"] == "ms"
    assert doc["meta"] == {"devices": 8}
    assert doc["metrics"]["counters"]["comm.wire_bytes"] == 128
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert set(events) == {"dispatch/fft3d.fwd", "tune/candidate"}
    ev = events["tune/candidate"]
    assert ev["ph"] == "X" and ev["cat"] == "tune"
    assert ev["args"]["grid_dim"] == "u"
    assert ev["args"]["parent"] == "dispatch/fft3d.fwd"
    assert events["dispatch/fft3d.fwd"]["cat"] == "dispatch"


def test_validate_chrome_trace_flags_malformed_documents():
    assert obs.validate_chrome_trace({}) != []
    assert obs.validate_chrome_trace({"traceEvents": {}}) != []
    bad_event = {"traceEvents": [{"name": "x", "ph": "B", "ts": 0.0,
                                  "dur": 1.0, "pid": 1, "tid": 1}]}
    assert any("ph" in p for p in obs.validate_chrome_trace(bad_event))
    missing_key = {"traceEvents": [{"name": "x", "ph": "X"}]}
    assert obs.validate_chrome_trace(missing_key) != []


def test_summary_table_lists_spans_and_counters():
    obs.enable()
    with obs.span("dispatch/solver.step"):
        pass
    obs.metrics.inc("plan_cache.hits")
    obs.disable()
    table = obs.summary_table(obs.tracer, obs.metrics)
    assert "dispatch/solver.step" in table
    assert "plan_cache.hits" in table
    empty = obs.summary_table(obs.Tracer(), obs.Metrics())
    assert "no spans" in empty


# ---------------------------------------------------------------------------
# timing helpers: percentile stats + the donated-buffer guard
# ---------------------------------------------------------------------------

def test_time_stats_distribution_keys_and_order():
    from repro.tuning.timing import time_stats

    stats = time_stats(lambda x: x + 1, 1.0, iters=7)
    assert stats["iters"] == 7
    assert stats["min_us"] <= stats["p50_us"] <= stats["p95_us"]
    assert stats["mean_us"] > 0
    with pytest.raises(ValueError, match="iters"):
        time_stats(lambda x: x, 1.0, iters=0)


def test_timing_refuses_donated_inputs():
    from repro.tuning.timing import time_stats, time_us

    class FakeDonated:
        deleted = False

        def is_deleted(self):
            return self.deleted

    def donating_fn(a):
        a.deleted = True  # what a jit with donate_argnums does on warm-up
        return 0.0

    with pytest.raises(ValueError, match="donated"):
        time_us(donating_fn, FakeDonated())
    with pytest.raises(ValueError, match="donated"):
        time_stats(donating_fn, FakeDonated())
