"""The trace reduction, on synthetic events and on a trace recorded on a
TPU v5e."""

import json
from pathlib import Path

import pytest

from bench import harness, tracing, work
from bench.tracing import Capture, Op

TABLES = tracing.load_tables()
DATA = Path(__file__).resolve().parent / "data"


def op(text, start, end, module="m", is_async=False):
    return Op(text, float(start), float(end), module, is_async)


def test_interval_arithmetic():
    u = tracing.union([(5, 7), (0, 2), (1, 3), (6, 8), (4, 4)])
    assert u == [(0, 3), (5, 8)]
    assert tracing.measure(u) == 6
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tracing.gaps(u, 0, 10) == [(3, 5), (8, 10)]


def test_op_tables_sort_ops_into_layers():
    scopes = {"m": {"fusion.1": "jit(fwd)/jit(rfft1d)/jit(fft1d)/jit(fft)",
                    "slice.2": "jit(fwd)/jit(rfft1d)/slice",
                    "custom-call.3": "jit(fft1d)/jit(fft1d_pallas)/pallas_call"}}
    cls = lambda text: tracing.classify(op(text, 0, 1), TABLES, scopes)
    assert cls("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kOutput") == "fft"
    assert cls("%slice.2 = f32[5]{0} slice(f32[8]{0} %fusion.1)") == "local"
    assert cls("%custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %x)") == "fft"
    assert cls("%all-to-all.4 = f32[8]{0} all-to-all(f32[8]{0} %x)") == "collective"
    assert cls("%collective-permute-done = f32[8]{0} collective-permute-done("
               "(f32[8]{0}) %cp)") == "collective"
    assert cls("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %all-to-all.4)") == "local"


def test_reduce_on_synthetic_devices():
    # 100 ns window, 2 steps; chip 0: fft 0-40, collective 30-60 (10 of it
    # under the fft), local 70-80; chip 1: fft 0-20
    scopes = {"m": {"fusion.1": "jit(fwd)/jit(fft1d)/jit(fft)"}}
    fft = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kOutput"
    cap = Capture(
        devices={0: [op(fft, 0, 40),
                     op("%all-to-all.1 = f32[8]{0} all-to-all(f32[8]{0} %y)",
                        30, 60),
                     op("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %z)", 70, 80)],
                 1: [op(fft, 0, 20)]},
        host=[("bench.wait", 60, 100), ("bench.dispatch", 0, 5)],
        window=(0, 100))
    peaks = {"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12}
    s = tracing.reduce(cap, [0, 1], 2, TABLES, scopes, (0.0, 10.0), peaks)
    assert s.chips == 2 and s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((70 + 20) / 2 * 1e-9)
    assert s.class_s["fft"] == pytest.approx((40 + 20) / 2 * 1e-9)
    assert s.class_s["collective"] == pytest.approx(30 / 2 * 1e-9)
    assert s.exposed_s["collective"] == pytest.approx(20 / 2 * 1e-9)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.wait tpu0"] == pytest.approx(10e-9)
    assert gaps["window edges tpu0"] == pytest.approx(20e-9)
    assert gaps["window edges tpu1"] == pytest.approx(80e-9)
    assert tracing.breakdown(s)["device_ops"][0][0] == "m:fusion.1 [fft]"


def test_recorded_v5e_trace():
    """Three speed3d_r2c steps at N=64 traced on a TPU v5e (default plan),
    with the instruction scopes of the same programs compiled for a
    described v5e. Pins the reduction's numbers for that trace."""
    cap = tracing.load(DATA / "speed3d_jnp_n64.xplane.pb")
    scopes = json.loads((DATA / "speed3d_jnp_n64.scopes.json").read_text())
    assert set(scopes) == {"jit__lambda", "jit_inv_local"}
    assert all(o.instr in scopes[o.module] for o in cap.devices[0])
    peaks = json.loads((tracing.OPTABLES.parent / "peaks.json").read_text())
    s = tracing.reduce(cap, [0], 3, TABLES, scopes,
                       work.fft_step_work(64, {"r2c": 1, "c2r": 1}, 1),
                       peaks["devices"]["TPU v5 lite"])
    read = lambda name: harness.load_reader(name)(s)
    assert read("fft_ms") == pytest.approx(0.086052)
    assert read("local_ms") == pytest.approx(0.013814333333)
    assert read("fft_hbm_roofline_pct") == pytest.approx(18.31897991)
    assert read("device_idle_pct") == pytest.approx(90.51647404)
    assert read("collective_ms") is None
    assert read("exposed_collective_ms") is None
