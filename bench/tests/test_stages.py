"""The stage reduction (``bench/stagetime.py``) on synthetic programs and
captures, and on traces recorded on a TPU v5e with the stage scopes in
place."""

import json
from pathlib import Path

import pytest

from bench import stagetime, tracing
from bench.tracing import Capture, Op

TABLES = tracing.load_tables()
STAGES = stagetime.load_stage_tables()
DATA = Path(__file__).resolve().parent / "data"


def op(instr, start, end, module="m"):
    return Op(f"%{instr} = f32[8]{{0}} {instr.split('.')[0]}(f32[8]{{0}} %x)",
              float(start), float(end), module)


def test_innermost_scope_wins():
    name = lambda s: stagetime.innermost(s, STAGES)
    assert name("jit(step)/pointwise.cross/relayout.permute/transpose") \
        == "relayout.permute"
    assert name("jit(inv)/fft.x/jit(irfft1d)/relayout.c2r_hermitian/gather") \
        == "relayout.c2r_hermitian"
    assert name("jit(fwd)/fft.y/jit(fft1d)/jit(fft)") == "fft.y"
    assert name("jit(b)/shard_map/fold.exchange/rdma_ring/pallas_call") \
        == "fold.exchange"
    assert name("jit(step)/pointwise.ifrk4_combine/mul;add") \
        == "pointwise.ifrk4_combine"
    assert name("jit(fwd)/jit(rfft1d)/slice") is None
    assert name("kr") is None and name("") is None


def test_copy_chain_takes_its_producers_stage():
    graph = {
        "fusion.1": ["fusion", "jit(s)/pointwise.cross/mul", "x"],
        "copy-start": ["copy-start", "", "fusion.1"],
        "copy-done": ["copy-done", "", "copy-start"],
        "copy.2": ["copy", "", "copy-done"],
        "bitcast.3": ["bitcast", "", "copy.2"],
        "copy.4": ["copy", "", "bitcast.3"],
    }
    for instr in graph:
        assert stagetime.resolve(instr, graph, STAGES) == "pointwise.cross"


def test_walk_to_a_parameter_is_the_entry_relayout():
    graph = {
        "kr.1": ["parameter", "kr"],
        "copy.1": ["copy", "kr", "kr.1"],
        "copy-start": ["copy-start", "", "kr.1"],
        "copy-done": ["copy-done", "", "copy-start"],
        "constant.5": ["constant", ""],
        "copy-start.1": ["copy-start", "", "constant.5"],
        "loop.1": ["copy", "", "loop.2"],
        "loop.2": ["copy", "", "loop.1"],
    }
    res = {i: stagetime.resolve(i, graph, STAGES) for i in graph}
    assert res["copy.1"] == res["copy-done"] == stagetime.ENTRY
    assert res["copy-start.1"] == stagetime.UNSTAGED      # no scope, no param
    assert res["loop.1"] == stagetime.UNSTAGED            # a cycle ends
    assert stagetime.resolve("missing", graph, STAGES) == stagetime.UNSTAGED


def test_stages_partition_the_local_class():
    # chip 0: an fft op, then local ops that overlap each other; chip 1:
    # one local op. Every op's share is the part no earlier op covers.
    graph = {"fusion.1": ["fusion", "jit(f)/fft.x/jit(fft1d)/jit(fft)", "x"],
             "copy.2": ["copy", "", "fusion.1"],
             "fusion.3": ["fusion", "jit(f)/relayout.permute/transpose", "y"],
             "fusion.4": ["fusion", "jit(f)/pointwise.dealias/mul", "y"],
             "x": ["parameter", ""], "y": ["parameter", ""],
             "copy.5": ["copy", "", "y"], "slice.6": ["slice", "", "z"]}
    scopes = {"m": {k: v[1] for k, v in graph.items()}}
    cap = Capture(
        devices={0: [op("fusion.1", 0, 40), op("copy.2", 40, 50),
                     op("fusion.3", 50, 70), op("fusion.4", 60, 80),
                     op("copy.5", 85, 90), op("slice.6", 90, 95),
                     Op("%all-to-all.9 = f32[8]{0} all-to-all(f32[8]{0} %x)",
                        0.0, 99.0, "m", True)],
                 1: [op("fusion.4", 0, 30)]},
        host=[], window=(0, 100))
    t = stagetime.reduce(cap, [0, 1], 2, TABLES, scopes, {"m": graph}, STAGES)
    ns = 1e-9 / 2                                   # per-chip mean, ns → s
    assert t.class_s["local"] == pytest.approx((10 + 30 + 10 + 30) * ns)
    assert t.local_s == pytest.approx({
        "fft.x": 10 * ns, "relayout.permute": 20 * ns,
        "pointwise.dealias": (10 + 30) * ns, stagetime.ENTRY: 5 * ns,
        stagetime.UNSTAGED: 5 * ns})
    assert sum(t.local_s.values()) == pytest.approx(t.class_s["local"])
    assert sum(t.all_s.values()) == pytest.approx(
        t.class_s["local"] + t.class_s["fft"])
    assert stagetime.layer_ms(t, "relayout") == pytest.approx(
        1e3 * 25 * ns / 2)
    assert stagetime.layer_ms(t, "pointwise") == pytest.approx(
        1e3 * 40 * ns / 2)
    assert stagetime.layer_ms(t, "fold") is None
    assert sorted(t.other_ops) == ["m:copy.2 [copy] fft.x",
                                   "m:slice.6 [slice] unstaged"]
    stages = stagetime.breakdown(t)
    assert stages[0][0] == "fft.x" and [s for s, _ in stages].count("fft.x") == 1


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/neg"}
}

%region_0.1 (lhs: f32[], rhs: f32[]) -> f32[] {
  %lhs = f32[] parameter(0)
  %rhs = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%lhs, %rhs)
}

ENTRY %main.2 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %neg_fusion = f32[8]{0:T(256)} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/pointwise.curl/neg"}
  %rdma_ring.1 = (f32[8]{0}, f32[8]{0}) custom-call(%neg_fusion, %x.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"mesh_axes":"[\\"data\\"]"
}}, metadata={op_name="jit(f)/fold.exchange/rdma_ring/pallas_call"}
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%rdma_ring.1), index=0
  %reduce.1 = f32[] reduce(%get-tuple-element.1, %c), dimensions={0}, to_apply=%region_0.1
  ROOT %copy.1 = f32[8]{0} copy(%get-tuple-element.1)
}
"""


def test_hlo_graph_reads_ops_across_lines_and_skips_called_bodies():
    graph = stagetime.hlo_graph(HLO)
    assert set(graph) == {"x.1", "neg_fusion", "rdma_ring.1",
                          "get-tuple-element.1", "reduce.1", "copy.1"}
    assert graph["x.1"] == ["parameter", "x"]
    assert graph["neg_fusion"] == ["fusion", "jit(f)/pointwise.curl/neg",
                                   "x.1"]
    assert graph["rdma_ring.1"] == [
        "custom-call", "jit(f)/fold.exchange/rdma_ring/pallas_call",
        "neg_fusion", "x.1"]
    assert graph["reduce.1"][2:] == ["get-tuple-element.1", "c"]
    assert stagetime.resolve("copy.1", graph, STAGES) == "fold.exchange"
    # the line-based reader of the op classes misses the kernel's scope
    assert tracing.op_names(HLO)["rdma_ring.1"] == ""


@pytest.mark.parametrize("stem,steps,local_ms,relayout_ms,pointwise_ms", [
    ("speed3d_stages_n64", 3, 0.013788333333, 0.013788333333, None),
    ("tgv_stages_n32", 2, 0.149155, 0.105705, 0.04345),
])
def test_recorded_v5e_stage_traces(stem, steps, local_ms, relayout_ms,
                                   pointwise_ms):
    """Traced on a TPU v5e with the stage scopes in place
    (``python3 bench/stagetime.py ... --record``): three speed3d r2c steps
    at N=64 and two TGV steps at N=32, with the programs' ``op_name``s and
    graphs. Pins the stage reduction's numbers for those traces."""
    cap = tracing.load(DATA / f"{stem}.xplane.pb")
    doc = json.loads((DATA / f"{stem}.scopes.json").read_text())
    assert all(o.instr in doc["graph"][o.module] for o in cap.devices[0])
    t = stagetime.reduce(cap, [0], steps, TABLES, doc["op_name"],
                         doc["graph"], STAGES)
    per_step = {k: stagetime.per_step_ms(v, steps)
                for k, v in t.local_s.items()}
    assert stagetime.per_step_ms(t.class_s["local"], steps) == \
        pytest.approx(local_ms)
    assert stagetime.layer_ms(t, "relayout") == pytest.approx(relayout_ms)
    if pointwise_ms is None:
        assert stagetime.layer_ms(t, "pointwise") is None
    else:
        assert stagetime.layer_ms(t, "pointwise") == pytest.approx(
            pointwise_ms)
    # every local op is on a relayout or pointwise stage, none unstaged,
    # and the stages add up to the local class
    assert stagetime.UNSTAGED not in per_step
    assert sum(per_step.values()) == pytest.approx(local_ms)
    stages = dict(stagetime.breakdown(t))
    assert {"fft.x", "fft.y", "fft.z"} <= set(stages)
    assert sum(stages.values()) == pytest.approx(
        stagetime.per_step_ms(t.class_s["local"] + t.class_s["fft"], steps))
    if stem.startswith("tgv"):
        assert {"pointwise.curl", "pointwise.cross", "pointwise.dealias",
                "pointwise.project", "pointwise.ifrk4_factors",
                "pointwise.ifrk4_combine", "relayout.c2r_hermitian",
                "relayout.permute"} <= set(per_step)
        assert per_step["relayout.c2r_hermitian"] == pytest.approx(0.0594205)
        assert per_step["pointwise.project"] == pytest.approx(0.0205625)


def test_every_program_scope_has_one_stage_table():
    from repro.obs import stages

    assert len(set(stages.SCOPES)) == len(stages.SCOPES)
    for scope in stages.SCOPES + (stages.RELAYOUT_ENTRY,):
        layers = [layer for layer, rx in STAGES
                  if rx.search(f"jit(f)/{scope}/mul")]
        assert layers == [scope.split(".")[0]], (scope, layers)
        assert stagetime.innermost(f"jit(f)/{scope}/mul", STAGES) == scope
