"""Compile rehearsals for TPU v5e: the main path's kernels and one solver
step, compiled by the TPU compiler for a described (not attached) v5e:2x2.

Nothing runs, so these say nothing about results or times; they catch what
interpret mode cannot — Mosaic refusing a kernel, an unsupported dtype, a
collective the kernel cannot lower. The topology is described inside a
module fixture (never at import), so every pytest-xdist worker collects
the same tests and only the worker running this file loads the TPU
compiler.

The same compiles check the stage scopes (``repro.obs.stages``): they
leave the compiled programs as they are, and every op the benchmark's
trace reduction counts as ``local`` can be put on a stage.
"""

import contextlib
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.core.fft3d import make_fft3d
from repro.kernels import ring_rdma
from repro.kernels.fft_mxu import fft1d_mxu
from repro.kernels.fft_radix2 import fft1d_pallas
from repro.solvers import make_solver

N = 256
N_STAGES = 16       # the stage-scope compiles: small, both grids

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import stagetime, tracing  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back without the chip:
        # keep it out of any persistent cache. The chip computes in 32 bits
        # (float64 is refused there), so compile as it runs: x64 off.
        was = (jax.config.jax_enable_compilation_cache,
               jax.config.jax_enable_x64)
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_enable_x64", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_enable_x64", was[1])
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def mesh_of(topo, shape):
    devices = np.array(topo.devices[:shape[0] * shape[1]]).reshape(shape)
    return Mesh(devices, ("data", "model"))


@pytest.mark.parametrize("fft", [fft1d_pallas, fft1d_mxu],
                         ids=["radix2", "mxu"])
def test_fft_kernel_compiles(one_chip, fft):
    x = jax.ShapeDtypeStruct((4096, N), jnp.float32, sharding=one_chip)
    compiled = fft.lower(x, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(4, 1), (2, 2)], ids=["4x1", "2x2"])
@pytest.mark.parametrize("exchange", [ring_rdma.ring_exchange_rdma,
                                      ring_rdma.ring_exchange_bidi_rdma],
                         ids=["ring", "bidi"])
def test_rdma_ring_compiles(topo, shape, exchange):
    mesh = mesh_of(topo, shape)
    spec = P("data", "model", None)

    def body(x):
        outs, _ = exchange([x], ("data",), split_axis=1, concat_axis=0,
                           interpret=False)
        return outs[0]

    f = jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(spec,),
        out_specs=P(None, ("data", "model"), None), check_vma=False))
    x = jax.ShapeDtypeStruct((N, N, N), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    text = f.lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    # each kernel call is named by its pallas_call and scoped as the fold's
    # exchange, so a trace puts its time on the fold.exchange stage
    kernel = "rdma_bidi_ring" if exchange is ring_rdma.ring_exchange_bidi_rdma \
        else "rdma_ring"
    graph = stagetime.hlo_graph(text)
    calls = {k: v for k, v in graph.items() if v[0] == "custom-call"}
    assert calls
    for instr, (_, op_name, *_) in calls.items():
        assert re.fullmatch(rf"{kernel}(\.\d+)?", instr), instr
        assert f"fold.exchange/{kernel}/pallas_call" in op_name, op_name
        assert stagetime.resolve(instr, graph, STAGE_TABLES) == "fold.exchange"


def test_heat_f32_step_compiles(topo):
    mesh = mesh_of(topo, (1, 1))
    solver = make_solver("heat", mesh, N, dtype="float32")
    sharding = NamedSharding(mesh, solver.field_spec())
    fields = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(solver.initial_fields))
    compiled = solver._stepj.lower(fields).compile()
    assert compiled.memory_analysis().argument_size_in_bytes == N ** 3 * 4


def test_float64_solver_refused_on_tpu(topo):
    mesh = mesh_of(topo, (1, 1))
    with pytest.raises(ValueError, match="no complex128/float64 FFT"):
        make_solver("heat", mesh, N, dtype="float64")


# ---------------------------------------------------------------------------
# stage scopes: the benchmark cells' programs (speed3d r2c pair, TGV step)
# ---------------------------------------------------------------------------

STAGE_TABLES = stagetime.load_stage_tables()
CLASS_TABLES = tracing.load_tables()
META = re.compile(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
DEBUG_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")
#: instructions that run as no op, or as none the trace reduction counts
NOT_OPS = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
#: local instructions no stage reaches, with the reason
UNSTAGED_OK = {
    ("copy-start", "constant"): "XLA's prefetch of a folded constant (the "
                                "r2c pad mask), which carries no op_name",
    ("copy-done", "copy-start", "constant"): "the same prefetch's done half",
}


def stripped(hlo_text: str) -> str:
    """The HLO text without debug metadata: ``metadata={...}`` and the
    source-location tables."""
    blocks = [b for b in hlo_text.split("\n\n")
              if b.split("\n", 1)[0].strip() not in DEBUG_SECTIONS]
    return META.sub("", "\n\n".join(blocks))


def cell_lowerings(topo, shape) -> dict:
    """Thunks that lower the speed3d r2c pair and a TGV step."""
    mesh = mesh_of(topo, shape)
    fwd, inv, plan = make_fft3d(mesh, N_STAGES, real=True)
    sharding = NamedSharding(mesh, plan.grid.pencil_spec())
    x = jax.ShapeDtypeStruct((N_STAGES,) * 3, jnp.float32, sharding=sharding)
    spectrum = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in jax.eval_shape(fwd, x)]
    solver = make_solver("navier_stokes", mesh, N_STAGES, dtype="float32")
    ssh = NamedSharding(mesh, solver.field_spec())
    fields = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=ssh),
        jax.eval_shape(solver.initial_fields))
    return {"fwd": lambda: fwd.lower(x), "inv": lambda: inv.lower(*spectrum),
            "tgv": lambda: solver._stepj.lower(fields)}


def cell_programs(topo, shape) -> dict:
    """The compiled HLO of the speed3d r2c pair and of a TGV step."""
    return {name: lower().compile().as_text()
            for name, lower in cell_lowerings(topo, shape).items()}


@pytest.fixture(scope="module")
def scoped(topo):
    return {shape: cell_programs(topo, shape) for shape in ((1, 1), (2, 2))}


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_stage_scopes_leave_programs_unchanged(topo, scoped, shape,
                                               monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = cell_programs(topo, shape)
    for name, text in scoped[shape].items():
        assert "fft.x" in text and "fft.x" not in bare[name], name
        assert stripped(text) == stripped(bare[name]), name


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_local_instructions_resolve_to_stages(scoped, shape):
    for name, text in scoped[shape].items():
        module = tracing.module_name(text)
        scopes = {module: tracing.op_names(text)}
        graph = stagetime.hlo_graph(text)
        lines = {i: t for _, i, t in stagetime.instructions(text)}
        seen, left = set(), []
        for instr, (opcode, *_) in graph.items():
            if opcode in NOT_OPS:
                continue
            op = tracing.Op(f"%{instr} = {lines[instr]}", 0.0, 1.0, module)
            cls = tracing.classify(op, CLASS_TABLES, scopes)
            stage = stagetime.resolve(instr, graph, STAGE_TABLES)
            seen.add(stage)
            if cls == "local" and stage == stagetime.UNSTAGED:
                left.append((instr, walk(instr, graph)))
        assert all(chain in UNSTAGED_OK for _, chain in left), (name, left)
        assert {"fft.x", "fft.y", "fft.z"} <= seen, (name, seen)
        if shape == (2, 2):
            assert "fold.exchange" in seen, name
    # the paired x pass: its untangle and Hermitian fusions are staged, and
    # (above) none of their ops or of XLA's copies after them is unstaged
    tgv = _stages(scoped[shape]["tgv"])
    assert {"pointwise.curl", "pointwise.cross", "pointwise.dealias",
            "pointwise.project", "pointwise.ifrk4_factors",
            "pointwise.ifrk4_combine", "relayout.permute",
            "relayout.r2c_untangle", "relayout.c2r_hermitian"} <= tgv, tgv
    assert "relayout.r2c_untangle" in _stages(scoped[shape]["fwd"])
    assert "relayout.c2r_hermitian" in _stages(scoped[shape]["inv"])


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_cell_programs_pair_real_lines(topo, shape):
    # each program traced afresh counts the path its x pass takes
    from repro import obs

    for name, lower in cell_lowerings(topo, shape).items():
        jax.clear_caches()
        with obs.capture() as (_, metrics):
            lower()
        counts = metrics.counters()
        assert counts.get("fft.r2c.paired", 0) >= 1, (name, counts)
        assert "fft.r2c.unpaired" not in counts, (name, counts)


def walk(instr, graph) -> tuple:
    """The opcodes along an instruction's first-operand chain."""
    chain = []
    while instr in graph and len(chain) < 16:
        chain.append(graph[instr][0])
        instr = graph[instr][2] if len(graph[instr]) > 2 else None
    return tuple(chain)


def _stages(text) -> set:
    graph = stagetime.hlo_graph(text)
    return {stagetime.resolve(i, graph, STAGE_TABLES) for i in graph}
