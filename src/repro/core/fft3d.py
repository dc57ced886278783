"""Distributed 3D FFT over a 2D pencil decomposition — the paper's core.

Implements the *transpose method* (§3.2.4): local X FFT → X↔Y fold → local Y
FFT → Y↔Z fold → local Z FFT, with the task-organization models of Chapter 4:

* ``schedule="sequential"`` — each phase processes the whole local volume
  before the next starts (Fig. 4.2; the paper's case B — XLA still overlaps
  DMA-like copies, but FFT phases are serialized on the full volume).
* ``schedule="pipelined"`` — the volume is split into ``chunks`` slabs along
  an axis untouched by the upcoming fold, and each slab's FFT→fold chain is
  emitted independently (Fig. 4.3 / case C). XLA's latency-hiding scheduler
  can then run slab i+1's butterflies underneath slab i's all-to-all — the
  TPU rendition of the paper's deep pipeline across engines and network.
* ``vector_mode="parallel"|"streaming"`` — μ-component vector fields are
  processed either simultaneously (leading component axis, ~μ× live memory;
  §4.4.1) or as a per-dimension stream (unrolled loop, §4.4.2/Fig. 4.6).

Configuration rides one object: ``make_fft3d(mesh, n, spec=EngineSpec(...))``
picks the comm engine, compute backend, schedule/chunks and vector mode in a
single frozen dataclass (``core.engine_spec``).

Beyond the plain transform pair, :func:`spectral_roundtrip_local` executes a
whole *spectral roundtrip* — forward FFT, pointwise-diagonal k-space
multiply (:class:`DiagonalKernel`), inverse FFT — and, when the plan's
``fused_roundtrip`` knob is on, streams the Y↔Z phase pair through the
engine's ``run_roundtrip`` schedule: slab k's Z-FFT→multiply→inverse runs
under slab k+1's fold and slab k−1's unfold, with no full-volume barrier
between the forward and inverse transforms.

Communication: the plan walks the axis-labelled :class:`CommDAG` from
``core.decomposition`` — the ``xy`` step exchanges over the grid's ``u``
dimension, the ``yz`` step over ``v`` — and hands each step to a pluggable
**TransposeEngine** (``core.comm``): ``engine="switched"`` (single
all-to-all, Fig. 5.10), ``"torus"`` (ppermute ring, Fig. 5.9),
``"overlap_ring"`` (the ring with the 1D FFT fused between its rounds —
block-granular compute/communication overlap, the paper's task C/G ↔ engine
pipelining of Fig. 4.3), ``"pallas_ring"`` (the same schedule as a Pallas
async-RDMA kernel with explicit double-buffered neighbor DMA — the paper's
NIC offload; interpret mode off-TPU) or ``"bidi_ring"`` (two-NIC
bidirectional ring, ⌈(P−1)/2⌉ rounds). When a grid dimension spans several
mesh axes (``u_axes=("pod", "data")`` on a 3-axis mesh) every engine runs
the staged per-axis exchange — one ring per mesh axis — instead of one flat
ring over the product group; ``spec.fabric`` is the derived §5.5 fabric
("switched" | "torus") the chosen engine runs on.

Real-to-complex: the X phase uses the general complex engine on real input
and keeps N/2+1 bins (padded to a Pu-divisible length), the paper's choice
(§3.2.5, §3.4: "we prefer a more general and flexible architecture"). The
engine takes two real lines per complex FFT, the first and second halves
of the pencil's Ny/Pu axis as its real and imaginary planes, and their
spectra are untangled from the one result; the inverse rebuilds both
Hermitian spectra into one complex line per pair (``kops.rfft1d`` /
``kops.irfft1d``). ``r2c_packed=True`` switches on the beyond-paper
even/odd packed real FFT.

All ``*_local`` functions run inside ``shard_map``; ``make_fft3d`` builds the
jitted global-array entry points.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.core import comm, precision
from repro.core.decomposition import CommDAG, PencilGrid, fft3d_dag
from repro.core.engine_spec import EngineSpec
from repro.kernels import ops as kops
from repro.obs import stages

Schedule = Literal["sequential", "pipelined"]
VectorMode = Literal["parallel", "streaming"]


@dataclasses.dataclass(frozen=True)
class FFT3DPlan:
    n: tuple[int, int, int]
    grid: PencilGrid
    real: bool = False
    backend: str = "jnp"             # "pallas" | "ref" | "jnp"
    schedule: Schedule = "sequential"
    chunks: int = 1                  # pipelined slab count (1 = sequential)
    net: str = "switched"            # fabric: "switched" | "torus" (derived)
    r2c_packed: bool = False         # beyond-paper packed real FFT
    comm_engine: str = ""            # "" -> engine named by ``net``
    dtype: str = ""                  # "" -> caller-supplied arrays decide
    fused_roundtrip: bool = False    # stream diagonal spectral roundtrips

    def __post_init__(self):
        self.grid.validate(self.n)
        if self.dtype:
            # refuse the silent f64→f32 demotion JAX performs with x64 off —
            # a plan that claims float64 must actually compute in it
            canonical = precision.require_dtype(self.dtype, who="FFT3DPlan")
            object.__setattr__(self, "dtype", canonical.name)
        if self.schedule == "sequential":
            object.__setattr__(self, "chunks", 1)
        assert self.chunks >= 1
        engine = self.comm_engine or self.net
        if engine not in comm.ENGINES:
            raise ValueError(f"unknown comm_engine {engine!r}; "
                             f"have {sorted(comm.ENGINES)}")
        object.__setattr__(self, "comm_engine", engine)
        object.__setattr__(self, "net", comm.engine_fabric(engine))

    def spec(self) -> EngineSpec:
        """This plan's engine configuration as one :class:`EngineSpec`."""
        return EngineSpec(engine=self.comm_engine, backend=self.backend,
                          schedule=self.schedule, chunks=self.chunks,
                          real=self.real, r2c_packed=self.r2c_packed,
                          fused_roundtrip=self.fused_roundtrip)

    @classmethod
    def from_spec(cls, n, grid: PencilGrid, spec: EngineSpec,
                  dtype: str = "") -> "FFT3DPlan":
        """Build a plan from an :class:`EngineSpec` (the new spelling)."""
        return cls(n=tuple(n), grid=grid, real=spec.real,
                   backend=spec.backend, schedule=spec.schedule,
                   chunks=spec.chunks, r2c_packed=spec.r2c_packed,
                   comm_engine=spec.engine, dtype=dtype,
                   fused_roundtrip=spec.fused_roundtrip)

    def dag(self) -> CommDAG:
        """The axis-labelled transpose DAG this plan executes (X↔Y fold on
        grid dimension ``u``, Y↔Z fold on ``v``)."""
        return fft3d_dag(self.real)

    def engine(self) -> comm.TransposeEngine:
        """The TransposeEngine instance scheduling this plan's fold phases."""
        return comm.build_engine(self.spec(), self.grid)

    @property
    def kx(self) -> int:
        """Spectral X length: padded N/2+1 bins if real, else Nx."""
        return self.grid.padded_r2c_len(self.n[0]) if self.real else self.n[0]

    @property
    def kx_keep(self) -> int:
        return self.n[0] // 2 + 1 if self.real else self.n[0]


def _pass(plan, stage, xr, xi, inverse=False):
    """One 1D-FFT pass along the last axis, in its stage scope."""
    with jax.named_scope(stage):
        return kops.fft1d(xr, xi, axis=-1, backend=plan.backend,
                          inverse=inverse)


def _fftx(plan, xr, xi):
    if not plan.real:
        return _pass(plan, stages.FFT_X, xr, xi)
    with jax.named_scope(stages.FFT_X):
        yr, yi = kops.rfft1d(xr, axis=-1, backend=plan.backend,
                             packed=plan.r2c_packed)
    pad = plan.kx - plan.kx_keep
    if pad:
        pw = [(0, 0)] * (yr.ndim - 1) + [(0, pad)]
        with jax.named_scope(stages.RELAYOUT_R2C_PAD):
            yr, yi = jnp.pad(yr, pw), jnp.pad(yi, pw)
    return yr, yi


def _ifftx(plan, xr, xi):
    if not plan.real:
        return _pass(plan, stages.FFT_X, xr, xi, inverse=True)
    with jax.named_scope(stages.RELAYOUT_R2C_CROP):
        xr = xr[..., : plan.kx_keep]
        xi = xi[..., : plan.kx_keep]
    with jax.named_scope(stages.FFT_X):
        return kops.irfft1d(xr, xi, n=plan.n[0], axis=-1,
                            backend=plan.backend)



# ---------------------------------------------------------------------------
# local (inside-shard_map) forward / inverse
# ---------------------------------------------------------------------------

def fft3d_local(plan: FFT3DPlan, xr, xi=None):
    """Forward 3D FFT of the local pencil (any leading axes).

    In : X-pencil ``(..., Ny/Pu, Nz/Pv, Nx)`` (xi may be None for real input)
    Out: Z-pencil ``(..., Kx/Pu, Ny/Pv, Nz)`` planar complex, natural order.
    """
    eng = plan.engine()
    dag = plan.dag()
    obs.metrics.inc("fft3d.retraces.forward")
    if xi is None:
        xi = jnp.zeros_like(xr)

    # Phase X + X↔Y fold over grid dim u (hardware tasks A–D), slabbable
    # along local z (the step's slab axis)
    def butterflies_x(cr, ci):
        return _fftx(plan, cr, ci)

    yr, yi = eng.run_fold(dag.step("xy"), butterflies_x, (xr, xi))

    # Phase Y + Y↔Z fold over grid dim v (tasks E–H), slabbable along kx
    def butterflies_y(cr, ci):
        return _pass(plan, stages.FFT_Y, cr, ci)

    yr, yi = eng.run_fold(dag.step("yz"), butterflies_y, (yr, yi))

    # Phase Z (tasks I–K)
    return _pass(plan, stages.FFT_Z, yr, yi)


def ifft3d_local(plan: FFT3DPlan, kr, ki):
    """Inverse 3D FFT: Z-pencil spectral in, X-pencil physical out.

    Returns real array if ``plan.real`` else a planar (re, im) pair.
    """
    eng = plan.engine()
    dag = plan.dag()
    obs.metrics.inc("fft3d.retraces.inverse")
    yr, yi = _pass(plan, stages.FFT_Z, kr, ki, inverse=True)

    def butterflies_y_inv(ur, ui):
        return _pass(plan, stages.FFT_Y, ur, ui, inverse=True)

    yr, yi = eng.run_unfold(dag.step("yz"), butterflies_y_inv, (yr, yi))

    def butterflies_x_inv(ur, ui):
        if plan.real:
            return (_ifftx(plan, ur, ui),)
        return _ifftx(plan, ur, ui)

    out = eng.run_unfold(dag.step("xy"), butterflies_x_inv, (yr, yi))
    if plan.real:
        return out[0] if isinstance(out, tuple) and len(out) == 1 else out
    return out


# ---------------------------------------------------------------------------
# fused spectral roundtrip (forward FFT → diagonal multiply → inverse FFT)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DiagonalKernel:
    """A spectral operator that is pointwise-diagonal in k-space.

    ``dr``/``di`` hold the real/imaginary parts of the multiplier on the
    local Z-pencil spectrum — rank-local arrays of shape
    ``(Kx/Pu, Ny/Pv, Nz)``, exactly the layout the wavenumber helpers of
    ``core.spectral`` produce. ``di=None`` marks a purely real multiplier
    (heat decay, inverse Laplacian, dealias masks); the NLS rotation
    ``exp(iθ(k))`` uses both parts.

    One object serves all three execution paths: the composed full-volume
    multiply, the per-slab multiply inside the fused ``run_roundtrip``
    kernel callback (``lo``/``hi`` slice the kx rows in lockstep with the
    slab stream), and — on the RDMA ring engines — the raw arrays that
    join the in-kernel butterfly payload (``arrays()``).
    """

    dr: object
    di: object = None

    def apply(self, kr, ki, lo: int | None = None, hi: int | None = None):
        """Multiply the planar spectrum by the kernel; ``[lo, hi)`` selects
        the kx rows of a slab (slab axis −3 of the Z-pencil)."""
        dr, di = self.dr, self.di
        with jax.named_scope(stages.POINTWISE_DIAGONAL):
            if lo is not None:
                axis = dr.ndim - 3
                dr = jax.lax.slice_in_dim(dr, lo, hi, axis=axis)
                if di is not None:
                    di = jax.lax.slice_in_dim(di, lo, hi, axis=axis)
            if di is None:
                return kr * dr, ki * dr
            return kr * dr - ki * di, kr * di + ki * dr

    def arrays(self):
        """The raw planar multiplier pair (``di`` may be None) for engines
        that fuse the multiply into their communication kernel."""
        return self.dr, self.di


def spectral_roundtrip_local(plan: FFT3DPlan, kernel: DiagonalKernel,
                             xr, xi=None):
    """Forward 3D FFT → diagonal k-space multiply → inverse 3D FFT of the
    local pencil, as one solver-step primitive.

    With ``plan.fused_roundtrip`` off this composes ``fft3d_local`` →
    ``kernel.apply`` → ``ifft3d_local`` (three barriered phases). With it
    on, the whole Y↔Z phase pair — forward Y butterflies, yz fold, Z-FFT,
    multiply, inverse Z-FFT, yz unfold, inverse Y butterflies — streams
    through the engine's ``run_roundtrip`` schedule per kx-slab (fold k+1
    ∥ kernel k ∥ unfold k−1), bit-exact vs the composed path.

    In/out: X-pencil like ``fft3d_local``/``ifft3d_local`` (a real array
    comes back when ``plan.real``).
    """
    if not plan.fused_roundtrip:
        kr, ki = fft3d_local(plan, xr, xi)
        kr, ki = kernel.apply(kr, ki)
        return ifft3d_local(plan, kr, ki)

    eng = plan.engine()
    dag = plan.dag()
    obs.metrics.inc("fft3d.retraces.roundtrip")
    if xi is None:
        xi = jnp.zeros_like(xr)

    def butterflies_x(cr, ci):
        return _fftx(plan, cr, ci)

    yr, yi = eng.run_fold(dag.step("xy"), butterflies_x, (xr, xi))

    def butterflies_y(cr, ci):
        return _pass(plan, stages.FFT_Y, cr, ci)

    def butterflies_y_inv(ur, ui):
        return _pass(plan, stages.FFT_Y, ur, ui, inverse=True)

    def middle(zr, zi, lo, hi):
        # everything at the Z pencil, for kx rows [lo, hi): the remaining
        # transform, the spectral multiply, and its inverse
        zr, zi = _pass(plan, stages.FFT_Z, zr, zi)
        zr, zi = kernel.apply(zr, zi, lo, hi)
        return _pass(plan, stages.FFT_Z, zr, zi, inverse=True)

    yr, yi = eng.run_roundtrip(dag.step("yz"), butterflies_y, middle,
                               butterflies_y_inv, (yr, yi),
                               diag=kernel.arrays())

    def butterflies_x_inv(ur, ui):
        if plan.real:
            return (_ifftx(plan, ur, ui),)
        return _ifftx(plan, ur, ui)

    out = eng.run_unfold(dag.step("xy"), butterflies_x_inv, (yr, yi))
    if plan.real:
        return out[0] if isinstance(out, tuple) and len(out) == 1 else out
    return out


def fft3d_vector_local(plan: FFT3DPlan, xr, xi=None,
                       vector_mode: VectorMode = "streaming"):
    """μ-component transform; leading axis 0 of ``xr`` is the component axis.

    ``parallel``  — one pass with the component axis live throughout (μ×
                    memory, paper §4.4.1).
    ``streaming`` — per-dimension stream X(c),Y(c),Z(c) per component c
                    (Fig. 4.4/4.6): unrolled so XLA pipelines component c+1
                    under component c.
    """
    if vector_mode == "parallel":
        return fft3d_local(plan, xr, xi)
    outs = [fft3d_local(plan, xr[c], None if xi is None else xi[c])
            for c in range(xr.shape[0])]
    return (jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs]))


def ifft3d_vector_local(plan: FFT3DPlan, kr, ki,
                        vector_mode: VectorMode = "streaming"):
    if vector_mode == "parallel":
        return ifft3d_local(plan, kr, ki)
    outs = [ifft3d_local(plan, kr[c], ki[c]) for c in range(kr.shape[0])]
    if plan.real:
        return jnp.stack(outs)
    return (jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs]))


# ---------------------------------------------------------------------------
# global entry points
# ---------------------------------------------------------------------------

def make_fft3d(mesh, n, *, spec: EngineSpec | None = None,
               u_axes=("data",), v_axes=("model",), real: bool | None = None,
               components: int = 0, autotune: bool = False,
               tune_kwargs: dict | None = None):
    """Build jitted (forward, inverse, plan) over globally-sharded arrays.

    Global input layout: X-pencil ``(Ny, Nz, Nx)`` sharded ``P(u, v, None)``
    (plus a leading component axis if ``components``); output Z-pencil
    ``(Kx, Ny, Nz)`` sharded the same way.

    ``spec`` is the one engine-configuration knob (engine, backend,
    schedule, chunks, vector_mode, r2c_packed, fused_roundtrip — see
    :class:`~repro.core.engine_spec.EngineSpec`); ``real`` stays a separate
    argument because it describes the *problem* (the data model of the
    field being transformed), overriding ``spec.real`` when given.

    ``u_axes``/``v_axes`` bind the two grid dimensions to mesh axes; either
    may span several (e.g. ``u_axes=("pod", "data")``), in which case every
    engine — including the RDMA rings — runs one per-axis exchange per
    mesh axis (the staged multi-axis schedule of ``core.transpose``).

    ``autotune=True`` ignores the explicit engine configuration and
    instead sweeps the plan space for this ``(n, mesh, real, components)``
    problem (see ``repro.tuning``), reusing the persistent plan cache when
    a prior run already timed it. ``tune_kwargs`` forwards extra options
    to ``repro.tuning.autotune`` (``cache_path``, ``max_candidates``,
    ``iters``, ``fwd_weight``, ``inv_weight``, ...).
    """
    n = (n, n, n) if isinstance(n, int) else tuple(n)
    s = spec if spec is not None else EngineSpec()
    if real is not None:
        s = s.replace(real=bool(real))
    if autotune:
        from repro.tuning import autotune as _autotune
        from repro.tuning.space import Candidate
        result = _autotune(mesh, n, real=s.real, components=components,
                           u_axes=u_axes, v_axes=v_axes,
                           **(tune_kwargs or {}))
        best = Candidate.from_config(result.best_config)  # legacy-net aware
        s = best.spec(real=s.real)
    grid = PencilGrid.from_mesh(mesh, u_axes, v_axes)
    plan = FFT3DPlan.from_spec(n, grid, s)
    real = s.real
    vector_mode = s.vector_mode
    base = grid.pencil_spec()
    spec = P(*((None,) + tuple(base))) if components else base

    def fwd_local(xr, xi):
        f = functools.partial(fft3d_vector_local, plan, vector_mode=vector_mode) \
            if components else functools.partial(fft3d_local, plan)
        return f(xr, xi)

    def inv_local(kr, ki):
        f = functools.partial(ifft3d_vector_local, plan, vector_mode=vector_mode) \
            if components else functools.partial(ifft3d_local, plan)
        return f(kr, ki)

    if real:
        fwd = jax.jit(compat.shard_map(
            lambda x: fwd_local(x, None), mesh=mesh,
            in_specs=spec, out_specs=(spec, spec), check_vma=False))
        inv = jax.jit(compat.shard_map(
            inv_local, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
            check_vma=False))
    else:
        fwd = jax.jit(compat.shard_map(
            fwd_local, mesh=mesh,
            in_specs=(spec, spec), out_specs=(spec, spec), check_vma=False))
        inv = jax.jit(compat.shard_map(
            inv_local, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
            check_vma=False))
    # dispatch-boundary spans (one branch + tail call while tracing is off);
    # jit surfaces like ``.lower`` forward through the wrapper
    attrs = {
        "engine": plan.comm_engine, "n": list(n),
        "mesh": "x".join(str(int(q)) for q in grid.u_sizes + grid.v_sizes),
    }
    fwd = obs.traced_call(fwd, "dispatch/fft3d.fwd", attrs)
    inv = obs.traced_call(inv, "dispatch/fft3d.inv", attrs)
    return fwd, inv, plan
