"""The ``SpectralSolver`` contract — the paper's simulation cycle as a class.

§1.2 frames the machine's purpose as the pseudo-spectral loop

    forward 3D FFT → spectral computation → inverse 3D FFT → local computation

over a distributed ``FFT3DPlan``. A :class:`SpectralSolver` packages one
such workload:

* ``init_state(plan)``   — build the t=0 :class:`SolverState`;
* ``step(state)``        — advance one Δt (one or more FFT cycles), jitted
  through ``shard_map`` over the plan's pencil grid;
* ``observables(state)`` — grid-reduced scalar diagnostics (energy, error
  norms, conserved quantities) as a ``{name: float}`` dict.

Concrete solvers implement the *local* hooks (``initial_fields`` /
``step_fields`` / ``observables_fields``) plus a ``validate`` check against
an analytic or NumPy reference; the base class owns plan construction
(including the x64/dtype gate and the TPU float64 refusal), shard_map
compilation, and the run loop.

The FFT plan knobs (backend / schedule / chunks / comm_engine /
vector_mode / r2c_packed / fused_roundtrip) come either from ``plan_cfg``
— e.g. the winner of ``repro.tuning.autotune_solver_step``, which times
*this class's whole step* per candidate — or from the same
pipelined/switched default the Navier–Stokes example always used.

Solvers whose spectral stage is a pointwise-diagonal k-space multiply
(heat, poisson, the NLS kinetic half-step) declare it via the
``spectral_kernel`` hook and step through
:func:`repro.core.fft3d.spectral_roundtrip_local`, which streams the
Y↔Z roundtrip as one slab pipeline when the plan's ``fused_roundtrip``
knob is on (and is the plain composed cycle when it is off).

The **batched** entry points (``batched_step`` / ``batched_observables``)
advance a stack of B independent instances of the same problem — the same
step body ``jax.vmap``-ed over an unsharded leading batch axis inside the
same ``shard_map`` — in one dispatch on the mesh; ``repro.serving`` builds
its request batching on them.
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, ClassVar

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.core import precision
from repro.core.decomposition import PencilGrid
from repro.core.fft3d import FFT3DPlan


@dataclasses.dataclass
class SolverState:
    """Evolving solver state: sharded field pytree + host-side clock."""

    fields: Any                # pytree of (possibly sharded) jax arrays
    t: float = 0.0             # physical time
    n_steps: int = 0


class SpectralSolver(abc.ABC):
    """Common contract every FFT-cycle simulation workload implements."""

    case: ClassVar[str]            # registry name (``--case`` on the CLI)
    real: ClassVar[bool] = True    # r2c transform (False: planar complex)
    components: ClassVar[int] = 0  # leading vector axis (0 = scalar field)

    def __init__(self, mesh, n, *, dt: float = 1e-2, dtype="float32",
                 plan_cfg: dict | None = None):
        self.mesh = mesh
        self.n = (n, n, n) if isinstance(n, int) else tuple(n)
        self.dt = float(dt)
        self.dtype = np.dtype(precision.require_dtype(
            dtype, who=f"solvers.{self.case}",
            platform=precision.mesh_platform(mesh)))
        grid = PencilGrid.from_mesh(mesh)
        cfg = dict(schedule="pipelined", chunks=2, backend="jnp",
                   comm_engine="switched", r2c_packed=False,
                   fused_roundtrip=False)
        self.vector_mode = "streaming"
        if plan_cfg:
            from repro.tuning.space import normalize_config
            plan_cfg = normalize_config(plan_cfg)
            cfg.update({k: plan_cfg[k] for k in cfg if k in plan_cfg})
            self.vector_mode = plan_cfg.get("vector_mode", self.vector_mode)
        self.plan = FFT3DPlan(n=self.n, grid=grid, real=self.real,
                              dtype=self.dtype.name, **cfg)
        self._compile()

    # ---- solver-specific hooks ------------------------------------------
    @abc.abstractmethod
    def initial_fields(self):
        """Global t=0 field pytree (host-side; base shards it on first use)."""

    @abc.abstractmethod
    def step_fields(self, plan: FFT3DPlan, fields):
        """One Δt of the FFT→spectral→iFFT→local cycle (inside shard_map)."""

    @abc.abstractmethod
    def observables_fields(self, plan: FFT3DPlan, fields) -> dict:
        """Grid-reduced scalar diagnostics (inside shard_map)."""

    def spectral_kernel(self, plan: FFT3DPlan, dtype):
        """The solver's k-space stage as a ``fft3d.DiagonalKernel``, when
        it is a pointwise-diagonal multiply (heat's ``e^{−κk²Δt}``,
        poisson's ``−1/k²``, NLS' kinetic rotation). ``None`` (the
        default) means the stage is not diagonal — e.g. the Navier–Stokes
        nonlinear term — and the fused-roundtrip executor does not apply."""
        del plan, dtype
        return None

    @abc.abstractmethod
    def validate(self, history: list[dict]) -> tuple[bool, list[str]]:
        """(ok, report lines) judging a run against the analytic reference.

        ``history[i]`` is ``observables`` after i steps (``history[0]`` is
        t=0), each dict augmented with ``"t"``.
        """

    def params(self) -> dict:
        """Physics parameters identifying this problem (cache fingerprint)."""
        return {"dt": self.dt}

    # ---- compiled machinery ---------------------------------------------
    def field_spec(self) -> P:
        """PartitionSpec prefix applied to every leaf of ``fields``."""
        base = self.plan.grid.pencil_spec()
        return P(None, *base) if self.components else base

    def _compile(self):
        plan, mesh, spec = self.plan, self.mesh, self.field_spec()
        self._stepj = jax.jit(compat.shard_map(
            functools.partial(self.step_fields, plan), mesh=mesh,
            in_specs=(spec,), out_specs=spec, check_vma=False))
        self._obsj = jax.jit(compat.shard_map(
            functools.partial(self.observables_fields, plan), mesh=mesh,
            in_specs=(spec,), out_specs=P(), check_vma=False))
        self._batched_stepj = None   # built lazily by batched_step_fns()

    # ---- batched stepping (the serving layer's entry point) --------------
    def batch_spec(self) -> P:
        """``field_spec()`` with an unsharded leading batch axis prepended:
        B independent problem instances stacked along axis 0, each shard
        holding the full batch of its own pencil."""
        return P(None, *self.field_spec())

    def batched_step_fns(self):
        """``(step, observables)`` jitted over a leading batch axis.

        ``step`` maps a fields pytree whose leaves carry an extra leading
        axis of size B — B independent simulations of *this* problem,
        stacked — through one sharded solver step: the per-instance
        ``step_fields`` body is ``jax.vmap``-ed over the batch axis inside
        the same ``shard_map`` the solo path compiles, so the whole batch
        advances in a single dispatch on the mesh and each lane's
        trajectory is bitwise what the solo ``step()`` computes (CI pins
        this across the mesh × engine matrix). ``observables`` reduces the
        same stack to ``{name: (B,) array}``.

        Compiled lazily on first use and cached on the solver; jit's shape
        cache keys on B, so a given batch size compiles once per solver.
        """
        if self._batched_stepj is None:
            plan, mesh, bspec = self.plan, self.mesh, self.batch_spec()
            self._batched_stepj = jax.jit(compat.shard_map(
                jax.vmap(functools.partial(self.step_fields, plan)),
                mesh=mesh, in_specs=(bspec,), out_specs=bspec,
                check_vma=False))
            self._batched_obsj = jax.jit(compat.shard_map(
                jax.vmap(functools.partial(self.observables_fields, plan)),
                mesh=mesh, in_specs=(bspec,), out_specs=P(None),
                check_vma=False))
        return self._batched_stepj, self._batched_obsj

    def batched_step(self, fields):
        """One Δt for a leading-batch-axis stack of field pytrees."""
        return self.batched_step_fns()[0](fields)

    def batched_observables(self, fields) -> dict:
        """``{name: (B,) float array}`` diagnostics for a batched stack."""
        return self.batched_step_fns()[1](fields)

    def problem_key(self) -> str:
        """This solver's plan-cache fingerprint key — the canonical id of
        (case, shape, dtype, physics params, substrate) that
        ``repro.tuning`` keys tuned plans by and ``repro.serving`` groups
        batchable requests by."""
        from repro.tuning.cache import problem_fingerprint

        g = self.plan.grid
        key, _ = problem_fingerprint(
            self.n, g.pu, g.pv, real=self.real, components=self.components,
            dtype=self.dtype.name, u_axes=g.u_axes, v_axes=g.v_axes,
            case=self.case, solver_params=self.params())
        return key

    # ---- checkpoint contract (repro.fleet rides on this) -----------------
    def state_tree(self, state: SolverState):
        """``state`` as a checkpointable pytree for ``CheckpointManager``.

        Leaves are the (sharded) field arrays plus the host-side clock as
        0-d numpy scalars; flat tree paths are mesh-shape-independent, so a
        snapshot written here restores on any pencil grid of the same
        problem (:meth:`restore_state` is the inverse)."""
        return {"fields": state.fields,
                "t": np.float64(state.t),
                "n_steps": np.int64(state.n_steps)}

    def restore_state(self, manager, step: int | None = None
                      ) -> tuple[SolverState, dict]:
        """``(state, manifest meta)`` from ``manager``'s checkpoint.

        The elastic path of the fleet's retry loop: the snapshot may have
        been written by a solver of the same problem on a *different*
        submesh shape — leaves are stored as full logical arrays, and this
        method re-places them with **this** solver's shardings
        (``NamedSharding(self.mesh, self.field_spec())``). Restoring onto
        the same shape is bitwise; a different shape changes only the
        layout, so the continued trajectory matches to roundoff."""
        fields = self.initial_fields()         # shape/dtype template
        target = {"fields": fields, "t": np.float64(0.0),
                  "n_steps": np.int64(0)}
        sh = jax.sharding.NamedSharding(self.mesh, self.field_spec())
        shardings = {"fields": jax.tree.map(lambda _: sh, fields)}
        tree, meta = manager.restore(target, step=step, shardings=shardings)
        return SolverState(fields=tree["fields"], t=float(tree["t"]),
                           n_steps=int(tree["n_steps"])), meta

    # ---- public contract -------------------------------------------------
    def init_state(self, plan: FFT3DPlan | None = None) -> SolverState:
        assert plan is None or plan == self.plan, \
            "a solver steps the plan it was compiled for"
        return SolverState(fields=self.initial_fields(), t=0.0, n_steps=0)

    def step(self, state: SolverState) -> SolverState:
        """One Δt. Under ``repro.obs`` the ``dispatch/solver.step`` span
        times the dispatch; the step's device time is in the device trace,
        under the stage scopes."""
        if not obs.is_enabled():
            return SolverState(fields=self._stepj(state.fields),
                               t=state.t + self.dt, n_steps=state.n_steps + 1)
        with obs.span("dispatch/solver.step", case=self.case,
                      engine=self.plan.comm_engine):
            fields = self._stepj(state.fields)
        return SolverState(fields=fields, t=state.t + self.dt,
                           n_steps=state.n_steps + 1)

    def observables(self, state: SolverState) -> dict:
        with obs.span("dispatch/solver.observables"):
            out = {k: float(v) for k, v in self._obsj(state.fields).items()}
        out["t"] = state.t
        return out

    def run(self, steps: int, *, callback=None):
        """Advance ``steps`` Δt from t=0; returns (state, observable history)."""
        state = self.init_state()
        history = [self.observables(state)]
        if callback:
            callback(state, history[-1])
        for _ in range(steps):
            state = self.step(state)
            history.append(self.observables(state))
            if callback:
                callback(state, history[-1])
        return state, history

    def plan_config(self) -> dict:
        """The FFT-plan knobs this solver compiled against (bench metadata)."""
        p = self.plan
        return {"backend": p.backend, "schedule": p.schedule,
                "chunks": p.chunks, "comm_engine": p.comm_engine,
                "net": p.net, "vector_mode": self.vector_mode,
                "r2c_packed": p.r2c_packed,
                "fused_roundtrip": p.fused_roundtrip, "dtype": p.dtype}
