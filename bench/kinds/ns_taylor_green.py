"""Taylor–Green vortex DNS: ``SpectralSolver.step`` of the Navier–Stokes case.

Set-up builds the solver through ``repro.solvers.make_solver`` with the
configuration's viscosity, Δt and dtype, and its own initial state (the
Taylor–Green vortex, Leray-projected in spectral space). The seed then
translates the vortex by a shift ``s`` in [0, 2π)³: every mode is
multiplied by ``exp(-i k·s)`` on the device. A translation leaves the
physics and the divergence-free constraint as they are and gives each
seed different data.

Correctness, after the window: one step of the timed path, drawn from the
seed with equal chances for every step of the window (reservoir
sampling), is redone by the float64 reference
(``bench.reference.navier_stokes``) from the state the program had before
it. ``step_err`` is the relative L2 error of the program's increment
``v_k − v_{k−1}`` against the reference's, over all three components and
the whole half spectrum (padding rows, which must stay zero, included).
"""

from __future__ import annotations

import numpy as np

from bench.reference import bf16
from bench.reference import navier_stokes as ref


class Case:
    def __init__(self, config, traffic, mesh, seed):
        self.config, self.mesh = config, mesh
        self.n = int(traffic["n"])
        if "n" in config and int(config["n"]) != self.n:
            raise ValueError(f"configuration {config['name']} runs at "
                             f"n={config['n']}, traffic asks n={self.n}")
        rng = np.random.default_rng(int(seed))
        self.shift = rng.uniform(0.0, 2 * np.pi, 3)
        self.pick = np.random.default_rng([int(seed), 1])

    def setup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from repro.solvers import SolverState, make_solver

        cfg, n = self.config, self.n
        self.solver = make_solver(cfg["case"], self.mesh, n, nu=cfg["nu"],
                                  dt=cfg["dt"], dtype=cfg["dtype"])
        fields = self.solver.init_state().fields
        sharding = NamedSharding(self.mesh, self.solver.field_spec())
        kx_len = fields[0].shape[1]

        def translate(vr, vi, s):
            k = jnp.arange(n)
            k = jnp.where(k < n // 2, k, k - n).astype(vr.dtype)
            kx = jnp.arange(kx_len, dtype=vr.dtype)[:, None, None]
            th = -(kx * s[0] + k[None, :, None] * s[1]
                   + k[None, None, :] * s[2])
            c, sn = jnp.cos(th), jnp.sin(th)
            return vr * c - vi * sn, vr * sn + vi * c

        shift = jnp.asarray(self.shift, fields[0].dtype)
        fields = jax.jit(translate, out_shardings=(sharding, sharding))(
            *fields, shift)
        self.state0 = SolverState(fields=fields)
        for _ in range(2):            # compile, then one warm call
            jax.block_until_ready(self.solver.step(self.state0).fields)
        self.state = self.state0
        self.count = 0

    def step(self):
        prev = self.state
        self.state = self.solver.step(prev)
        self.count += 1
        if self.pick.random() * self.count < 1:     # keep with chance 1/count
            self.sampled = (prev.fields, self.state.fields)
        return self.state.fields

    def programs(self):
        """The compiled program the window runs, with arguments to lower it
        by: its HLO names the trace's ops. ``SpectralSolver.step`` runs the
        solver's jitted step, which it keeps as ``_stepj``."""
        return [(self.solver._stepj, (self.state0.fields,))]

    def collect(self):
        """Host copies of the sampled step's states; frees the program's
        device state."""
        host = {"states": tuple(_complex(f) for f in self.sampled)}
        del self.state, self.state0, self.sampled
        return host

    def compare(self, host):
        """The compared numbers: ``{name: value}``; keeps the reference's
        step in ``host`` for :meth:`control`."""
        prev, got = host["states"]
        host["want"] = self._reference()(prev[:, :self.n // 2 + 1])
        return {"step_err": self._err(prev, got, host["want"])}

    def control(self, host):
        """``step_err`` with the reference in bfloat16 in the program's
        place."""
        prev, _ = host["states"]
        low = self._reference(bf16)(prev[:, :self.n // 2 + 1])
        return {"step_err": self._err(prev, low, host["want"])}

    def _reference(self, rnd=None):
        cfg = self.config
        return ref.NSStep(self.n, cfg["nu"], cfg["dt"], rnd=rnd)

    def _err(self, prev, got, want):
        """Relative L2 gap between a step's increment and the reference's;
        rows past N/2 (padding, which must be zero) count as error."""
        keep = self.n // 2 + 1
        pad = np.sum(np.abs(got[:, keep:]) ** 2)
        num = np.sum(np.abs(got[:, :keep] - want) ** 2) + pad
        den = np.sum(np.abs(want - prev[:, :keep]) ** 2)
        return float(np.sqrt(num / den))


def _complex(fields):
    vr, vi = fields
    return np.asarray(vr).astype(np.float64) + 1j * np.asarray(vi)
