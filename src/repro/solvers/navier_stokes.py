"""Incompressible Navier–Stokes — the paper's §1.2 case study as a solver.

Pseudo-spectral rotational form on the 2π³ torus:

    ∂v̂/∂t = P( \\widehat{u × ω} ) − ν k² v̂,    ∇·v = 0

The state lives in spectral space (planar ``(vr, vi)``, 3 components); the
nonlinear stage is :func:`repro.core.spectral.rotational_nonlinear_term`
(two inverse + one forward vector FFT per evaluation), time stepping is the
shared integrating-factor RK4 (:func:`integrators.ifrk4`) — the stiff
viscous term is integrated exactly, RK4 handles convection. A Leray
projection after each step pins the velocity to the divergence-free
manifold.

Ported out of ``examples/navier_stokes.py`` (now a thin CLI wrapper).
The Taylor–Green vortex validation — monotone viscous energy decay and
``max|k·v̂|`` at roundoff — matches the example's historical checks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spectral as sp
from repro.core.fft3d import fft3d_vector_local
from repro.obs import stages
from repro.solvers import integrators
from repro.solvers.base import SpectralSolver


class NavierStokesSolver(SpectralSolver):
    case = "navier_stokes"
    real = True
    components = 3

    def __init__(self, mesh, n, *, nu: float = 0.1, dt: float = 2e-3, **kw):
        self.nu = float(nu)
        super().__init__(mesh, n, dt=dt, **kw)

    def params(self) -> dict:
        return {"dt": self.dt, "nu": self.nu}

    def initial_fields(self):
        """Taylor–Green vortex, transformed to spectral space and projected.

        The vortex is divergence-free analytically, but its transform is
        not to roundoff: at float32 ``max|k·v̂|`` grows like ε·N³ (about 2
        at N=256). The same Leray projection every step ends with puts
        the t=0 state on the manifold the solver keeps it on.
        """
        from repro import compat

        nx = self.n[0]
        x = np.linspace(0, 2 * np.pi, nx, endpoint=False)
        Y, Z, X = np.meshgrid(x, x, x, indexing="ij")  # (y, z, x) layout
        u = np.cos(X) * np.sin(Y) * np.sin(Z)
        v = -np.sin(X) * np.cos(Y) * np.sin(Z)
        w = np.zeros_like(u)
        u0 = jnp.asarray(np.stack([u, v, w]).astype(self.dtype))
        spec = self.field_spec()
        def fwd(x):
            vr, vi = fft3d_vector_local(self.plan, x, None,
                                        vector_mode=self.vector_mode)
            return sp.project_divergence_free(self.plan, vr, vi)

        fwd = jax.jit(compat.shard_map(
            fwd, mesh=self.mesh, in_specs=(spec,), out_specs=(spec, spec),
            check_vma=False))
        return fwd(u0)

    def step_fields(self, plan, fields):
        with jax.named_scope(stages.POINTWISE_IFRK4_FACTORS):
            decay = -self.nu * sp.k_squared(plan, fields[0].dtype)

        def nonlin(y):
            return sp.rotational_nonlinear_term(
                plan, y[0], y[1], vector_mode=self.vector_mode)

        vr, vi = integrators.ifrk4(nonlin, decay, fields, self.dt)
        return sp.project_divergence_free(plan, vr, vi)

    def observables_fields(self, plan, fields):
        vr, vi = fields
        return {"energy": sp.energy_spectrum_total(plan, vr, vi),
                "max_div": sp.max_divergence(plan, vr, vi)}

    def validate(self, history):
        energies = [h["energy"] for h in history]
        decays = all(b <= a * (1 + 1e-9) for a, b in zip(energies,
                                                         energies[1:]))
        div_tol = 1e-8 if self.dtype == np.float64 else 1e-3
        div_ok = all(h["max_div"] < div_tol for h in history)
        lines = [f"energy monotone decay: {decays}",
                 f"divergence-free (max|k.v| < {div_tol:g}): {div_ok}"]
        return decays and div_ok, lines
