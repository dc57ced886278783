"""Spectral-space operators for FFT-based pseudo-spectral solvers (§1.2).

All functions operate on Z-pencil spectral fields — local shape
``(..., Kx/Pu, Ny/Pv, Nz)`` inside ``shard_map`` — and therefore need the
*local* wavenumber slabs, which depend on the rank's (u, v) grid coordinates.

Complex spectral fields are carried as planar ``(re, im)`` array pairs.
``dtype=None`` arguments resolve to :func:`repro.core.precision
.default_real_dtype` — the widest real dtype this process actually computes
in — instead of silently demoting a hardcoded float64.

These operators are the shared vocabulary of ``repro.solvers``: every
concrete solver's "spectral computation" stage (the middle of the paper's
FFT → spectral → iFFT → local cycle) is built from them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat
from repro.core import precision
from repro.core.fft3d import FFT3DPlan, fft3d_vector_local, ifft3d_vector_local
from repro.obs import stages


_flat_index = compat.flat_axis_index


def _dtype(dtype):
    return precision.default_real_dtype() if dtype is None else dtype


def local_wavenumbers(plan: FFT3DPlan, dtype=None):
    """(kx, ky, kz) integer wavenumbers for this rank's Z-pencil slab.

    kx: slab of the padded spectral X axis (r2c keeps 0..N/2 then zeros);
    ky: slab of fftfreq-ordered Ny; kz: full fftfreq-ordered Nz.
    """
    dtype = _dtype(dtype)
    nx, ny, nz = plan.n
    g = plan.grid
    u = _flat_index(g.u_axes)
    v = _flat_index(g.v_axes)

    def fftfreq_int(n):
        k = jnp.arange(n)
        return jnp.where(k <= n // 2 - 1 + (n % 2), k, k - n).astype(dtype)

    if plan.real:
        kx_full = jnp.arange(plan.kx, dtype=dtype)  # bins beyond keep are pad
    else:
        kx_full = fftfreq_int(nx)
    lx = plan.kx // g.pu
    kx = lax.dynamic_slice_in_dim(kx_full, u * lx, lx)

    ky_full = fftfreq_int(ny)
    ly = ny // g.pv
    ky = lax.dynamic_slice_in_dim(ky_full, v * ly, ly)

    kz = fftfreq_int(nz)
    return kx[:, None, None], ky[None, :, None], kz[None, None, :]


def pad_mask(plan: FFT3DPlan, dtype=None):
    """1 on significant kx bins, 0 on the r2c shard padding."""
    dtype = _dtype(dtype)
    g = plan.grid
    u = _flat_index(g.u_axes)
    lx = plan.kx // g.pu
    idx = u * lx + jnp.arange(lx)
    return (idx < plan.kx_keep).astype(dtype)[:, None, None]


def dealias_mask(plan: FFT3DPlan, dtype=None):
    """2/3-rule mask for the pseudo-spectral nonlinear term."""
    dtype = _dtype(dtype)
    kx, ky, kz = local_wavenumbers(plan, dtype)
    nx, ny, nz = plan.n
    m = ((jnp.abs(kx) < nx / 3.0)
         & (jnp.abs(ky) < ny / 3.0)
         & (jnp.abs(kz) < nz / 3.0))
    out = m.astype(dtype)
    if plan.real:
        out = out * pad_mask(plan, dtype)
    return out


def k_squared(plan: FFT3DPlan, dtype=None):
    kx, ky, kz = local_wavenumbers(plan, _dtype(dtype))
    return kx * kx + ky * ky + kz * kz


def invert_laplacian(plan: FFT3DPlan, fr, fi, *, mean: float = 0.0):
    """Solve ∇²φ = f in spectral space: φ̂ = −f̂ / k².

    The inverse Laplacian is defined only up to a constant — the k=0 mode
    carries the domain mean, and −f̂/k² is singular there. ``mean`` fixes
    the gauge: the returned field's mean is set to it (``0.0`` reproduces
    the classic zero-mean Poisson gauge). Only the rank owning the k=0 bin
    touches it, expressed uniformly via the local ``k² == 0`` mask.
    """
    k2 = k_squared(plan, fr.dtype)
    inv = jnp.where(k2 > 0, -1.0 / jnp.maximum(k2, 1e-30), 0.0)
    if plan.real:
        inv = inv * pad_mask(plan, fr.dtype)
    pr, pi = fr * inv, fi * inv
    if mean:
        ntot = plan.n[0] * plan.n[1] * plan.n[2]  # unnormalized forward FFT
        zero_mode = (k2 == 0)
        if plan.real:
            zero_mode = zero_mode & (pad_mask(plan, fr.dtype) > 0)
        pr = jnp.where(zero_mode, jnp.asarray(mean * ntot, pr.dtype), pr)
    return pr, pi


def poisson_solve(plan: FFT3DPlan, fr, fi):
    """∇²φ = f  ⇒  φ̂ = −f̂ / k² (zero-mean gauge; k=0 mode zeroed)."""
    return invert_laplacian(plan, fr, fi, mean=0.0)


def gradient(plan: FFT3DPlan, fr, fi):
    """∂/∂(x,y,z) in spectral space: multiply by i·k (planar complex)."""
    kx, ky, kz = local_wavenumbers(plan, fr.dtype)
    outs = []
    for k in (kx, ky, kz):
        outs.append((-k * fi, k * fr))  # i*k*(fr + i fi) = -k fi + i k fr
    return outs


def curl(plan: FFT3DPlan, vr, vi):
    """Vorticity ω̂ = i k × v̂ for a planar (3, ...) spectral field."""
    with jax.named_scope(stages.POINTWISE_CURL):
        kx, ky, kz = local_wavenumbers(plan, vr.dtype)

        def cross_k(ar):
            return jnp.stack([ky * ar[2] - kz * ar[1],
                              kz * ar[0] - kx * ar[2],
                              kx * ar[1] - ky * ar[0]])

        # i*(k × v): (i k) × (vr + i vi) = -(k × vi) + i (k × vr)
        return -cross_k(vi), cross_k(vr)


def project_divergence_free(plan: FFT3DPlan, vr, vi):
    """Leray projection: v̂ ← v̂ − k (k·v̂)/k² for a 3-component field.

    Used by the Navier–Stokes solver to enforce incompressibility.
    """
    with jax.named_scope(stages.POINTWISE_PROJECT):
        kx, ky, kz = local_wavenumbers(plan, vr.dtype)
        ks = (kx, ky, kz)
        k2 = k_squared(plan, vr.dtype)
        dot_r = sum(ks[c] * vr[c] for c in range(3))
        dot_i = sum(ks[c] * vi[c] for c in range(3))
        inv = jnp.where(k2 > 0, 1.0 / jnp.maximum(k2, 1e-30), 0.0)
        pr = jnp.stack([vr[c] - ks[c] * dot_r * inv for c in range(3)])
        pi = jnp.stack([vi[c] - ks[c] * dot_i * inv for c in range(3)])
        return pr, pi


def rotational_nonlinear_term(plan: FFT3DPlan, vr, vi, *,
                              vector_mode="streaming", project=True):
    """Dealiased rotational-form convection term \\widehat{u × ω}.

    The pseudo-spectral nonlinear stage shared by the incompressible
    Navier–Stokes solver (and any rotational-form momentum equation):
    inverse-transform velocity and vorticity, form u × ω pointwise in
    physical space, forward-transform, 2/3-dealias, and (optionally) Leray
    project. Exactly one forward + two inverse vector transforms — the cost
    model the tuning objective prices.
    """
    u = ifft3d_vector_local(plan, vr, vi, vector_mode=vector_mode)
    wr, wi = curl(plan, vr, vi)
    w = ifft3d_vector_local(plan, wr, wi, vector_mode=vector_mode)
    with jax.named_scope(stages.POINTWISE_CROSS):
        uxw = jnp.stack([u[1] * w[2] - u[2] * w[1],
                         u[2] * w[0] - u[0] * w[2],
                         u[0] * w[1] - u[1] * w[0]])
    nr, ni = fft3d_vector_local(plan, uxw, None, vector_mode=vector_mode)
    with jax.named_scope(stages.POINTWISE_DEALIAS):
        mask = dealias_mask(plan, nr.dtype)
        nr, ni = nr * mask, ni * mask
    if project:
        nr, ni = project_divergence_free(plan, nr, ni)
    return nr, ni


def grid_sum(plan: FFT3DPlan, x):
    """Sum of local scalar ``x`` over the whole Pu×Pv processor grid."""
    g = plan.grid
    axes = tuple(g.u_axes) + tuple(g.v_axes)
    if axes:
        x = lax.psum(x, axes if len(axes) > 1 else axes[0])
    return x


def grid_max(plan: FFT3DPlan, x):
    """Max of local scalar ``x`` over the whole Pu×Pv processor grid."""
    g = plan.grid
    axes = tuple(g.u_axes) + tuple(g.v_axes)
    if axes:
        x = lax.pmax(x, axes if len(axes) > 1 else axes[0])
    return x


def energy_spectrum_total(plan: FFT3DPlan, vr, vi):
    """Total kinetic energy Σ|v̂|² over local slab (psum over the grid)."""
    return grid_sum(plan, jnp.sum(vr * vr + vi * vi))


def max_divergence(plan: FFT3DPlan, vr, vi):
    """max |k·v̂| over the grid — the divergence-free diagnostic."""
    kx, ky, kz = local_wavenumbers(plan, vr.dtype)
    div = jnp.max(jnp.abs(kx * vr[0] + ky * vr[1] + kz * vr[2])) + \
        jnp.max(jnp.abs(kx * vi[0] + ky * vi[1] + kz * vi[2]))
    return grid_max(plan, div)
