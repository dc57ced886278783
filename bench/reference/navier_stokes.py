"""Reference for one incompressible Navier–Stokes step on the 2π³ torus.

The rotational-form pseudo-spectral method of the Taylor–Green DNS
literature: ∂v̂/∂t = P(\\widehat{u × ω}) − ν k² v̂, with the nonlinear term
dealiased by the 2/3 rule (a mode is kept when every |k_i| < N/3), the
Leray projection P(â) = â − k (k·â)/k² (k = 0 left as it is), the viscous
term integrated exactly by an integrating-factor RK4 (IFRK4), and the new
state projected once more.

Arrays: a velocity spectrum is ``[3, kx, ky, kz]`` complex with
``kx = 0 .. N/2`` (the program's Z-pencil layout, components x, y, z);
physical fields are ``[3, y, z, x]`` (its X-pencil layout). Wavenumbers are
integers, ``ky``/``kz`` in FFT order with the Nyquist bin at −N/2.

The pointwise stages run on slabs of the second axis in a thread pool
(NumPy releases the interpreter lock in its loops); the transforms use
SciPy's own workers. Both are float64 throughout.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.fft as sfft

WORKERS = os.cpu_count() or 1


class NSStep:
    """``NSStep(n, nu, dt)(v)`` advances a ``[3, kx, ky, kz]`` spectrum one
    IFRK4 step. ``rnd`` rounds every stored array (the control)."""

    def __init__(self, n: int, nu: float, dt: float, rnd=None):
        self.n, self.nu, self.dt = n, nu, dt
        self.rnd = rnd if rnd is not None else (lambda a: a)
        k = np.fft.fftfreq(n, 1.0 / n)
        # internal layout [.., ky, kz, kx] (NumPy's rfftn of [y, z, x])
        self.k = (np.arange(n // 2 + 1)[None, None, :],   # kx
                  k[:, None, None],                       # ky
                  k[None, :, None])                       # kz
        kx, ky, kz = self.k
        self.k2 = kx * kx + ky * ky + kz * kz
        self.inv_k2 = np.where(self.k2 > 0, 1.0 / np.maximum(self.k2, 1), 0.0)
        self.mask = ((np.abs(kx) < n / 3) & (np.abs(ky) < n / 3)
                     & (np.abs(kz) < n / 3)).astype(np.float64)
        self.e1 = np.exp(-nu * self.k2 * (dt / 2))
        self.e2 = self.e1 * self.e1

    # ---- slab-parallel pointwise work -----------------------------------
    def _slabs(self, fn, *arrays):
        """``fn(sl, *slabs)`` on slabs ``sl`` of axis 1 (ky, or y in
        physical space), run in threads; results joined on axis 1."""
        n1 = arrays[0].shape[1]
        step = -(-n1 // WORKERS)
        sls = [slice(i, min(i + step, n1)) for i in range(0, n1, step)]
        with ThreadPoolExecutor(len(sls)) as pool:
            parts = list(pool.map(
                lambda sl: self.rnd(fn(sl, *(a[:, sl] for a in arrays))), sls))
        return np.concatenate(parts, axis=1)

    def _k(self, sl):
        kx, ky, kz = self.k
        return kx, ky[sl], kz

    def _project(self, sl, a):
        kx, ky, kz = ks = self._k(sl)
        dot = (kx * a[0] + ky * a[1] + kz * a[2]) * self.inv_k2[sl]
        return np.stack([a[c] - ks[c] * dot for c in range(3)])

    def project(self, a):
        return self._slabs(self._project, a)

    def _curl(self, sl, v):
        kx, ky, kz = self._k(sl)
        return 1j * np.stack([ky * v[2] - kz * v[1],
                              kz * v[0] - kx * v[2],
                              kx * v[1] - ky * v[0]])

    @staticmethod
    def _cross(sl, u, w):
        return np.stack([u[1] * w[2] - u[2] * w[1],
                         u[2] * w[0] - u[0] * w[2],
                         u[0] * w[1] - u[1] * w[0]])

    def _dealias_project(self, sl, a):
        return self._project(sl, a * self.mask[sl])

    # ---- the method ------------------------------------------------------
    def _phys(self, a):
        return self.rnd(sfft.irfftn(a, s=(self.n,) * 3, axes=(1, 2, 3),
                                    workers=WORKERS))

    def _spec(self, u):
        return self.rnd(sfft.rfftn(u, axes=(1, 2, 3), workers=WORKERS))

    def nonlinear(self, v):
        u = self._phys(v)
        w = self._phys(self._slabs(self._curl, v))
        uxw = self._slabs(self._cross, u, w)
        del u, w
        return self._slabs(self._dealias_project, self._spec(uxw))

    def __call__(self, v_kxkykz):
        """One Δt from a ``[3, kx, ky, kz]`` spectrum; same layout out."""
        dt, e1, e2 = self.dt, self.e1, self.e2
        y = self.rnd(np.moveaxis(np.asarray(v_kxkykz, np.complex128), 1, 3))
        k1 = self.nonlinear(y)
        k2 = self.nonlinear(self._slabs(
            lambda sl, y, k1: e1[sl] * (y + dt / 2 * k1), y, k1))
        k3 = self.nonlinear(self._slabs(
            lambda sl, y, k2: e1[sl] * y + dt / 2 * k2, y, k2))
        k4 = self.nonlinear(self._slabs(
            lambda sl, y, k3: e2[sl] * y + dt * (e1[sl] * k3), y, k3))

        def combine(sl, y, k1, k2, k3, k4):
            a, b = e1[sl], e2[sl]
            out = b * y + dt / 6 * (b * k1 + 2 * a * (k2 + k3) + k4)
            return self._project(sl, self.rnd(out))

        return np.moveaxis(self._slabs(combine, y, k1, k2, k3, k4), 3, 1)
