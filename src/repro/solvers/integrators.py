"""Shared spectral time integrators.

All integrators operate on an arbitrary pytree of arrays (a solver's state
fields — typically planar ``(re, im)`` pairs, possibly with a leading
component axis) so every solver reuses the same stepping machinery:

* :func:`rk4` — classic explicit 4th-order Runge–Kutta on ``∂y = rhs(y)``.
* :func:`ifrk4` — integrating-factor RK4 for ``∂y = decay·y + N(y)``: the
  stiff diagonal linear term (e.g. spectral diffusion ``−νk²``) is
  integrated *exactly* through exponential factors, RK4 handles only the
  nonlinearity. With ``N ≡ 0`` this is the exact propagator, which is how
  the heat solver steps.
* :func:`exp_decay` — that exact linear propagator alone.

``decay`` is a single real array broadcastable against every leaf of ``y``
(spectral multipliers act identically on the re and im planes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import tree_util

from repro.obs import stages


def _map(f, *trees):
    return tree_util.tree_map(f, *trees)


def _axpy(a, x, y):
    """y + a·x, leafwise."""
    return _map(lambda xi, yi: yi + a * xi, x, y)


def rk4(rhs, y, dt):
    """One classic RK4 step of ``∂y = rhs(y)`` on a pytree state."""
    k1 = rhs(y)
    k2 = rhs(_axpy(dt / 2, k1, y))
    k3 = rhs(_axpy(dt / 2, k2, y))
    k4 = rhs(_axpy(dt, k3, y))
    return _map(
        lambda yi, a, b, c, d: yi + (dt / 6) * (a + 2 * b + 2 * c + d),
        y, k1, k2, k3, k4)


def exp_decay(decay, y, dt):
    """Exact propagator of ``∂y = decay·y``: y ← e^{decay·dt} y."""
    e = jnp.exp(decay * dt)
    return _map(lambda yi: e * yi, y)


def ifrk4(nonlin, decay, y, dt):
    """Integrating-factor RK4 for ``∂y = decay·y + N(y)``.

    Substituting ``w = e^{-decay·t} y`` removes the stiff term exactly;
    RK4 on ``w`` then gives (E = e^{decay·dt/2}):

        k1 = N(y)
        k2 = N(E·(y + dt/2·k1))
        k3 = N(E·y + dt/2·k2)
        k4 = N(E²·y + dt·E·k3)
        y ← E²·y + dt/6·(E²·k1 + 2E·(k2 + k3) + k4)
    """
    with jax.named_scope(stages.POINTWISE_IFRK4_FACTORS):
        e1 = jnp.exp(decay * (dt / 2))
        e2 = e1 * e1
    combine = stages.POINTWISE_IFRK4_COMBINE
    mul = lambda e, t: _map(lambda a: e * a, t)
    k1 = nonlin(y)
    with jax.named_scope(combine):
        y2 = mul(e1, _axpy(dt / 2, k1, y))
    k2 = nonlin(y2)
    with jax.named_scope(combine):
        y3 = _axpy(dt / 2, k2, mul(e1, y))
    k3 = nonlin(y3)
    with jax.named_scope(combine):
        y4 = _axpy(dt, mul(e1, k3), mul(e2, y))
    k4 = nonlin(y4)
    with jax.named_scope(combine):
        return _map(
            lambda yi, a, b, c, d: e2 * yi + (dt / 6) * (
                e2 * a + 2 * e1 * (b + c) + d),
            y, k1, k2, k3, k4)
