"""Jit'd public wrappers around the FFT engine.

``fft1d(x_re, x_im, axis=..., backend=...)`` is the only entry point the rest
of the framework uses; ``backend`` selects:

* ``"pallas"`` — the Pallas radix-2 DIF engine (interpret mode off-TPU),
* ``"ref"``    — the pure-jnp oracle with the identical dataflow,
* ``"jnp"``    — ``jnp.fft`` (XLA's FFT), used as ground truth and as the
  fastest CPU path for large development runs,
* ``"mxu"``    — beyond-paper four-step FFT as MXU matmuls (fft_mxu.py).

All take/return planar complex (re, im) pairs, any float dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref as _ref
from repro.kernels.fft_radix2 import fft1d_pallas, ifft1d_pallas
from repro.obs import stages

BACKENDS = ("pallas", "ref", "jnp", "mxu")


def _move_last(x, axis):
    return jnp.moveaxis(x, axis, -1)


def _unmove_last(x, axis):
    return jnp.moveaxis(x, -1, axis)


@functools.partial(jax.jit, static_argnames=("axis", "backend", "inverse"))
def fft1d(x_re, x_im, *, axis: int = -1, backend: str = "pallas", inverse: bool = False):
    """Complex-to-complex FFT along ``axis`` (planar in/out)."""
    assert backend in BACKENDS, backend
    xr, xi = _move_last(x_re, axis), _move_last(x_im, axis)
    if backend == "jnp":
        z = xr.astype(jnp.complex64 if xr.dtype == jnp.float32 else jnp.complex128)
        z = z + 1j * xi.astype(z.dtype)
        z = jnp.fft.ifft(z) if inverse else jnp.fft.fft(z)
        yr, yi = z.real.astype(xr.dtype), z.imag.astype(xr.dtype)
    elif backend == "ref":
        f = _ref.ifft_dif_planar if inverse else _ref.fft_dif_planar
        yr, yi = f(xr, xi)
    elif backend == "mxu":
        from repro.kernels.fft_mxu import fft1d_mxu
        if inverse:
            yr, yi = fft1d_mxu(xr, -xi)
            scale = jnp.asarray(1.0 / xr.shape[-1], xr.dtype)
            yr, yi = yr * scale, -yi * scale
        else:
            yr, yi = fft1d_mxu(xr, xi)
    else:
        f = ifft1d_pallas if inverse else fft1d_pallas
        yr, yi = f(xr, xi)
    return _unmove_last(yr, axis), _unmove_last(yi, axis)


@functools.partial(jax.jit, static_argnames=("axis", "backend", "packed"))
def rfft1d(x, *, axis: int = -1, backend: str = "pallas", packed: bool = False):
    """Real-to-complex FFT keeping N/2+1 bins (paper §3.2.5).

    The faithful default mirrors the thesis: the general complex engine
    does the real transform. It runs two real lines per complex FFT: where
    a leading axis has even length (the outermost such axis), the first
    half of its lines is the real plane and the second half the imaginary
    plane of one ``fft1d`` over half the lines, and
    ``A[k] = (Z[k] + conj Z[-k]) / 2``, ``B[k] = (Z[k] - conj Z[-k]) / 2i``
    untangle the two spectra (scope ``relayout.r2c_untangle``). With no
    such axis each line runs alone as ``(x, 0)``. The choice follows the
    shape only; the trace counts it as ``fft.r2c.paired`` or
    ``fft.r2c.unpaired``.

    ``packed=True`` enables the beyond-paper even/odd packing (one N/2-point
    complex FFT instead of an N-point one). Packing requires an
    even length — the even/odd split assumes ``n % 2 == 0``; odd lengths
    raise at trace time rather than silently mangling the spectrum.
    """
    xr = _move_last(x, axis)
    n = xr.shape[-1]
    if packed and n % 2:
        raise ValueError(
            f"rfft1d(packed=True) requires an even transform length (the "
            f"even/odd packing splits n into two n/2 streams), got n={n}; "
            f"use packed=False for odd lengths")
    if packed:
        yr, yi = _ref.rfft_packed_planar(xr) if backend != "pallas" else _rfft_packed_pallas(xr)
        return _unmove_last(yr, axis), _unmove_last(yi, axis)
    p = _pair_axis(xr.shape)
    obs.metrics.inc("fft.r2c.unpaired" if p is None else "fft.r2c.paired")
    if p is None:
        zr, zi = fft1d(xr, jnp.zeros_like(xr), axis=-1, backend=backend)
        with jax.named_scope(stages.RELAYOUT_R2C_CROP):
            yr, yi = zr[..., : n // 2 + 1], zi[..., : n // 2 + 1]
    else:
        zr, zi = fft1d(*jnp.split(xr, 2, axis=p), axis=-1, backend=backend)
        h = n // 2

        def mirrored(z):  # z[(n - k) mod n] for k = 0..n/2
            return jnp.concatenate([z[..., :1], jnp.flip(z[..., n - h:], -1)], -1)

        with jax.named_scope(stages.RELAYOUT_R2C_UNTANGLE):
            rr, ri = mirrored(zr), mirrored(zi)
            zr, zi = zr[..., : h + 1], zi[..., : h + 1]
            yr = jnp.concatenate([0.5 * (zr + rr), 0.5 * (zi + ri)], axis=p)
            yi = jnp.concatenate([0.5 * (zi - ri), 0.5 * (rr - zr)], axis=p)
    return _unmove_last(yr, axis), _unmove_last(yi, axis)


def _pair_axis(shape) -> int | None:
    """The outermost leading axis of even length, whose halves are paired
    line by line, or None."""
    return next((a for a, d in enumerate(shape[:-1]) if d % 2 == 0), None)


def _rfft_packed_pallas(x):
    """Packed R2C on top of the Pallas engine (untangle stays in jnp)."""
    import numpy as np

    n = x.shape[-1]
    h = n // 2
    zr, zi = fft1d_pallas(x[..., 0::2], x[..., 1::2])
    idx = (-jnp.arange(h)) % h
    zcr, zci = zr[..., idx], -zi[..., idx]
    er, ei = 0.5 * (zr + zcr), 0.5 * (zi + zci)
    o_r, o_i = 0.5 * (zi - zci), -0.5 * (zr - zcr)
    k = np.arange(h)
    wr = jnp.asarray(np.cos(-2 * np.pi * k / n), dtype=x.dtype)
    wi = jnp.asarray(np.sin(-2 * np.pi * k / n), dtype=x.dtype)
    yr = er + (o_r * wr - o_i * wi)
    yi = ei + (o_r * wi + o_i * wr)
    yr = jnp.concatenate([yr, er[..., :1] - o_r[..., :1]], axis=-1)
    yi = jnp.concatenate([yi, ei[..., :1] - o_i[..., :1]], axis=-1)
    return yr, yi


@functools.partial(jax.jit, static_argnames=("axis", "backend", "n"))
def irfft1d(x_re, x_im, *, n: int, axis: int = -1, backend: str = "pallas"):
    """Complex-to-real inverse of N/2+1 bins, rebuilding the Hermitian
    upper half (scope ``relayout.c2r_hermitian``).

    Pairs spectra as :func:`rfft1d` pairs lines: spectrum A (first half of
    the pairing axis) and spectrum B (second half) build one full line
    ``Z = A_full + i B_full``, and one inverse ``fft1d`` over half the lines
    returns line a as its real plane and line b as its imaginary plane. The
    imaginary parts of bin 0 and of bin n/2 (even n) are dropped first, as
    ``numpy.fft.irfft`` drops them; they would leak into the partner line.
    Unpaired, each line's own Hermitian extension runs and the imaginary
    plane of the result is discarded.
    """
    xr, xi = _move_last(x_re, axis), _move_last(x_im, axis)
    h = n // 2
    assert xr.shape[-1] == h + 1, (xr.shape[-1], n)
    p = _pair_axis(xr.shape)
    obs.metrics.inc("fft.r2c.unpaired" if p is None else "fft.r2c.paired")

    def upper(v):  # bins n-1 .. h+1 are the conjugates of bins 1 .. n-h-1
        return jnp.flip(v[..., 1: n - h], -1)

    with jax.named_scope(stages.RELAYOUT_C2R_HERMITIAN):
        if p is None:
            fr = jnp.concatenate([xr, upper(xr)], -1)
            fi = jnp.concatenate([xi, -upper(xi)], -1)
        else:
            ar, br = jnp.split(xr, 2, axis=p)
            k = jax.lax.broadcasted_iota(jnp.int32, ar.shape, ar.ndim - 1)
            real_bin = (k == 0) | (k == h) if n % 2 == 0 else k == 0
            ai, bi = (jnp.where(real_bin, 0, v) for v in jnp.split(xi, 2, axis=p))
            # Z[k] = A[k] + i B[k] up to n/2, conj A[n-k] + i conj B[n-k] above
            fr = jnp.concatenate([ar - bi, upper(ar + bi)], -1)
            fi = jnp.concatenate([ai + br, upper(br - ai)], -1)
    yr, yi = fft1d(fr, fi, axis=-1, backend=backend, inverse=True)
    if p is not None:
        yr = jnp.concatenate([yr, yi], axis=p)
    return _unmove_last(yr, axis)
