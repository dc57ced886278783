"""Pallas TPU kernel: batched radix-2 DIF 1D FFT (the paper's FFT engine).

Paper mapping (§3.3–3.4, §5.1–5.3 → TPU):

* The FPGA engine is ``R`` rows of ``log2(N)`` pipelined butterfly stages with
  shift-register shufflers between stages and a twiddle ROM. On TPU the same
  dataflow becomes: load a tile of ``TB`` independent pencils into VMEM, run
  all ``log2(N)`` butterfly stages back-to-back *in VMEM* (no HBM round trips
  between stages — the analogue of the fully-pipelined chain), apply the
  bit-reversal reordering table, store the tile. The paper's row-parallelism
  ``R`` maps onto the 8×128 vector lanes via the ``TB``-deep batch tile.
* The twiddle ROM is a precomputed ``(log2 N, N)`` planar table (one row per
  stage, laid out along the lanes) passed as a kernel operand and resident in
  VMEM for the whole grid step.
* The shift-register shufflers become lane rotations (``pltpu.roll``) plus
  lane masks: no value is ever reshaped across the lane axis, which Mosaic
  does not lower.
* Complex data is planar ``(re, im)`` float32/float64 — Pallas TPU has no
  native complex dtype.

BlockSpec tiling: grid over the pencil batch; each grid step owns a
``(TB, N)`` block of ``x_re``/``x_im`` plus the full twiddle table. ``TB`` is
chosen so the working set (≈ 6 live ``(TB, N)`` planes + table, double
buffered) fits in 16 MB of VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import is_pow2, twiddle_lanes_np

VMEM_BUDGET_BYTES = 8 * 1024 * 1024  # conservative half of v5e VMEM


def pick_batch_tile(n: int, batch: int, itemsize: int) -> int:
    """Largest power-of-two TB so ~6 live (TB, N) planes fit the VMEM budget."""
    tb = 512
    while tb > 8 and 6 * tb * n * itemsize > VMEM_BUDGET_BYTES:
        tb //= 2
    return max(8, min(tb, max(8, batch)))


def butterfly_stages(xr, xi, twr, twi, n: int):
    """All log2(N) DIF butterfly stages + bit-reversal of (TB, N) values.

    The one copy of the paper's butterfly pipeline (Eq. 3.8) used *inside*
    Pallas kernels: the 1D engine kernel below and the fused RDMA ring
    kernel (``kernels/ring_rdma.py``) both call it, so the stand-alone
    engine and the communication-fused engine cannot drift apart.
    ``twr``/``twi`` are the lane-layout ``(log2 N, N)`` twiddle values
    (``ref.twiddle_lanes_np``).

    Every value keeps its ``(TB, N)`` shape — Mosaic cannot split the lane
    axis — so a butterfly's partner is fetched with a lane rotation
    (``pltpu.roll``) and the top/bottom halves are chosen by a lane mask:
    at stage s (span ``h = N/2^{s+1}``) a top position j takes
    ``x[j] + x[j+h]``, a bottom one ``(x[j-h] − x[j])·W``. The bit-reversal
    "reordering table" is ⌊log2 N / 2⌋ bit-pair swaps, each two rotations
    and a select.
    """
    stages = n.bit_length() - 1
    ax = xr.ndim - 1
    lane = lax.broadcasted_iota(jnp.int32, xr.shape, ax)

    def roll(x, shift):
        # Mosaic rotates by an int32 amount, also when x64 is on
        return pltpu.roll(x, jnp.int32(shift), ax)

    for s in range(stages):  # unrolled: the butterfly pipeline
        half = n >> (s + 1)
        top = (lane & half) == 0

        def pair(x, half=half, top=top):
            # top: a + b (Eq. 3.8); bottom: a − b, twiddled below
            return jnp.where(top, x + roll(x, n - half), roll(x, half) - x)

        sr, si = pair(xr), pair(xi)
        wr, wi = twr[s:s + 1, :], twi[s:s + 1, :]
        xr, xi = sr * wr - si * wi, sr * wi + si * wr
    for a in range(stages // 2):
        b = stages - 1 - a
        d = (1 << b) - (1 << a)
        bit_a, bit_b = (lane >> a) & 1, (lane >> b) & 1
        keep, from_below = bit_a == bit_b, bit_b == 1

        def swap(x, d=d, keep=keep, from_below=from_below):
            # exchange index bits a and b: j ← j ∓ (2^b − 2^a)
            moved = jnp.where(from_below, roll(x, d), roll(x, n - d))
            return jnp.where(keep, x, moved)

        xr, xi = swap(xr), swap(xi)
    return xr, xi


def _fft_kernel(xr_ref, xi_ref, twr_ref, twi_ref, or_ref, oi_ref, *, n: int):
    """One grid step: full DIF FFT of a (TB, N) tile of pencils."""
    yr, yi = butterfly_stages(xr_ref[...], xi_ref[...],
                              twr_ref[...], twi_ref[...], n)
    or_ref[...] = yr
    oi_ref[...] = yi


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def fft1d_pallas(x_re, x_im, *, tb: int | None = None, interpret: bool | None = None):
    """Batched 1D FFT over the last axis via the Pallas engine.

    Accepts any leading shape; pads the flattened pencil batch up to a
    multiple of the batch tile.
    """
    n = x_re.shape[-1]
    assert is_pow2(n) and n >= 2, f"N must be a power of two >= 2, got {n}"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = x_re.dtype
    lead = x_re.shape[:-1]
    xr = x_re.reshape(-1, n)
    xi = x_im.reshape(-1, n)
    b = xr.shape[0]
    tile = tb or pick_batch_tile(n, b, jnp.dtype(dtype).itemsize)
    pad = (-b) % tile
    if pad:
        xr = jnp.concatenate([xr, jnp.zeros((pad, n), dtype)], axis=0)
        xi = jnp.concatenate([xi, jnp.zeros((pad, n), dtype)], axis=0)
    bp = b + pad
    stages = n.bit_length() - 1
    twr_np, twi_np = twiddle_lanes_np(n, str(jnp.dtype(dtype)))
    twr = jnp.asarray(twr_np)
    twi = jnp.asarray(twi_np)

    grid = (bp // tile,)
    out_shape = [
        jax.ShapeDtypeStruct((bp, n), dtype),
        jax.ShapeDtypeStruct((bp, n), dtype),
    ]
    yr, yi = pl.pallas_call(
        functools.partial(_fft_kernel, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, n), lambda i: (i, 0)),
            pl.BlockSpec((tile, n), lambda i: (i, 0)),
            pl.BlockSpec((stages, n), lambda i: (0, 0)),
            pl.BlockSpec((stages, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, n), lambda i: (i, 0)),
            pl.BlockSpec((tile, n), lambda i: (i, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
        name="fft_radix2",
    )(xr, xi, twr, twi)
    yr = yr[:b].reshape(*lead, n)
    yi = yi[:b].reshape(*lead, n)
    return yr, yi


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def ifft1d_pallas(x_re, x_im, *, tb: int | None = None, interpret: bool | None = None):
    """Inverse FFT via the conjugate trick (paper §3.2.4), same engine."""
    n = x_re.shape[-1]
    yr, yi = fft1d_pallas(x_re, -x_im, tb=tb, interpret=interpret)
    scale = jnp.asarray(1.0 / n, dtype=x_re.dtype)
    return yr * scale, -yi * scale
