"""Beyond-paper Pallas kernel: four-step (Bailey) FFT as MXU matmuls.

The thesis' radix-2 butterfly engine is the faithful baseline
(``fft_radix2.py``); it maps to the TPU VPU (8×128 vector lanes,
~4 TFLOP/s f32). The four-step decomposition N = n1·n2 instead computes

    X = DFT_N(x)  via  B = A @ DFT_{n2};  C = B ∘ W (twiddle);
                       D = DFT_{n1}ᵀ @ C;  X = flatten(Dᵀ)

— three dense (n1, n2)-shaped complex matmuls per pencil, which run on the
MXU at ``Precision.HIGHEST`` (f32 in bf16 passes would miss the f32 bound) (197 TFLOP/s bf16 / ~99 f32). Napkin: N=4096 → n1=n2=64; matmul FLOPs
8·N·√N ≈ 2.1 MF vs radix-2's 5·N·log₂N ≈ 0.25 MF — 8.5× more arithmetic on
units with 25–50× the throughput ⇒ ~3–6× faster per pencil, with no
lane-shuffle reorder network at all (the bit-reversal disappears). Mosaic
cannot split a lane axis, so the (n1, n2) view and the final transpose
are XLA relayouts around the kernel. This is the hardware-adaptation
argument of DESIGN.md §3 taken one step further than the paper.

Planar complex in/out; exact vs jnp.fft in tests (f32 ≤2e-4, f64 ≤1e-10).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.ref import is_pow2
from repro.kernels.fft_radix2 import VMEM_BUDGET_BYTES


@functools.lru_cache(maxsize=32)
def _plan(n: int, dtype: str):
    """(n1, n2, DFT_n2, twiddle, DFT_n1) planar numpy tables."""
    s = n.bit_length() - 1
    n1 = 1 << (s // 2)
    n2 = n // n1
    j2 = np.arange(n2)
    d2 = np.exp(-2j * np.pi * np.outer(j2, j2) / n2)
    j1 = np.arange(n1)
    d1 = np.exp(-2j * np.pi * np.outer(j1, j1) / n1)
    tw = np.exp(-2j * np.pi * np.outer(j1, np.arange(n2)) / n)
    cast = lambda a: (a.real.astype(dtype), a.imag.astype(dtype))
    return n1, n2, cast(d2), cast(tw), cast(d1)


def _kernel(xr_ref, xi_ref, d2r_ref, d2i_ref, twr_ref, twi_ref,
            d1r_ref, d1i_ref, or_ref, oi_ref):
    """Steps 1–3 of the four-step FFT on a (TB, n1, n2) tile of pencils.

    Pencil x arrives as A[j1, j2] (n = j1·n2 + j2) — the (n1, n2) split is
    made outside the kernel, since Mosaic cannot split the lane axis — and
    leaves as D[k1, k2] = X[k1 + n1·k2]; the caller transposes D.
    """
    tb, n1, n2 = xr_ref.shape
    dtype = xr_ref.dtype
    xr, xi = xr_ref[...], xi_ref[...]
    # f32 inputs would otherwise run the MXU in single bf16 passes
    mm = functools.partial(lax.dot_general, precision=lax.Precision.HIGHEST,
                           preferred_element_type=dtype)
    batched = (((2,), (1,)), ((0,), (0,)))     # (b,k,j) x (b,j,l) -> (b,k,l)
    d1r = jnp.broadcast_to(d1r_ref[...], (tb, n1, n1))
    d1i = jnp.broadcast_to(d1i_ref[...], (tb, n1, n1))
    # X[k1 + n1·k2] = Σ_{j2} W_{n2}^{j2 k2} W_N^{j2 k1} Σ_{j1} A[j1,j2] W_{n1}^{j1 k1}
    # step 1: length-n1 DFTs along columns (batched MXU matmul)
    br = mm(d1r, xr, batched) - mm(d1i, xi, batched)
    bi = mm(d1r, xi, batched) + mm(d1i, xr, batched)
    # step 2: twiddle W_N^{k1·j2}
    twr, twi = twr_ref[...][None], twi_ref[...][None]
    cr = (br * twr - bi * twi).reshape(tb * n1, n2)
    ci = (br * twi + bi * twr).reshape(tb * n1, n2)
    # step 3: length-n2 DFTs along rows
    plain = (((1,), (0,)), ((), ()))
    d2r, d2i = d2r_ref[...], d2i_ref[...]
    dr = mm(cr, d2r, plain) - mm(ci, d2i, plain)
    di = mm(cr, d2i, plain) + mm(ci, d2r, plain)
    or_ref[...] = dr.reshape(tb, n1, n2)
    oi_ref[...] = di.reshape(tb, n1, n2)


def pick_tile(n1: int, n2: int, batch: int, itemsize: int) -> int:
    """Largest power-of-two TB whose working set fits the VMEM budget.

    VMEM pads each (n1, n2) plane to whole (8, 128) tiles, so small
    factors cost far more than n1·n2 words; about twelve such planes plus
    the two broadcast (n1, n1) DFT blocks are live per pencil.
    """
    def padded(rows, cols):
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * itemsize

    per_pencil = 12 * padded(n1, n2) + 2 * padded(n1, n1)
    tb = 512
    while tb > 8 and tb * per_pencil > VMEM_BUDGET_BYTES:
        tb //= 2
    return max(8, min(tb, max(8, batch)))


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def fft1d_mxu(x_re, x_im, *, tb: int | None = None, interpret: bool | None = None):
    """Batched 1D FFT over the last axis via the four-step MXU kernel."""
    n = x_re.shape[-1]
    assert is_pow2(n) and n >= 4, f"N must be a power of two >= 4, got {n}"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = x_re.dtype
    lead = x_re.shape[:-1]
    n1, n2, (d2r, d2i), (twr, twi), (d1r, d1i) = _plan(n, str(jnp.dtype(dtype)))
    xr = x_re.reshape(-1, n1, n2)
    xi = x_im.reshape(-1, n1, n2)
    b = xr.shape[0]
    tile = tb or pick_tile(n1, n2, b, jnp.dtype(dtype).itemsize)
    pad = (-b) % tile
    if pad:
        xr = jnp.concatenate([xr, jnp.zeros((pad, n1, n2), dtype)], axis=0)
        xi = jnp.concatenate([xi, jnp.zeros((pad, n1, n2), dtype)], axis=0)
    bp = b + pad

    full = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))
    block = pl.BlockSpec((tile, n1, n2), lambda i: (i, 0, 0))
    dr, di = pl.pallas_call(
        _kernel,
        grid=(bp // tile,),
        in_specs=[
            block, block,
            full((n2, n2)), full((n2, n2)),
            full((n1, n2)), full((n1, n2)),
            full((n1, n1)), full((n1, n1)),
        ],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((bp, n1, n2), dtype),
                   jax.ShapeDtypeStruct((bp, n1, n2), dtype)],
        interpret=interpret,
        name="fft_mxu",
    )(xr, xi, jnp.asarray(d2r), jnp.asarray(d2i), jnp.asarray(twr),
      jnp.asarray(twi), jnp.asarray(d1r), jnp.asarray(d1i))
    # step 4: X[k1 + n1·k2] = D[k1, k2]  →  transpose
    yr = dr[:b].transpose(0, 2, 1).reshape(*lead, n)
    yi = di[:b].transpose(0, 2, 1).reshape(*lead, n)
    return yr, yi


def fft_mxu_flops(n: int) -> float:
    """Complex-matmul FLOPs per pencil: 8·N·(n1 + n2)."""
    n1, n2 = _plan(n, "float32")[:2]
    return 8.0 * n * (n1 + n2)


def mxu_vs_butterfly_napkin(n: int, *, mxu_tflops=197e12, vpu_tflops=4e12):
    """The §Perf napkin: time per pencil on each unit (seconds)."""
    butterfly = 5.0 * n * math.log2(n) / vpu_tflops
    four_step = fft_mxu_flops(n) / mxu_tflops
    return {"butterfly_vpu_s": butterfly, "four_step_mxu_s": four_step,
            "speedup": butterfly / four_step}
