"""Pallas TPU flash-attention forward kernel (GQA-aware).

The jnp-level chunked attention in ``models/layers.py`` is the portable
implementation (and what the CPU dry-run lowers); this kernel is the
TPU-native version of the same online-softmax dataflow, with explicit
BlockSpec VMEM tiling:

* grid = (batch·heads, S/blk_q, T/blk_k) — the kv dimension is the innermost
  (sequential) grid axis, so (m, l, acc) accumulators live in VMEM scratch
  across kv steps;
* K/V blocks are indexed per *kv-head* (grouped-query: q-head h reads kv-head
  h // group) so grouped heads never materialize repeated K/V;
* blocks strictly above the causal diagonal are masked (and contribute
  nothing); f32 accumulation, bf16/f32 inputs.

Validated in interpret mode against ``ref.flash_attention_ref`` and the
direct softmax oracle (tests/test_flash_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            blk_q: int, blk_k: int, causal: bool, scale: float, nk: int):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # (blk_q, d)
    k = k_ref[0]                                  # (blk_k, d)
    v = v_ref[0]
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale
    if causal:
        qpos = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v.astype(jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 256,
                    blk_k: int = 256, interpret: bool | None = None):
    """q: (B, S, H, D); k/v: (B, T, Hkv, D) with H % Hkv == 0 → (B, S, H, D)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, t)
    while s % blk_q:
        blk_q //= 2
    while t % blk_k:
        blk_k //= 2
    nq, nk = s // blk_q, t // blk_k
    scale = 1.0 / (d ** 0.5)

    qh = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)

    def kv_index(bh, i, j):
        # grouped-query: q row (b*h + hh) reads kv row (b*hkv + hh//g)
        return (bh // h) * hkv + (bh % h) // g, j, 0

    out = pl.pallas_call(
        functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
                          scale=scale, nk=nk),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, blk_k, d), kv_index),
            pl.BlockSpec((1, blk_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
