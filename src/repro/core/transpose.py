"""Distributed pencil transposes — the paper's "fold communications" (§3.2.4).

Two network models, mirroring §5.5:

* ``mode="switched"`` — a single ``lax.all_to_all`` along the processor-grid
  axis. This is the 2D switched fabric of Fig. 5.10: XLA lowers it to one
  full-bisection exchange; required bandwidth follows Eq. 5.5.
* ``mode="torus"``   — a ring algorithm of P−1 ``lax.ppermute`` rounds, round
  r carrying the block destined r hops away. On a TPU torus a shift-by-r
  collective-permute is routed over r ICI hops, reproducing the multi-hop
  degradation of Eq. 5.6 / Fig. 5.12 (APEnet-style DOR routing).

The torus ring also comes in a **bidirectional** flavor
(:func:`ring_exchange_bidi`): the paper's NIC drives both torus directions
at once (Fig. 5.9 — every node has a +u and a −u link), so the exchange
splits its blocks into a clockwise and a counter-clockwise stream and ships
one block per direction per round, finishing in ``ceil((P−1)/2)`` rounds
instead of P−1 (:func:`bidi_rounds` vs :func:`ring_rounds`).

All functions run *inside* ``shard_map`` over the FFT mesh axes. This module
is the shared block-exchange layer; scheduling (chunking, compute overlap)
belongs to the TransposeEngine implementations in ``core.comm``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat, obs
from repro.obs import stages

MODES = ("switched", "torus")


_flat_axis_index = compat.flat_axis_index
_axis_size = compat.axes_size
_ppermute = lax.ppermute   # one wire-hop primitive (patchable in unit tests)


def _meter_exchange(axes, p: int, rounds: int, arrs, *,
                    dispatch_kind: str, dispatches: int) -> None:
    """Trace-time wire accounting of one single-axis block exchange.

    Runs while jit traces the shard_map body, so it fires once per
    *compilation* from one rank's SPMD view — the analytically pinnable
    quantities: ``comm.exchange_rounds.<axis>`` (wire rounds this exchange
    costs), ``comm.exchanges.<axis>`` (exchange invocations, so tests can
    divide out chunking), ``comm.wire_bytes`` (bytes this rank ships:
    (p−1)/p of the payload), and per-primitive dispatch counters
    (``comm.ppermute_dispatches`` / ``comm.all_to_all_dispatches`` /
    ``comm.rdma_dispatches``). Shapes/dtypes are static under tracing, so
    this is pure Python on ints — and a no-op branch when obs is disabled.
    """
    if not obs.is_enabled():
        return
    ax = "*".join(axes)
    obs.metrics.inc(f"comm.exchanges.{ax}")
    obs.metrics.inc(f"comm.exchange_rounds.{ax}", rounds)
    obs.metrics.inc(f"comm.{dispatch_kind}_dispatches", dispatches)
    payload = sum(int(a.size) * a.dtype.itemsize for a in arrs)
    obs.metrics.inc("comm.wire_bytes", payload * (p - 1) // p)


def axis_sizes(axes) -> tuple[int, ...]:
    """Per-mesh-axis bound sizes of a tuple of axis names (static ints)."""
    return tuple(_axis_size((a,)) for a in axes)


def comm_axis_sizes(axes) -> tuple[int, ...]:
    """Sizes of the axes that actually communicate (size > 1).

    The per-axis ring round model sums over exactly these: a grid dimension
    spanning mesh axes of sizes (q₀, …) costs Σᵢ ``wire_rounds(qᵢ)`` rounds,
    not ``wire_rounds(Πqᵢ)``.
    """
    return tuple(q for q in axis_sizes(axes) if q > 1)


def ring_rounds(p: int) -> int:
    """Exchange rounds of the unidirectional ring: P−1 (Fig. 5.9, one NIC)."""
    return max(p - 1, 0)


def bidi_rounds(p: int) -> int:
    """Exchange rounds of the bidirectional ring: ``ceil((P−1)/2)``.

    Both torus directions carry one block per round; when P is even the
    farthest block (P/2 hops either way) goes clockwise only, which is
    exactly what makes ``ceil((P−1)/2) == P//2``. P=2 degenerates to one
    round — both directions name the same neighbor.
    """
    return max(p, 1) // 2


def all_to_all_blocks(x, axes: tuple[str, ...], *, split_axis: int,
                      concat_axis: int, mode: str = "switched"):
    """Exchange P equal blocks of ``x`` (split along ``split_axis``) so block
    j goes to rank j; received blocks concatenate along ``concat_axis``
    ordered by source rank. ``tiled`` all-to-all semantics."""
    assert mode in MODES, mode
    axes = tuple(axes)
    if not axes:  # Pu (or Pv) == 1: the exchange degenerates to identity
        return x
    if mode == "switched":
        name = axes if len(axes) > 1 else axes[0]
        _meter_exchange(axes, _axis_size(axes), 1, (x,),
                        dispatch_kind="all_to_all", dispatches=1)
        with jax.named_scope(stages.FOLD_EXCHANGE):
            return lax.all_to_all(x, name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)
    return _ring_all_to_all(x, axes, split_axis=split_axis,
                            concat_axis=concat_axis)


def stack_blocks(x, p: int, split_axis: int):
    """Cut ``x`` into P blocks along ``split_axis``, stacked on a fresh
    leading axis: (P, ..., blk, ...). Shared by every ring implementation
    (``ring_exchange`` below and the Pallas RDMA ring of
    ``kernels.ring_rdma``) so their wire layouts are identical."""
    n = x.shape[split_axis]
    assert n % p == 0, (n, p)
    with jax.named_scope(stages.RELAYOUT_FOLD_PACK):
        xs = x.reshape(x.shape[:split_axis] + (p, n // p)
                       + x.shape[split_axis + 1:])
        return jnp.moveaxis(xs, split_axis, 0)


def merge_blocks(o, p: int, concat_axis: int):
    """Inverse of the receive side: fold the leading rank axis of ``o`` into
    ``concat_axis`` in rank-major block order (tiled all_to_all semantics)."""
    with jax.named_scope(stages.RELAYOUT_FOLD_UNPACK):
        o = jnp.moveaxis(o, 0, concat_axis)
        return o.reshape(o.shape[:concat_axis]
                         + (p * o.shape[concat_axis + 1],)
                         + o.shape[concat_axis + 2:])


def staged_exchange(arrs, axes, *, split_axis: int, concat_axis: int,
                    exchange, interleave=None, **first_stage_kw):
    """Factor one tiled all-to-all over several mesh axes into sequential
    **per-axis** exchanges — the multi-axis contract of every ring engine.

    A flat ring over the product group Π qᵢ would route most hops across
    pods; running one ring per mesh axis keeps every hop a single-axis
    neighbor exchange (the wafer-scale "all communication is local" layout
    of Orenes-Vera et al.) and costs Σᵢ ``rounds(qᵢ)`` instead of
    ``rounds(Πqᵢ)`` rounds. Because :func:`compat.flat_axis_index` is
    row-major over ``axes``, staging the single-axis exchanges innermost
    axis first reproduces the flat tiled all-to-all **bit-exactly**: the
    result equals ``exchange(arrs, axes, ...)`` over the whole tuple.

    ``exchange(arrs, (axis,), *, split_axis, concat_axis, **kw)`` is the
    single-axis primitive (:func:`ring_exchange`, :func:`ring_exchange_bidi`,
    or the RDMA kernels of ``kernels.ring_rdma``). ``interleave`` and any
    ``first_stage_kw`` (e.g. a fusable RDMA ``payload``) ride the first
    executed stage only — later stages exchange already-transformed blocks.
    """
    axes = tuple(axes)
    sizes = axis_sizes(axes)
    p = math.prod(sizes)
    k = len(axes)
    xss = [stack_blocks(x, p, split_axis) for x in arrs]
    # leading flat-rank axis -> one axis per mesh axis, row-major like the
    # flat rank index, so axis i of the block grid addresses mesh axis i
    xss = [x.reshape(sizes + x.shape[1:]) for x in xss]
    follow, first = None, True
    for i in reversed(range(k)):
        if sizes[i] <= 1:
            continue
        cur = tuple(jnp.moveaxis(x, i, 0) for x in xss)
        kw = dict(first_stage_kw) if first else {}
        if first and interleave is not None:
            kw["interleave"] = interleave
        outs, fl = exchange(cur, (axes[i],), split_axis=0, concat_axis=0, **kw)
        if first:
            follow, first = fl, False
        xss = [jnp.moveaxis(o, 0, i) for o in outs]
    xss = [x.reshape((p,) + x.shape[k:]) for x in xss]
    return [merge_blocks(x, p, concat_axis) for x in xss], follow


def ring_exchange(arrs, axes, *, split_axis: int, concat_axis: int,
                  interleave=None):
    """P−1 ppermute rounds over same-shaped ``arrs``; round r ships the block
    for rank (me+r) mod P. The single ring primitive every ring engine shares
    (``torus`` and ``overlap_ring`` in ``core.comm`` — one implementation, so
    their relayouts cannot drift apart).

    When ``axes`` spans several communicating mesh axes the exchange is
    staged per axis (:func:`staged_exchange`): one ring per mesh axis, bit
    exact vs the flat multi-axis ring but with only neighbor hops per stage.

    ``interleave()`` — compute that is data-independent of the in-flight
    blocks — is emitted right after the first round's sends, so XLA's
    scheduler can run it underneath the remaining P−2 rounds (the
    block-granular overlap of paper Fig. 4.3). Returns
    ``(outs, interleave_result)``; the result is None when no callback ran.
    """
    axes = tuple(axes)
    if len(comm_axis_sizes(axes)) > 1:
        comm_axes = tuple(a for a, q in zip(axes, axis_sizes(axes)) if q > 1)
        return staged_exchange(arrs, comm_axes, split_axis=split_axis,
                               concat_axis=concat_axis, exchange=ring_exchange,
                               interleave=interleave)
    p = _axis_size(axes)
    _meter_exchange(axes, p, ring_rounds(p), arrs,
                    dispatch_kind="ppermute",
                    dispatches=ring_rounds(p) * len(arrs))
    with jax.named_scope(stages.FOLD_EXCHANGE):
        return _ring_rounds(arrs, axes, p, split_axis=split_axis,
                            concat_axis=concat_axis, interleave=interleave)


def _ring_rounds(arrs, axes, p, *, split_axis, concat_axis, interleave):
    """The P−1 rounds of :func:`ring_exchange` over one mesh axis."""
    me = _flat_axis_index(axes)
    name = axes if len(axes) > 1 else axes[0]
    xss = [stack_blocks(x, p, split_axis) for x in arrs]
    # own block stays local
    outs = [lax.dynamic_update_index_in_dim(
        jnp.zeros_like(xs),
        lax.dynamic_index_in_dim(xs, me, axis=0, keepdims=True), me, axis=0)
        for xs in xss]
    follow = None
    for r in range(1, p):
        perm = [(i, (i + r) % p) for i in range(p)]
        recvs = [_ppermute(
            lax.dynamic_index_in_dim(xs, (me + r) % p, axis=0, keepdims=True),
            name, perm) for xs in xss]
        if follow is None and interleave is not None:
            follow = interleave()
        outs = [lax.dynamic_update_index_in_dim(o, recv, (me - r) % p, axis=0)
                for o, recv in zip(outs, recvs)]

    return [merge_blocks(o, p, concat_axis) for o in outs], follow


def ring_exchange_bidi(arrs, axes, *, split_axis: int, concat_axis: int,
                       interleave=None):
    """The ring exchange over *both* torus directions at once (Fig. 5.9).

    Round r ships the block for rank (me+r) mod P clockwise and the block
    for rank (me−r) mod P counter-clockwise — two counter-rotating
    ``ppermute`` streams on opposite links, so all P−1 foreign blocks are
    on the wire after ``bidi_rounds(P) == ceil((P−1)/2)`` rounds instead of
    P−1. When P is even, the farthest block (r == P−r) is shared between
    the directions and goes clockwise only. Same contract, block order, and
    rank-major merge as :func:`ring_exchange` — the relayout is
    bit-identical; only the schedule (and the round count) changes.

    Multi-axis tuples stage per axis like :func:`ring_exchange`, with both
    directions driven within every stage.
    """
    axes = tuple(axes)
    if len(comm_axis_sizes(axes)) > 1:
        comm_axes = tuple(a for a, q in zip(axes, axis_sizes(axes)) if q > 1)
        return staged_exchange(arrs, comm_axes, split_axis=split_axis,
                               concat_axis=concat_axis,
                               exchange=ring_exchange_bidi,
                               interleave=interleave)
    p = _axis_size(axes)
    # ppermute dispatches: one clockwise stream per round, plus the
    # counter-clockwise stream except the shared-farthest-block round
    ccw = bidi_rounds(p) - (1 if p % 2 == 0 else 0)
    _meter_exchange(axes, p, bidi_rounds(p), arrs,
                    dispatch_kind="ppermute",
                    dispatches=(bidi_rounds(p) + ccw) * len(arrs))
    with jax.named_scope(stages.FOLD_EXCHANGE):
        return _bidi_rounds(arrs, axes, p, split_axis=split_axis,
                            concat_axis=concat_axis, interleave=interleave)


def _bidi_rounds(arrs, axes, p, *, split_axis, concat_axis, interleave):
    """The ``bidi_rounds(P)`` rounds of :func:`ring_exchange_bidi` over one
    mesh axis."""
    me = _flat_axis_index(axes)
    name = axes if len(axes) > 1 else axes[0]
    xss = [stack_blocks(x, p, split_axis) for x in arrs]
    # own block stays local
    outs = [lax.dynamic_update_index_in_dim(
        jnp.zeros_like(xs),
        lax.dynamic_index_in_dim(xs, me, axis=0, keepdims=True), me, axis=0)
        for xs in xss]
    follow = None
    for r in range(1, bidi_rounds(p) + 1):
        # clockwise stream: block me+r over the +r direction
        perm_cw = [(i, (i + r) % p) for i in range(p)]
        recvs_cw = [_ppermute(
            lax.dynamic_index_in_dim(xs, (me + r) % p, axis=0, keepdims=True),
            name, perm_cw) for xs in xss]
        # counter-clockwise stream: block me−r over the −r direction,
        # concurrently on the opposite links (skipped when it would be the
        # clockwise block again: P even, r == P−r)
        recvs_ccw = None
        if r != p - r:
            perm_ccw = [(i, (i - r) % p) for i in range(p)]
            recvs_ccw = [_ppermute(
                lax.dynamic_index_in_dim(xs, (me - r) % p, axis=0,
                                         keepdims=True),
                name, perm_ccw) for xs in xss]
        if follow is None and interleave is not None:
            follow = interleave()
        outs = [lax.dynamic_update_index_in_dim(o, recv, (me - r) % p, axis=0)
                for o, recv in zip(outs, recvs_cw)]
        if recvs_ccw is not None:
            outs = [lax.dynamic_update_index_in_dim(o, recv, (me + r) % p,
                                                    axis=0)
                    for o, recv in zip(outs, recvs_ccw)]

    return [merge_blocks(o, p, concat_axis) for o in outs], follow


def _ring_all_to_all(x, axes, *, split_axis: int, concat_axis: int):
    outs, _ = ring_exchange((x,), axes, split_axis=split_axis,
                            concat_axis=concat_axis)
    return outs[0]


# ---------------------------------------------------------------------------
# The two fold communications of the 3D FFT (hardware tasks C and G, §4.2).
# All operate on the LAST THREE axes; arbitrary leading (batch / μ-component)
# axes pass through untouched — this is what the paper's "parallel vector
# processing" (§4.4.1) rides on.
# ---------------------------------------------------------------------------

def permute_last3(a, perm: tuple[int, int, int]):
    """Apply a permutation of the LAST THREE axes; leading axes untouched.

    This is the ``CommStep.permute`` executor: ``(2, 1, 0)`` is the X↔Y
    fold's transpose (`_swap_last3`), ``(0, 2, 1)`` the Y↔Z fold's
    (`_swap_last2`).
    """
    d = a.ndim
    with jax.named_scope(stages.RELAYOUT_PERMUTE):
        return a.transpose(tuple(range(d - 3)) + tuple(d - 3 + i for i in perm))


def _swap_last3(a):
    perm = tuple(range(a.ndim - 3)) + (a.ndim - 1, a.ndim - 2, a.ndim - 3)
    with jax.named_scope(stages.RELAYOUT_PERMUTE):
        return a.transpose(perm)


def _swap_last2(a):
    perm = tuple(range(a.ndim - 3)) + (a.ndim - 3, a.ndim - 1, a.ndim - 2)
    with jax.named_scope(stages.RELAYOUT_PERMUTE):
        return a.transpose(perm)


def xy_fold(a, u_axes, *, mode="switched"):
    """X-pencil → Y-pencil: (..., Ny/Pu, Nz/Pv, Kx) → (..., Kx/Pu, Nz/Pv, Ny).

    Data moves only among the Pu ranks of the same processor-grid row
    (§3.2.6) — rows and columns never exchange traffic.
    """
    d = a.ndim
    b = all_to_all_blocks(a, u_axes, split_axis=d - 1, concat_axis=d - 3, mode=mode)
    return _swap_last3(b)


def xy_unfold(a, u_axes, *, mode="switched"):
    """Y-pencil → X-pencil (inverse of xy_fold)."""
    d = a.ndim
    b = _swap_last3(a)  # (..., Ny, Nz/Pv, Kx/Pu)
    return all_to_all_blocks(b, u_axes, split_axis=d - 3, concat_axis=d - 1, mode=mode)


def yz_fold(a, v_axes, *, mode="switched"):
    """Y-pencil → Z-pencil: (..., Kx/Pu, Nz/Pv, Ny) → (..., Kx/Pu, Ny/Pv, Nz).

    Moves along the Pv ranks of the same grid column.
    """
    d = a.ndim
    b = all_to_all_blocks(a, v_axes, split_axis=d - 1, concat_axis=d - 2, mode=mode)
    return _swap_last2(b)


def yz_unfold(a, v_axes, *, mode="switched"):
    """Z-pencil → Y-pencil (inverse of yz_fold)."""
    d = a.ndim
    b = _swap_last2(a)  # (..., Kx/Pu, Nz, Ny/Pv)
    return all_to_all_blocks(b, v_axes, split_axis=d - 2, concat_axis=d - 1, mode=mode)
