"""Pallas async-RDMA ring exchange — the paper's NIC offload (§4.2–4.3).

The thesis' central hardware claim is that the fold communications are
driven by a NIC that streams blocks *while* the butterfly engines compute
(Fig. 4.3, tasks C/G): the send of block r+1 is started, the current
block's butterflies run, and only then does the engine wait on the wire.
``overlap_ring`` (``core.comm``) emits that schedule at the XLA level and
*hopes* the latency-hiding scheduler honors it; this module makes the
schedule explicit in a Pallas kernel built on double-buffered
``pltpu.make_async_remote_copy`` neighbor DMA — the TPU rendition of the
FPGA NIC's APEnet-style RDMA engine.

Two lowerings behind one contract (``ring_exchange_rdma`` mirrors
``core.transpose.ring_exchange`` exactly — same block order, same
rank-major merge, bit-identical relayout):

* **TPU** — one fused kernel per exchange: a barrier-semaphore handshake
  with the ring's peers, then P−1 direct-send rounds (``device_id`` = mesh
  coordinate ``me+r`` along the ring axis, routed over the torus exactly
  like the shift-by-r ``ppermute`` the plain ring uses, Eq. 5.6), each
  round starting the next RDMA before waiting the current one. When a planar
  ``payload`` pair rides along, its radix-2 butterflies
  (``fft_radix2.butterfly_stages`` — the same stage code as the 1D
  engine kernel) run *inside* the kernel between ``start`` and ``wait``,
  so send/compute overlap is explicit rather than hoped-for.
* **interpret (CPU/CI)** — this JAX has no cross-device DMA emulation, so
  the wire hop is ``lax.ppermute`` while the NIC's *local* data movement
  (staging the send block, landing the received block in its output slot)
  runs through Pallas kernels in interpret mode. Numerically this path is
  the torus ring relayout by construction; CI pins it bit-exact against
  ``torus`` on 4x2/2x4/8x1 meshes (``tests/_dist_transpose_check.py``).

``ring_exchange_bidi_rdma`` is the **two-NIC** variant (Fig. 5.9): each
round sends to *both* torus neighbors over per-direction semaphores, so the
exchange finishes in ``ceil((P−1)/2)`` double-buffered rounds instead of
P−1; off-TPU it lowers to the counter-rotating ``ppermute`` streams of
``transpose.ring_exchange_bidi``.

When a grid dimension spans several mesh axes (a ``CommStep`` whose
``grid_dim`` resolves to e.g. ``("pod", "data")``), both entry points run
**one ring per mesh axis** via ``transpose.staged_exchange``: every hop
stays a single-axis neighbor RDMA, the round count drops to
Σᵢ rounds(qᵢ), and the composition is bit-exact vs the flat ring over the
product group.

All entry points run *inside* ``shard_map`` over the FFT mesh axes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core import transpose as tr
from repro.kernels.fft_radix2 import butterfly_stages
from repro.kernels.ref import is_pow2, twiddle_lanes_np
from repro.obs import stages


def use_rdma() -> bool:
    """True when the real inter-chip RDMA lowering is available (TPU)."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# interpret path: Pallas NIC staging kernels + ppermute wire hop
# ---------------------------------------------------------------------------

def _nic_take_kernel(idx_ref, xs_ref, out_ref):
    """Stage block ``idx`` of the stacked (P, ...) buffer for the wire."""
    out_ref[0] = xs_ref[idx_ref[0]]


def _nic_place_kernel(idx_ref, blk_ref, out_in_ref, out_ref):
    """Land a received block in output slot ``idx`` (in-place via aliasing)."""
    del out_in_ref  # aliased with out_ref — the in-place landing buffer
    out_ref[idx_ref[0]] = blk_ref[0]


def _smem_index(idx):
    return jnp.reshape(jnp.asarray(idx, jnp.int32), (1,))


def nic_take(xs, idx):
    """Pallas-staged read of block ``idx`` from a stacked (P, ...) buffer,
    keeping the leading length-1 axis (the wire format of one block)."""
    return pl.pallas_call(
        _nic_take_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((1,) + xs.shape[1:], xs.dtype),
        interpret=True,
        name="nic_take",
    )(_smem_index(idx), xs)


def nic_place(out, blk, idx):
    """Pallas-staged write of one received (1, ...) block into slot ``idx``
    of the stacked output buffer (aliased — no copy of the full buffer)."""
    return pl.pallas_call(
        _nic_place_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={2: 0},
        interpret=True,
        name="nic_place",
    )(_smem_index(idx), blk, out)


def _ring_interpret(arrs, axes, *, split_axis: int, concat_axis: int,
                    interleave=None):
    """The RDMA ring's schedule with ``lax.ppermute`` standing in for the
    inter-chip hop (no DMA emulation off-TPU). Identical block order to
    ``transpose.ring_exchange``: round r ships the block for rank me+r and
    lands the block from rank me−r; ``interleave()`` is emitted right after
    the first round's sends (the Fig. 4.3 overlap window)."""
    p = compat.axes_size(axes)
    me = compat.flat_axis_index(axes)
    name = axes if len(axes) > 1 else axes[0]

    xss = [tr.stack_blocks(x, p, split_axis) for x in arrs]
    with jax.named_scope(stages.FOLD_EXCHANGE):
        outs = [nic_place(jnp.zeros_like(xs), nic_take(xs, me), me)
                for xs in xss]
        follow = None
        for r in range(1, p):
            perm = [(i, (i + r) % p) for i in range(p)]
            recvs = [lax.ppermute(nic_take(xs, (me + r) % p), name, perm)
                     for xs in xss]
            if follow is None and interleave is not None:
                follow = interleave()
            outs = [nic_place(o, recv, (me - r) % p)
                    for o, recv in zip(outs, recvs)]
    return [tr.merge_blocks(o, p, concat_axis) for o in outs], follow


# ---------------------------------------------------------------------------
# TPU path: fused double-buffered RDMA kernel
# ---------------------------------------------------------------------------

def _chunk_bounds(total: int, parts: int, i: int) -> tuple[int, int]:
    """Row range [off, off+cnt) of chunk ``i`` when ``total`` rows are cut
    into ``parts`` near-equal chunks (first ``total % parts`` get +1)."""
    base, rem = divmod(total, parts)
    off = i * base + min(i, rem)
    return off, base + (1 if i < rem else 0)


def _payload_chunk(pr_ref, pi_ref, twr_ref, twi_ref, diag_refs, *,
                   off, cnt, n_payload: int, inverse: bool):
    """Transform payload rows [off, off+cnt): the in-kernel compute of one
    ring round. Plain mode runs the forward (or conjugate-trick inverse)
    radix-2 butterflies; roundtrip mode (``diag_refs``) runs the *whole*
    spectral middle — forward butterflies, pointwise diagonal multiply,
    inverse butterflies — in one visit (the paper's NIC offload extended
    from butterflies to the spectral computation)."""
    cr = pr_ref[pl.ds(off, cnt), :]
    ci = pi_ref[pl.ds(off, cnt), :]
    if inverse:
        ci = -ci
    yr, yi = butterfly_stages(cr, ci, twr_ref[...], twi_ref[...], n_payload)
    if inverse:
        scale = jnp.asarray(1.0 / n_payload, yr.dtype)
        yr, yi = yr * scale, -(yi * scale)
    if diag_refs is not None:
        dr_ref, di_ref = diag_refs
        dr = dr_ref[pl.ds(off, cnt), :]
        di = di_ref[pl.ds(off, cnt), :]
        kr = yr * dr - yi * di
        ki = yr * di + yi * dr
        zr, zi = butterfly_stages(kr, -ki, twr_ref[...], twi_ref[...],
                                  n_payload)
        scale = jnp.asarray(1.0 / n_payload, zr.dtype)
        yr, yi = zr * scale, -(zi * scale)
    return yr, yi


def _ring_barrier(me, axis_name: str, p: int):
    """Handshake with every other rank of the ring before the first send.

    Each rank writes straight into its peers' output buffers, so it may
    start only once every peer has entered this kernel: signal the shared
    barrier semaphore on each peer, then wait for all P−1 signals.
    """
    barrier = pltpu.get_barrier_semaphore()
    for r in range(1, p):
        pltpu.semaphore_signal(barrier, 1,
                               device_id={axis_name: lax.rem(me + r, p)},
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, p - 1)


def _rdma_ring_kernel(*refs, axis_name: str, p: int, n_arrays: int,
                      n_payload: int, payload_rows: int, inverse: bool,
                      roundtrip: bool):
    """P−1 direct-send RDMA rounds with in-kernel butterflies.

    Round r: start the round-r+1 send, run payload chunk r−1's butterfly
    stages while both copies are in flight, then wait round r. Per-round
    semaphore slots (no reuse) keep the one-ahead pipeline hazard-free.
    """
    fused = n_payload > 0
    xs = refs[:n_arrays]
    i = n_arrays
    diag_refs = None
    if fused:
        pr_ref, pi_ref, twr_ref, twi_ref = refs[i:i + 4]
        i += 4
        if roundtrip:
            diag_refs = refs[i:i + 2]
            i += 2
    outs = refs[i:i + n_arrays]
    i += n_arrays
    if fused:
        qr_ref, qi_ref = refs[i:i + 2]
        i += 2
    copy_sem, send_sem, recv_sem = refs[i:i + 3]

    me = lax.axis_index(axis_name)
    _ring_barrier(me, axis_name, p)

    # own block never touches the wire: local async DMA x[me] -> out[me]
    for a in range(n_arrays):
        dma = pltpu.make_async_copy(xs[a].at[me], outs[a].at[me], copy_sem)
        dma.start()
        dma.wait()

    def start_round(r):
        dst = lax.rem(me + r, p)
        ops = []
        for a in range(n_arrays):
            rdma = pltpu.make_async_remote_copy(
                src_ref=xs[a].at[dst],       # block destined for rank me+r
                dst_ref=outs[a].at[me],      # lands in the remote slot "me"
                send_sem=send_sem.at[r - 1, a],
                recv_sem=recv_sem.at[r - 1, a],
                device_id={axis_name: dst},
                device_id_type=pltpu.DeviceIdType.MESH)
            rdma.start()
            ops.append(rdma)
        return ops

    in_flight = {1: start_round(1)}
    for r in range(1, p):
        if r + 1 < p:
            in_flight[r + 1] = start_round(r + 1)   # next block's send
        if fused:
            # current block's butterflies, while the copies fly (Fig. 4.3)
            off, cnt = _chunk_bounds(payload_rows, p - 1, r - 1)
            if cnt:
                yr, yi = _payload_chunk(pr_ref, pi_ref, twr_ref, twi_ref,
                                        diag_refs, off=off, cnt=cnt,
                                        n_payload=n_payload, inverse=inverse)
                qr_ref[pl.ds(off, cnt), :] = yr
                qi_ref[pl.ds(off, cnt), :] = yi
        for rdma in in_flight.pop(r):               # then wait
            rdma.wait()


def _rdma_bidi_kernel(*refs, axis_name: str, p: int, n_arrays: int,
                      n_payload: int, payload_rows: int, inverse: bool,
                      roundtrip: bool):
    """ceil((P−1)/2) double-buffered rounds over *both* torus directions.

    Round r starts the clockwise send (block me+r, routed +r) and the
    counter-clockwise send (block me−r, routed −r on the opposite links) —
    the paper's two-NIC node of Fig. 5.9 — then starts round r+1's pair,
    runs payload chunk r−1's butterflies while all copies fly, and waits
    round r. Semaphore slots are per (round, direction, array): dim 1 is
    0=clockwise, 1=counter-clockwise, so the counter-rotating streams never
    share a semaphore. Even rings skip the duplicate farthest hop
    (r == P−r) and ship that block clockwise only.
    """
    fused = n_payload > 0
    xs = refs[:n_arrays]
    i = n_arrays
    diag_refs = None
    if fused:
        pr_ref, pi_ref, twr_ref, twi_ref = refs[i:i + 4]
        i += 4
        if roundtrip:
            diag_refs = refs[i:i + 2]
            i += 2
    outs = refs[i:i + n_arrays]
    i += n_arrays
    if fused:
        qr_ref, qi_ref = refs[i:i + 2]
        i += 2
    copy_sem, send_sem, recv_sem = refs[i:i + 3]

    me = lax.axis_index(axis_name)
    rounds = tr.bidi_rounds(p)
    _ring_barrier(me, axis_name, p)

    # own block never touches the wire: local async DMA x[me] -> out[me]
    for a in range(n_arrays):
        dma = pltpu.make_async_copy(xs[a].at[me], outs[a].at[me], copy_sem)
        dma.start()
        dma.wait()

    def start_round(r):
        dirs = [(0, lax.rem(me + r, p))]            # clockwise: +r
        if r != p - r:                              # ccw: −r (skip duplicate)
            dirs.append((1, lax.rem(me - r + p, p)))
        ops = []
        for d, dst in dirs:
            for a in range(n_arrays):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=xs[a].at[dst],       # block destined for rank dst
                    dst_ref=outs[a].at[me],      # lands in the remote slot "me"
                    send_sem=send_sem.at[r - 1, d, a],
                    recv_sem=recv_sem.at[r - 1, d, a],
                    device_id={axis_name: dst},
                    device_id_type=pltpu.DeviceIdType.MESH)
                rdma.start()
                ops.append(rdma)
        return ops

    in_flight = {1: start_round(1)}
    for r in range(1, rounds + 1):
        if r + 1 <= rounds:
            in_flight[r + 1] = start_round(r + 1)   # next pair's sends
        if fused:
            off, cnt = _chunk_bounds(payload_rows, rounds, r - 1)
            if cnt:
                yr, yi = _payload_chunk(pr_ref, pi_ref, twr_ref, twi_ref,
                                        diag_refs, off=off, cnt=cnt,
                                        n_payload=n_payload, inverse=inverse)
                qr_ref[pl.ds(off, cnt), :] = yr
                qi_ref[pl.ds(off, cnt), :] = yi
        for rdma in in_flight.pop(r):               # then wait both streams
            rdma.wait()


def _lane_dense(xs):
    """View a stacked (P, ...) block buffer as (P, M/128, 128).

    The kernel moves whole blocks (``.at[rank]`` on the leading axis), so
    a block's inner shape is free; Mosaic pads a last axis shorter than
    the 128 lanes and then refuses the block slice, so the DMAs see the
    block as full lane rows where its size allows.
    """
    m = math.prod(xs.shape[1:])
    return xs.reshape(xs.shape[0], m // 128, 128) if m % 128 == 0 else xs


def _ring_rdma_tpu(arrs, axes, *, split_axis: int, concat_axis: int,
                   payload=None, diag=None, inverse: bool = False,
                   bidi: bool = False, interpret=False):
    """Build and invoke the fused RDMA kernel for one exchange
    (``interpret``: a ``pltpu.InterpretParams`` runs it under Pallas' TPU
    interpreter, remote DMAs and semaphores emulated across CPU devices)."""
    p = compat.axes_size(axes)
    axis_name = axes[0]
    stacked = [tr.stack_blocks(x, p, split_axis) for x in arrs]
    xss = [_lane_dense(xs) for xs in stacked]
    dtype = xss[0].dtype

    fused = payload is not None
    operands = list(xss)
    out_shape = [jax.ShapeDtypeStruct(xs.shape, xs.dtype) for xs in xss]
    n_payload = payload_rows = 0
    n_vmem_in = 0
    lead = None
    if fused:
        pr, pi = payload
        lead = pr.shape[:-1]
        n_payload = pr.shape[-1]
        payload_rows = int(pr.size) // n_payload
        twr_np, twi_np = twiddle_lanes_np(n_payload, str(jnp.dtype(dtype)))
        operands += [pr.reshape(payload_rows, n_payload),
                     pi.reshape(payload_rows, n_payload),
                     jnp.asarray(twr_np), jnp.asarray(twi_np)]
        n_vmem_in = 4
        if diag is not None:
            # roundtrip mode: the diagonal multiplier rows ride along,
            # already broadcast to the payload's shape by the caller
            dgr, dgi = diag
            operands += [dgr.reshape(payload_rows, n_payload),
                         dgi.reshape(payload_rows, n_payload)]
            n_vmem_in = 6
        out_shape += [jax.ShapeDtypeStruct((payload_rows, n_payload), dtype)
                      for _ in range(2)]

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [any_spec] * len(xss)
    out_specs = [any_spec] * len(xss)
    if fused:
        # payload + twiddles (+ diag rows) live in VMEM for the in-kernel
        # butterflies
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)] * n_vmem_in
        out_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2

    kernel = functools.partial(
        _rdma_bidi_kernel if bidi else _rdma_ring_kernel,
        axis_name=axis_name, p=p, n_arrays=len(xss),
        n_payload=n_payload, payload_rows=payload_rows, inverse=inverse,
        roundtrip=diag is not None)
    # per-direction semaphore slots for the bidi kernel (dim 1: cw, ccw)
    sem_shape = ((max(tr.bidi_rounds(p), 1), 2, len(xss)) if bidi
                 else (max(p - 1, 1), len(xss)))
    with jax.named_scope(stages.FOLD_EXCHANGE):
        results = pl.pallas_call(
            kernel,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA(sem_shape),
                pltpu.SemaphoreType.DMA(sem_shape),
            ],
            compiler_params=pltpu.CompilerParams(collective_id=0),
            interpret=interpret,
            name="rdma_bidi_ring" if bidi else "rdma_ring",
        )(*operands)

    outs = [tr.merge_blocks(o.reshape(xs.shape), p, concat_axis)
            for o, xs in zip(results[:len(xss)], stacked)]
    follow = None
    if fused:
        qr, qi = results[len(xss):len(xss) + 2]
        follow = (qr.reshape(*lead, n_payload), qi.reshape(*lead, n_payload))
    return outs, follow


# ---------------------------------------------------------------------------
# public contract (mirrors transpose.ring_exchange)
# ---------------------------------------------------------------------------

def _kernel_interpret(interpret):
    """The fused kernel's ``pallas_call(interpret=...)``, or ``None`` for the
    ppermute path: ``False`` compiles it for the chip, a
    ``pltpu.InterpretParams`` runs the kernel itself on CPU devices."""
    if isinstance(interpret, pltpu.InterpretParams):
        return interpret
    return None if interpret else False


def fusable_payload(payload) -> bool:
    """True when the TPU kernel can butterfly this payload in-kernel:
    a planar (re, im) pair with a power-of-two last axis."""
    if payload is None:
        return False
    pr, pi = payload
    return (pr.shape == pi.shape and pr.ndim >= 1
            and is_pow2(pr.shape[-1]) and pr.shape[-1] >= 2)


def ring_exchange_rdma(arrs, axes, *, split_axis: int, concat_axis: int,
                       interleave=None, payload=None, diag=None,
                       inverse: bool = False,
                       interpret=None):
    """Tiled ring all-to-all of ``arrs`` through the async-RDMA NIC engine.

    Contract-compatible with ``transpose.ring_exchange``: returns
    ``(outs, follow)`` where ``follow`` is the ``interleave()`` result (the
    block-granular overlap thunk) or, on the fused TPU path, the
    butterflied ``payload`` pair. ``interleave`` and ``payload`` are
    mutually exclusive: a thunk is emitted between rounds at the JAX level
    (interpret path — XLA schedules it under the remaining hops), a payload
    is transformed *inside* the kernel between ``start`` and ``wait``
    (TPU path). ``inverse`` applies the conjugate-trick inverse FFT to the
    payload. ``diag`` (a planar multiplier pair broadcast to the payload's
    shape) switches the payload to **roundtrip** mode: forward
    butterflies, pointwise diagonal multiply, conjugate-trick inverse
    butterflies — the whole spectral middle of a fused solver step in one
    payload visit.

    ``interpret``: ``None`` picks the kernel on TPU and the ppermute path
    elsewhere; ``True`` forces the ppermute path, ``False`` the kernel; a
    ``pltpu.InterpretParams`` runs the kernel under Pallas' TPU interpreter
    on CPU devices (its remote DMAs and barrier emulated), which is how CI
    checks the kernel's own results.

    A grid dimension spanning several communicating mesh axes is **staged
    per axis** (``transpose.staged_exchange``): one double-buffered RDMA
    ring kernel per mesh axis, each with a proper single-axis neighbor
    ``device_id`` — never the flat ``ppermute`` fallback. The payload (or
    thunk) rides the first stage; later stages relay the already
    transformed blocks. The composition is bit-exact vs the flat ring.
    """
    assert interleave is None or payload is None, \
        "interleave (JAX-level thunk) and payload (in-kernel) are exclusive"
    assert diag is None or (payload is not None and not inverse), \
        "diag (roundtrip payload mode) needs a forward payload"
    axes = tuple(axes)
    p = compat.axes_size(axes)
    if p <= 1:
        return [jnp.asarray(a) for a in arrs], None
    if interpret is None:
        interpret = not use_rdma()
    kernel_interpret = _kernel_interpret(interpret)
    comm_axes = tuple(a for a in axes if compat.axes_size((a,)) > 1)
    if len(comm_axes) > 1:
        ex = functools.partial(ring_exchange_rdma, interpret=interpret)
        return tr.staged_exchange(arrs, comm_axes, split_axis=split_axis,
                                  concat_axis=concat_axis, exchange=ex,
                                  interleave=interleave, payload=payload,
                                  diag=diag, inverse=inverse)
    # both single-axis lowerings below bypass tr.ring_exchange, so the wire
    # metering happens here (one fused kernel dispatch covers all rounds)
    tr._meter_exchange(comm_axes, p, tr.ring_rounds(p), arrs,
                       dispatch_kind="rdma", dispatches=1)
    if kernel_interpret is not None:
        # the fused kernel is atomic — a JAX-level thunk can't run between
        # its rounds, so non-fusable compute is emitted before the kernel
        # (serialized; the chunk model prices this, and fusable compute
        # takes the in-kernel payload path instead). The contract still
        # returns the thunk's result so callers' slab pipelines advance.
        follow = interleave() if interleave is not None else None
        outs, fused = _ring_rdma_tpu(arrs, comm_axes,
                                     split_axis=split_axis,
                                     concat_axis=concat_axis, payload=payload,
                                     diag=diag, inverse=inverse,
                                     interpret=kernel_interpret)
        return outs, (fused if payload is not None else follow)
    if payload is not None:
        # no in-kernel butterflies off-TPU: degrade to the thunk contract
        raise ValueError("payload fusion requires the TPU RDMA lowering; "
                         "pass interleave= on the interpret path")
    return _ring_interpret(arrs, axes, split_axis=split_axis,
                           concat_axis=concat_axis, interleave=interleave)


def ring_exchange_bidi_rdma(arrs, axes, *, split_axis: int, concat_axis: int,
                            interleave=None, payload=None, diag=None,
                            inverse: bool = False,
                            interpret=None):
    """Bidirectional (two-NIC) ring all-to-all through the async-RDMA engine.

    Contract-compatible with ``transpose.ring_exchange_bidi`` (and therefore
    with ``ring_exchange_rdma``): same block order, same rank-major merge,
    bit-identical relayout — only the schedule changes, finishing in
    ``ceil((P−1)/2)`` rounds by driving both torus directions per round
    (paper Fig. 5.9). On TPU the exchange is one fused kernel of
    double-buffered ``make_async_remote_copy`` sends to *both* neighbors
    per round with per-direction semaphores (``_rdma_bidi_kernel``); a
    fusable ``payload`` pair is butterflied in-kernel exactly like the
    unidirectional kernel (including the ``diag`` roundtrip payload mode).
    Off-TPU the exchange is the two counter-rotating
    ``ppermute`` streams of ``transpose.ring_exchange_bidi`` — the
    interpret-portable schedule CI pins bit-exact vs ``torus``. Multi-axis
    grid dimensions stage one bidirectional ring per mesh axis
    (``transpose.staged_exchange``), exactly like ``ring_exchange_rdma``.
    """
    assert interleave is None or payload is None, \
        "interleave (JAX-level thunk) and payload (in-kernel) are exclusive"
    assert diag is None or (payload is not None and not inverse), \
        "diag (roundtrip payload mode) needs a forward payload"
    axes = tuple(axes)
    p = compat.axes_size(axes)
    if p <= 1:
        return [jnp.asarray(a) for a in arrs], None
    if interpret is None:
        interpret = not use_rdma()
    kernel_interpret = _kernel_interpret(interpret)
    comm_axes = tuple(a for a in axes if compat.axes_size((a,)) > 1)
    if len(comm_axes) > 1:
        ex = functools.partial(ring_exchange_bidi_rdma, interpret=interpret)
        return tr.staged_exchange(arrs, comm_axes, split_axis=split_axis,
                                  concat_axis=concat_axis, exchange=ex,
                                  interleave=interleave, payload=payload,
                                  diag=diag, inverse=inverse)
    if kernel_interpret is not None:
        # the fused kernel is atomic (see ring_exchange_rdma): non-fusable
        # compute is emitted before it, fusable compute rides the payload.
        # Only this branch meters: the interpret fallback below is
        # tr.ring_exchange_bidi, which meters its own rounds.
        tr._meter_exchange(comm_axes, p, tr.bidi_rounds(p), arrs,
                           dispatch_kind="rdma", dispatches=1)
        follow = interleave() if interleave is not None else None
        outs, fused = _ring_rdma_tpu(arrs, comm_axes,
                                     split_axis=split_axis,
                                     concat_axis=concat_axis, payload=payload,
                                     diag=diag, inverse=inverse, bidi=True,
                                     interpret=kernel_interpret)
        return outs, (fused if payload is not None else follow)
    if payload is not None:
        raise ValueError("payload fusion requires the TPU RDMA lowering; "
                         "pass interleave= on the interpret path")
    return tr.ring_exchange_bidi(arrs, axes, split_axis=split_axis,
                                 concat_axis=concat_axis,
                                 interleave=interleave)
