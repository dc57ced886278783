"""The serving loop: admit a same-fingerprint batch, advance it as one
sharded step per Δt, stream each lane's observables back to its requester.

:class:`SimServer` ties the layer together. ``submit(request)`` returns a
:class:`~repro.serving.request.Ticket` immediately (or raises
:class:`~repro.serving.queue.QueueFullError` under backpressure); a
scheduling round pulls the oldest fingerprint lane from the queue, fetches
that shape's persistent compiled engine from the
:class:`~repro.serving.registry.EngineRegistry`, stacks the lanes' initial
fields along a leading batch axis, and then steps the whole batch through
``SpectralSolver.batched_step`` — one dispatch on the mesh per Δt, however
many requests ride in it. After every step the batched observables are
pulled once and fanned out as per-lane ``StepUpdate``s, so requesters see
their trajectory live, not at the end.

**Identity guarantee**: a lane's streamed history is exactly what a solo
``SpectralSolver`` run of the same request computes — the batched step is
the same ``shard_map`` body ``vmap``-ed over the batch axis, and the
clocks accumulate identically. ``tests/_dist_serving_check.py`` pins this
bitwise across the CI mesh × engine matrix.

**Run-to-longest batching**: lanes whose ``steps`` differ batch together;
the batch advances ``max(steps)`` times and a lane simply stops receiving
updates (and gets its result) once its own horizon is reached. Finished
lanes keep computing until the batch retires — wasted FLOPs bounded by the
step spread, zero recompiles (retiring a lane mid-flight would change the
batch shape and force a fresh XLA executable).

The server can run synchronously (``serve_pending()`` drains the queue on
the caller's thread — tests, batch jobs) or threaded (``start()`` spawns a
scheduler thread that wakes on submit — the load generator and the CLI).
All jax dispatch happens on whichever single thread runs the scheduling
rounds.
"""

from __future__ import annotations

import collections
import threading
import time

import jax

from repro import obs
from repro.fleet.records import FailureRecord
from repro.serving.queue import RequestQueue
from repro.serving.registry import EngineRegistry
from repro.serving.request import (SimRequest, SimResult, StepUpdate, Ticket,
                                   request_key)


def scaled_initial_fields(solver, scale: float):
    """The solver's t=0 fields with the request's amplitude applied.

    The one definition both the server and the solo-reference checks use,
    so "batched ≡ solo" compares identical initial conditions.
    """
    fields = solver.initial_fields()
    if scale == 1.0:
        return fields
    return jax.tree.map(lambda a: a * scale, fields)


class SimServer:
    """Batched spectral-simulation server bound to one device mesh."""

    def __init__(self, mesh, *, max_batch: int = 8,
                 max_pending: int | None = None,
                 registry: EngineRegistry | None = None,
                 use_plan_cache: bool = True,
                 cache_path: str | None = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.mesh = mesh
        self.max_batch = max_batch
        self.registry = registry or EngineRegistry(
            mesh, use_plan_cache=use_plan_cache, cache_path=cache_path)
        self.queue = RequestQueue(max_pending)
        # per-lane failure trail, same structured type the fleet uses
        # (bounded: serving failures are diagnostics, not campaign state)
        self.failures: collections.deque[FailureRecord] = collections.deque(
            maxlen=256)
        self._seq = 0
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # ---- submission ------------------------------------------------------
    def submit(self, req: SimRequest) -> Ticket:
        """Enqueue; returns the requester's streaming ticket immediately."""
        if req.steps < 0:
            raise ValueError(f"steps must be >= 0, got {req.steps}")
        fp = request_key(req)
        with self._lock:
            self._seq += 1
            ticket = Ticket(req, fp, self._seq)
        self.queue.submit(ticket)          # raises QueueFullError when full
        obs.metrics.inc("serving.requests.submitted")
        self._wake.set()
        return ticket

    # ---- scheduling rounds ----------------------------------------------
    def serve_once(self) -> int:
        """Admit and run one batch; returns the number of requests served."""
        batch = self.queue.next_batch(self.max_batch)
        if not batch:
            return 0
        self._run_batch(batch)
        return len(batch)

    def serve_pending(self) -> int:
        """Drain the queue on the calling thread; total requests served."""
        total = 0
        while True:
            served = self.serve_once()
            if not served:
                return total
            total += served

    def _run_batch(self, tickets: list[Ticket]) -> None:
        fp, req0 = tickets[0].fingerprint, tickets[0].request
        nbatch = len(tickets)
        try:
            with obs.span("serve/admit", fingerprint=fp, case=req0.case,
                          batch=nbatch) if obs.is_enabled() else obs.NULL_SPAN:
                solver = self.registry.get(req0, fingerprint=fp)
            obs.metrics.inc("serving.batches")
            obs.metrics.inc("serving.requests.admitted", nbatch)
            obs.metrics.set_gauge("serving.batch_size", nbatch)
            self._step_batch(solver, tickets)
        except Exception as e:  # fail every lane loudly, keep serving
            obs.metrics.inc("serving.batches_failed")
            err = f"{type(e).__name__}: {e}"
            now = time.monotonic()
            wall = time.time()
            for t in tickets:
                self.failures.append(FailureRecord(
                    kind="batch_error", where="serving.batch",
                    job_id=t.request.request_id or fp, detail=err,
                    retryable=False, time_s=wall))
                obs.metrics.inc("serving.requests.failed")
                t._push(SimResult(request=t.request, fingerprint=fp,
                                  history=[], batch_size=nbatch,
                                  submitted_s=t.submitted_s, finished_s=now,
                                  error=err))

    def _step_batch(self, solver, tickets: list[Ticket]) -> None:
        import jax.numpy as jnp

        nbatch = len(tickets)
        lanes = [scaled_initial_fields(solver, t.request.scale)
                 for t in tickets]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
        histories: list[list] = [[] for _ in tickets]
        open_lanes = set(range(nbatch))

        def emit(step: int, t: float) -> None:
            # one batched observables dispatch, fanned out per open lane
            batched = solver.batched_observables(stacked)
            for i in sorted(open_lanes):
                o = {k: float(v[i]) for k, v in batched.items()}
                o["t"] = t
                histories[i].append(o)
                tickets[i]._push(StepUpdate(step=step, t=t, observables=o))
                if step >= tickets[i].request.steps:
                    self._finish(tickets[i], histories[i], nbatch)
                    open_lanes.discard(i)

        t = 0.0
        emit(0, t)
        steps_max = max(tk.request.steps for tk in tickets)
        for step in range(1, steps_max + 1):
            if obs.is_enabled():
                with obs.span("dispatch/serving.batch_step", case=tickets[0]
                              .request.case, batch=nbatch, step=step,
                              fingerprint=tickets[0].fingerprint):
                    stacked = solver.batched_step(stacked)
            else:
                stacked = solver.batched_step(stacked)
            t = t + solver.dt             # same accumulation as solo step()
            emit(step, t)
        assert not open_lanes

    def _finish(self, ticket: Ticket, history: list, nbatch: int) -> None:
        obs.metrics.inc("serving.requests.completed")
        ticket._push(SimResult(
            request=ticket.request, fingerprint=ticket.fingerprint,
            history=history, batch_size=nbatch,
            submitted_s=ticket.submitted_s, finished_s=time.monotonic()))

    # ---- threaded mode ---------------------------------------------------
    def start(self) -> None:
        """Spawn the scheduler thread (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="sim-serve", daemon=True)
        self._thread.start()

    def stop(self, drain: bool = True) -> None:
        """Stop the scheduler thread; ``drain`` serves what's queued first."""
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if drain:
            self.serve_pending()

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _loop(self) -> None:
        while not self._stop.is_set():
            if not self.serve_once():
                self._wake.wait(timeout=0.05)
                self._wake.clear()
