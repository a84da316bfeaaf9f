"""FCFS admission + request lifecycle over the slot-pool engine.

The engine (:mod:`chainermn_tpu.serving.engine`) is pure mechanism: it
advances whatever occupies its slots. This module is the policy layer — a
first-come-first-served queue whose requests move through

    QUEUED -> PREFILL -> DECODE -> DONE      (or CANCELLED, or ERRORED)

One :meth:`FCFSScheduler.step` is one engine round: shed expired QUEUED
requests, fill freed slots from the queue (prefill interleaves with
decode at step granularity, the classic continuous-batching schedule),
advance all active slots one token, deliver tokens to per-request
streams, and retire slots whose request hit EOS or its token budget.
Retirement frees the slot for the NEXT step's admissions, so the pool
refills without ever waiting for the whole batch to finish — the property
that separates this from the offline ``generate()`` path.

Cost-aware admission (the PR-5 fast path): when the engine has batched
prefill, a bucket ladder, or the prefix cache enabled, admissions are
built as **groups** — the head of the queue anchors a group, the queue is
scanned for companions whose (prefix-discounted) padded suffix lands in
the SAME bucket, companions sharing the head's cached prefix are
preferred, and the whole group admits in ONE batched device call
(:meth:`ServingEngine.admit_batch`: per-member prefix fetch + one bucket
prefill). Decode stall is bounded: at most ``max_prefills_per_step``
prefill calls interleave per decode step (default 1 in cost-aware mode;
unbounded in the legacy single-request configuration, whose behavior —
including the ``serving.prefill`` fault cut-point and per-request retry —
is preserved exactly).

Block-budget admission (paged engines): when the engine runs the paged
KV store, a group member admits only if its WORST-CASE block growth
(``blocks_needed(prompt, max_new)``) fits ``free + evictable −
reserved`` — an unaffordable head is put back QUEUED (FCFS preserved)
instead of being allowed to starve mid-decode later. Before each decode
step the scheduler appends blocks for slots crossing block boundaries;
a genuinely dry pool (or an injected ``serving.kv_append`` fault)
preempts the LOWEST-priority (newest) request back to the queue — its
re-admission replays prompt+rng from scratch, reproducing the identical
token stream — rather than failing anyone or burning a restart.

Chunked prefill (PR 19): with ``chunk_tokens_per_step=N`` on a paged
engine, a long prompt whose suffix exceeds ``N`` tokens admits as a
**chunked** prefill instead of one monolithic device call — the engine
stages the slot (:meth:`ServingEngine.begin_chunked`) and the request
enters ``PREFILLING``; each subsequent step advances exactly ONE chunk
(:meth:`_advance_chunks`) through the same compiled bucket programs the
batched path uses (zero recompiles), interleaved with every decode step,
so a 1k-token prompt no longer stalls in-flight decodes for its whole
prefill. The final chunk samples with the request's own rng (one
admission split — token parity with the unchunked path and with a solo
``generate()``), commits the slot, and the request proceeds to DECODE
exactly as if it had admitted unchunked.

KV migration (disaggregated prefill/decode tiers): when a supervising
layer sets :attr:`migrate_cb`, a request that just completed its prefill
(chunked or not) is offered for handover — the slot's KV blocks are read
out host-side (:meth:`ServingEngine.export_slot_kv`) and the callback
decides placement. On ``True`` the SAME :class:`Request` object now
belongs to the destination scheduler (:meth:`enqueue_migrated` /
``_pending_imports``; its ``stream_cb``/trace/``_done`` ride along, so
consumers never notice the move) and the source frees the slot; on
``False`` — or any export/handshake failure — the request simply keeps
decoding in place. Never a lost request, by construction.

Graceful degradation (the resilience layer):

- **Bounded admission** — ``max_queue`` rejects overload at submit time
  with :class:`QueueFullError` instead of queueing unboundedly (the
  caller sees backpressure immediately; a shed deep in the queue later
  helps nobody).
- **Deadlines** — a request carrying ``deadline_s`` (or the scheduler's
  ``default_deadline_s``) that is still QUEUED past its deadline is shed:
  terminal ``ERRORED`` with a stored :class:`DeadlineExceededError`, so
  ``wait()`` raises instead of blocking on work that will never start.
- **Engine exception boundary** — a raised device call fails every
  in-flight request loudly (``ERRORED`` with the exception stored; no
  ``wait()`` ever hangs on a dead engine), then — ``restart_on_error``,
  the default — warm-restarts the engine (fresh caches and slot mirrors,
  SAME compiled programs) and keeps serving the queue. Restarts are
  bounded by ``max_restarts``; past the budget the exception propagates.
- **Admission retry** — an optional
  :class:`~chainermn_tpu.resilience.retry.RetryPolicy` around each
  prefill absorbs transient faults before they count as engine failures.

Every transition is observable: ``reject`` / ``shed`` / ``engine_error``
/ ``engine_restart`` events in the flight recorder and matching
``ServingMetrics`` registry counters. Every submission also opens a
request-scoped :class:`~chainermn_tpu.monitor.trace.Trace` that rides
the request end to end — ``queue`` (submit -> popped), ``admit`` (host
planning), ``prefill`` (the batched device call, attributed to every
group member with bucket/batch/cached labels), one ``decode_step`` span
per decode call it participates in, closed at retire/shed/error with the
reason. Shed and errored requests are retained regardless of the
tracer's sampling, lifecycle events carry ``trace=`` ids, the watchdog
window around every device call is labelled with the in-flight
request/trace ids, and each retired trace's critical-path breakdown
feeds ``ServingMetrics.report()["critical_path"]``.

Thread model: ``submit``/``cancel`` are safe from any thread (they only
touch the locked queue and request state); ``step`` must be driven from
ONE thread — the engine's device state is not concurrent. The in-process
:class:`~chainermn_tpu.serving.client.ServingClient` owns that thread.
"""

from __future__ import annotations

import enum
import itertools
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional

import jax
import numpy as np

from chainermn_tpu.analysis import sanitizer
from chainermn_tpu.monitor import annotate
from chainermn_tpu.monitor._state import get_event_log
from chainermn_tpu.monitor.costs import CostLedger
from chainermn_tpu.monitor.trace import NULL_TRACE, get_tracer
from chainermn_tpu.resilience.cutpoints import SERVING_ADMIT_FAIR
from chainermn_tpu.resilience.faults import inject
from chainermn_tpu.resilience.retry import RetryPolicy
from chainermn_tpu.serving.engine import EngineStateError
from chainermn_tpu.serving.fairness import (
    BrownoutPolicy,
    FairAdmission,
    PRIORITY_CLASSES,
)
from chainermn_tpu.serving.metrics import ServingMetrics


class QueueFullError(RuntimeError):
    """Submission rejected: the bounded admission queue is at capacity.

    ``retry_after_s`` is the machine-readable backpressure hint (scaled
    by queue depth at rejection time) a well-behaved client should wait
    before retrying — the fleet edge surfaces it end to end."""

    def __init__(self, msg: str = "", *,
                 retry_after_s: Optional[float] = None) -> None:
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class DeadlineExceededError(TimeoutError):
    """The request spent its deadline queued (or decoding) and was shed.
    Carries the same structured ``retry_after_s`` hint as
    :class:`QueueFullError`."""

    def __init__(self, msg: str = "", *,
                 retry_after_s: Optional[float] = None) -> None:
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    PREFILLING = "prefilling"   # chunked prefill in progress (owns a slot)
    DECODE = "decode"
    DONE = "done"
    CANCELLED = "cancelled"
    ERRORED = "errored"


class EngineFailed(RuntimeError):
    """Stored on requests that were in flight when the engine raised (the
    original engine exception is the ``__cause__``)."""


@dataclass(eq=False)
class Request:
    """One inference request and its full lifecycle state. Created by
    :meth:`FCFSScheduler.submit`; treat as read-only outside the scheduler
    (``wait()``/``output``/``stream()`` are the consumer surface).

    ``eq=False``: requests compare by identity. The generated
    field-wise ``__eq__`` would compare ndarray prompts (ambiguous
    truth value) the moment ``deque.remove`` / ``in`` walks past a
    same-shape neighbor — fair admission removes mid-queue elements, so
    identity semantics are load-bearing, not just faster."""

    prompt: np.ndarray
    max_new_tokens: int
    rng: object = None                 # per-request PRNG key (solo-parity)
    stream_cb: Optional[Callable[[int], None]] = None
    # cost-attribution label (PR 17): rides the request end to end and
    # keys the ledger's per-tenant aggregates; with fair admission on it
    # also keys the DRR budget this request draws from
    tenant: str = "default"
    # admission class (PR 18): "interactive" admits first and is
    # preempted last; "batch" only admits once interactive is drained
    priority: str = "interactive"
    id: int = -1
    state: RequestState = RequestState.QUEUED
    slot: int = -1
    tokens: list = field(default_factory=list)
    error: Optional[BaseException] = None
    deadline_s: Optional[float] = None
    t_submit: float = 0.0
    t_deadline: Optional[float] = None
    t_last_token: float = 0.0
    # when the request last (re-)entered the queue — the cost ledger's
    # queue-wait clock, reset on preempt/defer (t_submit stays the TTFT
    # anchor and is never touched)
    _t_enqueue: float = 0.0
    # engine weight version this request decodes on, stamped at slot
    # commit (None until admitted, or on engines without versioning)
    weight_version: Optional[int] = None
    # request-scoped trace context: rides the request through queue ->
    # admit -> prefill -> decode -> retire (NULL_TRACE when tracing off)
    trace: object = NULL_TRACE
    _span_queue: object = None
    _span_admit: object = None
    _done: threading.Event = field(default_factory=threading.Event)

    @property
    def finished(self) -> bool:
        return self.state in (RequestState.DONE, RequestState.CANCELLED,
                              RequestState.ERRORED)

    @property
    def output(self) -> np.ndarray:
        """``prompt + generated`` tokens (the ``generate()``-shaped
        result, without its trailing pad). An ERRORED request re-raises
        its stored exception instead of returning a silent partial."""
        if self.error is not None:
            raise self.error
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until DONE/CANCELLED/ERRORED; True if finished. An
        ERRORED request re-raises its stored exception in the caller."""
        ok = self._done.wait(timeout)
        if self.error is not None:
            raise self.error
        return ok

    def stream(self, poll_s: float = 0.01) -> Iterator[int]:
        """Yield generated tokens as they arrive; returns at a terminal
        state — re-raising the stored exception for ERRORED requests, so
        a streaming consumer hears about the failure instead of seeing a
        quietly truncated stream. (``tokens`` is append-only, so the
        index scan is safe against the engine thread.)"""
        i = 0
        while True:
            while i < len(self.tokens):
                yield self.tokens[i]
                i += 1
            if self._done.is_set():
                while i < len(self.tokens):
                    yield self.tokens[i]
                    i += 1
                if self.error is not None:
                    raise self.error
                return
            self._done.wait(poll_s)


class SwapTicket:
    """Handle for one pending weight swap (see
    :meth:`FCFSScheduler.request_swap`). ``wait()`` blocks until the
    scheduler's driving thread executed (or failed) the swap; ``result``
    holds the swap fn's return value, ``error`` the exception if it
    raised — a failed swap leaves the engine on its prior weights (the
    swap fn validates before assigning), so the ticket is the only place
    the failure surfaces."""

    def __init__(self, fn: Callable[[], object]) -> None:
        self.fn = fn
        self.result: object = None
        self.error: Optional[BaseException] = None
        self.t_request = time.perf_counter()
        self.t_executed: Optional[float] = None
        self._done = threading.Event()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the swap executed; re-raises the swap's exception
        in the caller. True when it completed within ``timeout``."""
        ok = self._done.wait(timeout)
        if self.error is not None:
            raise self.error
        return ok

    @property
    def fence_s(self) -> Optional[float]:
        """Wall time the swap spent fenced (request -> execution)."""
        if self.t_executed is None:
            return None
        return self.t_executed - self.t_request


class KvReuseTicket:
    """Handle for one pending fleet KV-reuse operation served on the
    scheduler's driving thread between steps (a prefix export for
    cross-replica sharing, or a mid-decode rebalance handover). The
    requesting thread ``wait()``s with a bounded timeout; a timeout or
    ``None``/``False`` result decays to the do-nothing fallback
    (re-prefill / decode in place) — the ticket never blocks the drive
    loop and never fails a request."""

    def __init__(self, kind: str, **kw) -> None:
        self.kind = kind
        self.kw = kw
        self.result: object = None
        self._done = threading.Event()

    def resolve(self, result: object) -> None:
        self.result = result
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> object:
        """Block until served (or ``timeout``); the result, else None."""
        if not self._done.wait(timeout):
            return None
        return self.result


#: What the profiler sees of one ``FCFSScheduler.step()``: sibling
#: ``monitor.annotate`` spans in step order, tiling the driving thread so
#: that every idle gap of the chip between two device programs lies under
#: one named phase (``serving_account``, the monitoring's own cost, has two
#: stretches). No span encloses a whole step: a trace reduction that gives
#: a gap to the span covering most of it would then name nothing else. A
#: speculative engine's round is ``chainermn.serving_spec_verify`` where
#: ``serving_decode`` stands; ``ServingClient`` sleeps under
#: ``chainermn.serving_idle``.
STEP_PHASES = (
    "chainermn.serving_policy",       # shed, policy tick, swap fence, imports
    "chainermn.serving_admit",        # the admission loop
    "chainermn.serving_blocks",       # chunk advance, block appends, snapshot
    "chainermn.serving_decode",       # engine: operands through fetch
    "chainermn.serving_decode_post",  # engine: counters, guard, slot mirror
    "chainermn.serving_account",      # cost ledger: decode and block-seconds
    "chainermn.serving_deliver",      # per token: metrics, trace, stream_cb
    "chainermn.serving_flush",        # deferred prefix inserts
    "chainermn.serving_account",      # step gauges, cost-ledger flush
)
#: The only spans that may open inside a phase or inside one of these
#: children, by parent, in the order they open. A program's host side is
#: split where its time goes: operands (``_args``), the jit call
#: (``_dispatch``), the wait for its result (``_fetch``).
_PREFILL_CHILDREN = ("chainermn.serving_prefill_args",
                     "chainermn.serving_prefill_dispatch",
                     "chainermn.serving_prefill_fetch")
STEP_PHASE_CHILDREN = {
    "chainermn.serving_admit": ("chainermn.serving_prefill",),
    "chainermn.serving_blocks": ("chainermn.serving_chunk_prefill",),
    "chainermn.serving_decode": ("chainermn.serving_decode_args",
                                 "chainermn.serving_decode_dispatch",
                                 "chainermn.serving_decode_fetch"),
    "chainermn.serving_prefill": _PREFILL_CHILDREN,
    "chainermn.serving_chunk_prefill": _PREFILL_CHILDREN,
}


class FCFSScheduler:
    """First-come-first-served continuous-batching scheduler.

    ``eos_id``: a request retires as soon as it samples this token (the
    EOS is kept as its last token — matching ``generate(eos_id=...)``,
    whose masked buffer holds the EOS then pads). Length retirement
    (``max_new_tokens``) applies either way. Both are host-side policy
    BETWEEN engine steps; inside the compiled programs shapes never
    change (see the engine's ``jnp.where`` masking).

    Degradation knobs (module docstring): ``max_queue``,
    ``default_deadline_s``, ``retry`` (prefill admission),
    ``restart_on_error``/``max_restarts``.
    """

    def __init__(self, engine, *, eos_id: Optional[int] = None,
                 metrics: Optional[ServingMetrics] = None,
                 max_queue: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 retry: Optional[RetryPolicy] = None,
                 restart_on_error: bool = True,
                 max_restarts: int = 8,
                 max_prefills_per_step: Optional[int] = None,
                 tracer=None, cost_accounting: bool = True,
                 fair=None, tenant_weights=None,
                 brownout: Optional[BrownoutPolicy] = None,
                 chunk_tokens_per_step: Optional[int] = None) -> None:
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if chunk_tokens_per_step is not None and chunk_tokens_per_step < 1:
            raise ValueError(
                f"chunk_tokens_per_step must be >= 1, got "
                f"{chunk_tokens_per_step}")
        self.engine = engine
        self.eos_id = eos_id
        self.metrics = metrics or ServingMetrics(engine.n_slots)
        # per-tenant resource ledger (PR 17): splits every measured
        # device interval across the requests that shared it. Pure
        # host-side dict arithmetic — default ON; ``cost_accounting=
        # False`` strips even that.
        self.costs: Optional[CostLedger] = None
        if cost_accounting:
            self.costs = CostLedger(instance=self.metrics.instance)
            self.metrics.attach_costs(self.costs)
        self._t_block_sample: Optional[float] = None
        self.max_queue = max_queue
        self.default_deadline_s = default_deadline_s
        self._retry = retry
        self._restart_on_error = restart_on_error
        self._max_restarts = int(max_restarts)
        self._restarts = 0
        # cost-aware mode: batched admission groups + bounded prefill
        # interleave. Auto-on when the engine has any of the fast-path
        # features; the legacy single-request configuration keeps filling
        # the whole pool per step (unbounded), exactly as before.
        self._cost_aware = (engine.prefill_batch > 1
                            or len(engine.prefill_buckets) > 1
                            or engine.prefix_enabled)
        if max_prefills_per_step is None:
            max_prefills_per_step = 1 if self._cost_aware else None
        self._max_prefills = max_prefills_per_step
        self._events = get_event_log()
        # request-scoped tracing: every submission opens a Trace that
        # rides the request through its whole lifecycle; the tracer's
        # sampling (and forced retention on shed/error) decides what the
        # ring keeps. NULL_TRACE when tracing is disabled.
        self._tracer = tracer if tracer is not None else get_tracer()
        # weighted-fair admission (PR 18): OFF by default — plain FIFO,
        # exactly as before. ``fair=True`` (or passing tenant_weights)
        # turns on class-ordered weighted-DRR selection; an existing
        # FairAdmission instance is accepted for sharing/inspection.
        if fair is None:
            fair = tenant_weights is not None
        if isinstance(fair, FairAdmission):
            self._fair: Optional[FairAdmission] = fair
        elif fair:
            self._fair = FairAdmission(tenant_weights=tenant_weights)
        else:
            self._fair = None
        # brownout ladder (PR 18): consulted every step when present —
        # pauses batch, forces single-token decode, caps max_new, sheds
        self._brownout = brownout
        # chunked prefill (PR 19): only meaningful on a paged engine with
        # the chunked path built; harmless (never triggers) elsewhere
        self._chunk_tokens = (int(chunk_tokens_per_step)
                              if chunk_tokens_per_step is not None else None)
        # KV migration handover hook: ``cb(req, payload) -> bool`` set by
        # a supervising layer (the fleet router's disaggregated tiers).
        # On True the callback took ownership of the request; None = off.
        self.migrate_cb: Optional[Callable] = None
        self._lock = sanitizer.make_lock("FCFSScheduler._lock")
        # sanitizer-guarded: mutating either without _lock held raises
        # when the runtime sanitizer is on (lock-discipline, enforced)
        self._queue: deque[Request] = sanitizer.guarded(
            deque(), lock=self._lock, name="FCFSScheduler._queue")
        self._by_slot: dict[int, Request] = sanitizer.guarded(
            {}, lock=self._lock, name="FCFSScheduler._by_slot")
        # slot -> request mid-chunked-prefill (disjoint from _by_slot:
        # a PREFILLING slot takes no decode token and appends no blocks)
        self._prefilling: dict[int, Request] = sanitizer.guarded(
            {}, lock=self._lock, name="FCFSScheduler._prefilling")
        # migrated-in requests awaiting a slot: (req, kv payload) pairs,
        # admitted FCFS at step() start once the engine can take them
        self._pending_imports: deque = sanitizer.guarded(
            deque(), lock=self._lock, name="FCFSScheduler._pending_imports")
        # fleet KV-reuse operations awaiting the drive thread: prefix
        # share exports/imports and mid-decode rebalance handovers (all
        # device work, so only step() may serve them)
        self._pending_kv_reuse: deque = sanitizer.guarded(
            deque(), lock=self._lock, name="FCFSScheduler._pending_kv_reuse")
        self._ids = itertools.count()
        self._pending_swap: Optional[SwapTicket] = None

    # ------------------------------------------------------------------ #
    # submission surface (any thread)                                     #
    # ------------------------------------------------------------------ #

    def submit(self, prompt, max_new_tokens: int, *, rng=None,
               stream_cb: Optional[Callable[[int], None]] = None,
               deadline_s: Optional[float] = None,
               tenant: str = "default",
               priority: str = "interactive") -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.engine.validate_request(len(prompt), max_new_tokens)
        if priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"priority must be one of {PRIORITY_CLASSES}, "
                f"got {priority!r}")
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        req = Request(
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            rng=rng if rng is not None else jax.random.PRNGKey(0),
            stream_cb=stream_cb, deadline_s=deadline_s,
            tenant=str(tenant), priority=str(priority),
        )
        req.t_submit = time.perf_counter()
        req._t_enqueue = req.t_submit
        if deadline_s is not None:
            req.t_deadline = req.t_submit + float(deadline_s)
        with self._lock:
            if (self.max_queue is not None
                    and len(self._queue) >= self.max_queue):
                self.metrics.record_rejected()
                self._events.emit("reject", prompt_len=len(prompt),
                                  queue_depth=len(self._queue))
                raise QueueFullError(
                    f"admission queue full ({self.max_queue} queued); "
                    "retry later or raise max_queue",
                    retry_after_s=self._retry_after_locked(),
                )
            req.id = next(self._ids)
            self._queue.append(req)
            self.metrics.record_submit()
        # the trace opens HERE (admitted to the queue): root span =
        # submit -> retire; first child = queue wait, closed when the
        # request is popped for admission
        req.trace = self._tracer.trace(
            "request", kind="serving", req=req.id, prompt_len=len(prompt),
            max_new=int(max_new_tokens))
        req._span_queue = req.trace.start_span("queue")
        self._events.emit("submit", req=req.id, prompt_len=len(prompt),
                          max_new=int(max_new_tokens),
                          **self._trace_label(req))
        return req

    @staticmethod
    def _trace_label(req: Request) -> dict:
        """``{"trace": id}`` when the request is traced, else ``{}`` —
        the join key flight-recorder events carry so dumps line up
        against exported span trees."""
        return {"trace": req.trace.trace_id} if req.trace.enabled else {}

    def cancel(self, req: Request) -> bool:
        """Cancel a request: dequeued if still QUEUED, slot freed if
        decoding. False if it already finished."""
        with self._lock:
            if req.finished:
                return False
            if req.state is RequestState.QUEUED:
                try:
                    self._queue.remove(req)
                except ValueError:
                    # not in the queue: a migrated-in request awaiting a
                    # slot? (mid-handover requests belong to nobody yet
                    # and report un-cancellable, same as the ValueError)
                    for i, (r, _) in enumerate(self._pending_imports):
                        if r is req:
                            del self._pending_imports[i]
                            break
                    else:
                        return False
            elif req.state is RequestState.PREFILLING:
                # mid-chunked-prefill: the driving thread owns the slot's
                # staged chunk state — it sees CANCELLED at the next
                # chunk tick and releases the slot itself (releasing here
                # would race the in-flight chunk's commit)
                pass
            elif req.slot >= 0:
                self.engine.release(req.slot)
                self._by_slot.pop(req.slot, None)
            # else: prefill in flight (no slot yet) — the step() admission
            # path sees the CANCELLED state and releases the slot itself
            req.state = RequestState.CANCELLED
            self.metrics.record_done(cancelled=True)
        if self.costs is not None:
            self.costs.finalize(req.id)
        self._events.emit("slot_retire", req=req.id, slot=req.slot,
                          reason="cancelled", **self._trace_label(req))
        req.trace.finish(reason="cancelled")
        req._done.set()
        return True

    @property
    def has_work(self) -> bool:
        with self._lock:
            return (bool(self._queue) or bool(self._by_slot)
                    or bool(self._prefilling)
                    or bool(self._pending_imports)
                    or bool(self._pending_kv_reuse)
                    or self._pending_swap is not None)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def engine_restarts(self) -> int:
        """Warm restarts this scheduler has driven (for reports/tests)."""
        return self._restarts

    # ------------------------------------------------------------------ #
    # supervisor surface (the fleet layer)                                 #
    # ------------------------------------------------------------------ #

    def drain_queued(self) -> list:
        """Remove and return every QUEUED request — the fleet failover
        hook: a supervising layer re-routes the drained work to a healthy
        replica instead of letting it wait on a scheduler whose engine
        just failed. Each drained request keeps state QUEUED (the caller
        owns it now); its trace is closed with ``reason="rerouted"`` —
        the re-submission opens a fresh one on the target replica."""
        with self._lock:
            drained = list(self._queue)
            self._queue.clear()
            # migrated-in work still waiting for a slot is QUEUED work
            # too: it never started decoding HERE, so the supervising
            # layer replays it (prompt + rng) on a healthy replica —
            # kill-mid-migration loses nothing
            drained.extend(req for req, _ in self._pending_imports)
            self._pending_imports.clear()
            # pending KV-reuse tickets resolve empty-handed NOW: a share
            # handshake waiting on this dead replica must decay to
            # re-prefill immediately, not after its full timeout
            reuse = list(self._pending_kv_reuse)
            self._pending_kv_reuse.clear()
        for ticket in reuse:
            ticket.resolve(None)
        for req in drained:
            if self.costs is not None:
                self.costs.finalize(req.id)
            if req._span_queue is not None:
                req.trace.end_span(req._span_queue)
                req._span_queue = None
            req.trace.finish(reason="rerouted")
        return drained

    def fail_inflight(self, e: BaseException) -> None:
        """Public supervisor boundary: fail every in-flight request loudly
        (terminal ERRORED, ``wait()`` re-raises) WITHOUT restarting the
        engine — the caller (a replica supervisor) owns the warm-restart /
        quarantine decision one level up. Idempotent per request: work
        already errored by the step's own exception boundary is left
        untouched."""
        with self._lock:
            has_inflight = bool(self._by_slot) or bool(self._prefilling)
            ticket, self._pending_swap = self._pending_swap, None
        if ticket is not None:
            # a publisher waiting on this ticket must hear about the
            # death instead of hanging on a fence that will never drain
            ticket.error = EngineFailed(
                "engine failed while a weight swap was fenced")
            ticket.error.__cause__ = e
            ticket.t_executed = time.perf_counter()
            ticket._done.set()
        if has_inflight:
            restart, self._restart_on_error = self._restart_on_error, False
            try:
                self._engine_failure(e)
            finally:
                self._restart_on_error = restart

    def request_swap(self, fn: Callable[[], object]) -> SwapTicket:
        """Enqueue a weight swap to run on the scheduler's driving thread
        at the next safe point (thread-safe; the publisher's entry point).

        The swap is a *version fence*: while a ticket is pending, NO new
        admissions happen — every in-flight request completes (or
        retires) entirely on the weights it started with — and once the
        slot pool drains, ``fn`` executes between decode steps on the one
        thread that touches the engine. Queued requests admit after the
        swap, on the new weights; the fence wait shows up as a ``swap``
        span in their traces. Only one swap may be pending at a time.
        """
        ticket = SwapTicket(fn)
        with self._lock:
            if self._pending_swap is not None:
                raise RuntimeError(
                    "a weight swap is already pending on this scheduler")
            self._pending_swap = ticket
        self._events.emit("swap_fence", queue_depth=self.queue_depth)
        return ticket

    # ------------------------------------------------------------------ #
    # the scheduling loop (one driving thread)                            #
    # ------------------------------------------------------------------ #

    def step(self) -> int:
        """One continuous-batching round; returns tokens emitted (0 when
        idle). Shedding, then admissions — freed slots refill BEFORE the
        decode step, so a retirement's slot never sits idle for a step."""
        emitted = 0
        with annotate("chainermn.serving_policy"):
            self._shed_expired()
            self._policy_tick()
            # 0. version fence: while a swap is pending, admissions pause
            # so every in-flight request finishes on the weights it
            # started with; once the pool drains the swap runs HERE,
            # between device calls, on the one thread that owns the engine
            with self._lock:
                swapping = self._pending_swap is not None
                if (swapping and not self._by_slot and not self._prefilling
                        and not self._pending_imports):
                    ticket, self._pending_swap = self._pending_swap, None
                    swapping = False
                else:
                    ticket = None
            if ticket is not None:
                self._execute_swap(ticket)
            # Fleet KV-reuse operations (prefix share export/import,
            # rebalance handover) run before admission: a shared prefix
            # landed here must be trie-resident BEFORE this step's fresh
            # admissions match.
            # Migrated-in requests admit first: their device time is
            # already spent elsewhere, they only need a slot + one
            # scatter. They admit even through a swap fence — they
            # STARTED on the current weights elsewhere, so they must
            # finish on them here (the fence simply waits for them like
            # any other in-flight work).
            self._serve_kv_reuse()
            self._admit_imports()
        # 1. admission: one group (>= 1 same-bucket requests, one device
        # call) per iteration, FCFS-anchored; bounded prefill interleave
        # in cost-aware mode so a deep queue can't stall decode.
        with annotate("chainermn.serving_admit", queue=self.queue_depth):
            calls = 0
            while not swapping and self.engine.free_slots and (
                    self._max_prefills is None or calls < self._max_prefills):
                group = self._next_group()
                if not group:
                    break
                calls += 1
                emitted += self._admit_group(group)
        with annotate("chainermn.serving_blocks"):
            # 1a. chunked prefill: advance the oldest PREFILLING request
            # by exactly ONE chunk — the bounded slice of prefill work
            # that interleaves with this step's decode. Runs through a
            # swap fence too: a staged chunked admission already started
            # on the current weights, so the fence waits for it rather
            # than stranding it
            emitted += self._advance_chunks()
            # 1b. paged: make sure every active slot can take this step's
            # token — lazily append blocks for slots crossing a block
            # boundary, preempting (requeueing, not failing) the lowest-
            # priority request when the pool runs dry
            if getattr(self.engine, "paged", False):
                self._ensure_decode_blocks()
            # GIL-atomic snapshot for cost attribution (same contract as
            # _flight_ctx): who occupied which slot when the decode
            # launched
            rows_snapshot = list(
                self._by_slot.items())  # graftlint: unguarded-ok
            ctx = self._flight_ctx()
        # 2. decode: every active slot, one compiled call — one token per
        # slot on the legacy path, up to k+1 (speculative) / decode_window
        # tokens per slot on the multi-token rounds
        # brownout L2: bypass decode_window / speculative rounds and run
        # the always-warmed single-token decode step — less work per
        # call, zero recompiles (warmup traces _decode_fn regardless)
        force_single = (self._brownout is not None
                        and self._brownout.force_single_token)
        t_dec0 = time.perf_counter()
        try:
            if force_single:
                decoded = {
                    slot: [tok] for slot, tok in
                    self.engine.decode_step(ctx=ctx).items()}
            else:
                decoded = self.engine.decode_round(ctx=ctx)
        except Exception as e:  # noqa: BLE001 — degradation boundary
            if not self._engine_failure(e):
                raise
            decoded = {}
        t_dec1 = time.perf_counter()
        with annotate("chainermn.serving_account"):
            if self.costs is not None and rows_snapshot and decoded:
                # split the shared decode call across the n_slots rows the
                # compiled program actually ran; slots with no request
                # book as `idle`, rejected speculative drafts as `wasted`.
                # Under brownout L2 the speculative window never ran, so
                # the (stale) last_spec_slots must not attribute draft
                # cost here.
                spec_info = (self.engine.last_spec_slots
                             if (not force_single
                                 and getattr(self.engine, "spec_enabled",
                                             False))
                             else {})
                rows = []
                for slot, req in rows_snapshot:
                    if slot in spec_info:
                        kd, a = spec_info[slot]
                        rows.append((req.id, req.tenant, a + 1, kd - a))
                    else:
                        rows.append((req.id, req.tenant,
                                     max(len(decoded.get(slot, ())), 1), 0))
                self.costs.record_decode(t_dec1 - t_dec0,
                                         n_rows=self.engine.n_slots,
                                         rows=rows)
            if self.costs is not None and getattr(self.engine, "paged",
                                                  False):
                # block-seconds: integral of blocks held over wall time,
                # sampled once per step; shared prefix blocks split by
                # live refcount so a popular prefix isn't billed N times
                if self._t_block_sample is not None and rows_snapshot:
                    shares = self.engine.slot_block_shares().tolist()
                    self.costs.record_block_seconds(
                        t_dec1 - self._t_block_sample,
                        [(req.tenant, shares[slot])
                         for slot, req in rows_snapshot])
                self._t_block_sample = t_dec1
        with annotate("chainermn.serving_deliver"):
            for slot, toks in decoded.items():
                for tok in toks:
                    # dict.get is GIL-atomic and a concurrent cancel() is
                    # handled by the None check — taking _lock per token
                    # would serialize the decode loop against the submit
                    # path for nothing. Re-fetched per token: EOS/length
                    # retirement can fire MID-window, and the window's
                    # tail past it must be dropped, not delivered to the
                    # next slot tenant.
                    req = self._by_slot.get(slot)  # graftlint: unguarded-ok
                    if req is None or req.finished:
                        break              # released / retired mid-window
                    now = time.perf_counter()
                    self.metrics.record_token(req.t_last_token, now)
                    # the shared decode call, attributed to every
                    # participant: one decode_step span per request per
                    # step (token index in the labels), bounded by the
                    # trace's span cap
                    req.trace.add_span("decode_step", t_dec0, t_dec1,
                                       token=len(req.tokens))
                    self._deliver(req, tok, now)
                    emitted += 1
        # deferred prefix-cache inserts run AFTER this step's tokens were
        # delivered (off the TTFT path) and before the next step can
        # reuse a donor slot
        with annotate("chainermn.serving_flush"):
            self.engine.flush_inserts()
        with annotate("chainermn.serving_account"):
            if getattr(self.engine, "spec_enabled", False):
                window = self.engine.pop_spec_window()
                if window is not None:
                    self.metrics.record_spec_window(*window)
            pop = getattr(self.engine, "pop_moe_counts", None)
            counts = pop() if pop is not None else None
            if counts is not None:
                self.metrics.record_moe_assignments(*counts)
            pop = getattr(self.engine, "pop_state_stats", None)
            state = pop() if pop is not None else None
            if state is not None:
                self.metrics.record_slot_state(*state)
            with self._lock:
                depth = len(self._queue)
                batch_depth = sum(1 for r in self._queue
                                  if r.priority == "batch")
            self.metrics.record_step(depth, self.engine.active_slots,
                                     batch_depth=batch_depth)
            if getattr(self.engine, "paged", False):
                self.metrics.record_kv_pool(*self.engine.kv_pool_stats())
            if self.costs is not None:
                self.costs.flush()
        return emitted

    def run_until_idle(self, max_steps: Optional[int] = None) -> int:
        """Drive ``step()`` until queue and slots drain; returns total
        tokens emitted. The offline convenience loop (tests, benchmarks);
        online serving drives ``step()`` from the client thread instead."""
        total = 0
        steps = 0
        while self.has_work:
            total += self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return total

    # ------------------------------------------------------------------ #
    # admission internals                                                 #
    # ------------------------------------------------------------------ #

    def _next_group(self) -> list:
        """Pop the next admission group: the queue head anchors it (FCFS —
        no starvation), then companions whose (prefix-discounted) padded
        suffix lands in the SAME bucket join, companions sharing the
        head's cached prefix first, until the group hits the rows of that
        bucket's program (``engine.prefill_rows``: ``prefill_batch`` at
        the smallest bucket, fewer at longer ones) or the free-slot
        count; a head whose bucket has one row returns before any queued
        request is planned. Returns ``[(req, plan),
        ...]``; every selected request is moved to PREFILL, every
        unselected candidate's plan is cancelled (match unpinned)."""
        eng = self.engine
        paged = getattr(eng, "paged", False)
        with self._lock:
            head = self._pop_head_locked()
        if head is None:
            return []
        self._span_to_admit(head)
        # chaos boundary: an injected fault at the fair-admit pick fails
        # ONLY the picked request (terminal ERRORED, no stranded waiter)
        # — every decoding slot keeps decoding, the queue keeps serving
        try:
            inject(SERVING_ADMIT_FAIR, req=head.id, tenant=head.tenant,
                   priority=head.priority)
        except Exception as e:  # noqa: BLE001 — containment boundary
            self._fail_group([head], e)
            return []
        plan = eng.plan_admission(head.prompt, head.rng,
                                  max_new=head.max_new_tokens)
        # block-budget admission (paged): admit only what free + evictable
        # blocks cover at WORST-CASE growth — an over-admitted request
        # would fail mid-decode later; a deferred one just stays QUEUED
        # until retirements return blocks (FCFS order preserved)
        budget = None
        if paged:
            # one count per kind of KV state the model keeps: every
            # kind's pool has to cover the request
            budget = eng.kv_blocks_admittable()
            need = eng.blocks_needed(len(head.prompt),
                                     head.max_new_tokens, plan.start)
            if (need > budget).any():
                self._defer_admission(head, plan, need, budget)
                return []
            budget = budget - need
        # chunked prefill: a long suffix admits as a staged chunk
        # schedule instead of one monolithic device call — the same
        # block-budget gate above already cleared its worst-case growth.
        # plan_chunks returns None when chunking doesn't apply (suffix
        # fits one chunk, or a frontier outgrows every bucket): fall
        # through to the ordinary one-shot admission
        if (paged and self._chunk_tokens is not None
                and len(head.prompt) - plan.start > self._chunk_tokens
                and hasattr(eng, "plan_chunks")):
            chunks = eng.plan_chunks(plan, self._chunk_tokens)
            if chunks is not None:
                self._begin_chunked(head, plan, chunks)
                return []
        group = [(head, plan)]
        cap = min(eng.prefill_rows(plan.bucket), len(eng.free_slots))
        if cap <= 1:
            return group
        with self._lock:
            candidates = list(self._queue)
        scored = []
        for idx, req in enumerate(candidates):
            # companions ride the head's class: a batch request must not
            # slip into an interactive group (it would dodge both the
            # batch-after-interactive gate and brownout's batch pause)
            if req.priority != head.priority:
                continue
            p = eng.plan_admission(req.prompt, req.rng,
                                   max_new=req.max_new_tokens)
            if p.bucket != plan.bucket:
                eng.cancel_plan(p)
                continue
            shares = (plan.match is not None and p.match is not None
                      and p.match.nodes[0] is plan.match.nodes[0])
            scored.append((0 if shares else 1, idx, req, p))
        scored.sort(key=lambda t: (t[0], t[1]))
        for rank, (_, _, req, p) in enumerate(scored):
            need = (eng.blocks_needed(len(req.prompt), req.max_new_tokens,
                                      p.start) if paged else 0)
            if rank < cap - 1 and (budget is None
                                   or (need <= budget).all()):
                with self._lock:
                    try:
                        self._queue.remove(req)   # lost a cancel() race?
                    except ValueError:
                        eng.cancel_plan(p)
                        continue
                    req.state = RequestState.PREFILL
                self._span_to_admit(req)
                group.append((req, p))
                if budget is not None:
                    budget = budget - need
            else:
                eng.cancel_plan(p)
        return group

    def _pop_head_locked(self) -> Optional[Request]:
        """Pick + remove the next admission candidate (lock held by the
        caller). Plain FIFO ``popleft`` by default — byte-identical to
        the pre-fairness scheduler; with fair admission on, the
        class-ordered weighted-DRR policy picks instead. Brownout L1
        holds the ``batch`` class back on both paths."""
        if not self._queue:
            return None
        allow_batch = not (self._brownout is not None
                           and self._brownout.pause_batch)
        if self._fair is not None:
            head = self._fair.select(self._queue, allow_batch=allow_batch)
            if head is None:
                return None
            self._queue.remove(head)
        elif allow_batch:
            head = self._queue.popleft()
        else:
            head = next((r for r in self._queue
                         if r.priority != "batch"), None)
            if head is None:
                return None
            self._queue.remove(head)
        head.state = RequestState.PREFILL
        return head

    def _defer_admission(self, req: Request, plan, need: int,
                         available: int) -> None:
        """Paged admission gate tripped: put the request BACK at the
        queue head (FCFS — it admits first once blocks free up) instead
        of letting it fail mid-decode later. The pinned plan is
        released; the wait shows up in the request's ``queue`` span."""
        self.engine.cancel_plan(plan)
        if req._span_admit is not None:
            req.trace.end_span(req._span_admit)
            req._span_admit = None
        req._span_queue = req.trace.start_span("queue")
        req._t_enqueue = time.perf_counter()
        with self._lock:
            req.state = RequestState.QUEUED
            self._queue.appendleft(req)
        self._events.emit("kv_admit_defer", req=req.id,
                          need=[int(n) for n in need],
                          available=[int(n) for n in available],
                          **self._trace_label(req))

    def _span_to_admit(self, req: Request) -> None:
        """Queue wait is over: close the request's ``queue`` span and open
        ``admit`` (host-side planning + group assembly, closed when the
        prefill device call starts)."""
        if req._span_queue is not None:
            req.trace.end_span(req._span_queue)
            req._span_queue = None
        req._span_admit = req.trace.start_span("admit")
        if self.costs is not None:
            # wall-clock wait since the last (re-)enqueue — a preempted
            # request's second wait books again, on purpose: the tenant
            # really did wait twice
            self.costs.record_queue_wait(
                req.tenant, time.perf_counter() - req._t_enqueue)

    def _flight_ctx(self) -> dict:
        """Request/trace identity of the in-flight slots — the labels the
        engine threads into its watchdog window so a hang dump names WHO
        was decoding, not just that decode wedged."""
        # GIL-atomic snapshot; labels-only consumer tolerates staleness
        reqs = list(self._by_slot.values())  # graftlint: unguarded-ok
        if not reqs:
            return {}
        ctx = {"reqs": [r.id for r in reqs]}
        traces = [r.trace.trace_id for r in reqs if r.trace.enabled]
        if traces:
            ctx["traces"] = traces
        return ctx

    def _admit_group(self, group: list) -> int:
        """Drive one group through the engine (legacy single-request path
        when nothing batched/cached is in play — preserving the PR-1
        ``serving.prefill`` cut-point and retry semantics exactly), then
        commit each member. Returns first tokens emitted."""
        reqs = [r for r, _ in group]
        plans = [p for _, p in group]
        # (a paged engine admits by its plan, which holds the request's
        # token budget: the blocks reserved for its growth)
        legacy = (len(group) == 1 and plans[0].match is None
                  and not self.engine.prefix_enabled
                  and not getattr(self.engine, "paged", False))
        ctx = {"reqs": [r.id for r in reqs]}
        traces = [r.trace.trace_id for r in reqs if r.trace.enabled]
        if traces:
            ctx["traces"] = traces
        t_pre0 = time.perf_counter()
        for req in reqs:               # planning done; the device call next
            if req._span_admit is not None:
                req.trace.end_span(req._span_admit)
                req._span_admit = None
        try:
            if legacy:
                self.engine.cancel_plan(plans[0])
                req = reqs[0]
                if self._retry is not None:
                    results = [self._retry.call(
                        self.engine.prefill, req.prompt, req.rng,
                        op="serving.prefill", ctx=ctx)]
                else:
                    results = [self.engine.prefill(req.prompt, req.rng,
                                                   ctx=ctx)]
            else:
                if self._retry is not None:
                    results = self._retry.call(
                        self.engine.admit_batch, plans,
                        op="serving.prefill_batch", ctx=ctx)
                else:
                    results = self.engine.admit_batch(plans, ctx=ctx)
        except Exception as e:  # noqa: BLE001 — degradation boundary
            if not legacy and not isinstance(e, EngineStateError):
                # the device state is intact (admit_batch re-raises as
                # EngineStateError when a failure consumed its donated
                # buffers): only this group is lost — error its members,
                # every decoding slot keeps decoding, no restart burned
                self._fail_group(reqs, e)
                return 0
            if not self._engine_failure(e, admitting=reqs):
                raise
            return 0  # engine restarted: keep serving the queue
        t_pre1 = time.perf_counter()
        rows_run = self.engine.prefill_rows(plans[0].bucket)
        if self.costs is not None:
            # one shared device call, split by token share: the compiled
            # program always runs all of its bucket's rows (prefill_batch
            # at the smallest bucket, at most prefill_batch x
            # prefill_buckets[0] tokens a program), so empty rows and
            # intra-row padding book as `padding`
            self.costs.record_prefill(
                t_pre1 - t_pre0, bucket=plans[0].bucket,
                batch_rows=rows_run,
                members=[(req.id, req.tenant,
                          len(req.prompt) - plan.start)
                         for req, plan in group])
        emitted = 0
        self.metrics.record_admission(len(group), rows_run, plans[0].bucket)
        for (req, plan), (slot, first) in zip(group, results):
            now = time.perf_counter()
            # the shared batched device call, attributed to every member
            req.trace.add_span("prefill", t_pre0, t_pre1,
                               bucket=plan.bucket, batch=len(group),
                               cached=plan.start, slot=slot)
            with self._lock:
                if req.state is RequestState.CANCELLED:
                    # cancelled while its prefill was in flight (it had
                    # no slot yet, so cancel() left the release to us)
                    self.engine.release(slot)
                    continue
                req.slot = slot
                self._by_slot[slot] = req
                req.state = RequestState.DECODE
                # stamp the engine weight version this request will
                # decode on — the fence guarantees it never changes
                # between here and retirement
                req.weight_version = getattr(
                    self.engine, "weight_version", None)
            self._events.emit("slot_admit", req=req.id, slot=slot,
                              prompt_len=len(req.prompt),
                              bucket=plan.bucket, cached=plan.start,
                              queue_depth=self.queue_depth,
                              **self._trace_label(req))
            self.metrics.record_first_token(req.t_submit, now,
                                            req_id=req.id,
                                            cached_frac=plan.cached_frac)
            self._deliver(req, first, now)
            emitted += 1
            if not req.finished:
                # prefill done in one shot — a disaggregated fleet may
                # still want the decode phase elsewhere
                self._maybe_migrate(req, slot)
        return emitted

    def _execute_swap(self, ticket: SwapTicket) -> None:
        """Run a fenced weight swap on the driving thread (pool already
        drained). A raising swap fn surfaces ONLY on the ticket — the
        engine keeps its prior weights (the fn validates before
        assigning), the queue keeps being served."""
        t0 = time.perf_counter()
        try:
            ticket.result = ticket.fn()
        except Exception as e:  # noqa: BLE001 — surfaced on the ticket
            ticket.error = e
        t1 = time.perf_counter()
        ticket.t_executed = t1
        with self._lock:
            waiting = list(self._queue)
        for req in waiting:
            # the fence held these requests back: make the wait visible
            # in their traces as the swap window itself
            req.trace.add_span("swap", t0, t1,
                               ok=ticket.error is None)
        self._events.emit(
            "swap_exec", ok=ticket.error is None,
            fence_s=round(t1 - ticket.t_request, 6),
            queue_depth=len(waiting),
            **({"error": type(ticket.error).__name__}
               if ticket.error is not None else {}))
        ticket._done.set()

    def _fail_group(self, reqs: list, e: BaseException) -> None:
        """A batched admission failed with the engine intact: the group's
        requests error terminally (``wait()`` re-raises — no stranded
        waiters), every other slot keeps decoding, no restart burned."""
        with self._lock:
            for req in reqs:
                if req.finished:
                    continue
                failure = EngineFailed(
                    f"batched admission failed for request {req.id}: "
                    f"{type(e).__name__}: {e}")
                failure.__cause__ = e
                req.error = failure
                req.state = RequestState.ERRORED
                self.metrics.record_errored()
        self._events.emit("admission_error", error=type(e).__name__,
                          detail=str(e)[:200], group=len(reqs),
                          traces=[r.trace.trace_id for r in reqs
                                  if r.trace.enabled])
        for req in reqs:
            if self.costs is not None:
                self.costs.finalize(req.id)
            req.trace.mark_error(type(e).__name__)
            req.trace.finish(reason="admission_error")
            req._done.set()

    # ------------------------------------------------------------------ #
    # chunked prefill + KV migration (PR 19)                              #
    # ------------------------------------------------------------------ #

    def _begin_chunked(self, req: Request, plan, chunks: list) -> None:
        """Stage ``req`` as a chunked admission: the engine claims a slot
        and allocates the prompt's blocks up front (the block-budget gate
        already cleared worst-case growth), the request enters
        ``PREFILLING``, and :meth:`_advance_chunks` runs one chunk per
        step from here on. The plan is consumed either way; a transient
        staging failure re-queues the head at the FRONT (FCFS preserved,
        it retries next step)."""
        eng = self.engine
        try:
            slot = eng.begin_chunked(plan, chunks)
        except Exception as e:  # noqa: BLE001 — containment boundary
            if req._span_admit is not None:
                req.trace.end_span(req._span_admit)
                req._span_admit = None
            req._span_queue = req.trace.start_span("queue")
            req._t_enqueue = time.perf_counter()
            with self._lock:
                req.state = RequestState.QUEUED
                self._queue.appendleft(req)
            self._events.emit("kv_admit_defer", req=req.id,
                              error=type(e).__name__,
                              **self._trace_label(req))
            return
        with self._lock:
            if req.state is RequestState.CANCELLED:
                # cancelled while staging (it had no slot yet, so
                # cancel() left the release to us)
                eng.release(slot)
                return
            req.state = RequestState.PREFILLING
            req.slot = slot
            self._prefilling[slot] = req
            req.weight_version = getattr(eng, "weight_version", None)
        if req._span_admit is not None:
            req.trace.end_span(req._span_admit)
            req._span_admit = None
        self._events.emit("slot_admit", req=req.id, slot=slot,
                          prompt_len=len(req.prompt),
                          bucket=chunks[0][2], cached=plan.start,
                          chunks=len(chunks),
                          queue_depth=self.queue_depth,
                          **self._trace_label(req))

    def _advance_chunks(self) -> int:
        """Advance the OLDEST ``PREFILLING`` request by exactly one chunk
        (one bounded device call per step — decode stall stays capped at
        one chunk regardless of prompt length). The final chunk commits
        the slot, records TTFT, delivers the first token, and offers the
        request for KV migration. Returns first tokens emitted (0/1)."""
        with self._lock:
            if not self._prefilling:
                return 0
            slot, req = min(self._prefilling.items(),
                            key=lambda kv: kv[1].id)
        if req.finished:
            # cancelled mid-chunking: cancel() deferred the slot release
            # to this (the driving) thread — no in-flight chunk to race
            with self._lock:
                self._prefilling.pop(slot, None)
            self.engine.release(slot)
            return 0
        st = self.engine.chunk_state(slot)
        if st is None:   # engine restarted under us: nothing staged left
            with self._lock:
                self._prefilling.pop(slot, None)
            return 0
        _, clen, bucket = st.chunks[st.next_idx]
        idx, total = st.next_idx, len(st.chunks)
        ctx = {"reqs": [req.id]}
        if req.trace.enabled:
            ctx["traces"] = [req.trace.trace_id]
        t0 = time.perf_counter()
        try:
            first = self.engine.prefill_chunk(slot, ctx=ctx)
        except Exception as e:  # noqa: BLE001 — degradation boundary
            if not self._engine_failure(e):
                raise
            return 0
        t1 = time.perf_counter()
        rows_run = self.engine.prefill_rows(bucket)
        self.metrics.record_prefill_rows(1, rows_run, bucket)
        if self.costs is not None:
            # each chunk is one device call of its bucket's rows (at most
            # prefill_batch x prefill_buckets[0] tokens) with a single
            # occupied row — the empty rows and the intra-row padding
            # book as `padding`, same as a batch of 1
            self.costs.record_prefill(
                t1 - t0, bucket=bucket, batch_rows=rows_run,
                members=[(req.id, req.tenant, clen)])
        req.trace.add_span("prefill_chunk", t0, t1, bucket=bucket,
                           chunk=idx, of=total, tokens=clen, slot=slot)
        if first is None:
            return 0
        with self._lock:
            self._prefilling.pop(slot, None)
            if req.state is RequestState.CANCELLED:
                self.engine.release(slot)
                return 0
            req.state = RequestState.DECODE
            self._by_slot[slot] = req
        now = time.perf_counter()
        self.metrics.record_first_token(
            req.t_submit, now, req_id=req.id,
            cached_frac=(st.start / len(st.prompt)
                         if len(st.prompt) else 0.0))
        self._deliver(req, first, now)
        if not req.finished:
            self._maybe_migrate(req, slot)
        return 1

    def _maybe_migrate(self, req: Request, slot: int) -> bool:
        """Offer a prefill-complete request to :attr:`migrate_cb` for
        handover to a decode-tier peer (see :meth:`_handover`)."""
        cb = self.migrate_cb
        if cb is None or not getattr(self.engine, "migration_supported",
                                     False):
            return False
        return self._handover(req, slot, cb, reason="migrated")

    def _handover(self, req: Request, slot: int, cb: Callable,
                  reason: str = "migrated") -> bool:
        """Hand an in-flight request's slot over to a peer through
        ``cb(req, payload) -> bool``. The slot's KV blocks are read out
        host-side first (read-only gather — the slot keeps decoding in
        place if anything below fails), then the callback places the
        request: on True the SAME Request object now belongs to the
        destination scheduler and the slot is released here; on False —
        or an export/callback raise — the request is re-bound to its slot
        unchanged. Never a lost request. Shared by the prefill-complete
        migration (``reason="migrated"``) and the mid-decode rebalance
        (``reason="rebalanced"``) — the payload format and the
        all-or-nothing import don't care why the blocks are moving."""
        t0 = time.perf_counter()
        try:
            payload = self.engine.export_slot_kv(
                slot, ctx={"reqs": [req.id]})
        except Exception:  # noqa: BLE001 — fall back to decoding in place
            return False
        t1 = time.perf_counter()
        n_tokens = len(req.tokens)
        # all request-side bookkeeping happens BEFORE the callback: on
        # True the destination owns the object immediately (possibly
        # already admitting it on its own thread)
        req.trace.add_span("migrate", t0, t1, blocks=payload["n_blocks"],
                           src_slot=slot)
        req._span_queue = req.trace.start_span("queue")
        req._t_enqueue = time.perf_counter()
        with self._lock:
            self._by_slot.pop(slot, None)
            req.state = RequestState.QUEUED
            req.slot = -1
        try:
            ok = bool(cb(req, payload))
        except Exception:  # noqa: BLE001 — handshake failure = stay local
            ok = False
        if not ok:
            # decode in place: re-bind the slot exactly as it was
            if req._span_queue is not None:
                req.trace.end_span(req._span_queue)
                req._span_queue = None
            with self._lock:
                req.state = RequestState.DECODE
                req.slot = slot
                self._by_slot[slot] = req
            return False
        if self.costs is not None:
            self.costs.record_migration(t1 - t0, req_id=req.id,
                                        tenant=req.tenant)
            self.costs.finalize(req.id)
        self.engine.release(slot)
        self._events.emit("slot_retire", req=req.id, slot=slot,
                          reason=reason, tokens=n_tokens,
                          **self._trace_label(req))
        return True

    # ------------------------------------------------------------------ #
    # fleet KV reuse (prefix sharing + mid-decode rebalancing)            #
    # ------------------------------------------------------------------ #

    def request_prefix_export(self, tokens, *,
                              min_blocks: int = 1) -> KvReuseTicket:
        """Ask the drive thread to export this engine's cached prefix of
        ``tokens`` (thread-safe; the fleet router's share handshake).
        The ticket resolves to the share payload, or ``None`` when the
        trie holds fewer than ``min_blocks`` — the caller's timeout on
        ``wait()`` is the whole backpressure story: a wedged holder just
        means the destination re-prefills."""
        ticket = KvReuseTicket("prefix_export", tokens=tokens,
                               min_blocks=int(min_blocks))
        with self._lock:
            self._pending_kv_reuse.append(ticket)
        return ticket

    def enqueue_prefix_import(self, payload: dict,
                              on_done: Optional[Callable] = None
                              ) -> KvReuseTicket:
        """Queue a shared prefix payload for adoption into this engine's
        trie (thread-safe). Served at the next step() BEFORE fresh
        admissions, so a request submitted after the returned ticket
        resolves admits against the already-populated trie — zero
        prefill of the shared blocks. The ticket resolves to the blocks
        adopted (0 = already cached here, or the import failed —
        decays to a plain prefill); ``on_done(adopted)`` additionally
        fires on the drive thread."""
        ticket = KvReuseTicket("prefix_import", payload=payload,
                               on_done=on_done)
        with self._lock:
            self._pending_kv_reuse.append(ticket)
        return ticket

    def request_rebalance(self, place_cb: Callable) -> KvReuseTicket:
        """Ask the drive thread to hand its cheapest decoding victim
        over through ``place_cb(req, payload) -> bool`` (thread-safe;
        the fleet controller's mid-decode rebalance). Resolves True when
        a victim moved; False/None keeps everything decoding in place."""
        ticket = KvReuseTicket("rebalance", place_cb=place_cb)
        with self._lock:
            self._pending_kv_reuse.append(ticket)
        return ticket

    def _serve_kv_reuse(self) -> None:
        """Drain the pending KV-reuse queue on the drive thread (step()
        start, before fresh admissions). Every operation is best-effort:
        an export that can't match resolves None, an import that can't
        land is dropped (the requester re-prefills), a rebalance that
        can't place leaves the victim decoding here. Only a store-
        consuming failure escalates (engine-failure boundary, same as
        migrated imports)."""
        eng = self.engine
        while True:
            with self._lock:
                if not self._pending_kv_reuse:
                    return
                ticket = self._pending_kv_reuse.popleft()
            if ticket.kind == "prefix_export":
                payload = None
                try:
                    payload = eng.export_prefix_kv(
                        ticket.kw["tokens"],
                        min_blocks=ticket.kw["min_blocks"])
                except Exception:  # noqa: BLE001 — share is best-effort
                    payload = None
                ticket.resolve(payload)
            elif ticket.kind == "prefix_import":
                adopted = 0
                payload = ticket.kw["payload"]
                try:
                    if eng.can_import_prefix(payload):
                        adopted = eng.import_prefix_kv(payload)
                except EngineStateError as e:
                    ticket.resolve(0)
                    if not self._engine_failure(e):
                        raise
                    return
                except Exception:  # noqa: BLE001 — decay to re-prefill
                    adopted = 0
                ticket.resolve(adopted)
                on_done = ticket.kw.get("on_done")
                if on_done is not None:
                    try:
                        on_done(adopted)
                    except Exception:  # noqa: BLE001 — observer only
                        pass
            elif ticket.kind == "rebalance":
                ok = False
                try:
                    ok = self._rebalance_once(ticket.kw["place_cb"])
                except Exception:  # noqa: BLE001 — decode in place
                    ok = False
                ticket.resolve(bool(ok))

    def _rebalance_once(self, place_cb: Callable) -> bool:
        """Pick this scheduler's cheapest decoding victim — batch class
        first, then fewest live KV blocks (least payload to move), then
        the PR-18 tenant-overshare/recency order — and hand it over
        mid-decode through :meth:`_handover`. PREFILLING slots are never
        victims (their staged chunk state is not transferable)."""
        with self._lock:
            cands = [(slot, req) for slot, req in self._by_slot.items()
                     if not req.finished]
        if not cands:
            return False

        def cheap_key(item):
            slot, req = item
            blocks = self.engine.slot_block_count(slot)
            return (req.priority == "batch", -blocks,
                    (self._fair.tenant_share(req.tenant)
                     if self._fair is not None else 0.0), req.id)

        slot, req = max(cands, key=cheap_key)
        return self._handover(req, slot, place_cb, reason="rebalanced")

    def enqueue_migrated(self, req: Request, payload: dict) -> Request:
        """Accept a prefill-complete request handed over from another
        scheduler (thread-safe). The SAME Request object continues here —
        its tokens/stream_cb/trace/``_done`` ride along, so the consumer
        never notices the move. It waits in the import queue until the
        engine can take the scatter (:meth:`_admit_imports` — FCFS among
        imports, ahead of fresh admissions)."""
        with self._lock:
            self._pending_imports.append((req, payload))
        return req

    def _admit_imports(self) -> None:
        """Land pending migrated-in requests (FCFS, head-of-line: a
        transient slot/block shortage waits rather than reordering). A
        structurally unplaceable payload fails its request loudly so a
        supervising layer replays it elsewhere; a scatter that consumed
        the donated store escalates through the engine-failure boundary.
        Either way: never silently stuck, never silently lost."""
        eng = self.engine
        while True:
            with self._lock:
                if not self._pending_imports:
                    return
                req, payload = self._pending_imports[0]
            if req.finished:
                with self._lock:
                    if (self._pending_imports
                            and self._pending_imports[0][0] is req):
                        self._pending_imports.popleft()
                continue
            remaining = max(1, req.max_new_tokens - len(req.tokens))
            if not eng.can_import(payload, max_new=remaining):
                if eng.can_import(payload, max_new=remaining,
                                  static_only=True):
                    return   # transient: slots/blocks free up later
                with self._lock:
                    if (self._pending_imports
                            and self._pending_imports[0][0] is req):
                        self._pending_imports.popleft()
                self._fail_group([req], RuntimeError(
                    "migrated payload can never land on this engine "
                    "(block layout / position / capacity mismatch)"))
                continue
            t0 = time.perf_counter()
            try:
                slot = eng.import_slot_kv(payload, prompt=req.prompt,
                                          max_new=remaining,
                                          ctx={"reqs": [req.id]})
            except EngineStateError as e:
                with self._lock:
                    if (self._pending_imports
                            and self._pending_imports[0][0] is req):
                        self._pending_imports.popleft()
                if not self._engine_failure(e, admitting=req):
                    raise
                return
            except Exception:  # noqa: BLE001 — engine intact: retry later
                return
            t1 = time.perf_counter()
            with self._lock:
                if (self._pending_imports
                        and self._pending_imports[0][0] is req):
                    self._pending_imports.popleft()
                if req.state is RequestState.CANCELLED:
                    eng.release(slot)
                    continue
                req.slot = slot
                req.state = RequestState.DECODE
                self._by_slot[slot] = req
                req.weight_version = getattr(eng, "weight_version", None)
            if req._span_queue is not None:
                req.trace.end_span(req._span_queue)
                req._span_queue = None
            req.trace.add_span("import", t0, t1, slot=slot,
                               blocks=payload["n_blocks"])
            if self.costs is not None:
                self.costs.record_queue_wait(
                    req.tenant, time.perf_counter() - req._t_enqueue)
            self._events.emit("slot_admit", req=req.id, slot=slot,
                              prompt_len=len(req.prompt), migrated=True,
                              queue_depth=self.queue_depth,
                              **self._trace_label(req))

    # ------------------------------------------------------------------ #
    # paged-KV block management (decode-side)                             #
    # ------------------------------------------------------------------ #

    def _ensure_decode_blocks(self) -> None:
        """Before a paged decode step: append a fresh block for every
        active slot whose next write crosses a block boundary. When the
        pool is dry (even after trie eviction), deterministically preempt
        the LOWEST-priority request — the most recently submitted
        (highest id) — requeueing it instead of failing anyone
        mid-decode; an injected ``serving.kv_append`` fault is contained
        the same way (only that slot's request preempts — no engine
        restart burned, every other slot keeps decoding)."""
        eng = self.engine
        # drive-thread read; concurrent release is caught by the .get
        # None check, same contract as the step() token loop
        for slot in sorted(self._by_slot):  # graftlint: unguarded-ok
            req = self._by_slot.get(slot)
            if req is None:
                continue
            while eng.slot_needs_block(slot):
                try:
                    appended = eng.append_block(slot)
                except Exception as e:  # noqa: BLE001 — containment
                    self._preempt(req, reason=f"kv_append_"
                                              f"{type(e).__name__}")
                    break
                if appended:
                    # re-check: a multi-token round (speculative window /
                    # decode_window) can span MORE than one new block
                    continue
                victim = max(self._by_slot.values(), key=self._preempt_key)
                self._preempt(victim, reason="kv_pool_dry")
                if victim is req:
                    break   # we were the lowest priority ourselves

    def _preempt_key(self, req: Request) -> tuple:
        """Victim ordering when blocks run dry (max = evicted first):
        ``batch`` before any ``interactive``, then the tenant with the
        largest measured device-second share (the noisy neighbor pays
        first), then recency (highest id) — (class, overshare, recency).
        Without fair admission the share term is 0 and this reduces to
        (class, recency); without classes it is exactly the old
        newest-first rule."""
        share = (self._fair.tenant_share(req.tenant)
                 if self._fair is not None else 0.0)
        return (req.priority == "batch", share, req.id)

    def _preempt(self, req: Request, reason: str) -> None:
        """Evict a decoding request back to QUEUED: its slot and blocks
        free immediately, its generated-so-far tokens are discarded, and
        it re-enters the queue in submission-id order (FCFS). On
        re-admission it replays the SAME prompt with the SAME rng, so the
        sampler split sequence — and therefore the token stream —
        reproduces exactly (greedy or sampled); a ``stream_cb`` consumer
        sees the replayed tokens again."""
        with self._lock:
            if req.finished:
                return
            if req.slot >= 0:
                self.engine.release(req.slot)
                self._by_slot.pop(req.slot, None)
            if self.costs is not None:
                # the work already booked as useful stays useful (the
                # counters are monotonic); the REPLAY of these discarded
                # tokens is what books as waste, forward, as it happens
                self.costs.note_preempt(req.id, req.tenant,
                                        len(req.tokens))
            req.slot = -1
            req.tokens = []
            req.state = RequestState.QUEUED
            # reinsert preserving id (arrival) order among QUEUED peers
            idx = 0
            for idx, queued in enumerate(self._queue):  # noqa: B007
                if queued.id > req.id:
                    break
            else:
                idx = len(self._queue)
            self._queue.insert(idx, req)
        self.metrics.record_preemption(priority=req.priority)
        req._t_enqueue = time.perf_counter()
        if req._span_admit is not None:
            req.trace.end_span(req._span_admit)
            req._span_admit = None
        req._span_queue = req.trace.start_span("queue")
        self._events.emit("kv_preempt", req=req.id, reason=reason,
                          priority=req.priority, tenant=req.tenant,
                          queue_depth=self.queue_depth,
                          **self._trace_label(req))

    # ------------------------------------------------------------------ #
    # degradation internals                                               #
    # ------------------------------------------------------------------ #

    def _shed_expired(self) -> None:
        """Fail requests past their deadline (terminal ERRORED with
        DeadlineExceededError stored) — work that can no longer meet its
        deadline must not consume a slot another request could use. Both
        sides are swept: QUEUED requests are dropped from the queue, and
        a DECODING request past its deadline is retired at this step
        boundary with its slot + blocks freed — before this fix it kept
        burning device time to finish an answer nobody would read. The
        retirement happens strictly BETWEEN engine steps, so surviving
        slots' token streams (and replay parity) are untouched."""
        now = time.perf_counter()
        expired: list[Request] = []
        decode_expired: list[Request] = []
        prefill_expired: list[Request] = []
        with self._lock:
            if (not self._queue and not self._by_slot
                    and not self._prefilling
                    and not self._pending_imports):
                return
            hint = self._retry_after_locked()
            if self._queue:
                keep: deque[Request] = deque()
                for req in self._queue:
                    if req.t_deadline is not None and now >= req.t_deadline:
                        req.error = DeadlineExceededError(
                            f"request {req.id} spent its {req.deadline_s}s "
                            "deadline in the admission queue",
                            retry_after_s=hint,
                        )
                        req.state = RequestState.ERRORED
                        self.metrics.record_shed()
                        expired.append(req)
                    else:
                        keep.append(req)
                self._queue = sanitizer.guarded(
                    keep, lock=self._lock, name="FCFSScheduler._queue")
            for slot in sorted(self._by_slot):
                req = self._by_slot[slot]
                if req.t_deadline is None or now < req.t_deadline:
                    continue
                self.engine.release(slot)
                self._by_slot.pop(slot, None)
                req.error = DeadlineExceededError(
                    f"request {req.id} passed its {req.deadline_s}s "
                    f"deadline after {len(req.tokens)} decoded token(s)",
                    retry_after_s=hint,
                )
                req.state = RequestState.ERRORED
                self.metrics.record_shed()
                decode_expired.append(req)
            # chunked prefills past deadline: this sweep runs on the
            # driving thread between steps, so no chunk is in flight and
            # the slot release cannot race a commit
            for slot in sorted(self._prefilling):
                req = self._prefilling[slot]
                if req.t_deadline is None or now < req.t_deadline:
                    continue
                self.engine.release(slot)
                self._prefilling.pop(slot, None)
                req.error = DeadlineExceededError(
                    f"request {req.id} passed its {req.deadline_s}s "
                    "deadline mid chunked prefill",
                    retry_after_s=hint,
                )
                req.state = RequestState.ERRORED
                self.metrics.record_shed()
                prefill_expired.append(req)
            if self._pending_imports:
                keep_imp: deque = deque()
                for item in self._pending_imports:
                    req = item[0]
                    if (req.t_deadline is not None
                            and now >= req.t_deadline):
                        req.error = DeadlineExceededError(
                            f"request {req.id} passed its "
                            f"{req.deadline_s}s deadline awaiting its "
                            "KV migration import",
                            retry_after_s=hint,
                        )
                        req.state = RequestState.ERRORED
                        self.metrics.record_shed()
                        expired.append(req)
                    else:
                        keep_imp.append(item)
                self._pending_imports = sanitizer.guarded(
                    keep_imp, lock=self._lock,
                    name="FCFSScheduler._pending_imports")
        for req in expired + decode_expired + prefill_expired:
            if self.costs is not None:
                self.costs.finalize(req.id)
            # deadline-missed traces are retained regardless of sampling
            # (always-sample-on-deadline-miss): exactly the requests an
            # SLO breach will want to name
            req.trace.mark_deadline_miss()
            req.trace.finish(reason="shed")
            self._events.emit("shed", req=req.id,
                              where=("decode" if req in decode_expired
                                     else "prefill"
                                     if req in prefill_expired
                                     else "queue"),
                              waited_s=round(now - req.t_submit, 6),
                              **self._trace_label(req))
            req._done.set()

    def _retry_after_locked(self) -> float:
        """The structured backpressure hint attached to rejections and
        sheds: scales with queue depth so a deeper backlog pushes
        retries further out (the fleet edge's retry budget and breaker
        honor it end to end)."""
        return round(0.05 + 0.01 * len(self._queue), 3)

    def _policy_tick(self) -> None:
        """Once per step, before admissions: feed the fair-admission
        policy the ledger's measured per-tenant device-seconds (the
        noisy-neighbor weight shrink), let a self-driving brownout
        policy observe queue pressure, and execute the L4 shed when the
        ladder is that deep."""
        if self._fair is not None and self.costs is not None:
            self._fair.set_shares(self.costs.tenant_device_seconds())
        bo = self._brownout
        if bo is None:
            return
        # pressure = INTERACTIVE depth only: a paused batch backlog must
        # not hold the ladder up (L1 pauses batch — counting it would
        # make the level self-sustaining and the queue never drain)
        with self._lock:
            depth = sum(1 for r in self._queue if r.priority != "batch")
        bo.auto_observe(depth)
        if bo.shed_lowest:
            self._brownout_shed()

    def _brownout_shed(self) -> None:
        """Brownout L4: shed the lowest-effective-weight tenant's QUEUED
        work with a Retry-After hint (terminal QueueFullError — the
        client-visible contract is identical to an admission-queue
        rejection, plus the hint). In-flight work is never touched: the
        shed frees queue pressure, not slots."""
        with self._lock:
            tenants = sorted({r.tenant for r in self._queue})
        if not tenants:
            return
        if self._fair is not None:
            victim_tenant = self._fair.lowest_weight_tenant(tenants)
        else:
            victim_tenant = tenants[0]
        dropped: list[Request] = []
        with self._lock:
            hint = max(self._retry_after_locked(),
                       float(self._brownout.down_after_s))
            keep: deque[Request] = deque()
            for req in self._queue:
                if req.tenant == victim_tenant:
                    req.error = QueueFullError(
                        f"request {req.id} shed by brownout L4 "
                        f"(tenant {victim_tenant})",
                        retry_after_s=round(hint, 3),
                    )
                    req.state = RequestState.ERRORED
                    self.metrics.record_shed()
                    dropped.append(req)
                else:
                    keep.append(req)
            self._queue = sanitizer.guarded(
                keep, lock=self._lock, name="FCFSScheduler._queue")
        for req in dropped:
            if self.costs is not None:
                self.costs.finalize(req.id)
            self.metrics.record_tenant_shed(req.tenant)
            req.trace.finish(reason="shed")
            self._events.emit("shed", req=req.id, where="brownout",
                              tenant=req.tenant,
                              retry_after_s=req.error.retry_after_s,
                              **self._trace_label(req))
            req._done.set()

    def _engine_failure(self, e: BaseException,
                        admitting=None) -> bool:
        """The engine raised mid-round: fail every in-flight request
        loudly (their cache/slot state is unknown), dump the flight
        recorder once, and — within the restart budget — warm-restart the
        engine (fresh caches, slot mirrors, AND prefix store/trie — one
        consistent rebuild) so the queue keeps being served. Returns True
        when the engine was restarted; False tells the caller to
        re-raise. ``admitting`` is the request or group mid-admission."""
        if admitting is None:
            admitting = []
        elif isinstance(admitting, Request):
            admitting = [admitting]
        with self._lock:
            victims = list(self._by_slot.values())
            self._by_slot.clear()
            # half-prefilled chunked requests die with the store too;
            # pending KV imports are KEPT — their payloads are host-side
            # copies, importable onto the restarted engine as-is
            victims.extend(self._prefilling.values())
            self._prefilling.clear()
            victims.extend(admitting)
            for req in victims:
                if req.finished:
                    continue
                if req.error is None:
                    failure = EngineFailed(
                        f"engine failed while request {req.id} was in "
                        f"flight: {type(e).__name__}: {e}")
                    failure.__cause__ = e
                    req.error = failure
                req.state = RequestState.ERRORED
                self.metrics.record_errored()
        self._events.emit("engine_error", error=type(e).__name__,
                          detail=str(e)[:200], in_flight=len(victims),
                          traces=[r.trace.trace_id for r in victims
                                  if r.trace.enabled])
        get_event_log().dump(file=sys.stderr, last=32, once="failure")
        for req in victims:
            if self.costs is not None:
                self.costs.finalize(req.id)
            req.trace.mark_error(type(e).__name__)
            req.trace.finish(reason="engine_error")
            req._done.set()
        if not self._restart_on_error or self._restarts >= self._max_restarts:
            return False
        self.engine.restart()
        self._restarts += 1
        self.metrics.record_restart()
        self._events.emit("engine_restart", restarts=self._restarts)
        get_event_log().reset_dump_guard()  # recovered: next failure dumps
        return True

    # ------------------------------------------------------------------ #
    # internals                                                           #
    # ------------------------------------------------------------------ #

    def _deliver(self, req: Request, tok: int, now: float) -> None:
        req.tokens.append(int(tok))
        req.t_last_token = now
        if req.stream_cb is not None:
            try:
                req.stream_cb(int(tok))
            except Exception:
                pass  # a consumer's callback must not kill the engine loop
        hit_eos = self.eos_id is not None and int(tok) == self.eos_id
        # brownout L3: the effective max_new ceiling tightens for
        # in-flight and future requests alike — early retirement yields
        # a PREFIX of the request's full token stream (determinism kept)
        limit = req.max_new_tokens
        if self._brownout is not None:
            cap = self._brownout.effective_max_new_cap
            if cap is not None:
                limit = min(limit, cap)
        if hit_eos or len(req.tokens) >= limit:
            self._retire(req, "eos" if hit_eos else "length")

    def _retire(self, req: Request, reason: str) -> None:
        paged = getattr(self.engine, "paged", False)
        with self._lock:
            if req.finished:   # a concurrent cancel() won the race
                return
            if paged:
                # sampled BEFORE release drops the table: how many store
                # blocks this request's whole life actually took
                self.metrics.record_request_blocks(
                    self.engine.slot_block_count(req.slot))
            self.engine.release(req.slot)
            self._by_slot.pop(req.slot, None)
            req.state = RequestState.DONE
            self.metrics.record_done()
        if self.costs is not None:
            self.costs.finalize(req.id)
        self._events.emit("slot_retire", req=req.id, slot=req.slot,
                          reason=reason, tokens=len(req.tokens),
                          **self._trace_label(req))
        req.trace.finish(reason=reason, tokens=len(req.tokens))
        if req.trace.enabled:
            # per-trace critical path into the metrics surface: where the
            # slowest request actually spent its time
            self.metrics.record_trace(req.id, req.trace.breakdown())
        req._done.set()


__all__ = [
    "DeadlineExceededError",
    "EngineFailed",
    "FCFSScheduler",
    "QueueFullError",
    "Request",
    "RequestState",
    "STEP_PHASES",
    "STEP_PHASE_CHILDREN",
    "SwapTicket",
]
