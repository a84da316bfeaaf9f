"""In-process serving client: a background thread drives the scheduler;
callers get blocking and streaming APIs.

This is the process-local front of the serving stack (engine = mechanism,
scheduler = policy, client = thread + API). A network front would sit
where this class sits — the scheduler surface is already
submission-threaded — but in-process is the tier-1-testable core and what
the benchmark's serving harness and ``examples/lm/serve_lm.py`` drive.

Usage::

    engine = ServingEngine(model, params, n_slots=4, prefill_len=16)
    with ServingClient(engine, eos_id=0) as client:
        out = client.generate(prompt, max_new_tokens=32)      # blocking
        req = client.submit(prompt, 32, stream_cb=print)       # streaming
        req.wait()

The engine thread wakes on submission and sleeps when idle (event-driven,
no spin); an engine-side exception fails every in-flight request loudly
(the ``global_except_hook`` stance: die informatively, never hang a
caller on a dead engine).
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import numpy as np

from chainermn_tpu.monitor import annotate
from chainermn_tpu.serving.scheduler import FCFSScheduler, Request


class ServingClient:
    """Background-threaded continuous-batching server, in process.

    Parameters mirror :class:`FCFSScheduler` (``eos_id``); the engine is
    built by the caller so model/sharding/sampler configuration stays in
    one place.
    """

    def __init__(self, engine, *, eos_id: Optional[int] = None,
                 idle_wait_s: float = 0.05,
                 max_queue: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 retry=None, restart_on_error: bool = True,
                 max_restarts: int = 8, fair=None, tenant_weights=None,
                 brownout=None,
                 chunk_tokens_per_step: Optional[int] = None) -> None:
        self.engine = engine
        self.scheduler = FCFSScheduler(
            engine, eos_id=eos_id, max_queue=max_queue,
            default_deadline_s=default_deadline_s, retry=retry,
            restart_on_error=restart_on_error, max_restarts=max_restarts,
            fair=fair, tenant_weights=tenant_weights, brownout=brownout,
            chunk_tokens_per_step=chunk_tokens_per_step)
        self.metrics = self.scheduler.metrics
        self._work = threading.Event()
        self._stop = threading.Event()
        self._failure: Optional[BaseException] = None
        self._idle_wait_s = idle_wait_s
        self._thread = threading.Thread(
            target=self._loop, name="chainermn-tpu-serving", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------ #
    # public API                                                          #
    # ------------------------------------------------------------------ #

    def submit(self, prompt, max_new_tokens: int, *, rng=None,
               stream_cb: Optional[Callable[[int], None]] = None,
               deadline_s: Optional[float] = None,
               tenant: str = "default",
               priority: str = "interactive") -> Request:
        """Enqueue a request; returns immediately. ``stream_cb`` (if set)
        is invoked from the engine thread once per generated token.
        ``tenant`` labels the request for the cost ledger's per-tenant
        attribution (and, with fair admission on, keys its DRR budget);
        ``priority`` picks the admission class (``"interactive"`` /
        ``"batch"``). Raises ``QueueFullError`` in the calling thread
        when the bounded admission queue (``max_queue``) is at capacity
        — backpressure is the submitter's signal, not a queued request's
        problem; its ``retry_after_s`` is the structured wait hint."""
        if self._failure is not None:
            raise RuntimeError("serving engine failed") from self._failure
        if self._stop.is_set():
            raise RuntimeError("client is closed")
        req = self.scheduler.submit(prompt, max_new_tokens, rng=rng,
                                    stream_cb=stream_cb,
                                    deadline_s=deadline_s,
                                    tenant=tenant, priority=priority)
        self._work.set()
        return req

    def generate(self, prompt, max_new_tokens: int, *, rng=None,
                 timeout: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 tenant: str = "default",
                 priority: str = "interactive") -> np.ndarray:
        """Blocking single-request decode: ``prompt + generated`` tokens,
        the :func:`chainermn_tpu.models.generate`-shaped result. A shed
        or engine-failed (ERRORED) request re-raises its stored exception
        here, in the caller's thread — degradation is loud, never a
        silent hang (a shed's ``retry_after_s`` rides the exception)."""
        req = self.submit(prompt, max_new_tokens, rng=rng,
                          deadline_s=deadline_s, tenant=tenant,
                          priority=priority)
        if not req.wait(timeout):
            self.cancel(req)
            raise TimeoutError(
                f"request {req.id} did not finish within {timeout}s")
        return req.output

    def cancel(self, req: Request) -> bool:
        return self.scheduler.cancel(req)

    def close(self, timeout: float = 10.0) -> None:
        """Stop the engine thread (in-flight work is abandoned; pending
        requests are cancelled so no waiter hangs)."""
        self._stop.set()
        self._work.set()
        self._thread.join(timeout)
        # fail any stragglers loudly rather than leaving waiters blocked
        with self.scheduler._lock:
            pending = list(self.scheduler._queue) + list(
                self.scheduler._by_slot.values())
        for req in pending:
            self.scheduler.cancel(req)

    def __enter__(self) -> "ServingClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------ #
    # engine thread                                                       #
    # ------------------------------------------------------------------ #

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                if self.scheduler.has_work:
                    self.scheduler.step()
                else:
                    # sleep until a submission (or periodic re-check);
                    # clear first so a submit during step() re-wakes us
                    self._work.clear()
                    if self.scheduler.has_work:
                        continue
                    # asleep for want of work: named so that a trace
                    # reduction does not read it as host overhead
                    with annotate("chainermn.serving_idle"):
                        self._work.wait(self._idle_wait_s)
        except BaseException as e:  # noqa: BLE001 — fail every waiter loudly
            self._failure = e
            with self.scheduler._lock:
                pending = list(self.scheduler._queue) + list(
                    self.scheduler._by_slot.values())
            for req in pending:
                req.error = e
                req._done.set()


__all__ = ["ServingClient"]
