"""Dispatch-ahead stepping: keep losses on device, fetch batched every K.

A per-step ``float(loss)`` costs a full device->host round trip on the
critical path, while JAX's async dispatch is happy to run several steps
ahead. :class:`LossWindow` is the loop-side half of that bargain: ``push``
enqueues the on-device loss of each step and returns immediately; once
``window`` losses are pending they are fetched in ONE host round trip,
which doubles as the bounded in-flight window — the fetch of step
``i-K+1..i`` cannot resolve before those steps complete, so dispatch never
runs more than ``window`` steps past completion (an unbounded run-ahead
queues device work and host memory without limit).

:func:`device_fetch` is the other half: a device->host VALUE fetch, the
completion barrier that also hands the host the numbers it waited for. It
is what :class:`LossWindow` closes its fetches with.

Telemetry (process registry): ``loss_fetch_total{loop=}`` (fetch EVENTS —
the per-step-host-sync guard test pins this at ``ceil(steps/window)``,
not ``steps``), ``loss_fetch_seconds`` histogram, ``dispatch_lag_steps``
histogram (how many steps were in flight when a fetch closed — the
dispatch-vs-complete lag), ``dispatch_inflight{loop=}`` gauge.
"""

from __future__ import annotations

from collections import deque
from time import perf_counter
from typing import Callable, Optional

from chainermn_tpu.monitor._state import get_registry
from chainermn_tpu.monitor.trace import span as _trace_span


def device_fetch(values):
    """Fetch device value(s) to host — a completion barrier that returns
    the values. Accepts any pytree of arrays; returns host (numpy) values.
    """
    import jax

    return jax.device_get(values)


class LossWindow:
    """Bounded in-flight window of on-device per-step losses.

    ``push(i, loss)`` is O(1) host work until the window fills; then all
    pending losses are fetched in one device round trip (amortized
    ``1/window`` syncs per step). ``drain()`` fetches the remainder and
    returns every loss, in step order, as floats.

    ``on_fetch(step_index, value)`` (optional) is called for each loss as
    its fetch completes — logging callbacks see values ``<= window-1``
    steps late, which is the price of keeping the loop unblocked.
    """

    def __init__(self, window: int = 8, *, name: str = "train",
                 on_fetch: Optional[Callable[[int, float], None]] = None
                 ) -> None:
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self._window = int(window)
        self._on_fetch = on_fetch
        self._pending: deque = deque()
        self._losses: list[float] = []
        reg = get_registry()
        labels = {"loop": name}
        self._c_fetches = reg.counter("loss_fetch_total", labels)
        self._h_fetch = reg.histogram("loss_fetch_seconds", labels, unit="s")
        self._h_lag = reg.histogram("dispatch_lag_steps", labels)
        self._g_inflight = reg.gauge("dispatch_inflight", labels)

    def push(self, step: int, loss) -> bool:
        """Enqueue step ``step``'s on-device loss; fetches (blocking once
        per ``window`` pushes) when the in-flight bound is reached.
        Returns True when this push closed a fetch — the caller's signal
        that the (rare) blocking host round trip happened here."""
        self._pending.append((step, loss))
        self._g_inflight.set(len(self._pending))
        if len(self._pending) >= self._window:
            self._fetch_pending()
            return True
        return False

    def _fetch_pending(self) -> None:
        if not self._pending:
            return
        steps = [s for s, _ in self._pending]
        vals = [v for _, v in self._pending]
        self._pending.clear()
        self._h_lag.observe(len(vals))
        t0 = perf_counter()
        # ONE round trip closes `len(vals)` steps; the ambient span puts
        # the blocking fetch on the current train-step trace (no-op when
        # no trace is ambient)
        with _trace_span("loss_fetch", n=len(vals)):
            host = device_fetch(vals)
        self._h_fetch.observe(perf_counter() - t0)
        self._c_fetches.inc()
        self._g_inflight.set(0)
        for s, v in zip(steps, host):
            v = float(v)
            self._losses.append(v)
            if self._on_fetch is not None:
                self._on_fetch(s, v)

    def drain(self) -> list[float]:
        """Fetch whatever is still in flight; returns ALL losses in step
        order. The loop's closing barrier — after ``drain`` every pushed
        step has verifiably completed on device."""
        self._fetch_pending()
        return list(self._losses)

    @property
    def losses(self) -> list[float]:
        """Losses fetched so far (excludes in-flight steps)."""
        return list(self._losses)

    @property
    def inflight(self) -> int:
        return len(self._pending)


__all__ = ["LossWindow", "device_fetch"]
