"""Recompile + memory tracking, and the per-step instrumentation wrapper.

``RecompileGuard`` generalizes the zero-recompile assertion the serving
tests pinned in PR 1 (``engine.compile_counts() == {'prefill': 1,
'decode': 1}``) into a reusable watcher over any jitted function's
executable count (``fn._cache_size()``): growth past the first compile is
a *recompile* — counted, event-logged, and optionally warned/raised on.
Shape-driven retraces are the classic silent TPU performance cliff; this
makes them a number.

``MonitoredFunction`` (via :func:`instrument`) wraps a step-shaped
callable with the whole telemetry spine: step start/end events, a step
counter + step-time histogram in the registry, recompile detection, a
profiler annotation, and periodic device-memory gauges. Attribute access
delegates to the wrapped function, so ``.lower()`` / ``._cache_size()``
callers (an AOT compile, ``collective_stats``) see no difference.
"""

from __future__ import annotations

import sys
import time
from typing import Callable, Optional

from chainermn_tpu.monitor._state import get_event_log, get_registry
from chainermn_tpu.monitor.annotations import annotate
from chainermn_tpu.monitor.events import EventLog
from chainermn_tpu.monitor.registry import MetricsRegistry


def _cache_size(fn) -> Optional[int]:
    """Executable count of a jitted function, or None when the wrapped
    object has no jit cache (AOT-compiled executables, plain callables)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


def record_memory_gauges(registry: MetricsRegistry) -> None:
    """Per-device HBM gauges (``device_bytes_in_use`` / ``_peak``) from
    ``memory_stats()``. Backends exposing none (CPU) record nothing;
    never raises (called from hot loops and reporting paths)."""
    try:
        import jax

        for i, d in enumerate(jax.devices()):
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            labels = {"device": str(i)}
            if "bytes_in_use" in stats:
                registry.gauge("device_bytes_in_use", labels).set(
                    stats["bytes_in_use"])
            if "peak_bytes_in_use" in stats:
                registry.gauge("device_peak_bytes_in_use", labels).set(
                    stats["peak_bytes_in_use"])
    except Exception:
        pass


class RecompileGuard:
    """Watch jitted functions for executable-cache growth.

    ``watch(name, fn)`` registers a function (baseline = its current
    ``_cache_size()``); ``check()`` re-reads every watched count and
    returns ``{name: new_executables}`` for those that grew *past their
    first compile*. Growth 0 -> 1 is the expected warmup compile (a
    ``compile`` event, not a recompile); any later growth increments
    ``recompiles_total{fn=name}`` and emits a ``recompile`` event — and,
    per ``on_recompile``, stays silent (``'count'``), prints to stderr
    (``'warn'``), or raises (``'raise'`` — the reusable form of the
    serving zero-recompile assertion).
    """

    def __init__(self, *, registry: Optional[MetricsRegistry] = None,
                 events: Optional[EventLog] = None,
                 on_recompile: str = "count") -> None:
        if on_recompile not in ("count", "warn", "raise"):
            raise ValueError(
                f"on_recompile must be count|warn|raise, got {on_recompile!r}")
        self._registry = registry if registry is not None else get_registry()
        self._events = events if events is not None else get_event_log()
        self._mode = on_recompile
        self._watched: dict[str, tuple] = {}   # name -> (fn, last_count)
        self._recompiles: dict[str, int] = {}

    def watch(self, name: str, fn) -> None:
        self._watched[name] = (fn, _cache_size(fn) or 0)

    def check(self) -> dict[str, int]:
        grown: dict[str, int] = {}
        for name, (fn, last) in list(self._watched.items()):
            cur = _cache_size(fn)
            if cur is None or cur <= last:
                continue
            self._watched[name] = (fn, cur)
            if last == 0 and cur == 1:
                self._events.emit("compile", fn=name, executables=cur)
                continue
            delta = cur - max(last, 1)
            if delta <= 0:            # 0 -> n>1 in one step: n-1 recompiles
                continue
            grown[name] = delta
            self._recompiles[name] = self._recompiles.get(name, 0) + delta
            self._registry.counter(
                "recompiles_total", {"fn": name}).inc(delta)
            self._events.emit("recompile", fn=name, executables=cur)
            # a step that recompiled is always worth its trace: flag the
            # ambient trace (if any) for forced retention
            from chainermn_tpu.monitor.trace import get_tracer

            get_tracer().mark_current_error(f"recompile:{name}")
            msg = (f"chainermn_tpu.monitor.RecompileGuard: {name!r} "
                   f"recompiled ({cur} executables) — a shape/dtype/static-"
                   "arg changed on a hot path")
            if self._mode == "warn":
                print(msg, file=sys.stderr, flush=True)
            elif self._mode == "raise":
                raise RuntimeError(msg)
        return grown

    @property
    def recompiles(self) -> dict[str, int]:
        """Total recompiles observed per watched name (beyond warmup)."""
        return dict(self._recompiles)

    def counts(self) -> dict[str, int]:
        """Current executable count per watched function."""
        return {
            name: _cache_size(fn) or 0
            for name, (fn, _) in self._watched.items()
        }

    def assert_no_recompiles(self) -> None:
        self.check()
        if self._recompiles:
            raise AssertionError(
                f"recompiles detected: {self._recompiles} (expected every "
                "watched function to keep its warmup executable)")


class MonitoredFunction:
    """Telemetry wrapper around a step-shaped callable (built by
    :func:`instrument`). Call-transparent: same signature, same result,
    and unknown attributes (``lower``, ``_cache_size``) delegate to the
    wrapped function so AOT/introspection callers keep working."""

    def __init__(self, fn: Callable, name: str, *,
                 registry: Optional[MetricsRegistry] = None,
                 events: Optional[EventLog] = None,
                 memory_interval: int = 64) -> None:
        self._fn = fn
        self._name = name
        self._registry = registry if registry is not None else get_registry()
        self._events = events if events is not None else get_event_log()
        self._memory_interval = int(memory_interval)
        labels = {"step": name}
        self._c_steps = self._registry.counter("steps_total", labels)
        self._h_time = self._registry.histogram(
            "step_time_seconds", labels, unit="s")
        self._guard = RecompileGuard(
            registry=self._registry, events=self._events)
        self._guard.watch(name, fn)
        self._n = 0

    @property
    def inner(self) -> Callable:
        return self._fn

    def __call__(self, *args, **kwargs):
        self._n += 1
        n = self._n
        ev = self._events
        ev.emit("step_start", step=self._name, n=n)
        t0 = time.perf_counter()
        with annotate(f"chainermn.step.{self._name}"):
            out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        self._c_steps.inc()
        self._h_time.observe(dt)
        ev.emit("step_end", step=self._name, n=n, dur_s=round(dt, 6))
        self._guard.check()
        if self._memory_interval and n % self._memory_interval == 0:
            record_memory_gauges(self._registry)
        return out

    def __getattr__(self, name: str):
        return getattr(self._fn, name)

    def __repr__(self) -> str:
        return f"<MonitoredFunction {self._name!r} of {self._fn!r}>"


def instrument(fn: Callable, name: str, **kwargs) -> MonitoredFunction:
    """Wrap ``fn`` with step events + metrics + recompile/memory tracking.
    Idempotent-ish: instrumenting a MonitoredFunction wraps the original
    function under a new name instead of stacking wrappers."""
    if isinstance(fn, MonitoredFunction):
        fn = fn.inner
    return MonitoredFunction(fn, name, **kwargs)


__all__ = [
    "MonitoredFunction",
    "RecompileGuard",
    "instrument",
    "record_memory_gauges",
]
