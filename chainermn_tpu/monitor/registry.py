"""Process-wide metrics registry: counters, gauges, histograms with labels.

The reference framework has no metrics surface at all (SURVEY.md S5: users
bolt Chainer hooks onto the trainer); the serving subsystem (PR 1) grew one
private list per latency series. This module is the one place both sides
publish into: get-or-create instruments keyed by ``name`` + sorted labels,
a JSON-able :meth:`MetricsRegistry.snapshot`, Prometheus-style text
:meth:`MetricsRegistry.exposition`, and cross-rank
:meth:`MetricsRegistry.aggregate` so rank 0 can report fleet-wide p50/p99.

Histograms keep a bounded reservoir of raw samples and report through the
same percentile convention as :func:`chainermn_tpu.extensions.profiling.
latency_report` (``mean/p50/p99``, ``_s``-suffixed for seconds-valued
series).
"""

from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Mapping, Optional

import numpy as np

from chainermn_tpu.analysis import sanitizer

# module import, not the package facade: chainermn_tpu.extensions/__init__
# may be mid-initialization when the communicator layer pulls monitor in
# NOTE: `latency_report` is imported lazily inside Histogram.stats().
# `extensions/__init__` imports `checkpoint`, which imports this package
# (registry counters + flight-recorder events on checkpoint I/O); a
# module-level import here would close that cycle.

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def _labels_key(labels: Optional[Mapping[str, str]]) -> tuple:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render_labels(lk: tuple) -> str:
    if not lk:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in lk)
    return "{" + inner + "}"


class _Instrument:
    kind = "untyped"

    def __init__(self, name: str, labels_key: tuple) -> None:
        self.name = name
        self.labels_key = labels_key
        # leaf: instruments are updated under arbitrary subsystem locks
        # (scheduler, router), so this lock must stay terminal — the
        # sanitizer enforces that nothing is acquired while it is held
        self._lock = sanitizer.make_lock("_Instrument._lock", leaf=True)

    @property
    def key(self) -> str:
        return self.name + _render_labels(self.labels_key)


class Counter(_Instrument):
    """Monotonic counter (requests served, steps run, recompiles)."""

    kind = "counter"

    def __init__(self, name: str, labels_key: tuple) -> None:
        super().__init__(name, labels_key)
        self._value = 0

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease (inc {n})")
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge(_Instrument):
    """Point-in-time value (queue depth now, device bytes in use)."""

    kind = "gauge"

    def __init__(self, name: str, labels_key: tuple) -> None:
        super().__init__(name, labels_key)
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


class Histogram(_Instrument):
    """Distribution with a bounded raw-sample reservoir.

    ``unit='s'`` marks a seconds-valued series: its stats come from
    :func:`latency_report` (``mean_s/p50_s/p99_s``) so every latency
    surface in the framework reports through one convention. Unit-less
    series get plain ``mean/p50/p99``. The reservoir keeps the newest
    ``max_samples`` observations — percentile memory is bounded no matter
    how long the process serves.
    """

    kind = "histogram"

    def __init__(self, name: str, labels_key: tuple, unit: str = "",
                 max_samples: int = 4096) -> None:
        super().__init__(name, labels_key)
        self.unit = unit
        self._samples: deque = deque(maxlen=max_samples)
        # observation times (time.monotonic), same maxlen so the two
        # deques stay aligned — the SLO engine's windowed reads
        self._times: deque = deque(maxlen=max_samples)
        self._count = 0
        self._sum = 0.0

    def observe(self, v: float, t: Optional[float] = None) -> None:
        v = float(v)
        with self._lock:
            self._samples.append(v)
            self._times.append(time.monotonic() if t is None else float(t))
            self._count += 1
            self._sum += v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def samples(self) -> list:
        """Newest retained raw samples (bounded; for percentile math)."""
        return list(self._samples)

    def recent(self, window_s: float, now: Optional[float] = None) -> list:
        """Retained samples observed within the last ``window_s`` seconds
        (``now`` defaults to ``time.monotonic()``) — the SLO engine's
        multi-window burn-rate input. Bounded by the reservoir: a window
        wider than the reservoir's history returns what is retained."""
        cutoff = (time.monotonic() if now is None else now) - float(window_s)
        with self._lock:
            return [v for v, t in zip(self._samples, self._times)
                    if t >= cutoff]

    def stats(self) -> dict:
        out: dict = {"count": int(self._count), "sum": float(self._sum)}
        samples = self.samples
        if not samples:
            return out
        if self.unit == "s":
            from chainermn_tpu.extensions.profiling import latency_report

            rep = latency_report(samples, "h")       # h_mean_s, h_p50_s, ...
            out.update({k[len("h_"):]: v for k, v in rep.items()})
        else:
            t = np.asarray(samples, np.float64)
            out["mean"] = float(t.mean())
            out["p50"] = float(np.percentile(t, 50))
            out["p99"] = float(np.percentile(t, 99))
        return out

    def percentile(self, q: float) -> float:
        samples = self.samples
        return float(np.percentile(samples, q)) if samples else 0.0


class MetricsRegistry:
    """Get-or-create instrument registry.

    One process-wide default instance lives in ``chainermn_tpu.monitor``;
    subsystems may also carry private registries (tests, isolation).
    Same ``(name, labels)`` always returns the same instrument; the same
    name with a different *kind* is a programming error and raises.
    """

    def __init__(self) -> None:
        self._lock = sanitizer.make_lock("MetricsRegistry._lock")
        self._instruments: dict[tuple, _Instrument] = sanitizer.guarded(
            {}, lock=self._lock, name="MetricsRegistry._instruments")

    # ------------------------------------------------------------------ #
    # instrument creation                                                 #
    # ------------------------------------------------------------------ #

    def _get(self, cls, name: str, labels, **kwargs):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        lk = _labels_key(labels)
        key = (name, lk)
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                inst = cls(name, lk, **kwargs)
                self._instruments[key] = inst
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {inst.kind}, "
                    f"requested {cls.kind}"
                )
            return inst

    def counter(self, name: str, labels: Optional[Mapping] = None) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, labels: Optional[Mapping] = None) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, labels: Optional[Mapping] = None, *,
                  unit: str = "", max_samples: int = 4096) -> Histogram:
        return self._get(Histogram, name, labels, unit=unit,
                         max_samples=max_samples)

    # ------------------------------------------------------------------ #
    # reporting                                                           #
    # ------------------------------------------------------------------ #

    def _by_kind(self):
        with self._lock:
            insts = list(self._instruments.values())
        counters = [i for i in insts if isinstance(i, Counter)]
        gauges = [i for i in insts if isinstance(i, Gauge)]
        hists = [i for i in insts if isinstance(i, Histogram)]
        return counters, gauges, hists

    def snapshot(self) -> dict:
        """JSON-able state of every instrument: ``{"counters": {key: int},
        "gauges": {key: float}, "histograms": {key: {count, sum, mean,
        p50, p99}}}`` where ``key`` is ``name{label="v",...}``."""
        counters, gauges, hists = self._by_kind()
        return {
            "counters": {c.key: int(c.value) for c in counters},
            "gauges": {g.key: float(g.value) for g in gauges},
            "histograms": {h.key: h.stats() for h in hists},
        }

    def exposition(self) -> str:
        """Prometheus text exposition. Counters/gauges verbatim; histograms
        as summaries (``quantile`` series + ``_sum``/``_count``) — the
        format a scrape endpoint or pushgateway ingests directly."""
        counters, gauges, hists = self._by_kind()
        lines: list[str] = []
        seen_type: set[str] = set()

        def type_line(name: str, kind: str) -> None:
            if name not in seen_type:
                seen_type.add(name)
                lines.append(f"# TYPE {name} {kind}")

        for c in sorted(counters, key=lambda i: i.key):
            type_line(c.name, "counter")
            lines.append(f"{c.key} {int(c.value)}")
        for g in sorted(gauges, key=lambda i: i.key):
            type_line(g.name, "gauge")
            lines.append(f"{g.key} {float(g.value):g}")
        for h in sorted(hists, key=lambda i: i.key):
            type_line(h.name, "summary")
            for q in (0.5, 0.99):
                ql = self._with_label(h, "quantile", str(q))
                lines.append(f"{h.name}{ql} {h.percentile(q * 100):g}")
            suffix = _render_labels(h.labels_key)
            lines.append(f"{h.name}_sum{suffix} {h.sum:g}")
            lines.append(f"{h.name}_count{suffix} {h.count}")
        return "\n".join(lines) + ("\n" if lines else "")

    @staticmethod
    def _with_label(inst: _Instrument, k: str, v: str) -> str:
        lk = tuple(sorted(inst.labels_key + ((k, v),)))
        return _render_labels(lk)

    # ------------------------------------------------------------------ #
    # cross-rank aggregation                                              #
    # ------------------------------------------------------------------ #

    def _rank_payload(self) -> dict:
        counters, gauges, hists = self._by_kind()
        return {
            "counters": {c.key: int(c.value) for c in counters},
            "gauges": {g.key: float(g.value) for g in gauges},
            "hist": {
                h.key: {"unit": h.unit, "count": h.count, "sum": h.sum,
                        "samples": h.samples}
                for h in hists
            },
        }

    def aggregate(self, comm) -> dict:
        """Fleet-wide snapshot over a communicator.

        Rides the same process-space object transport as
        :class:`~chainermn_tpu.extensions.observation_aggregator.
        ObservationAggregator` (one ``allgather_obj`` of the per-rank
        state), then merges: counters SUM across ranks, gauges MEAN (the
        ObservationAggregator convention), histogram reservoirs
        concatenate so the reported p50/p99 are over the fleet's pooled
        samples — rank 0's log then reflects the whole job, not one
        shard. Every rank returns the same merged dict.
        """
        gathered = comm.allgather_obj(self._rank_payload())
        return merge_rank_payloads(gathered)


def merge_rank_payloads(payloads: list) -> dict:
    """Merge per-rank :meth:`MetricsRegistry._rank_payload` dicts into one
    fleet snapshot (split out of :meth:`MetricsRegistry.aggregate` so the
    merge semantics are unit-testable without processes)."""
    counters: dict[str, int] = {}
    gauge_vals: dict[str, list] = {}
    hist: dict[str, dict] = {}
    for p in payloads:
        for k, v in p.get("counters", {}).items():
            counters[k] = counters.get(k, 0) + int(v)
        for k, v in p.get("gauges", {}).items():
            gauge_vals.setdefault(k, []).append(float(v))
        for k, h in p.get("hist", {}).items():
            ent = hist.setdefault(
                k, {"unit": h.get("unit", ""), "count": 0, "sum": 0.0,
                    "samples": []})
            ent["count"] += int(h.get("count", 0))
            ent["sum"] += float(h.get("sum", 0.0))
            ent["samples"].extend(h.get("samples", ()))
    histograms = {}
    for k, ent in hist.items():
        out = {"count": ent["count"], "sum": ent["sum"]}
        samples = ent["samples"]
        if samples:
            if ent["unit"] == "s":
                from chainermn_tpu.extensions.profiling import latency_report

                rep = latency_report(samples, "h")
                out.update({f[len("h_"):]: v for f, v in rep.items()})
            else:
                t = np.asarray(samples, np.float64)
                out["mean"] = float(t.mean())
                out["p50"] = float(np.percentile(t, 50))
                out["p99"] = float(np.percentile(t, 99))
        histograms[k] = out
    return {
        "ranks": len(payloads),
        "counters": counters,
        "gauges": {k: float(np.mean(v)) for k, v in gauge_vals.items()},
        "histograms": histograms,
    }


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "merge_rank_payloads",
]
