"""Serving observability: TTFT, per-token latency, throughput, queue depth
and slot occupancy — the serving counterpart of the training side's
``extensions.StepTimer``/``collective_stats`` layer.

Since the monitor subsystem landed, this class keeps NO private sample
lists: every series lives in the process-wide
:class:`chainermn_tpu.monitor.MetricsRegistry` (labelled ``instance=N``
per scheduler so concurrent/successive schedulers never mix), which makes
the same numbers scrapeable through ``monitor.exposition()`` and
embeddable via ``monitor.snapshot()`` while :meth:`report` stays
field-compatible with the PR-1 records (``ttft_p50_s`` etc. via the same
:func:`chainermn_tpu.extensions.latency_report` convention). First-token
recordings also emit ``first_token`` events into the flight recorder, so
a TTFT outlier in a report can be traced to the specific ``slot_admit``
events around it.

All timestamps are caller-supplied ``time.perf_counter()`` values (the
scheduler owns the clock); this module only aggregates, so it is trivially
testable and thread-agnostic (the scheduler serializes all calls).
"""

from __future__ import annotations

import itertools
from typing import Optional

import numpy as np

from chainermn_tpu.analysis import sanitizer
from chainermn_tpu.extensions import latency_report
from chainermn_tpu.monitor import EventLog, MetricsRegistry
from chainermn_tpu.monitor._state import get_event_log, get_registry

_instance_ids = itertools.count()


class ServingMetrics:
    """Aggregate serving statistics.

    Latency definitions (the standard inference-serving ones):

    - **TTFT** (time to first token): request submission -> its first
      generated token (queue wait + prefill; the admission-policy number).
    - **TPOT** (time per output token): gap between consecutive tokens of
      the SAME request (decode-step cadence; the streaming-smoothness
      number). First tokens don't contribute (they're TTFT).
    - **tokens/s**: generated tokens over the span between the first and
      last recorded token across all requests (engine-level throughput;
      0.0 until two tokens exist).

    Gauges (queue depth, slot occupancy) are sampled once per scheduler
    step and reported as mean + p50/p99 — occupancy is the fraction of
    the slot pool decoding, the continuous-batching utilization number;
    its p99 says whether the pool ever actually fills under the offered
    load, which the mean alone hides.
    """

    def __init__(self, n_slots: int, *,
                 registry: Optional[MetricsRegistry] = None,
                 events: Optional[EventLog] = None) -> None:
        self.n_slots = n_slots
        self._registry = registry if registry is not None else get_registry()
        self._events = events if events is not None else get_event_log()
        labels = {"instance": str(next(_instance_ids))}
        reg = self._registry
        self._c_submitted = reg.counter(
            "serving_requests_submitted_total", labels)
        self._c_completed = reg.counter(
            "serving_requests_completed_total", labels)
        self._c_cancelled = reg.counter(
            "serving_requests_cancelled_total", labels)
        # degradation counters (resilience layer): overload rejections at
        # submit, deadline sheds from the queue, engine-failure erroreds,
        # and warm engine restarts this scheduler drove
        self._c_rejected = reg.counter(
            "serving_requests_rejected_total", labels)
        self._c_shed = reg.counter("serving_requests_shed_total", labels)
        self._c_errored = reg.counter(
            "serving_requests_errored_total", labels)
        self._c_restarts = reg.counter(
            "serving_scheduler_restarts_total", labels)
        self._c_tokens = reg.counter("serving_tokens_total", labels)
        self._h_ttft = reg.histogram("serving_ttft_seconds", labels, unit="s")
        self._h_tpot = reg.histogram("serving_tpot_seconds", labels, unit="s")
        self._h_queue = reg.histogram("serving_queue_depth", labels)
        self._h_occ = reg.histogram("serving_slot_occupancy", labels)
        # admission fast path (PR 5): how full each batched prefill call
        # ran, and what fraction of each admitted prompt the prefix cache
        # covered (0.0 on a miss — so the mean IS the amortized discount,
        # and the >0 fraction is the hit rate)
        self._h_batch = reg.histogram("prefill_batch_size", labels)
        # rows the prefill programs ran and rows that held a request, by
        # bucket (prefill_rows_run_total, prefill_rows_filled_total): a
        # bucket's program runs all of its rows whatever the group's size
        self._labels = labels
        self._c_rows: dict[int, tuple] = {}
        self._h_cached = reg.histogram("cached_prefix_frac", labels)
        self._c_moe_local = reg.counter("moe_assignments_local_total", labels)
        self._c_moe_total = reg.counter("moe_assignments_total", labels)
        # state kept a row a slot beside the KV blocks (a linear-attention
        # layer's): rows holding a request, the bytes of the arrays, and
        # tokens x layers whose state a program advanced
        self._g_state_slots = reg.gauge("serving_state_slots_live", labels)
        self._g_state_bytes = reg.gauge("serving_state_bytes", labels)
        self._c_state_tokens = reg.counter("linear_state_tokens_total",
                                           labels)
        # chunks x layers a prefill's whole-prompt form walked (the rows'
        # prompts) and skipped (the rest of the bucket)
        self._c_prefill_chunks = {
            kind: reg.counter("linear_prefill_chunks_total",
                              dict(labels, kind=kind))
            for kind in ("live", "padding")}
        # rows x layers a decode program ran through the step, by form
        self._c_decode_rows = {
            path: reg.counter("linear_decode_rows_total",
                              dict(labels, path=path))
            for path in ("kernel", "xla")}
        self._g_queue = reg.gauge("serving_queue_depth_now", labels)
        self._g_active = reg.gauge("serving_active_slots", labels)
        # paged-KV series (PR 7): store occupancy gauges sampled per step,
        # preemptions (pool ran dry / injected append fault -> requeue),
        # and how many blocks each retired request's whole life took —
        # the "memory per request" distribution dense slots can't see
        self._g_kv_used = reg.gauge("kv_blocks_in_use", labels)
        self._g_kv_free = reg.gauge("kv_blocks_free", labels)
        self._g_kv_live = reg.gauge("kv_blocks_live", labels)
        self._c_preempt = reg.counter("kv_preemptions_total", labels)
        self._h_req_blocks = reg.histogram("kv_blocks_per_request", labels)
        # speculative decode (PR 12): per-round accept-length histogram
        # plus draft-economy counters — accepted/proposed IS the live
        # accept rate the drafter choice is judged by
        self._c_spec_proposed = reg.counter(
            "spec_tokens_proposed_total", labels)
        self._c_spec_accepted = reg.counter(
            "spec_tokens_accepted_total", labels)
        self._h_spec_accept = reg.histogram("spec_accept_length", labels)
        # overload robustness (PR 18): per-class queue depth (the
        # batch-behind-interactive split), class-labelled preemptions
        # (did batch really evict first?), and per-tenant brownout sheds
        self._g_class_queue = {
            cls: reg.gauge("serving_class_queue_depth",
                           dict(labels, priority=cls))
            for cls in ("interactive", "batch")
        }
        self._c_class_preempt = {
            cls: reg.counter("serving_class_preemptions_total",
                             dict(labels, priority=cls))
            for cls in ("interactive", "batch")
        }
        self._t_first_token: Optional[float] = None
        self._t_last_token: Optional[float] = None
        # EWMA TTFT (alpha=0.2): the routing layer's cheap "how slow is
        # this replica right now" signal — O(1), no percentile math on
        # the admission path
        self.ttft_ewma: Optional[float] = None
        # per-trace critical path (the tracing layer): phase-attributed
        # time per retired request, plus the single worst request's full
        # breakdown — the "where did the p99 go" exhibit in report()
        self._labels = labels
        self._worst_trace: Optional[dict] = None
        # continuous-telemetry hook (attach_health): a zero-arg callable
        # returning this instance's current HealthScore as a JSON dict;
        # report() embeds it so the health verdict rides every record
        self._health_fn = None
        # cost-accounting hook (attach_costs): the scheduler's per-tenant
        # CostLedger; report() embeds its rendered breakdown as "costs"
        self._costs = None

    # ------------------------------------------------------------------ #
    # recording (scheduler-driven)                                        #
    # ------------------------------------------------------------------ #

    def record_submit(self) -> None:
        self._c_submitted.inc()

    def record_first_token(self, t_submit: float, t_token: float,
                           req_id: Optional[int] = None,
                           cached_frac: Optional[float] = None) -> None:
        ttft = t_token - t_submit
        self._h_ttft.observe(ttft)
        self.ttft_ewma = (ttft if self.ttft_ewma is None
                          else 0.8 * self.ttft_ewma + 0.2 * ttft)
        self._record_token_time(t_token)
        self._c_tokens.inc()
        if cached_frac is not None:
            self._h_cached.observe(cached_frac)
        # the flight-recorder hook: a TTFT outlier names its request, so
        # it can be joined against the surrounding slot_admit events (and
        # its cached fraction says whether the prefix cache helped it)
        self._events.emit("first_token", req=req_id,
                          ttft_s=round(ttft, 6),
                          **({} if cached_frac is None
                             else {"cached_frac": round(cached_frac, 4)}))

    def record_admission(self, batch_size: int, rows_run: int,
                         bucket: int) -> None:
        """One admission device call admitted ``batch_size`` requests in
        ``bucket``'s program of ``rows_run`` rows — the batched-prefill
        occupancy series."""
        self._h_batch.observe(batch_size)
        self.record_prefill_rows(batch_size, rows_run, bucket)

    def record_prefill_rows(self, filled: int, run: int,
                            bucket: int) -> None:
        """One prefill program of ``bucket`` ran ``run`` rows, ``filled``
        of them holding a request (an admission group, or one chunk)."""
        if bucket not in self._c_rows:
            labels = dict(self._labels, prefill_bucket=str(bucket))
            self._c_rows[bucket] = (
                self._registry.counter("prefill_rows_filled_total", labels),
                self._registry.counter("prefill_rows_run_total", labels))
        c_filled, c_run = self._c_rows[bucket]
        c_filled.inc(filled)
        c_run.inc(run)

    def record_moe_assignments(self, local: int, total: int) -> None:
        """Expert assignments of the tokens some programs processed:
        ``total`` in all (tokens x sparse layers x top_k), ``local`` of them
        to experts this engine's model holds (a share of an expert-parallel
        deployment, ``DroplessMoE.held``)."""
        self._c_moe_local.inc(local)
        self._c_moe_total.inc(total)

    def record_slot_state(self, slots_live: int, n_bytes: int,
                          tokens: int, chunks_live: int,
                          chunks_padding: int, rows_kernel: int,
                          rows_xla: int) -> None:
        """The engine's state kept a row a slot: rows holding a request
        now, the bytes of its arrays, the tokens x layers whose state the
        programs advanced, the chunks x layers the prefill programs walked
        and skipped, and the rows x layers the decode programs ran
        through the step in a kernel and in XLA, since the last call."""
        self._g_state_slots.set(slots_live)
        self._g_state_bytes.set(n_bytes)
        self._c_state_tokens.inc(tokens)
        self._c_prefill_chunks["live"].inc(chunks_live)
        self._c_prefill_chunks["padding"].inc(chunks_padding)
        self._c_decode_rows["kernel"].inc(rows_kernel)
        self._c_decode_rows["xla"].inc(rows_xla)

    def record_token(self, t_prev_token: float, t_token: float) -> None:
        self._h_tpot.observe(t_token - t_prev_token)
        self._record_token_time(t_token)
        self._c_tokens.inc()

    def record_done(self, cancelled: bool = False) -> None:
        (self._c_cancelled if cancelled else self._c_completed).inc()

    def record_rejected(self) -> None:
        self._c_rejected.inc()

    def record_shed(self) -> None:
        self._c_shed.inc()

    def record_errored(self) -> None:
        self._c_errored.inc()

    def record_restart(self) -> None:
        self._c_restarts.inc()

    def record_kv_pool(self, in_use: int, free: int, live: int) -> None:
        """Paged-store occupancy, sampled once per scheduler step:
        ``in_use`` counts every block off the free list (live slots and
        the finished prompts the prefix trie still holds), ``live`` only
        those a live slot's table references."""
        self._g_kv_used.set(in_use)
        self._g_kv_free.set(free)
        self._g_kv_live.set(live)

    def record_preemption(self, priority: Optional[str] = None) -> None:
        """A decoding request was evicted back to the queue (block pool
        dry, or an injected ``serving.kv_append`` fault contained).
        ``priority`` feeds the per-class split — the batch-preempts-
        first contract is asserted against these counters."""
        self._c_preempt.inc()
        if priority in self._c_class_preempt:
            self._c_class_preempt[priority].inc()

    def record_tenant_shed(self, tenant: str) -> None:
        """A brownout L4 shed dropped one of ``tenant``'s queued
        requests (lazily-created per-tenant counter, same pattern as the
        ``trace_phase_seconds`` labelled histograms)."""
        self._registry.counter(
            "serving_tenant_sheds_total",
            dict(self._labels, tenant=str(tenant))).inc()

    def record_request_blocks(self, n_blocks: int) -> None:
        """Store blocks a retiring request's table referenced."""
        self._h_req_blocks.observe(n_blocks)

    def record_spec_window(self, proposed: int, accepted: int,
                           lengths: list) -> None:
        """One speculative verify round's accounting, drained from
        :meth:`~chainermn_tpu.serving.engine.ServingEngine
        .pop_spec_window`: totals feed the draft-economy counters, each
        slot's accept length feeds the histogram."""
        self._c_spec_proposed.inc(proposed)
        self._c_spec_accepted.inc(accepted)
        for a in lengths:
            self._h_spec_accept.observe(a)

    def record_trace(self, req_id: int, breakdown: dict) -> None:
        """One retired request's span-tree breakdown (built by
        :meth:`~chainermn_tpu.monitor.trace.Trace.breakdown`): each phase
        feeds a ``trace_phase_seconds{phase=}`` histogram (so queue wait
        vs prefill vs decode distributions are scrapeable), and the
        slowest request so far is kept whole as the critical-path
        exemplar."""
        phases = breakdown.get("phases_s", {})
        for phase, dur in phases.items():
            self._registry.histogram(
                "trace_phase_seconds", dict(self._labels, phase=phase),
                unit="s").observe(dur)
        total = breakdown.get("total_s", 0.0)
        if (self._worst_trace is None
                or total > self._worst_trace.get("total_s", 0.0)):
            self._worst_trace = dict(breakdown, req=req_id)

    def record_step(self, queue_depth: int, active_slots: int,
                    batch_depth: int = 0) -> None:
        self._h_queue.observe(queue_depth)
        self._h_occ.observe(active_slots / self.n_slots)
        self._g_queue.set(queue_depth)
        self._g_active.set(active_slots)
        self._g_class_queue["batch"].set(batch_depth)
        self._g_class_queue["interactive"].set(queue_depth - batch_depth)

    def _record_token_time(self, t: float) -> None:
        if self._t_first_token is None:
            self._t_first_token = t
        self._t_last_token = t

    # ------------------------------------------------------------------ #
    # reporting                                                           #
    # ------------------------------------------------------------------ #

    @property
    def instance(self) -> str:
        """This scheduler's ``instance=`` label value — the key the
        continuous-telemetry collector uses to find this instance's
        series in the shared registry."""
        return self._labels["instance"]

    def attach_health(self, fn) -> None:
        """Attach a zero-arg callable returning the current
        :class:`~chainermn_tpu.monitor.health.HealthScore` JSON for this
        instance (wired by :func:`~chainermn_tpu.monitor.health.
        fleet_health`); :meth:`report` then carries a ``health`` block.
        Detach with ``attach_health(None)``."""
        self._health_fn = fn

    def attach_costs(self, ledger) -> None:
        """Attach the scheduler's :class:`~chainermn_tpu.monitor.costs.
        CostLedger`; :meth:`report` then carries a ``costs`` block (per-
        tenant device/block/queue seconds + goodput + conservation) and
        the fleet layer pools :meth:`~chainermn_tpu.monitor.costs.
        CostLedger.payload` across replicas. Detach with
        ``attach_costs(None)``."""
        self._costs = ledger

    @property
    def costs(self):
        """The attached cost ledger, or None (accounting disabled)."""
        return self._costs

    @property
    def requests_submitted(self) -> int:
        return self._c_submitted.value

    @property
    def requests_completed(self) -> int:
        return self._c_completed.value

    @property
    def requests_cancelled(self) -> int:
        return self._c_cancelled.value

    @property
    def requests_rejected(self) -> int:
        return self._c_rejected.value

    @property
    def requests_shed(self) -> int:
        return self._c_shed.value

    @property
    def requests_errored(self) -> int:
        return self._c_errored.value

    @property
    def engine_restarts(self) -> int:
        return self._c_restarts.value

    @property
    def tokens_generated(self) -> int:
        return self._c_tokens.value

    @property
    def tokens_per_sec(self) -> float:
        if self._t_first_token is None or self._t_last_token is None:
            return 0.0
        span = self._t_last_token - self._t_first_token
        if span <= 0.0:
            return 0.0
        # the first token opens the span, the rest fill it
        return (self.tokens_generated - 1) / span

    def payload(self) -> dict:
        """This scheduler's series in the
        :meth:`~chainermn_tpu.monitor.registry.MetricsRegistry.
        _rank_payload` shape, keyed by PLAIN metric names (no ``instance``
        label) — so a fleet router can pool N replicas' metrics with
        :func:`~chainermn_tpu.monitor.registry.merge_rank_payloads`
        exactly the way ``aggregate(comm)`` pools ranks: counters sum,
        gauges mean, histogram reservoirs concatenate into fleet-wide
        p50/p99."""
        hists = {
            "serving_ttft_seconds": self._h_ttft,
            "serving_tpot_seconds": self._h_tpot,
            "serving_queue_depth": self._h_queue,
            "serving_slot_occupancy": self._h_occ,
        }
        return {
            "counters": {
                "serving_requests_submitted_total": self.requests_submitted,
                "serving_requests_completed_total": self.requests_completed,
                "serving_requests_cancelled_total": self.requests_cancelled,
                "serving_requests_rejected_total": self.requests_rejected,
                "serving_requests_shed_total": self.requests_shed,
                "serving_requests_errored_total": self.requests_errored,
                "serving_scheduler_restarts_total": self.engine_restarts,
                "serving_tokens_total": self.tokens_generated,
            },
            "gauges": {
                "serving_queue_depth_now": float(self._g_queue.value),
                "serving_active_slots": float(self._g_active.value),
            },
            "hist": {
                name: {"unit": h.unit, "count": h.count, "sum": h.sum,
                       "samples": h.samples}
                for name, h in hists.items()
            },
        }

    def report(self) -> dict:
        out = {
            "requests_submitted": self.requests_submitted,
            "requests_completed": self.requests_completed,
            "requests_cancelled": self.requests_cancelled,
            "requests_rejected": self.requests_rejected,
            "requests_shed": self.requests_shed,
            "requests_errored": self.requests_errored,
            "engine_restarts": self.engine_restarts,
            "tokens_generated": self.tokens_generated,
            "tokens_per_sec": round(self.tokens_per_sec, 2),
            "n_slots": self.n_slots,
        }
        out.update(latency_report(self._h_ttft.samples, "ttft"))
        out.update(latency_report(self._h_tpot.samples, "tpot"))
        cached = self._h_cached.samples
        if cached:
            t = np.asarray(cached, np.float64)
            out["cached_prefix_frac_mean"] = round(float(t.mean()), 4)
            out["prefix_hit_rate"] = round(float((t > 0).mean()), 4)
        batch = self._h_batch.samples
        if batch:
            t = np.asarray(batch, np.float64)
            out["prefill_batch_size_mean"] = round(float(t.mean()), 3)
            out["prefill_batch_size_max"] = int(t.max())
        if self._c_rows:
            filled = sum(f.value for f, _ in self._c_rows.values())
            run = sum(r.value for _, r in self._c_rows.values())
            out["prefill_fill_share"] = round(filled / run, 4)
        if self._c_moe_total.value:
            out["moe_local_share"] = round(
                self._c_moe_local.value / self._c_moe_total.value, 4)
        if self._g_state_bytes.value:
            out["state_slots_live"] = int(self._g_state_slots.value)
            out["state_bytes"] = int(self._g_state_bytes.value)
            out["linear_state_tokens"] = int(self._c_state_tokens.value)
            for kind, counter in self._c_prefill_chunks.items():
                out[f"linear_prefill_chunks_{kind}"] = int(counter.value)
            for path, counter in self._c_decode_rows.items():
                out[f"linear_decode_rows_{path}"] = int(counter.value)
        for hist, prefix in ((self._h_queue, "queue_depth"),
                             (self._h_occ, "slot_occupancy")):
            samples = hist.samples
            if not samples:
                continue
            t = np.asarray(samples, np.float64)
            out[f"{prefix}_mean"] = round(float(t.mean()), 3)
            out[f"{prefix}_p50"] = round(float(np.percentile(t, 50)), 3)
            out[f"{prefix}_p99"] = round(float(np.percentile(t, 99)), 3)
        req_blocks = self._h_req_blocks.samples
        if req_blocks:   # paged engines only — dense reports stay as-is
            t = np.asarray(req_blocks, np.float64)
            out["kv_blocks_per_request_mean"] = round(float(t.mean()), 3)
            out["kv_blocks_per_request_max"] = int(t.max())
            out["kv_preemptions"] = int(self._c_preempt.value)
            out["kv_blocks_in_use"] = int(self._g_kv_used.value)
            out["kv_blocks_free"] = int(self._g_kv_free.value)
            out["kv_blocks_live"] = int(self._g_kv_live.value)
        spec_prop = int(self._c_spec_proposed.value)
        if spec_prop:   # speculative engines only
            spec_acc = int(self._c_spec_accepted.value)
            out["spec_tokens_proposed"] = spec_prop
            out["spec_tokens_accepted"] = spec_acc
            out["spec_accept_rate"] = round(spec_acc / spec_prop, 4)
            accept = self._h_spec_accept.samples
            if accept:
                t = np.asarray(accept, np.float64)
                out["spec_accept_length_mean"] = round(float(t.mean()), 3)
        if self._worst_trace is not None:
            # the slowest traced request's full phase attribution — the
            # compact "where the p99 TTFT went" answer, per trace
            out["critical_path"] = self._worst_trace
        if self._health_fn is not None:
            try:
                out["health"] = self._health_fn()
            except Exception as e:  # noqa: BLE001 — reporting never raises
                out["health"] = {"error": f"{type(e).__name__}: {e}"}
        if self._costs is not None:
            try:
                out["costs"] = self._costs.report()
            except Exception as e:  # noqa: BLE001 — reporting never raises
                out["costs"] = {"error": f"{type(e).__name__}: {e}"}
        if sanitizer.enabled():
            # lock-hold / contention accounting (sanitizer runs only):
            # which lock the serving path actually spends its time in
            holds = sanitizer.hold_stats()
            if holds:
                out["lock_hold_seconds"] = {
                    name: {"count": s["count"],
                           "total_s": round(s["total_s"], 6),
                           "max_s": round(s["max_s"], 6)}
                    for name, s in holds.items()
                }
            contended = sanitizer.contention_counts()
            if contended:
                out["lock_contended"] = contended
        return out


__all__ = ["ServingMetrics"]
