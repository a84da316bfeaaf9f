"""Request-scoped tracing through the serving stack: span-tree coverage
and Chrome export (the acceptance scenario), each monitor (the tracer, the
cost ledger, a running collector) ON vs OFF on the same warm engine, dense
and paged (token-identical, dispatch-count-identical, zero recompiles:
counts, not wall clock), forced retention of shed requests, the SLO
deadline-miss storm, and watchdog fires carrying request/trace identity."""

import io
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.extensions import Watchdog
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.monitor import get_event_log, get_registry
from chainermn_tpu.monitor.events import EventLog
from chainermn_tpu.monitor.registry import MetricsRegistry
from chainermn_tpu.monitor.slo import LatencyObjective, SLOEngine
from chainermn_tpu.monitor.timeseries import Collector
from chainermn_tpu.monitor.trace import Tracer
from chainermn_tpu.resilience import FaultInjector
from chainermn_tpu.serving import (
    FCFSScheduler,
    ServingEngine,
    ServingMetrics,
)


@pytest.fixture(scope="module")
def lm_and_params():
    lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=1,
                       max_len=32, compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.asarray([[1, 2, 3]], jnp.int32))
    return lm, params


@pytest.fixture(scope="module")
def warm_engine(lm_and_params):
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=6,
                           cache_len=24)
    engine.warmup()
    return engine


@pytest.fixture(scope="module")
def warm_paged_engine(lm_and_params):
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=6,
                           cache_len=24, paged=True, kv_block_size=2)
    engine.warmup()
    return engine


def _workload(sched, n=4, max_new=4, after_step=lambda: None):
    """Deterministic burst: same prompts/rngs/budgets every call;
    ``after_step`` runs between the scheduler's steps."""
    rng = np.random.RandomState(0)
    reqs = []
    for i in range(n):
        prompt = rng.randint(1, 17, 1 + i % 4).astype(np.int32)
        reqs.append(sched.submit(prompt, max_new,
                                 rng=jax.random.PRNGKey(100 + i)))
    while sched.has_work:
        sched.step()
        after_step()
    return reqs


# --------------------------------------------------------------------- #
# the acceptance scenario: span tree + valid Chrome export               #
# --------------------------------------------------------------------- #

def test_request_span_tree_covers_lifecycle(warm_engine):
    tracer = Tracer(sample=1, ring=32)
    sched = FCFSScheduler(warm_engine, tracer=tracer)
    reqs = _workload(sched)
    traces = tracer.finished(kind="serving")
    assert len(traces) == len(reqs)
    for t in traces:
        names = [s.name for s in t.spans]
        # queue -> admit -> prefill -> decode -> retire, one tree
        assert names[0] == "request"
        assert {"queue", "admit", "prefill", "decode_step"} <= set(names)
        assert t.root.labels["reason"] == "length"
        assert t.error is None and not t.deadline_miss
        # one decode_step span per generated token after the first
        n_decode = sum(1 for s in t.spans if s.name == "decode_step")
        req = next(r for r in reqs if r.id == t.root.labels["req"])
        assert n_decode == len(req.tokens) - 1
        prefill = next(s for s in t.spans if s.name == "prefill")
        assert prefill.labels["bucket"] == 6
    # schema-checked Chrome export: loadable event list
    out = tracer.export_chrome()
    json.dumps(out)
    events = out["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert complete and all(
        set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}
        and e["dur"] >= 0 for e in complete)
    assert len({e["tid"] for e in events}) == len(traces)
    # critical-path breakdown reaches the metrics report
    cp = sched.metrics.report()["critical_path"]
    assert cp["total_s"] > 0 and "queue" in cp["phases_s"]
    assert json.dumps(cp)


def _dispatched():
    """Prefill programs by bucket and decode steps dispatched so far in
    this process, from the registry's counters."""
    return {k: v for k, v in get_registry().snapshot()["counters"].items()
            if k.startswith(("serving_prefills_total",
                             "serving_decode_steps_total"))}


def _serve(engine, monitor, on):
    """The scripted workload through a fresh scheduler with one monitor
    off or on; on, the monitor is seen at work, so ON is not a second
    OFF."""
    if monitor == "tracer":
        tracer = Tracer(sample=1 if on else 0, ring=32)
        reqs = _workload(FCFSScheduler(engine, tracer=tracer))
        assert len(tracer.finished(kind="serving")) == (len(reqs) if on
                                                        else 0)
    elif monitor == "cost_ledger":
        sched = FCFSScheduler(engine, cost_accounting=on)
        reqs = _workload(sched)
        if on:
            assert sched.costs.report()["device_time"]["dispatches"] > 0
        else:
            assert sched.costs is None
    elif on:                                   # a collector over the registry
        collector = Collector(cadence_s=0.25)
        reqs = _workload(
            FCFSScheduler(engine),
            after_step=lambda: collector.tick(
                now=collector.cadence_s * (collector.ticks + 1)))
        assert collector.ticks > 0
        assert any(name.startswith("serving_decode_steps_total")
                   for name in collector.store.names())
    else:
        reqs = _workload(FCFSScheduler(engine))
    return reqs


@pytest.mark.parametrize("substrate", ["dense", "paged"])
@pytest.mark.parametrize("monitor", ["tracer", "cost_ledger", "collector"])
def test_monitoring_on_vs_off_parity_and_dispatch_counts(
        monitor, substrate, request):
    """A monitor must not change a single token OR a single device call:
    the same warm engine serves the identical workload with the monitor
    off then on, and tokens, prefill/decode dispatch counters, executable
    counts, and the zero-recompile invariant all match (dispatch counts,
    not wall clock)."""
    engine = request.getfixturevalue(
        {"dense": "warm_engine", "paged": "warm_paged_engine"}[substrate])
    counts_before = engine.compile_counts_detailed()

    def run(on):
        before = _dispatched()
        reqs = _serve(engine, monitor, on)
        after = _dispatched()
        return ([tuple(r.tokens) for r in reqs],
                {k: after[k] - before.get(k, 0) for k in after})

    toks_off, dispatched_off = run(False)
    toks_on, dispatched_on = run(True)
    assert toks_on == toks_off                 # token-for-token parity
    assert sum(dispatched_off.values()) > 0
    assert dispatched_on == dispatched_off     # zero extra device calls
    assert engine.compile_counts_detailed() == counts_before
    assert engine.recompiles == {}             # invariant held live


def test_tracing_off_records_nothing(warm_engine):
    tracer = Tracer(sample=0)
    sched = FCFSScheduler(warm_engine, tracer=tracer)
    reqs = _workload(sched, n=2)
    assert tracer.finished() == []
    assert all(not r.trace.enabled for r in reqs)
    assert "critical_path" not in sched.metrics.report()


# --------------------------------------------------------------------- #
# forced retention + the SLO storm                                       #
# --------------------------------------------------------------------- #

def test_shed_request_trace_retained_despite_sampling(warm_engine):
    tracer = Tracer(sample=1000, ring=32)   # sampling would drop all
    sched = FCFSScheduler(warm_engine, tracer=tracer)
    req = sched.submit(np.array([1, 2], np.int32), 2, deadline_s=0.001)
    time.sleep(0.01)
    sched.step()
    with pytest.raises(TimeoutError):
        req.wait(timeout=1)
    kept = [t for t in tracer.finished(kind="serving") if t.deadline_miss]
    assert len(kept) == 1
    assert kept[0].root.labels["reason"] == "shed"
    assert kept[0].trace_id == req.trace.trace_id
    # the shed event names the trace — flight recorder joins traces
    shed = [e for e in get_event_log().tail(64) if e["kind"] == "shed"
            and e.get("req") == req.id]
    assert shed and shed[0]["trace"] == req.trace.trace_id


def test_slo_burn_gauge_flips_on_deadline_miss_storm(lm_and_params):
    """The acceptance criterion: a FaultInjector delay at
    ``serving.prefill`` makes every admission blow a tight deadline —
    queued requests shed, admitted ones land TTFTs past the objective —
    and the SLO engine's burn-rate gauge flips with a breach event naming
    the offending trace ids."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=1, prefill_len=6,
                           cache_len=24)
    engine.warmup()
    # private registry/events/tracer: earlier tests' TTFT samples in the
    # process registry must not pre-burn this objective's windows
    reg, events = MetricsRegistry(), EventLog()
    tracer = Tracer(sample=1, ring=64)
    metrics = ServingMetrics(1, registry=reg, events=events)
    sched = FCFSScheduler(engine, tracer=tracer, metrics=metrics,
                          default_deadline_s=0.02)
    slo = SLOEngine(registry=reg, events=events, tracer=tracer)
    slo.add(LatencyObjective("ttft_p99", "serving_ttft_seconds",
                             threshold_s=0.02, windows=(30.0, 60.0)))
    assert slo.evaluate()["ttft_p99"]["compliant"]   # pre-storm: healthy
    inj = FaultInjector(seed=0)
    inj.arm("serving.prefill", kind="delay", delay_s=0.06, times=None)
    with inj:
        # max_new=2 keeps the slot busy through a decode step, so the
        # queued requests genuinely wait — and expire — behind the
        # delayed admissions
        reqs = [sched.submit(np.array([1 + i], np.int32), 2)
                for i in range(4)]
        sched.run_until_idle()
    errs = sum(1 for r in reqs if r.state.value == "errored")
    assert errs >= 1                       # the storm shed someone
    rep = slo.evaluate()
    ent = rep["ttft_p99"]
    assert not ent["compliant"]
    assert ent["max_burn_rate"] > 1.0
    # the gauge flipped in the registry (scrapeable through /metrics)
    snap = reg.snapshot()
    assert snap["gauges"]['slo_burn_rate{slo="ttft_p99",window="30s"}'] \
        > 1.0
    assert snap["gauges"]['slo_compliant{slo="ttft_p99"}'] == 0.0
    # the breach names offending traces, and shed requests are among them
    breach = [e for e in events.tail(128) if e["kind"] == "slo_breach"
              and e["slo"] == "ttft_p99"][-1]
    shed_ids = {r.trace.trace_id for r in reqs
                if r.state.value == "errored"}
    assert shed_ids & set(breach["traces"])


# --------------------------------------------------------------------- #
# watchdog identity                                                      #
# --------------------------------------------------------------------- #

def test_watchdog_fire_names_requests_and_traces(lm_and_params):
    """A hang mid-decode fires the watchdog; the fire banner and the
    ``watchdog_fire`` event must carry the in-flight request/trace ids so
    the flight-recorder dump joins against exported traces."""
    lm, params = lm_and_params
    sink = io.StringIO()
    dog = Watchdog(timeout=0.05, on_timeout="warn", _sink=sink)
    engine = ServingEngine(lm, params, n_slots=1, prefill_len=6,
                           cache_len=24, watchdog=dog)
    engine.warmup()
    tracer = Tracer(sample=1, ring=8)
    sched = FCFSScheduler(engine, tracer=tracer)
    req = sched.submit(np.array([1, 2], np.int32), 3)
    sched.step()                            # admit (prefill watched too)
    inj = FaultInjector(seed=0)
    inj.arm("serving.decode", kind="delay", delay_s=0.25, times=1)
    with inj:
        sched.step()                        # decode hangs; dog fires
    assert dog.fired
    banner = sink.getvalue()
    assert f"reqs=[{req.id}]" in banner
    assert req.trace.trace_id in banner
    fires = [e for e in get_event_log().tail(128)
             if e["kind"] == "watchdog_fire"]
    assert fires and fires[-1]["reqs"] == [req.id]
    assert fires[-1]["traces"] == [req.trace.trace_id]
    sched.run_until_idle()


def test_watchdog_step_context_is_optional():
    sink = io.StringIO()
    dog = Watchdog(timeout=10.0, on_timeout="warn", _sink=sink)
    with dog.step("plain"):
        pass
    assert not dog.fired
