"""The profiler's view of ``FCFSScheduler.step()``: sibling phase spans in
the order ``STEP_PHASES`` gives, children only inside their stated parent,
``serving_decode`` at its old extent, the host side of a decode and of a
prefill program split into operands, dispatch and fetch, the counts three
spans carry, an idle client under ``serving_idle`` — read through a
recording stand-in put in place of ``jax.profiler.TraceAnnotation`` (no
timing is asserted). And the ``blocks_live`` gauge the same bookkeeping
feeds."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import TransformerLM
from chainermn_tpu.monitor.registry import MetricsRegistry
from chainermn_tpu.serving import (
    FCFSScheduler,
    ServingClient,
    ServingEngine,
    ServingMetrics,
    SpeculativeConfig,
)
from chainermn_tpu.serving import engine as engine_mod
from chainermn_tpu.serving.scheduler import STEP_PHASE_CHILDREN, STEP_PHASES

DECODE, SPEC = "chainermn.serving_decode", "chainermn.serving_spec_verify"
PREFILL = "chainermn.serving_prefill"
IDLE = "chainermn.serving_idle"
ENGINES = {
    "dense": dict(n_slots=2, prefill_len=6, cache_len=24),
    "paged": dict(n_slots=2, prefill_buckets=(4, 8), prefill_batch=2,
                  paged=True, kv_block_size=2, cache_len=24),
    "window": dict(n_slots=2, prefill_buckets=(4, 8), prefill_batch=2,
                   paged=True, kv_block_size=2, cache_len=24,
                   decode_window=2),
    "spec": dict(n_slots=2, prefill_buckets=(4, 8), prefill_batch=2,
                 paged=True, kv_block_size=2, cache_len=32,
                 speculative=SpeculativeConfig(k=2)),
}


@pytest.fixture(scope="module")
def lm_and_params():
    lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=1,
                       max_len=32, compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.asarray([[1, 2, 3]], jnp.int32))
    return lm, params


@pytest.fixture(scope="module")
def engines(lm_and_params):
    built = {}

    def get(kind):
        if kind not in built:
            built[kind] = ServingEngine(*lm_and_params, **ENGINES[kind])
            built[kind].warmup()
        return built[kind]
    return get


class Recorder:
    """Stands where ``jax.profiler.TraceAnnotation`` stood: every span's
    opening and closing in one list, with the thread and the keyword
    statistics; ``note`` puts a call of the program between them."""

    def __init__(self):
        self.log = []            # (what, name, thread, stats)

    def note(self, what, name, stats=None):
        self.log.append((what, name, threading.get_ident(), stats))

    def annotation(self):
        rec = self

        class Span:
            def __init__(self, name, **stats):
                self.name, self.stats = name, stats

            def __enter__(self):
                rec.note("open", self.name, self.stats)
                return self

            def __exit__(self, *exc):
                rec.note("close", self.name)

        return Span

    def tree(self, thread=None):
        """``[(name, stats, [children...])]`` of the top-level spans of one
        thread, failing on a span closed out of turn."""
        top, stack = [], []
        for what, name, ident, stats in self.log:
            if what not in ("open", "close") or (
                    thread is not None and ident != thread):
                continue
            if what == "open":
                node = (name, stats, [])
                (stack[-1][2] if stack else top).append(node)
                stack.append(node)
            else:
                assert stack and stack[-1][0] == name, (name, self.log)
                stack.pop()
        assert not stack, stack
        return top


def series(reg):
    return {key for kind in reg.snapshot().values() for key in kind}


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.annotation())
    return rec


def one_recorded_step(engine, recorder, **sched_kw):
    """A step with a live slot and a queued request: the first request is
    admitted and decoding before the recording starts."""
    sched = FCFSScheduler(engine, **sched_kw)
    sched.submit(np.array([1, 2, 3], np.int32), 8,
                 rng=jax.random.PRNGKey(1))
    sched.step()
    assert engine.active_slots == 1
    sched.submit(np.array([4, 5, 6, 7, 8], np.int32), 8,
                 rng=jax.random.PRNGKey(2))
    del recorder.log[:]
    emitted = sched.step()
    log = list(recorder.log)
    assert emitted >= 3          # a first token and two slots decoded
    sched.run_until_idle()
    return log


@pytest.mark.parametrize("kind", list(ENGINES))
def test_step_is_tiled_by_its_phases_in_order(engines, recorder, kind):
    engine = engines(kind)
    recorder.log = one_recorded_step(engine, recorder)
    top = recorder.tree()
    want = tuple(SPEC if kind == "spec" and n == DECODE else n
                 for n in STEP_PHASES)
    # siblings only, in the program's own order: no span holds a whole step
    assert tuple(n for n, _, _ in top) == want

    def check(name, children):
        allowed = STEP_PHASE_CHILDREN.get(DECODE if name == SPEC else name,
                                          ())
        for child, _, grand in children:
            assert child in allowed, (name, child)
            check(child, grand)

    for name, _, children in top:
        check(name, children)
    by_name = {n: c for n, _, c in top}
    admit = by_name["chainermn.serving_admit"]
    assert [c[0] for c in admit] == [PREFILL]
    assert [c[0] for c in admit[0][2]] == list(STEP_PHASE_CHILDREN[PREFILL])
    assert [c[0] for c in by_name[want[3]]] == list(
        STEP_PHASE_CHILDREN[DECODE])


def noted(recorder, what, fn):
    """``fn``, noting each call in the recording under ``what``."""
    def call(*args, **kw):
        recorder.note("call", what)
        return fn(*args, **kw)
    return call


def note_calls(engine, recorder, monkeypatch):
    """Note the operand builder, every program and every fetch."""
    monkeypatch.setattr(engine, "_decode_args", noted(
        recorder, "_decode_args", engine._decode_args))
    for attr in ("_decode_fn", "_window_fn", "_spec_fn"):
        if getattr(engine, attr, None) is not None:
            monkeypatch.setattr(engine, attr, noted(
                recorder, "program", getattr(engine, attr)))
    monkeypatch.setattr(engine, "_prefill_fns", {
        b: noted(recorder, "program", f)
        for b, f in engine._prefill_fns.items()})
    monkeypatch.setattr(engine_mod, "device_fetch", noted(
        recorder, "device_fetch", engine_mod.device_fetch))


def inside(seq, name):
    """What opens, closes and is called inside the first span of ``name``,
    and what follows it."""
    lo, hi = seq.index(("open", name)), seq.index(("close", name))
    return seq[lo + 1:hi], seq[hi + 1]


@pytest.mark.parametrize("kind", list(ENGINES))
def test_decode_span_keeps_its_extent(engines, recorder, monkeypatch, kind):
    """``serving_decode`` still opens before the operands are built and
    closes after the fetch; its three children hold the operands, the
    program's call and the fetch, in that order. A speculative round
    builds its operands inline."""
    engine = engines(kind)
    note_calls(engine, recorder, monkeypatch)
    log = one_recorded_step(engine, recorder)
    held, after = inside([(w, n) for w, n, _, _ in log],
                         SPEC if kind == "spec" else DECODE)
    args = [] if kind == "spec" else [("call", "_decode_args")]
    assert held == [
        ("open", "chainermn.serving_decode_args"), *args,
        ("close", "chainermn.serving_decode_args"),
        ("open", "chainermn.serving_decode_dispatch"), ("call", "program"),
        ("close", "chainermn.serving_decode_dispatch"),
        ("open", "chainermn.serving_decode_fetch"), ("call", "device_fetch"),
        ("close", "chainermn.serving_decode_fetch")]
    assert after == ("open", "chainermn.serving_decode_post")


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_prefill_span_is_split_where_its_time_goes(engines, recorder,
                                                   monkeypatch, kind):
    """An admission's ``serving_prefill`` holds its operands (the block
    allocation of a paged engine among them), the program's call and the
    fetch of the first tokens, each in its own child, in that order."""
    engine = engines(kind)
    note_calls(engine, recorder, monkeypatch)
    if kind == "paged":
        monkeypatch.setattr(engine, "_paged_alloc_slot", noted(
            recorder, "_paged_alloc_slot", engine._paged_alloc_slot))
    log = one_recorded_step(engine, recorder)
    held, after = inside([(w, n) for w, n, _, _ in log], PREFILL)
    alloc = [("call", "_paged_alloc_slot")] if kind == "paged" else []
    assert held == [
        ("open", "chainermn.serving_prefill_args"), *alloc,
        ("close", "chainermn.serving_prefill_args"),
        ("open", "chainermn.serving_prefill_dispatch"), ("call", "program"),
        ("close", "chainermn.serving_prefill_dispatch"),
        ("open", "chainermn.serving_prefill_fetch"), ("call", "device_fetch"),
        ("close", "chainermn.serving_prefill_fetch")]
    assert after == ("close", "chainermn.serving_admit")


def test_three_spans_carry_counts(engines, recorder):
    engine = engines("paged")
    recorder.log = one_recorded_step(engine, recorder)
    stats = {}
    for name, st, children in recorder.tree():
        for n, s in [(name, st)] + [(c[0], c[1]) for c in children]:
            if s:
                stats[n] = s
    assert set(stats) == {"chainermn.serving_admit",
                          "chainermn.serving_prefill", DECODE}
    assert stats["chainermn.serving_admit"] == {"queue": 1}
    # (the shared engine may hold the prompt's prefix from a test before:
    # the suffix then fits the smaller bucket)
    bucket = stats["chainermn.serving_prefill"].pop("bucket")
    assert bucket in (4, 8)
    assert stats["chainermn.serving_prefill"] == {"rows": 1, "of": 2}
    # the first request holds its 3 prompt tokens and one decoded, the
    # second its 5 prompt tokens
    assert stats[DECODE] == {"active": 2, "live_tokens": 9}


def test_idle_client_sleeps_under_its_own_span(engines, recorder):
    with ServingClient(engines("dense"), idle_wait_s=0.01) as client:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and sum(
                1 for w, n, _, _ in recorder.log
                if (w, n) == ("close", IDLE)) < 2:
            time.sleep(0.01)
        ident = client._thread.ident
    names = {n for n, _, _ in recorder.tree(thread=ident)}
    assert names == {IDLE}


def test_nothing_is_recorded_without_a_profiler(engines):
    """The phases are ``TraceAnnotation``s and nothing else: once one
    request has run its course (and made the series that are made on first
    use), another leaves no new series and no new state behind."""
    engine = engines("paged")
    reg = MetricsRegistry()
    sched = FCFSScheduler(
        engine, metrics=ServingMetrics(engine.n_slots, registry=reg))

    def serve_one():
        sched.submit(np.array([1, 2, 3], np.int32), 3,
                     rng=jax.random.PRNGKey(1))
        sched.run_until_idle()
        return series(reg), set(vars(sched)), set(vars(engine))

    assert serve_one() == serve_one()


def test_blocks_live_falls_when_a_cached_prompt_retires(lm_and_params):
    """``blocks_in_use`` counts what is off the free list, so a retired
    request whose prompt the prefix trie keeps leaves it where it was;
    ``blocks_live`` counts what live slots reference, and falls."""
    engine = ServingEngine(*lm_and_params, n_slots=2, prefill_buckets=(8,),
                           paged=True, kv_block_size=2, cache_len=24)
    reg = MetricsRegistry()
    sched = FCFSScheduler(engine, metrics=ServingMetrics(engine.n_slots, registry=reg))

    def walked():
        return len({b for ids in engine._slot_blocks for b in ids})

    prompt = np.array([1, 2, 3, 4, 5, 6], np.int32)
    a = sched.submit(prompt, 6, rng=jax.random.PRNGKey(1))
    b = sched.submit(prompt, 2, rng=jax.random.PRNGKey(2))
    sched.step()
    sched.step()
    assert b.finished and not a.finished
    shared = engine.kv_stats()
    # both held the prompt's three blocks; one reference each is not two
    assert shared["blocks_live"] == walked() >= 3
    sched.run_until_idle()
    assert a.finished
    after = engine.kv_stats()
    assert after["blocks_live"] == walked() == 0
    assert after["blocks_in_use"] >= 3        # the cached prompt stays
    assert engine.kv_pool_stats() == (
        after["blocks_in_use"], after["blocks_free"], 0)
    assert sched.metrics.report()["kv_blocks_live"] == 0
    assert any(k.startswith("kv_blocks_live") for k in series(reg))
