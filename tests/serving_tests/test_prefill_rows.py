"""A prefill program holds a budget of tokens, not a count of rows (ISSUE 30).

``prefill_batch`` is the rows at the smallest bucket; bucket ``b``'s program
has ``max(1, min(prefill_batch, n_slots) * prefill_buckets[0] // b)`` rows,
so no program runs more than ``prefill_batch x prefill_buckets[0]`` padded
tokens. Pinned here: the rule itself; still one executable a bucket and no
recompile through a stream of every bucket; the scheduler never groups past
the head bucket's rows (and plans nobody else where that is one); a prompt
admitted alone at a long bucket decodes token for token as before, dense,
paged and through the int8 store, with and without window layers; a chunk
runs at its bucket's rows; the cost ledger books ``padding`` from the rows
that ran; and ``prefill_fill_share`` is filled over run. Small sizes, CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import SmallThinkerLM, TransformerLM, generate
from chainermn_tpu.monitor import MetricsRegistry
from chainermn_tpu.monitor.costs import UNATTRIBUTED
from chainermn_tpu.serving import FCFSScheduler, ServingEngine
from chainermn_tpu.serving.metrics import ServingMetrics

VOCAB = 31


@pytest.fixture(scope="module")
def tlm():
    lm = TransformerLM(vocab_size=VOCAB, d_model=16, n_heads=4, n_layers=2,
                       max_len=64, compute_dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0),
                       jnp.asarray([[1, 2, 3]], jnp.int32))


@pytest.fixture(scope="module")
def long_lm():
    """Positions enough for the benchmark cells' bucket ladders; only the
    rule is read from its engines, nothing runs."""
    lm = TransformerLM(vocab_size=VOCAB, d_model=8, n_heads=2, n_layers=1,
                       max_len=6656, compute_dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0),
                       jnp.asarray([[1, 2, 3]], jnp.int32))


@pytest.fixture(scope="module")
def st():
    """The tiny SmallThinker: one full layer in four, a window of 16."""
    lm = SmallThinkerLM(
        vocab_size=VOCAB, d_model=32, n_heads=14, n_kv_heads=2, head_dim=8,
        n_layers=4, d_ff=16, n_experts=8, top_k=3, window=16,
        window_layers=(0, 1, 1, 1), rope_layers=(0, 1, 1, 1),
        rope_theta=10000.0, rms_norm_eps=1e-6, max_len=64,
        compute_dtype=jnp.float32)
    return lm, {"params": lm.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]}


# -- the rule --------------------------------------------------------------- #

@pytest.mark.parametrize("buckets,batch,n_slots,rows", [
    ((128, 256, 512), 4, 8, (4, 2, 1)),                  # cgpt13b-serve-decode
    ((256, 1024, 2048, 4096, 6144), 4, 8, (4, 1, 1, 1, 1)),   # st21b-l8
    ((64,), 4, 8, (4,)),                                # one bucket: as before
    ((4, 8, 16), 1, 8, (1, 1, 1)),
    ((48, 64, 100), 3, 8, (3, 2, 1)),                    # a non-dividing pair
    ((4, 8), 8, 2, (2, 1)),                             # fewer slots than rows
], ids=["cell1", "cell3", "one-bucket", "batch-1", "non-dividing",
        "few-slots"])
def test_rows_of_a_bucket_follow_the_token_budget(long_lm, buckets, batch,
                                                  n_slots, rows):
    lm, params = long_lm
    engine = ServingEngine(lm, params, n_slots=n_slots,
                           prefill_buckets=buckets, prefill_batch=batch,
                           paged=True, kv_block_size=16, kv_blocks=32,
                           cache_len=max(buckets) + 16)
    assert tuple(engine.prefill_rows(b) for b in buckets) == rows
    # no program holds more tokens than the smallest bucket's, or one row
    budget = engine.prefill_batch * buckets[0]
    assert all(engine.prefill_rows(b) * b <= max(budget, b) for b in buckets)


# -- one program a bucket, nothing recompiles ------------------------------- #

def mixed_engine(lm, params, **kw):
    args = dict(n_slots=4, prefill_buckets=(4, 8, 16), prefill_batch=4,
                cache_len=40)
    args.update(kw)
    return ServingEngine(lm, params, **args)


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("mode", [
    dict(), dict(paged=True, kv_block_size=4),
    dict(prefix_cache_blocks=16, prefix_block_size=2)],
    ids=["dense", "paged", "dense-prefix"])
def test_one_executable_a_bucket_and_no_recompile(tlm, mode):
    lm, params = tlm
    engine = mixed_engine(lm, params, **mode)
    assert [engine.prefill_rows(b) for b in (4, 8, 16)] == [4, 2, 1]
    engine.warmup()
    assert engine.compile_counts() == {"prefill": 3, "decode": 1}
    sched = FCFSScheduler(engine)
    # every bucket, alone and in company, in arrival order
    reqs = [sched.submit(p, 3) for p in prompts_of(
        [3, 4, 2, 4, 7, 6, 16, 3, 12, 8, 5, 1, 9, 2])]
    sched.run_until_idle()
    assert all(r.finished and len(r.tokens) == 3 for r in reqs)
    for r in reqs:
        want = generate(lm, params, jnp.asarray(r.prompt)[None], 3)[0]
        assert r.tokens == [int(t) for t in want[len(r.prompt):]]
    assert engine.compile_counts() == {"prefill": 3, "decode": 1}
    assert set(engine.compile_counts_detailed().values()) == {1}
    assert engine.recompiles == {}


def test_group_past_the_buckets_rows_is_refused(tlm):
    lm, params = tlm
    engine = mixed_engine(lm, params)
    plans = [engine.plan_admission(p) for p in prompts_of([7, 6, 5])]
    with pytest.raises(ValueError, match="exceeds the 2 rows of bucket 8"):
        engine.admit_batch(plans)
    assert len(engine.admit_batch(plans[:2])) == 2


# -- the scheduler's groups ------------------------------------------------- #

def test_next_group_stays_inside_the_head_buckets_rows(tlm, monkeypatch):
    lm, params = tlm
    engine = mixed_engine(lm, params, n_slots=8, paged=True, kv_block_size=4)
    sched = FCFSScheduler(engine)
    planned, groups = [], []
    plan_admission, next_group = engine.plan_admission, sched._next_group

    def counting_plan(*a, **kw):
        planned[-1] += 1
        return plan_admission(*a, **kw)

    def recording_group():
        planned.append(0)
        group = next_group()
        if group:
            groups.append(([r.id for r, _ in group], group[0][1].bucket,
                           planned[-1], sched.queue_depth))
        return group

    monkeypatch.setattr(engine, "plan_admission", counting_plan)
    monkeypatch.setattr(sched, "_next_group", recording_group)
    lengths = [3, 2, 4, 1, 3, 7, 6, 5, 13, 16, 8, 2, 9, 3]
    reqs = [sched.submit(p, 2) for p in prompts_of(lengths)]
    sched.run_until_idle()
    assert all(r.finished for r in reqs)
    assert sorted(i for ids, _, _, _ in groups for i in ids) == sorted(
        r.id for r in reqs)                      # nobody deferred or dropped
    for ids, bucket, n_planned, queued in groups:
        assert len(ids) <= engine.prefill_rows(bucket)
        if engine.prefill_rows(bucket) == 1:
            # the head alone is planned: no walk over the queue
            assert n_planned == 1 and len(ids) == 1
    heads = [ids[0] for ids, _, _, _ in groups]
    assert heads == sorted(heads)                # the oldest waiting leads
    sizes = {(bucket, len(ids)) for ids, bucket, _, _ in groups}
    assert (4, 4) in sizes and (8, 2) in sizes   # full groups still form
    assert any(queued and b == 16 for _, b, _, queued in groups)
    m = sched.metrics.report()
    run = sum(engine.prefill_rows(b) for _, b, _, _ in groups)
    assert m["prefill_fill_share"] == round(len(reqs) / run, 4)


# -- the same tokens -------------------------------------------------------- #

def cacheless_greedy(lm, params, prompt, n):
    """Greedy decoding by a whole forward a token, no cache: one buffer of
    the final length (a position's logits do not see what follows it)."""
    seq = np.zeros((1, len(prompt) + n), np.int32)
    seq[0, :len(prompt)] = prompt
    forward = jax.jit(lm.apply)
    for i in range(len(prompt), len(prompt) + n):
        logits = forward(params, jnp.asarray(seq))
        seq[0, i] = int(jnp.argmax(logits[0, i - 1]))
    return [int(t) for t in seq[0, len(prompt):]]


@pytest.mark.parametrize("family,mode", [
    ("tlm", dict()),
    ("tlm", dict(paged=True, kv_block_size=4)),
    ("tlm", dict(paged=True, kv_block_size=4, kv_quant="int8")),
    ("st", dict(paged=True, kv_block_size=4)),
    ("st", dict(paged=True, kv_block_size=4, kv_quant="int8")),
], ids=["tlm-dense", "tlm-paged", "tlm-int8", "st-paged", "st-int8"])
def test_long_prompt_alone_decodes_as_before(tlm, st, family, mode):
    """One row at the long bucket against the reference decode; through the
    int8 store, whose rounding is not the reference's, against the program
    of the old shape (``prefill_batch`` rows at that bucket, one of them
    filled), which a one-bucket engine still builds."""
    lm, params = {"tlm": tlm, "st": st}[family]
    prompt = prompts_of([27], seed=11)[0]        # past the window of 16
    n_new = 8
    engine = ServingEngine(lm, params, n_slots=2, prefill_buckets=(8, 32),
                           prefill_batch=2, cache_len=48, **mode)
    assert engine.prefill_rows(32) == 1
    sched = FCFSScheduler(engine)
    req = sched.submit(prompt, n_new)
    sched.run_until_idle()
    assert len(req.tokens) == n_new
    if mode.get("kv_quant") == "int8":
        old = ServingEngine(lm, params, n_slots=2, prefill_buckets=(32,),
                            prefill_batch=2, cache_len=48, **mode)
        assert old.prefill_rows(32) == 2
        sched_old = FCFSScheduler(old)
        want = sched_old.submit(prompt, n_new)
        sched_old.run_until_idle()
        want = want.tokens
    elif family == "tlm":
        want = [int(t) for t in generate(
            lm, params, jnp.asarray(prompt)[None], n_new)[0][len(prompt):]]
    else:
        want = cacheless_greedy(lm, params, prompt, n_new)
    assert req.tokens == want
    m = sched.metrics.report()
    assert m["prefill_fill_share"] == 1.0 and m["prefill_batch_size_max"] == 1
    assert engine.recompiles == {}


# -- chunks, the ledger, the counters --------------------------------------- #

def test_chunk_runs_at_its_buckets_rows(tlm, monkeypatch):
    lm, params = tlm
    engine = ServingEngine(lm, params, n_slots=2, prefill_buckets=(4, 8, 16),
                           prefill_batch=2, paged=True, kv_block_size=2,
                           kv_blocks=64, cache_len=48)
    engine.warmup()
    shapes = []
    for b, fn in list(engine._prefill_fns.items()):
        def spy(*args, _fn=fn):
            shapes.append(args[3].shape)         # the tokens operand
            return _fn(*args)
        monkeypatch.setitem(engine._prefill_fns, b, spy)
    prompt = prompts_of([14], seed=5)[0]
    sched = FCFSScheduler(engine, chunk_tokens_per_step=8)
    booked = []
    record_prefill = sched.costs.record_prefill
    monkeypatch.setattr(
        sched.costs, "record_prefill",
        lambda dt, **kw: booked.append(kw) or record_prefill(dt, **kw))
    req = sched.submit(prompt, 4)
    sched.run_until_idle()
    want = generate(lm, params, jnp.asarray(prompt)[None], 4)[0]
    assert req.tokens == [int(t) for t in want[len(prompt):]]
    # 8 tokens in the one-row program of 8, then 6 in that program again
    assert shapes == [(1, 8), (1, 8)]
    assert [kw["batch_rows"] for kw in booked] == [1, 1]
    assert sched.metrics.report()["prefill_fill_share"] == 1.0
    assert engine.recompiles == {}
    # a chunk of the smallest bucket pays for that bucket's rows
    sched = FCFSScheduler(engine, chunk_tokens_per_step=4)
    del shapes[:]
    req = sched.submit(prompts_of([7], seed=6)[0], 2)    # no cached prefix
    sched.run_until_idle()
    assert req.finished and shapes == [(2, 4), (2, 4)]
    assert sched.metrics.report()["prefill_fill_share"] == 0.5


def test_ledger_books_padding_from_the_rows_run(tlm, monkeypatch):
    lm, params = tlm
    engine = ServingEngine(lm, params, n_slots=2, prefill_buckets=(4, 8),
                           prefill_batch=2, paged=True, kv_block_size=2,
                           kv_blocks=64, cache_len=32)
    sched = FCFSScheduler(engine)
    booked = []
    record_prefill = sched.costs.record_prefill

    def recording(dt, **kw):
        out = record_prefill(dt, **kw)
        booked.append((dt, kw, out))
        return out

    monkeypatch.setattr(sched.costs, "record_prefill", recording)
    sched.submit(prompts_of([6])[0], 2, tenant="a")      # bucket 8: one row
    sched.run_until_idle()
    sched.submit(prompts_of([3], seed=4)[0], 2, tenant="a")   # 4: two rows
    sched.run_until_idle()
    (dt8, kw8, out8), (dt4, kw4, out4) = booked
    assert (kw8["bucket"], kw8["batch_rows"]) == (8, 1)
    assert (UNATTRIBUTED, "padding") not in out8         # no row ran empty
    assert out8[("a", "useful")] == pytest.approx(dt8 * 6 / 8)
    assert out8[("a", "padding")] == pytest.approx(dt8 * 2 / 8)
    assert (kw4["bucket"], kw4["batch_rows"]) == (4, 2)
    assert out4[(UNATTRIBUTED, "padding")] == pytest.approx(dt4 / 2)
    assert out4[("a", "useful")] == pytest.approx(dt4 / 2 * 3 / 4)
    assert sched.costs.conservation_error < 1e-6


def test_fill_share_is_filled_over_run():
    reg = MetricsRegistry()
    m = ServingMetrics(4, registry=reg)
    assert "prefill_fill_share" not in m.report()
    for filled, run, bucket in [(2, 4, 4), (1, 1, 16), (1, 2, 8), (4, 4, 4)]:
        m.record_admission(filled, run, bucket)
    m.record_prefill_rows(1, 2, 8)                       # a chunk
    out = m.report()
    assert out["prefill_fill_share"] == round(9 / 13, 4)
    assert out["prefill_batch_size_mean"] == 2.0         # admissions only
    by_bucket = {}
    for key, value in reg.snapshot()["counters"].items():
        if key.startswith("prefill_rows_"):
            bucket = int(key.split('prefill_bucket="')[1].split('"')[0])
            by_bucket.setdefault(bucket, {})[key.split("{")[0]] = value
    assert by_bucket == {
        4: {"prefill_rows_filled_total": 6, "prefill_rows_run_total": 8},
        8: {"prefill_rows_filled_total": 2, "prefill_rows_run_total": 4},
        16: {"prefill_rows_filled_total": 1, "prefill_rows_run_total": 1}}
