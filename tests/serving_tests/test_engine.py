"""Serving engine correctness: the continuous-batching invariant.

The load-bearing property of the whole subsystem: requests admitted at
STAGGERED times into a shared slot pool — mixed (ragged) prompt lengths,
slots freed and reused mid-run — produce token-for-token the same output
as a solo :func:`chainermn_tpu.models.generate` call with the same params
and rng. Plus the zero-recompile guarantee (two executables, ever) and
the slot-reuse-without-zeroing safety argument."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.models import TransformerLM, generate
from chainermn_tpu.serving import FCFSScheduler, ServingEngine


@pytest.fixture(scope="module")
def lm_and_params():
    lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=2,
                       max_len=48, compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.asarray([[1, 2, 3]], jnp.int32))
    return lm, params


def solo(lm, params, prompt, n, **kw):
    """The isolated single-request reference decode."""
    out = generate(lm, params, jnp.asarray(prompt, jnp.int32)[None], n, **kw)
    return np.asarray(out[0])


@pytest.mark.slow  # ~7s; staggered ragged admission parity stays tier-1 via test_paged_kv's staggered test — keep tier-1 inside its timeout
def test_ragged_staggered_admission_matches_solo_generate(lm_and_params):
    """THE continuous-batching parity test (acceptance criterion): mixed
    prompt lengths admitted at different times — more requests than
    slots, so retirements free slots for later admissions mid-decode —
    each bit-identical to its solo generate() run."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=3, prefill_len=8,
                           cache_len=32)
    sched = FCFSScheduler(engine)
    prompts = [
        np.array([1, 2, 3]),
        np.array([4, 5, 6, 7, 8]),
        np.array([9, 10]),
        np.array([11, 12, 13, 14]),
        np.array([2, 4, 6, 8, 10, 12, 14, 16]),  # exactly prefill_len
        np.array([5]),
    ]
    n_new = [6, 4, 7, 5, 3, 8]
    # first wave fills the pool; remaining requests queue and are
    # admitted whenever a retirement frees a slot — staggered by design
    reqs = [sched.submit(p, n) for p, n in zip(prompts, n_new)]
    sched.run_until_idle()
    assert all(r.finished for r in reqs)
    for p, n, r in zip(prompts, n_new, reqs):
        np.testing.assert_array_equal(r.output, solo(lm, params, p, n))


def test_mid_flight_admission_and_slot_reuse(lm_and_params):
    """Requests submitted WHILE others are mid-decode (true staggering,
    not just a deep queue) land in reused slots and still match solo
    decode — pins that a slot's previous tenant leaves nothing behind
    (the engine never zeroes caches; the causal mask is the fence)."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=6,
                           cache_len=24)
    sched = FCFSScheduler(engine)
    r1 = sched.submit(np.array([1, 2, 3]), 8)
    r2 = sched.submit(np.array([4, 5]), 2)      # retires early -> slot frees
    for _ in range(3):
        sched.step()
    assert r2.finished and not r1.finished
    # admitted mid-flight into r2's freed slot, while r1 keeps decoding
    r3 = sched.submit(np.array([6, 7, 8, 9]), 6)
    sched.run_until_idle()
    np.testing.assert_array_equal(r1.output, solo(lm, params, [1, 2, 3], 8))
    np.testing.assert_array_equal(r2.output, solo(lm, params, [4, 5], 2))
    np.testing.assert_array_equal(r3.output,
                                  solo(lm, params, [6, 7, 8, 9], 6))
    assert r3.slot == r2.slot  # genuinely reused, not a fresh slot


def test_zero_recompiles_after_warmup(lm_and_params):
    """Acceptance criterion: the engine owns exactly TWO executables —
    one prefill, one decode — and a second wave of requests with
    different ragged lengths/budgets adds none (jit cache-size count).
    Parameters committed to devices are the harder case: the engine's own
    state must follow them there, or each program's first outputs change
    its jit cache key. Here they come as a trainer's ``bcast_data`` leaves
    them, replicated over the communicator's mesh; ``tests/
    test_chip_smoke.py`` serves from a plain ``device_put``."""
    lm, params = lm_and_params
    params = chainermn_tpu.create_communicator("tpu").bcast_data(params)
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=8,
                           cache_len=32)
    sched = FCFSScheduler(engine)
    sched.submit(np.array([1, 2, 3]), 4)
    sched.run_until_idle()  # warmup: compiles both programs
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}
    for p, n in [([4, 5], 6), ([6, 7, 8, 9, 10, 11], 3), ([12], 9)]:
        sched.submit(np.array(p), n)
    sched.run_until_idle()
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}


def test_sampling_parity_with_per_request_rng(lm_and_params):
    """Temperature sampling: each request carries its own PRNG key and
    draws through the same split sequence as a solo B=1 generate(), so
    sharing the batch never perturbs a request's samples."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=6,
                           cache_len=24, temperature=0.8, top_k=5)
    sched = FCFSScheduler(engine)
    prompts = [np.array([1, 2, 3]), np.array([4, 5]), np.array([6, 7, 8])]
    reqs = [sched.submit(p, 5, rng=jax.random.PRNGKey(10 + i))
            for i, p in enumerate(prompts)]
    sched.run_until_idle()
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        ref = solo(lm, params, p, 5, temperature=0.8, top_k=5,
                   rng=jax.random.PRNGKey(10 + i))
        np.testing.assert_array_equal(r.output, ref)


def test_eos_retirement_matches_generate_eos(lm_and_params):
    """A request sampling EOS retires its slot immediately; its tokens
    equal generate(eos_id=...)'s output truncated at the EOS (the solo
    path pads after EOS, the serving path stops emitting)."""
    lm, params = lm_and_params
    prompt = np.array([1, 2, 3])
    # find a token the greedy decode actually emits, use it as EOS
    ref = solo(lm, params, prompt, 8)
    eos = int(ref[4])  # second generated token -> retirement mid-stream
    masked = solo(lm, params, prompt, 8, eos_id=eos)
    gen = list(masked[3:])
    expect = gen[: gen.index(eos) + 1]
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=6,
                           cache_len=24)
    sched = FCFSScheduler(engine, eos_id=eos)
    req = sched.submit(prompt, 8)
    sched.run_until_idle()
    assert req.tokens == expect
    assert engine.free_slots == set(range(2))  # slot actually freed


def test_engine_rejects_bad_configs(lm_and_params):
    lm, params = lm_and_params
    with pytest.raises(ValueError, match="n_slots"):
        ServingEngine(lm, params, n_slots=0, prefill_len=4)
    with pytest.raises(ValueError, match="prefill_len"):
        ServingEngine(lm, params, n_slots=1, prefill_len=0)
    with pytest.raises(ValueError, match="cache_len"):
        ServingEngine(lm, params, n_slots=1, prefill_len=4, cache_len=1024)
    tp_lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=1,
                          tensor_axis="x")
    with pytest.raises(ValueError, match="comm"):
        ServingEngine(tp_lm, params, n_slots=1, prefill_len=4)
    sp_lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=1,
                          attention="ring", sequence_axis="x")
    with pytest.raises(ValueError, match="sequence"):
        ServingEngine(sp_lm, params, n_slots=1, prefill_len=4)
    engine = ServingEngine(lm, params, n_slots=1, prefill_len=4,
                           cache_len=16)
    with pytest.raises(ValueError, match="prefill_len"):
        engine.validate_request(5, 1)       # prompt longer than prefill
    with pytest.raises(ValueError, match="cache_len"):
        engine.validate_request(4, 100)     # budget exceeds the slot
    engine.prefill(np.array([1, 2, 3]), jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="free slot"):
        engine.prefill(np.array([1, 2]), jax.random.PRNGKey(0))


@pytest.mark.slow  # ~16s; TP-serving parity also pinned by the paged-KV TP test below — keep tier-1 inside its timeout
def test_tp_serving_matches_solo_tp_generate():
    """Tensor-parallel serving (the _generate_tp_fn pattern through the
    scheduler): head-sharded slot caches inside comm.shard_map, both head
    variants, token-for-token vs the solo TP decode. The vocab-parallel
    variant runs the PR-5 fast path (bucket ladder + batched prefill +
    prefix cache) so the head-sharded block store and the in-program
    prefix splice get TP coverage too."""
    comm = chainermn_tpu.create_communicator("tpu")
    for vp in (False, True):
        lm = TransformerLM(vocab_size=32, d_model=16, n_heads=8, n_layers=2,
                           max_len=32, tensor_axis=comm.axis_name,
                           vocab_parallel_head=vp, compute_dtype=jnp.float32)
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        params = jax.jit(comm.shard_map(
            lambda t: lm.init(jax.random.PRNGKey(1), t),
            in_specs=P(), out_specs=P(),
        ))(prompt)
        ref = generate(lm, params, prompt, 5, comm=comm)
        fast = dict(prefill_buckets=(4, 8), prefill_batch=2,
                    prefix_cache_blocks=8, prefix_block_size=2) if vp else {}
        engine = ServingEngine(lm, params, n_slots=2, prefill_len=8,
                               cache_len=16, comm=comm, **fast)
        if vp:
            engine.warmup()
        sched = FCFSScheduler(engine)
        r1 = sched.submit(np.array([1, 2, 3]), 5)
        r2 = sched.submit(np.array([4, 5, 6, 7]), 4)  # ragged companion
        sched.run_until_idle()
        np.testing.assert_array_equal(r1.output, np.asarray(ref[0]))
        assert len(r2.tokens) == 4
        if vp:
            # a same-prefix follower hits the head-sharded block store
            r3 = sched.submit(np.array([1, 2, 9]), 5)
            sched.run_until_idle()
            assert engine.prefix_cache.hits >= 1
            ref3 = generate(lm, params, jnp.asarray([[1, 2, 9]], jnp.int32),
                            5, comm=comm)
            np.testing.assert_array_equal(r3.output, np.asarray(ref3[0]))
            assert set(engine.compile_counts_detailed().values()) == {1}
        else:
            assert engine.compile_counts() == {"prefill": 1, "decode": 1}
