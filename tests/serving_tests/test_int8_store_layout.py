"""Engines over the int8 block store after ISSUE 32 moved its scales to
``[blocks, bs*H]`` rows: what they serve did not move.

``RECORDED`` holds the greedy tokens the PARENT commit (3a6e06d, scales as
``[n_blocks, bs, H]``) served for the same models, weights and prompts, by
model file and read path; a tensor-parallel engine over the 8-device mesh
(its scale columns are a rank's own heads) and a migration gather -> scatter
round trip are held to the same tokens as an engine that does neither."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.models import SmallThinkerLM, TransformerLM
from chainermn_tpu.serving import FCFSScheduler, ServingEngine

JOBS = [(np.array([1, 4, 2, 7, 3, 5, 6, 2, 9, 4, 1, 3]), 9),
        (np.array([5, 6, 7]), 12), (np.array([8, 1, 8, 1, 8, 2, 8]), 6)]

# served by the parent commit: this file's own builders, run against a
# ``git archive`` of it (``python -c "import test_int8_store_layout as t;
# t.record()"`` with the archive first on the path, 8 CPU devices)
_PLAIN = [[4, 28, 2, 23, 16, 23, 2, 28, 2],
          [4, 28, 23, 26, 2, 4, 9, 2, 23, 26, 2, 14], [26, 26, 26, 26, 26, 26]]
_WINDOWED = [[16, 16, 16, 16, 16, 16, 16, 16, 16],
             [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 5, 27], [6, 6, 16, 16, 16, 16]]
_SHARDED = [[16, 6, 16, 6, 27, 21, 16, 16, 6],
            [28, 28, 28, 8, 6, 27, 28, 28, 3, 28, 8, 16], [6, 8, 8, 8, 6, 8]]
RECORDED = {
    "transformer-xla": _PLAIN, "transformer-kernel": _PLAIN,
    "smallthinker-xla": _WINDOWED, "smallthinker-kernel": _WINDOWED,
    "transformer-tp-xla": _SHARDED, "transformer-tp-kernel": _SHARDED,
}


def _transformer(**kw):
    lm = TransformerLM(vocab_size=29, d_model=32, n_heads=8, n_layers=2,
                       max_len=48, compute_dtype=jnp.float32, **kw)
    return lm, jnp.asarray([[1, 2, 3]], jnp.int32)


def _smallthinker():
    lm = SmallThinkerLM(
        vocab_size=29, d_model=32, n_heads=6, n_kv_heads=2, head_dim=8,
        n_layers=2, d_ff=16, n_experts=4, top_k=2, window=8,
        window_layers=(0, 1), rope_layers=(0, 1), max_len=48,
        compute_dtype=jnp.float32)
    return lm, lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _engine(lm, params, **kw):
    engine = ServingEngine(lm, params, n_slots=2, prefill_buckets=(4, 8, 16),
                           prefill_batch=2, paged=True, kv_block_size=4,
                           cache_len=32, kv_quant="int8", **kw)
    engine.warmup()
    return engine


def _serve(engine):
    sched = FCFSScheduler(engine)
    reqs = [sched.submit(p, n) for p, n in JOBS]
    sched.run_until_idle()
    assert engine.recompiles == {}
    return [list(map(int, r.tokens)) for r in reqs]


def serve(name):
    """The tokens of ``JOBS`` from the engine ``RECORDED`` calls ``name``."""
    model, _, path = name.rpartition("-")
    kernel = path == "kernel"
    if model == "smallthinker":
        return _serve(_engine(*_smallthinker(), paged_kernel=kernel))
    if model == "transformer":
        lm, prompt = _transformer()
        params = lm.init(jax.random.PRNGKey(0), prompt)
        return _serve(_engine(lm, params, paged_kernel=kernel))
    comm = chainermn_tpu.create_communicator("tpu")
    lm, prompt = _transformer(tensor_axis=comm.axis_name)
    params = jax.jit(comm.shard_map(
        lambda t: lm.init(jax.random.PRNGKey(0), t),
        in_specs=P(), out_specs=P()))(prompt)
    return _serve(_engine(lm, params, comm=comm, paged_kernel=kernel))


def record():
    print({name: serve(name) for name in RECORDED})


@pytest.mark.parametrize("name", list(RECORDED))
def test_greedy_tokens_are_the_parents(name):
    assert serve(name) == RECORDED[name]


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_migrated_slot_decodes_what_it_would_have(kernel):
    """A slot's blocks gathered out of one engine's store (int8 rows and
    scale rows, as stored) and scattered into another's: the importer goes
    on to the tokens an engine that kept the request serves."""
    lm, prompt = _transformer()
    params = lm.init(jax.random.PRNGKey(0), prompt)
    kept, src, dst = (_engine(lm, params, paged_kernel=kernel)
                      for _ in range(3))
    prompt, n_new = JOBS[0]
    rng = jax.random.PRNGKey(7)

    def admit(engine):
        plan = engine.plan_admission(prompt, rng=rng, max_new=n_new)
        (slot, first), = engine.admit_batch([plan])
        return slot, [first]

    def decode(engine, slot, tokens, upto):
        while len(tokens) < upto:
            while engine.slot_needs_block(slot):
                assert engine.append_block(slot)
            tokens.extend(engine.decode_round()[slot])
        return [int(t) for t in tokens[:upto]]

    want = decode(kept, *admit(kept), n_new)
    slot, tokens = admit(src)
    decode(src, slot, tokens, 3)                 # a block half written
    payload = src.export_slot_kv(slot)
    moved = dst.import_slot_kv(payload, prompt=prompt, max_new=n_new)
    src.release(slot)
    assert decode(dst, moved, tokens, n_new) == want
    assert src.recompiles == {} and dst.recompiles == {}
