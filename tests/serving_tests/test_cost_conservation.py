"""Engine-backed conservation property (ISSUE 17): a live scheduler's
cost ledger must attribute every measured device interval back to the
dispatch that produced it — under the sequential path AND under fuzzed
thread schedules that interleave submit/preempt/step at the sanitizer's
sync points. Conservation here is by construction (each record call
splits the interval into shares that sum to it), so the bound asserted
is float-epsilon tight, well inside the ±10% contract."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.analysis import sanitizer
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.monitor.costs import KINDS, UNATTRIBUTED
from chainermn_tpu.serving import FCFSScheduler, RequestState, ServingEngine

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11], [12], [13, 14, 3]]
TENANTS = ["bulk", "bulk", "quiet", "bulk", "quiet", "bulk"]
MAX_NEW = 9


@pytest.fixture(scope="module")
def rig():
    """One compiled paged engine for the module; the scheduler carries
    a live cost ledger (the default)."""
    lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=1,
                       max_len=64, compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.asarray([[1, 2, 3]], jnp.int32))
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=6,
                           paged=True, kv_blocks=64, kv_block_size=2,
                           decode_window=4, cache_len=48)
    sched = FCFSScheduler(engine)
    assert sched.costs is not None
    return sched


def _assert_conserved(sched):
    pay = sched.costs.payload()
    assert pay["dispatches"] > 0
    assert sched.costs.conservation_error <= 0.10   # the PR contract
    assert pay["max_dispatch_error"] <= 0.10
    # by construction the split is exact, not merely within tolerance
    assert sched.costs.conservation_error < 1e-6
    assert pay["max_dispatch_error"] < 1e-6
    assert {k.split("\x00")[1] for k in pay["device"]} <= set(KINDS)
    ranked = sched.costs.tenant_device_seconds()
    assert set(ranked) <= {"bulk", "quiet"}
    assert all(s > 0.0 for s in ranked.values())
    assert UNATTRIBUTED not in ranked


def _run_fuzzed(sched, seed):
    stop = threading.Event()

    def drive():
        while not stop.is_set():
            sched.step()

    with sanitizer.fuzz(seed, p=0.3, sleep_s=0.0005,
                        points=("lock:", "guarded:", "mutate:")):
        t = threading.Thread(target=drive, daemon=True)
        t.start()
        try:
            reqs = [sched.submit(np.asarray(p, np.int32), MAX_NEW,
                                 tenant=tenant)
                    for p, tenant in zip(PROMPTS, TENANTS)]
            for r in reqs:
                assert r.wait(timeout=120)
        finally:
            stop.set()
            t.join(30)
    assert not t.is_alive()
    return reqs


def test_sequential_schedule_conserves_device_time(rig):
    sched = rig
    reqs = [sched.submit(np.asarray(p, np.int32), MAX_NEW, tenant=tenant)
            for p, tenant in zip(PROMPTS, TENANTS)]
    sched.run_until_idle()
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(r.tenant == t for r, t in zip(reqs, TENANTS))
    _assert_conserved(sched)
    # bulk ran 4 of 6 prompts: it must out-cost quiet
    ranked = sched.costs.tenant_device_seconds()
    assert ranked["bulk"] > ranked["quiet"]


def test_fuzzed_schedule_conserves_device_time(rig):
    sched = rig
    reqs = _run_fuzzed(sched, seed=1234)
    assert [r.state for r in reqs] == [RequestState.DONE] * len(PROMPTS)
    _assert_conserved(sched)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [7, 99, 2024])
def test_fuzzed_conservation_soak(rig, seed):
    """More schedules of the same window — full-suite only."""
    sched = rig
    reqs = _run_fuzzed(sched, seed)
    assert [r.state for r in reqs] == [RequestState.DONE] * len(PROMPTS)
    _assert_conserved(sched)


# --------------------------------------------------------------------- #
# chunked prefill + migration attribution (ISSUE 19)                     #
# --------------------------------------------------------------------- #


def test_chunked_schedule_conserves_device_time(rig):
    """Chunked prefill books each chunk's interval through the same
    record_prefill path (one member, the chunk's token count): the
    ledger stays exact under a fuzzed chunked schedule too."""
    sched = FCFSScheduler(rig.engine, chunk_tokens_per_step=2)
    reqs = _run_fuzzed(sched, seed=77)
    assert [r.state for r in reqs] == [RequestState.DONE] * len(PROMPTS)
    _assert_conserved(sched)


def test_migrated_request_books_migrate_kind(rig):
    """A migration's export+handover interval lands on the source
    ledger under the ``migrate`` kind — and both ledgers still conserve
    exactly."""
    eng = rig.engine
    eng.warmup()        # can_import gates on an explicitly warm engine
    sa = FCFSScheduler(eng, chunk_tokens_per_step=2)
    sb = FCFSScheduler(eng)
    sa.migrate_cb = lambda req, payload: bool(
        sb.enqueue_migrated(req, payload))
    r = sa.submit(np.asarray([1, 2, 3, 4, 5, 6], np.int32), MAX_NEW,
                  tenant="bulk")
    for _ in range(400):
        sa.step()
        sb.step()
        if r.finished:
            break
    assert r.state is RequestState.DONE, (r.state, r.error)
    pay = sa.costs.payload()
    kinds = {k.split("\x00")[1] for k in pay["device"]}
    assert "migrate" in kinds
    _assert_conserved(sa)
    _assert_conserved(sb)
    # the migrate seconds belong to the request's tenant, not overhead
    assert sa.costs.tenant_device_seconds()["bulk"] > 0.0


# --------------------------------------------------------------------- #
# block shares of all slots in one pass (ISSUE 34)                       #
# --------------------------------------------------------------------- #


def _per_block_shares(engine):
    """What the ledger's shares are by definition: every block a slot
    holds, split between its holders, summed block by block."""
    kv = engine._kv[0]
    return [sum(1.0 / max(kv.pool.refs(b), 1) for b in held)
            for held in kv.slot_blocks]


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["decoding", "staged_chunks"])
def test_all_slots_block_shares_equal_the_per_block_sums(rig, chunked):
    """With a trie, ``slot_block_shares()`` reads every slot's share
    through the tables in one pass. Two live requests on the prefix a
    retired third left in the trie hold its blocks at a third each, and
    a chunked prefill's staged blocks are in no table yet: the floats
    are the per-block sums' to the last bit in both states."""
    eng = rig.engine
    sched = FCFSScheduler(eng, chunk_tokens_per_step=2 if chunked else None)
    pool = eng._kv[0].pool
    first = sched.submit(np.asarray([15, 16, 15, 16, 15], np.int32), 3,
                         tenant="quiet")
    sched.run_until_idle()
    assert first.state is RequestState.DONE and not eng._kv[0].live
    if chunked:
        sched.submit(np.asarray([14, 13, 12, 11, 10, 9], np.int32),
                     MAX_NEW, tenant="bulk")
        sched.step()
        assert eng._chunking                      # staged, not committed
        (slot,) = eng._chunking
        assert not eng._tables[slot].any()
        assert eng.slot_block_shares()[slot] == 3.0
    else:
        for tail in (9, 7):
            sched.submit(np.asarray([15, 16, 15, 16, tail], np.int32),
                         30, tenant="bulk")
            sched.step()                          # one admission a step
        assert len(sched._by_slot) == 2
        held = eng._kv[0].slot_blocks
        assert {pool.refs(b) for b in held[0]} == {1, 3}   # thirds
    want = _per_block_shares(eng)
    assert eng.slot_block_shares().tolist() == want
    assert sum(want) > 0.0
    sched.run_until_idle()
    assert eng.slot_block_shares().tolist() == [0.0, 0.0]
    _assert_conserved(sched)
