"""Prefix KV reuse + bucketed batched prefill: the admission fast path.

Two layers of pinning. The host trie (``PrefixCacheIndex``) is tested
standalone — ref-counting, LRU eviction, block accounting — because it is
pure host state. Then the load-bearing engine properties: requests whose
prompts share a cached prefix are admitted in one bucketed batch with the
prefix COPIED (not recomputed) and still produce token-for-token the same
output as a solo :func:`chainermn_tpu.models.generate`; hits survive the
donor request's retirement (the store, not the slot, owns the blocks);
eviction falls back to a full prefill with identical tokens; warmup
compiles every program exactly once and NOTHING recompiles after; and a
warm ``restart()`` rebuilds the trie together with the store (a stale
trie would hand new requests KV blocks that no longer exist)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import TransformerLM, generate
from chainermn_tpu.resilience import FaultInjector
from chainermn_tpu.serving import (
    FCFSScheduler,
    PrefixCacheIndex,
    ServingEngine,
)
from chainermn_tpu.serving.prefix_cache import BlockPool, _Node

# --------------------------------------------------------------------- #
# host trie (no jax, sub-millisecond)                                    #
# --------------------------------------------------------------------- #


def test_trie_match_is_block_granular_and_never_whole_prompt():
    idx = PrefixCacheIndex(n_blocks=8, block_size=2)
    plan = idx.plan_insert(np.arange(1, 8))        # 7 tokens -> 3 blocks
    assert [len(k) for k in plan.keys] == [2, 2, 2]
    assert plan.row_starts == [0, 2, 4]
    idx.commit_insert(plan)
    m = idx.match(np.arange(1, 8))                 # same 7 tokens
    assert m.length == 6 and len(m.block_ids) == 3
    idx.release(m)
    # a prompt that IS exactly the cached blocks must keep >= 1 suffix
    # token: the match may cover at most (len-1)//bs blocks
    m = idx.match(np.arange(1, 7))                 # 6 tokens, all cached
    assert m.length == 4                           # 2 blocks, not 3
    idx.release(m)
    assert idx.match(np.array([9, 9, 9, 9])) is None
    assert idx.stats()["used_blocks"] == 3


def test_alloc_blocks_atomic_is_all_or_nothing():
    """The migration/chunked-staging primitive (ISSUE 19): either every
    requested block comes back, or none stick — a shortfall rolls the
    partial grab straight back so a failed import can't bleed the pool."""
    idx = PrefixCacheIndex(n_blocks=6, block_size=2)
    got = idx.alloc_blocks_atomic(4)
    assert got is not None and len(got) == 4
    free_before = idx.pool.free_blocks
    assert idx.alloc_blocks_atomic(free_before + 1) is None
    assert idx.pool.free_blocks == free_before         # rollback exact
    assert idx.alloc_blocks_atomic(free_before) is not None
    assert idx.alloc_blocks_atomic(0) == []


def test_trie_refcount_blocks_eviction_until_release():
    idx = PrefixCacheIndex(n_blocks=2, block_size=2)
    idx.commit_insert(idx.plan_insert(np.array([1, 2, 3, 4])))
    m = idx.match(np.array([1, 2, 3, 4, 5]))
    assert m.length == 4
    # store is full and the chain tail is pinned: nothing may be evicted,
    # so a new insert gets NO blocks (partial alloc -> None)
    assert idx.plan_insert(np.array([5, 6, 7, 8])) is None
    idx.release(m)
    plan = idx.plan_insert(np.array([5, 6, 7, 8]))  # now evicts the chain
    assert plan is not None and len(plan.block_ids) == 2
    idx.commit_insert(plan)
    assert idx.evictions == 2
    assert idx.match(np.array([1, 2, 3, 4, 5])) is None  # evicted
    m = idx.match(np.array([5, 6, 7, 8, 9]))
    assert m is not None and m.length == 4


def test_trie_lru_evicts_coldest_leaf_first():
    idx = PrefixCacheIndex(n_blocks=2, block_size=2)
    idx.commit_insert(idx.plan_insert(np.array([1, 2])))    # A
    idx.commit_insert(idx.plan_insert(np.array([3, 4])))    # B
    idx.release(idx.match(np.array([1, 2, 9])))             # touch A
    idx.commit_insert(idx.plan_insert(np.array([5, 6])))    # evicts B (LRU)
    assert idx.match(np.array([1, 2, 9])) is not None       # A survived
    assert idx.match(np.array([3, 4, 9])) is None


def test_trie_abort_returns_blocks_and_unpins():
    idx = PrefixCacheIndex(n_blocks=4, block_size=2)
    plan = idx.plan_insert(np.array([1, 2, 3, 4]))
    assert idx.used_blocks == 2                    # allocated, uncommitted
    idx.abort_insert(plan)
    assert idx.used_blocks == 0
    assert idx.match(np.array([1, 2, 3])) is None  # nothing was linked
    idx.clear()
    assert idx.used_blocks == 0


# --------------------------------------------------------------------- #
# the index against the two walks it replaced (PR 34)                    #
# --------------------------------------------------------------------- #


def walk_evictable(idx):
    """All ref-zero leaves, by a walk of every node: how the index found
    its victim before it kept the eviction order itself."""
    out, stack = [], [idx._root]
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        if node is not idx._root and not node.children and not node.refs:
            out.append(node)
    return out


def walk_evictable_blocks(idx):
    """Nodes in fully-unpinned subtrees whose block has no other holder,
    by the recursive walk ``evictable_blocks()`` used to be."""
    pool = idx.pool

    def walk(node):
        unpinned = node is idx._root or node.refs == 0
        count = 0
        for child in node.children.values():
            child_ok, child_count = walk(child)
            count += child_count
            unpinned = unpinned and child_ok
        if (node is not idx._root and unpinned
                and pool.refs(node.block) == 1):
            count += 1
        return unpinned, count

    return walk(idx._root)[1]


class WalkIndex(PrefixCacheIndex):
    """The index as it answered before: both questions by a walk. Driven
    in lock-step with the real one, it says which block must go when."""

    def alloc_blocks(self, n):
        out = []
        while len(out) < n:
            block = self.pool.alloc()
            if block is not None:
                out.append(block)
                continue
            victims = walk_evictable(self)
            if not victims:
                break
            victim = min(victims, key=lambda nd: nd.last_use)
            del victim.parent.children[victim.key]
            self.pool.decref(victim.block)
            self.evictions += 1
        return out

    def evictable_blocks(self):
        return walk_evictable_blocks(self)


def trie_shape(idx):
    """Every cached path with its block, pins and last use."""
    out, stack = [], [((), idx._root)]
    while stack:
        path, node = stack.pop()
        for key, child in node.children.items():
            stack.append((path + key, child))
            out.append((path + key, child.block, child.refs, child.last_use))
    return sorted(out)


class Driver:
    """One index under the random walk's operations, with the slots,
    matches and plans a caller would hold. Each method returns what the
    caller could observe."""

    def __init__(self, cls, shared, n_blocks=24, block_size=2):
        if shared:
            self.pool = BlockPool(n_blocks, reserve_scratch=True)
            self.idx = cls(n_blocks, block_size, pool=self.pool)
        else:
            self.idx = cls(n_blocks, block_size)
            self.pool = self.idx.pool
        self.shared = shared
        self.held, self.matches, self.plans = [], [], []

    def admit(self, tokens, keep_match):
        """A paged admission: reference the matched prefix, allocate the
        rest, adopt the prompt's full blocks into the trie."""
        idx, bs = self.idx, self.idx.block_size
        m = idx.match(tokens)
        shared = list(m.block_ids) if m is not None else []
        new = idx.alloc_blocks_atomic(-(-len(tokens) // bs) - len(shared))
        if new is None:
            idx.release(m)
            return None
        for block in shared:
            self.pool.incref(block)
        if keep_match and m is not None:
            self.matches.append(m)
        else:
            idx.release(m)
        ids = shared + new
        self.held.append(ids)
        return ids, idx.insert_shared(tokens, ids)

    def append(self, i, n):
        got = self.idx.alloc_blocks(n)
        if not self.held:
            self.held.append([])
        self.held[i].extend(got)
        return got

    def share(self, i):
        """A second holder of a slot's first block, as a migration
        import's or a fleet share's would be."""
        block = self.held[i][0]
        self.pool.incref(block)
        self.held.append([block])
        return block

    def retire(self, i):
        ids = self.held.pop(i)
        for block in ids:
            self.pool.decref(block)
        return ids

    def plan(self, tokens):
        plan = self.idx.plan_insert(tokens)
        if plan is not None:
            self.plans.append(plan)
        return plan and (plan.block_ids, plan.start_block)

    def close_plan(self, i, commit):
        plan = self.plans.pop(i)
        (self.idx.commit_insert if commit else self.idx.abort_insert)(plan)
        self.idx.commit_insert(plan)          # closed: idempotent

    def match(self, tokens, cap):
        m = self.idx.match(tokens, cap)
        if m is not None:
            self.matches.append(m)
        return m and (m.length, m.block_ids)

    def release(self, i):
        m = self.matches.pop(i)
        self.idx.release(m)
        self.idx.release(m)                   # idempotent

    def probe(self, tokens):
        return (self.idx.missing_blocks(tokens),
                self.idx.ngram_continuation(tokens, 3))

    def clear(self):
        """The engine's restart: trie and pool together, and holders of
        a match or a plan from before let go of them afterwards."""
        self.idx.clear()
        self.pool.reset()
        self.held = []
        while self.matches:
            self.release(0)
        while self.plans:
            self.plans[0].block_ids = []      # the reset took them back
            self.close_plan(0, commit=len(self.plans) % 2 == 0)


def random_prompt(rng):
    """Few distinct blocks, so that prompts share prefixes and branch."""
    n = int(rng.integers(1, 14))
    return rng.integers(0, 2, n) if rng.random() < 0.8 \
        else rng.integers(0, 5, n)


@pytest.mark.parametrize("shared", [False, True],
                         ids=["private_pool", "shared_pool"])
@pytest.mark.parametrize("seed", range(8))
def test_index_agrees_with_the_walks_after_every_operation(seed, shared):
    """ISSUE 34: the index keeps its eviction order and its count of
    evictable blocks as it goes; the walks it replaced are the oracles.
    After every operation of a seeded random walk the real index and one
    that still walks (same operations, a pool of its own) have handed out
    the same blocks, evicted as often and hold the same trie — so every
    victim was the walk's — and on the real index's own trie the next
    victim and ``evictable_blocks()`` are what the walks find."""
    rng = np.random.default_rng(1000 * seed + shared)
    real, walk = Driver(PrefixCacheIndex, shared), Driver(WalkIndex, shared)
    ops = ["admit", "append", "retire", "plan", "close_plan", "match",
           "release", "probe", "share", "clear"]
    weights = np.array([5, 4, 4, 3, 3, 3, 3, 1, 2, 0.15])
    evictions = 0
    for step in range(400):
        op = rng.choice(ops, p=weights / weights.sum())
        tokens = random_prompt(rng)

        def pick(of):
            return int(rng.integers(len(of)))

        if op == "admit":
            args = (tokens, bool(rng.integers(2)))
        elif op == "append":
            args = (pick(real.held or [0]), int(rng.integers(1, 4)))
        elif op == "retire" and real.held:
            args = (pick(real.held),)
        elif op in ("plan", "probe"):
            args = (tokens,)
        elif op == "close_plan" and real.plans:
            args = (pick(real.plans), bool(rng.integers(2)))
        elif op == "match":
            args = (tokens, None if rng.random() < 0.7
                    else int(rng.integers(0, 4)))
        elif op == "release" and real.matches:
            args = (pick(real.matches),)
        elif op == "share" and any(real.held):
            holding = [i for i, held in enumerate(real.held) if held]
            args = (holding[pick(holding)],)
        elif op == "clear":
            args = ()
        else:
            continue
        got, want = (getattr(side, op)(*args) for side in (real, walk))
        assert got == want, (step, op, args)
        idx = real.idx
        assert idx.evictions == walk.idx.evictions, (step, op)
        assert real.pool.free_blocks == walk.pool.free_blocks, (step, op)
        assert trie_shape(idx) == trie_shape(walk.idx), (step, op)
        leaves = walk_evictable(idx)
        assert len({nd.last_use for nd in leaves}) == len(leaves)
        # half of the seeds never look at the heap between evictions
        if seed % 2 == 0:
            assert idx._coldest() is min(
                leaves, key=lambda nd: nd.last_use, default=None), (step, op)
        count = walk_evictable_blocks(idx)
        assert idx.evictable_blocks() == count, (step, op)
        assert idx.stats()["evictable_blocks"] == count
        assert idx._n_nodes == len(trie_shape(idx))
        assert len(idx._lru) <= idx._n_nodes  # one entry a node at most
        evictions = idx.evictions
    assert evictions > 20          # the walk did run the pool dry


def count_children_reads(monkeypatch):
    """Count reads of ``_Node.children``: every way of visiting the trie
    goes through it."""
    slot = _Node.__dict__["children"]
    reads = [0]

    def get(node):
        reads[0] += 1
        return slot.__get__(node, _Node)

    monkeypatch.setattr(_Node, "children", property(
        get, lambda node, value: slot.__set__(node, value)))
    return reads


def test_dry_pool_bookkeeping_does_not_grow_with_the_trie(monkeypatch):
    """The cost, not a time: with 4,000 cached blocks and a dry pool, a
    block handed out and a count of the evictable ones each touch a
    handful of nodes, where the walks touched every node every time; on
    an empty trie they touch none."""
    pool = BlockPool(4097, reserve_scratch=True)
    idx = PrefixCacheIndex(4097, 2, pool=pool)
    rng = np.random.default_rng(0)
    for _ in range(250):                      # 250 prompts of 16 blocks
        tokens = rng.integers(0, 50_000, 32)
        ids = idx.alloc_blocks(16)
        idx.insert_shared(tokens, ids)
        for block in ids:
            pool.decref(block)                # the donor slot retires
    assert pool.free_blocks == 96 and idx._n_nodes == 4000
    idx.alloc_blocks(96)
    assert pool.free_blocks == 0 and idx.evictable_blocks() == 4000
    reads = count_children_reads(monkeypatch)
    walk_evictable(idx)
    assert reads[0] >= 4000                   # the probe sees a walk
    reads[0] = 0
    for _ in range(64):
        assert len(idx.alloc_blocks(1)) == 1
        idx.evictable_blocks()
    assert idx.evictions == 64
    assert reads[0] <= 64 * 4, reads[0]
    empty = PrefixCacheIndex(64, 2, pool=BlockPool(64, reserve_scratch=True))
    reads[0] = 0
    for _ in range(64):
        empty.alloc_blocks(1)                 # 63 blocks, then none
        assert empty.evictable_blocks() == 0
    assert reads[0] == 0


@pytest.mark.parametrize("seed", range(4))
def test_pool_shares_are_the_per_block_sums_to_the_last_bit(seed):
    """``BlockPool.shares`` splits every block between its holders for
    all rows in one pass; the floats are those of the per-block Python
    sum (thirds, fifths and sevenths included), the scratch entries of a
    table counting nothing."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(600, reserve_scratch=True)
    blocks = [pool.alloc() for _ in range(599)]
    for block in blocks:
        for _ in range(int(rng.integers(0, 130 if seed % 2 else 4))):
            pool.incref(block)
    tables = np.zeros((128, 64), np.int32)
    for row in tables:
        n = int(rng.integers(0, 65))
        row[:n] = rng.choice(blocks, n, replace=False)
    want = [sum(1.0 / max(pool.refs(b), 1) for b in row if b) for row in
            tables.tolist()]
    assert pool.shares(tables).tolist() == want


# --------------------------------------------------------------------- #
# engine: parity, warmup, restart                                        #
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def lm_and_params():
    lm = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=2,
                       max_len=48, compute_dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0),
                     jnp.asarray([[1, 2, 3]], jnp.int32))
    return lm, params


@pytest.fixture(scope="module")
def warm_engine(lm_and_params):
    """One warmed fast-path engine shared by the parity tests: two
    buckets, batch-2 prefill, blocks of 2 tokens."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=3,
                           prefill_buckets=(4, 8), prefill_batch=2,
                           prefix_cache_blocks=16, prefix_block_size=2,
                           cache_len=32)
    engine.warmup()
    return engine


def solo(lm, params, prompt, n, **kw):
    out = generate(lm, params, jnp.asarray(prompt, jnp.int32)[None], n, **kw)
    return np.asarray(out[0])


PREFIX = [1, 2, 3, 4, 5, 6]


def test_shared_prefix_batch_admission_matches_solo(lm_and_params,
                                                    warm_engine):
    """Acceptance criterion (a)+(b): a donor seeds the trie and RETIRES;
    two followers sharing its prefix are admitted in the SAME bucket
    batch, each prefilling only its suffix against COPIED prefix KV — and
    each is token-for-token a solo generate()."""
    lm, params = lm_and_params
    engine = warm_engine
    sched = FCFSScheduler(engine)
    donor = sched.submit(np.array(PREFIX + [7]), 5)
    sched.run_until_idle()
    assert donor.finished                      # donor retired; trie seeded
    h0 = engine.prefix_cache.hits
    r1 = sched.submit(np.array(PREFIX + [8]), 6)
    r2 = sched.submit(np.array(PREFIX + [9, 10]), 4)
    sched.step()                               # ONE admission round
    # both followers entered in one batched call (same bucket, shared
    # prefix preferred) — not two singleton admissions
    assert r1.slot >= 0 and r2.slot >= 0
    sched.run_until_idle()
    np.testing.assert_array_equal(donor.output, solo(lm, params,
                                                     PREFIX + [7], 5))
    np.testing.assert_array_equal(r1.output, solo(lm, params,
                                                  PREFIX + [8], 6))
    np.testing.assert_array_equal(r2.output, solo(lm, params,
                                                  PREFIX + [9, 10], 4))
    assert engine.prefix_cache.hits >= h0 + 2  # the reuse really happened
    m = sched.metrics.report()
    assert m["prefill_batch_size_max"] == 2
    assert m["prefix_hit_rate"] > 0


def test_zero_recompiles_across_buckets_after_warmup(lm_and_params,
                                                     warm_engine):
    """Acceptance criterion: warmup compiles each bucket program, the
    decode step, and both prefix-copy programs exactly ONCE; a mixed
    workload spanning every bucket, prefix hits, inserts, and slot reuse
    adds zero executables."""
    lm, params = lm_and_params
    engine = warm_engine
    before = engine.compile_counts_detailed()
    assert set(before.values()) == {1}, before
    sched = FCFSScheduler(engine)
    for prompt, n in [(PREFIX + [11], 4),          # bucket 4 via prefix hit
                      (list(range(1, 9)), 3),      # bucket 4 (hit) or 8
                      ([12, 13, 14, 15, 16, 1, 2], 5),   # bucket 8, miss
                      ([3], 6),                    # bucket 4, tiny
                      (PREFIX + [9], 2)]:          # hit again
        sched.submit(np.array(prompt), n)
    sched.run_until_idle()
    assert engine.compile_counts_detailed() == before
    assert engine.recompiles == {}
    assert engine.compile_counts() == {"prefill": 2, "decode": 1}


@pytest.mark.slow  # ~4s; the paged block-store twin of this scenario stays tier-1 in test_paged_kv — keep tier-1 inside its timeout
def test_eviction_then_readmit_matches_solo(lm_and_params):
    """Acceptance criterion (c): once a cached prefix is evicted (tiny
    store), the same prompt admits as a miss — full prefill — with
    identical tokens; a later readmit re-caches it."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2,
                           prefill_buckets=(4, 8), prefill_batch=2,
                           prefix_cache_blocks=3, prefix_block_size=2,
                           cache_len=32)
    engine.warmup()
    sched = FCFSScheduler(engine)
    a = np.array(PREFIX + [7])                 # 3 blocks — fills the store
    b = np.array([9, 10, 11, 12, 13, 14, 15])  # 3 blocks — must evict A
    ra1 = sched.submit(a, 4)
    sched.run_until_idle()
    rb = sched.submit(b, 4)
    sched.run_until_idle()
    assert engine.prefix_cache.evictions >= 1
    ra2 = sched.submit(a, 4)                   # A evicted: admits as miss
    sched.run_until_idle()
    ref = solo(lm, params, a, 4)
    np.testing.assert_array_equal(ra1.output, ref)
    np.testing.assert_array_equal(ra2.output, ref)
    np.testing.assert_array_equal(rb.output, solo(lm, params, b, 4))


@pytest.mark.slow  # ~4s; restart semantics stay tier-1 via test_paged_kv restart coverage — keep tier-1 inside its timeout
def test_restart_rebuilds_trie_with_store(lm_and_params):
    """The PR-5 bugfix: a warm restart must clear the prefix trie
    together with the slot mirrors/caches — a stale trie would 'hit' on
    blocks of the discarded store. Pinned fault-injected: a decode fault
    errors the in-flight work, the scheduler warm-restarts, and a
    same-prefix readmit sees an EMPTY cache, misses, and still matches
    solo decode (with the same executables — nothing recompiled)."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2,
                           prefill_buckets=(4, 8), prefill_batch=2,
                           prefix_cache_blocks=16, prefix_block_size=2,
                           cache_len=32)
    engine.warmup()
    counts = engine.compile_counts_detailed()
    sched = FCFSScheduler(engine)
    seed = sched.submit(np.array(PREFIX + [7]), 4)
    sched.run_until_idle()
    assert seed.finished and engine.prefix_cache.used_blocks > 0
    inj = FaultInjector(seed=0)
    inj.arm("serving.decode", kind="raise", times=1)
    with inj:
        victim = sched.submit(np.array(PREFIX + [8]), 6)
        sched.run_until_idle()
    assert victim.state.value == "errored"
    assert sched.engine_restarts == 1
    # the restart rebuilt store AND trie together: nothing cached anymore
    assert engine.prefix_cache.used_blocks == 0
    assert engine.prefix_cache.match(np.array(PREFIX + [8])) is None
    # and a fresh same-prefix request is correct from the clean slate
    redo = sched.submit(np.array(PREFIX + [8]), 6)
    sched.run_until_idle()
    np.testing.assert_array_equal(redo.output,
                                  solo(lm, params, PREFIX + [8], 6))
    assert engine.compile_counts_detailed() == counts  # warm = no compile


def test_cost_aware_grouping_is_bucket_homogeneous(lm_and_params,
                                                   warm_engine):
    """Admission groups never mix buckets (one compiled program per
    call): a long head admits alone even with short companions queued;
    the shorts then share the next round's batch."""
    lm, params = lm_and_params
    engine = warm_engine
    sched = FCFSScheduler(engine)
    long = sched.submit(np.array([7, 8, 9, 10, 11, 12, 13]), 3)  # bucket 8
    s1 = sched.submit(np.array([14, 15]), 3)                     # bucket 4
    s2 = sched.submit(np.array([16, 1]), 3)                      # bucket 4
    sched.step()
    assert long.slot >= 0 and s1.slot < 0 and s2.slot < 0
    sched.step()
    assert s1.slot >= 0 and s2.slot >= 0                         # one batch
    sched.run_until_idle()
    for req, (p, n) in [(long, ([7, 8, 9, 10, 11, 12, 13], 3)),
                        (s1, ([14, 15], 3)), (s2, ([16, 1], 3))]:
        np.testing.assert_array_equal(req.output, solo(lm, params, p, n))


def test_single_bucket_engine_keeps_pr1_surface(lm_and_params):
    """Back-compat: the default configuration (one bucket, batch 1, no
    prefix cache) keeps the PR-1 compile-count contract and the direct
    ``prefill()`` API."""
    lm, params = lm_and_params
    engine = ServingEngine(lm, params, n_slots=2, prefill_len=8,
                           cache_len=32)
    slot, first = engine.prefill(np.array([1, 2, 3]),
                                 jax.random.PRNGKey(0))
    assert slot == 0 and engine.active_slots == 1
    engine.decode_step()
    assert engine.compile_counts() == {"prefill": 1, "decode": 1}
    ref = solo(lm, params, [1, 2, 3], 1)
    assert first == ref[3]
