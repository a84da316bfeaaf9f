"""Host-side ref-counted prefix index for KV reuse across requests.

Continuous batching (PR 1) made *decode* cheap — one compiled call advances
every slot — but admission still pays one full-length prefill per request,
even when ten queued prompts share the same system-prompt prefix. This
module is the host half of closing that gap (the vLLM/SGLang-style prefix
cache): a trie over fixed-size token *blocks* whose nodes own block slots
in a device-side KV store (the engine's ``[n_blocks, block_size, heads,
d_head]`` buffers per layer). On admission the scheduler asks for the
longest cached prefix; the engine either copies the matched blocks
slot-locally (the legacy dense path's compiled gather) or — in **paged**
mode — simply references them from the request's block table (sharing,
no copy), and prefills only the uncached suffix.

Design points:

- **Block granularity.** A node caches exactly ``block_size`` tokens, so
  matches are multiples of ``block_size`` and the device copy programs have
  static shapes (one executable each, ever). A prompt inserts only its
  *full* blocks; the ragged tail is never cached.
- **Ref-counting, two levels.** Trie-level pins (``_Node.refs``): ``match``
  pins the matched chain (tail refcount +1) until the holder is done with
  it (``release``); ``plan_insert`` pins the attachment point until the
  copy commits or aborts. Eviction only ever takes *leaf* nodes with
  refcount zero, so a pinned tail protects its whole chain. Pool-level
  refcounts (:class:`BlockPool`): each holder of a block — the trie node,
  and in paged mode every decode slot whose table references it — holds
  one reference; a block returns to the free list only at refcount zero,
  so evicting a trie node while a slot still reads its block merely
  *defers* the free until that slot retires.
- **LRU eviction.** When an insert needs more blocks than are free, the
  least-recently-used ref-zero leaves are evicted (hits refresh the whole
  matched path). Partial allocations are fine — caching a prompt's first
  few blocks is still useful.
- **Bookkeeping costs what changed, never the size of the trie.** The
  serving step asks "which block goes" for every block it hands out from
  a dry pool and "how many could go" for every admission, so neither
  walks: the ref-zero leaves wait in a heap ordered by ``last_use``
  whose entries are checked when they surface (:meth:`PrefixCacheIndex.
  _coldest`), and a per-block count of unpinned subtrees beside the
  pool's refcounts answers :meth:`evictable_blocks` in one vector
  operation. Only ``clear()`` visits every node.
- **Shared-pool (paged) mode.** Pass ``pool=`` to make the trie allocate
  from the same :class:`BlockPool` the engine's decode slots draw from:
  inserts then *adopt* a slot's already-resident blocks
  (:meth:`insert_shared` — zero device copies), and
  :meth:`evictable_blocks` tells the scheduler how many blocks an
  admission could reclaim on top of the free list.
- **Correctness rides on the engine's masking argument.** Copied or
  shared block spans may carry garbage rows past the real prefix; the
  causal position mask hides them until the tenant's own prefill/decode
  overwrites them (see ``engine.py``'s module docstring). Token parity vs
  solo ``generate()`` is pinned in ``tests/serving_tests``.

This module is **pure host state** (numpy + the monitor spine; no jax):
the trie, the block pool, and the hit/eviction telemetry. The device
store and its programs live in :class:`~chainermn_tpu.serving.engine.
ServingEngine`, which drives this index through ``match`` / ``release`` /
``plan_insert`` / ``commit_insert`` / ``abort_insert`` /
``insert_shared`` from the single scheduler thread (this class is
intentionally not thread-safe).
"""

from __future__ import annotations

import heapq
import itertools
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from chainermn_tpu.analysis import sanitizer
from chainermn_tpu.monitor._state import get_event_log, get_registry


class BlockPool:
    """Ref-counted allocator over the device block store's slots (host
    bookkeeping only — the arrays live in the engine).

    ``reserve_scratch=True`` pins block 0 as the **scratch block**: never
    allocated, the well-known target for writes that must land nowhere
    (inactive batch rows, positions beyond a slot's allocated span). The
    paged engine points every unused block-table entry at it.

    A block is *allocated* with refcount 1 (:meth:`alloc`); additional
    holders :meth:`incref`, and :meth:`decref` returns it to the free
    list only when the last holder lets go — which is what lets a trie
    eviction and a decode slot disagree about a block's lifetime without
    ever handing out KV that someone still reads."""

    def __init__(self, n_blocks: int, *, reserve_scratch: bool = False):
        lo = 1 if reserve_scratch else 0
        if n_blocks < lo + 1:
            raise ValueError(
                f"n_blocks must be >= {lo + 1}, got {n_blocks}")
        self.n_blocks = int(n_blocks)
        self.scratch: Optional[int] = 0 if reserve_scratch else None
        self._lo = lo
        self._free = list(range(self.n_blocks - 1, lo - 1, -1))
        self._refs = np.zeros(self.n_blocks, np.int64)
        # single-writer contract, enforced at runtime: two threads
        # observed inside a mutator concurrently raise GuardViolation
        self._mut = sanitizer.mutation_guard("BlockPool")

    @property
    def capacity(self) -> int:
        """Allocatable blocks (excludes the scratch block)."""
        return self.n_blocks - self._lo

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.capacity - len(self._free)

    def refs(self, block: int) -> int:
        return int(self._refs[block])

    def ref_counts(self) -> np.ndarray:
        """Every block's holders by block id — a read-only view, for
        whoever asks about the whole store at once."""
        view = self._refs.view()
        view.flags.writeable = False
        return view

    def shares(self, ids: np.ndarray) -> np.ndarray:
        """Per row of block ids, ``sum(1 / max(refs(b), 1))`` with the
        scratch block counting nothing: what each row's holder owes when
        a block's cost is split between its holders, for all rows in one
        pass. The floats are those of Python's ``sum`` over the row, in
        any order: each share is split into a multiple of ``2**-34`` and
        the rest, both parts add up exactly whatever the order (a row
        would need 2**18 blocks to round), and the one rounding left is
        the last addition — the correctly rounded sum, which is also what
        ``sum``'s compensated loop returns."""
        inv = 1.0 / np.maximum(self._refs, 1)
        if self.scratch is not None:
            inv[self.scratch] = 0.0
        hi = np.round(inv * 2.0 ** 34) * 2.0 ** -34
        lo = inv - hi
        return hi[ids].sum(axis=-1) + lo[ids].sum(axis=-1)

    def alloc(self) -> Optional[int]:
        """One free block at refcount 1, or ``None`` when the pool is dry
        (the caller may then evict trie leaves and retry)."""
        with self._mut:
            if not self._free:
                return None
            block = self._free.pop()
            self._refs[block] = 1
            return block

    def incref(self, block: int) -> None:
        with self._mut:
            self._refs[block] += 1

    def decref(self, block: int) -> None:
        with self._mut:
            self._refs[block] -= 1
            if self._refs[block] == 0:
                self._free.append(block)
            elif self._refs[block] < 0:
                raise RuntimeError(
                    f"block {block} over-released (refcount went negative)")

    def reset(self) -> None:
        """Everything free, all refcounts dropped — the engine's warm
        ``restart()`` path (device store is rebuilt alongside)."""
        with self._mut:
            self._free = list(range(self.n_blocks - 1, self._lo - 1, -1))
            self._refs[:] = 0


class _Node:
    """One cached block: ``block_size`` tokens -> one device store block."""

    __slots__ = ("key", "block", "parent", "children", "refs", "pins",
                 "queued", "last_use")

    def __init__(self, key, block, parent):
        self.key = key            # tuple of block_size token ints
        self.block = block        # index into the device block store
        self.parent = parent      # None once out of the trie
        self.children: dict = {}
        self.refs = 0             # active matches/insert-plans pinning here
        self.pins = 0             # refs of this node and every node below
        self.queued = False       # the eviction heap holds an entry for it
        self.last_use = 0


@dataclass
class PrefixMatch:
    """A pinned longest-cached-prefix result. ``length`` tokens
    (= ``len(block_ids) * block_size``) of the prompt are covered by
    ``block_ids`` in the device store; the holder must ``release()`` it
    back to the index once the blocks have been copied slot-locally (or,
    paged mode, referenced from the slot's table)."""

    nodes: list
    length: int
    block_ids: list
    released: bool = False


@dataclass
class InsertPlan:
    """Blocks allocated for a pending insert (device copy not yet done).
    ``start_block`` is the first NEW block's index within the prompt —
    blocks before it were already cached; ``row_starts`` are the matching
    slot-cache row offsets the engine's insert program copies from.
    ``commit`` links the nodes; ``abort`` returns the blocks to the free
    list."""

    parent: object
    keys: list
    block_ids: list
    start_block: int
    row_starts: list = field(default_factory=list)
    closed: bool = False


class PrefixCacheIndex:
    """Ref-counted trie over token blocks mapping prefixes to device KV
    block ids (module docstring). Drive from ONE thread (the scheduler's).

    Parameters
    ----------
    n_blocks : total block slots in the device store (capacity). Ignored
        when ``pool`` is given (the pool already knows).
    block_size : tokens per block; matches/inserts are multiples of this.
    pool : optional shared :class:`BlockPool` — paged mode, where decode
        slots and the trie draw from one store. Default: a private pool
        of ``n_blocks`` (the legacy dense-engine configuration).
    """

    def __init__(self, n_blocks: int, block_size: int,
                 pool: Optional[BlockPool] = None) -> None:
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if pool is None:
            if n_blocks < 1:
                raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
            pool = BlockPool(n_blocks)
            self._pool_private = True
        else:
            self._pool_private = False
        self.pool = pool
        self.n_blocks = pool.n_blocks
        self.block_size = int(block_size)
        self._root = _Node(None, -1, None)
        self._clock = itertools.count(1)
        self._n_nodes = 0
        # eviction order: ``(last_use, push number, node)``, one entry a
        # node at most (``_Node.queued``). Every ref-zero leaf has one; an
        # entry may be stale (the node since pinned, extended, or touched,
        # so its key is at most the node's ``last_use``): ``_coldest``
        # sorts that out when the entry reaches the top
        self._lru: list = []
        self._pushes = itertools.count()
        # per block of the store: the trie nodes on it with no pin at or
        # below them — with the pool's refcounts, ``evictable_blocks()``
        self._idle = np.zeros(pool.n_blocks, np.int32)
        # single-writer contract (same as BlockPool): the scheduler
        # thread owns all trie mutation; enforced when the sanitizer is on
        self._mut = sanitizer.mutation_guard("PrefixCacheIndex")
        self._events = get_event_log()
        reg = get_registry()
        self._c_hits = reg.counter("prefix_cache_hits_total")
        self._c_misses = reg.counter("prefix_cache_misses_total")
        self._c_evictions = reg.counter("prefix_cache_evictions_total")
        self._c_inserted = reg.counter("prefix_cache_inserted_blocks_total")
        # per-instance stats (the registry counters are process-cumulative;
        # ``stats()`` reports THIS cache's numbers)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.inserted_blocks = 0

    # ------------------------------------------------------------------ #
    # lookup                                                              #
    # ------------------------------------------------------------------ #

    def _key(self, tokens: np.ndarray, i: int) -> tuple:
        bs = self.block_size
        return tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])

    def match(self, tokens, max_blocks: Optional[int] = None
              ) -> Optional[PrefixMatch]:
        """Longest cached prefix of ``tokens``, pinned; ``None`` on miss.

        The match never covers the whole prompt (at most
        ``(len - 1) // block_size`` blocks): at least one real token must
        remain for the suffix prefill to produce the first sampled token's
        logits — the same trick vLLM uses. ``max_blocks`` caps further
        (the engine shrinks matches that would not leave room for a
        prefill bucket inside ``cache_len``)."""
        tokens = np.asarray(tokens).reshape(-1)
        cap = (len(tokens) - 1) // self.block_size
        if max_blocks is not None:
            cap = min(cap, max_blocks)
        with self._mut:
            node, nodes = self._root, []
            for i in range(cap):
                child = node.children.get(self._key(tokens, i))
                if child is None:
                    break
                nodes.append(child)
                node = child
            if not nodes:
                self.misses += 1
                self._c_misses.inc()
                return None
            self._pin(nodes[-1], 1)
            t = next(self._clock)
            for nd in nodes:
                nd.last_use = t
            self.hits += 1
            self._c_hits.inc()
            return PrefixMatch(nodes=nodes,
                               length=len(nodes) * self.block_size,
                               block_ids=[nd.block for nd in nodes])

    def missing_blocks(self, tokens) -> int:
        """How many of ``tokens``' full blocks are NOT yet cached — the
        engine's insert cost/benefit probe (no allocation, no pinning, no
        LRU touch)."""
        tokens = np.asarray(tokens).reshape(-1)
        total = len(tokens) // self.block_size
        node, i = self._root, 0
        while i < total:
            child = node.children.get(self._key(tokens, i))
            if child is None:
                break
            node, i = child, i + 1
        return total - i

    def ngram_continuation(self, tokens, k: int) -> Optional[list]:
        """Model-free continuation probe for the speculative n-gram
        drafter: if ``tokens`` walks the trie cleanly — every full block
        present, and the ragged tail a prefix of exactly ONE child key —
        propose up to ``k`` of the tokens a cached prompt says come next
        (the tail key's remainder, then deeper blocks while the path
        stays unambiguous). Returns ``None`` when the trie has no
        unambiguous opinion.

        Read-only on purpose: no pins, no LRU touch, no hit/miss
        counting — a probe must never change eviction order or skew the
        admission-path hit rate. Staleness is harmless: the result is a
        *draft*, and the target-model verify step rejects anything the
        real distribution disagrees with."""
        if k <= 0:
            return None
        tokens = np.asarray(tokens, np.int64).reshape(-1)
        bs = self.block_size
        node = self._root
        for i in range(len(tokens) // bs):
            child = node.children.get(self._key(tokens, i))
            if child is None:
                return None
            node = child
        tail = tuple(int(t) for t in tokens[(len(tokens) // bs) * bs:])
        out: list = []
        if tail:
            matches = [key for key in node.children
                       if key[: len(tail)] == tail]
            if len(matches) != 1:
                return None
            key = matches[0]
            out.extend(key[len(tail):])
            node = node.children[key]
        while len(out) < k and len(node.children) == 1:
            (key, node), = node.children.items()
            out.extend(key)
        return out[:k] if out else None

    def release(self, match: PrefixMatch) -> None:
        """Unpin a match (idempotent) — its blocks become evictable again
        once no other holder pins them."""
        if match is None or match.released:
            return
        with self._mut:
            match.released = True
            self._pin(match.nodes[-1], -1)

    # ------------------------------------------------------------------ #
    # insertion                                                           #
    # ------------------------------------------------------------------ #

    def plan_insert(self, tokens) -> Optional[InsertPlan]:
        """Allocate blocks for the not-yet-cached full blocks of
        ``tokens`` (evicting LRU ref-zero leaves as needed) and pin the
        attachment node. Returns ``None`` when nothing new would be cached
        (already present, no full block, or zero blocks allocatable). The
        caller copies KV device-side then ``commit_insert``s (or
        ``abort_insert``s on failure)."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        total = len(tokens) // bs
        with self._mut:
            node, i = self._root, 0
            t = next(self._clock)
            while i < total:
                child = node.children.get(self._key(tokens, i))
                if child is None:
                    break
                child.last_use = t
                node, i = child, i + 1
            if i >= total:
                return None
            self._pin(node, 1)            # pin the attachment point
            blocks = self.alloc_blocks(total - i)
            if not blocks:
                self._pin(node, -1)
                return None
        return InsertPlan(
            parent=node,
            keys=[self._key(tokens, i + j) for j in range(len(blocks))],
            block_ids=blocks, start_block=i,
            row_starts=[(i + j) * bs for j in range(len(blocks))],
        )

    def commit_insert(self, plan: InsertPlan) -> None:
        if plan.closed:
            return
        with self._mut:
            plan.closed = True
            node = plan.parent
            if not self._attached(node):
                return                    # planned before a clear()
            t = next(self._clock)
            n = 0
            for key, block in zip(plan.keys, plan.block_ids):
                child = node.children.get(key)
                if child is None:
                    child = self._link(node, key, block, t)
                    n += 1
                else:
                    # cached since the plan by another holder of the same
                    # prompt: theirs stays, and linking over it would
                    # leave a chain that nothing can reach or evict
                    child.last_use = t
                    self.pool.decref(block)
                node = child
            self._pin(plan.parent, -1)
            self._queue(node)
            self.inserted_blocks += n
        self._c_inserted.inc(n)
        self._events.emit("prefix_insert", blocks=n,
                          depth=plan.start_block + n,
                          used=self.used_blocks)

    def abort_insert(self, plan: InsertPlan) -> None:
        if plan.closed:
            return
        with self._mut:
            plan.closed = True
            self._pin(plan.parent, -1)
            for block in plan.block_ids:
                self.pool.decref(block)

    def insert_shared(self, tokens, block_ids) -> int:
        """Paged-mode zero-copy insert: **adopt** already-resident blocks.
        ``block_ids[j]`` must hold the KV of the prompt's ``j``-th full
        block (a freshly prefilled slot's table entries do, by
        construction). Links trie nodes for the not-yet-cached tail of
        full blocks, increfing each adopted block — the trie becomes a
        co-owner alongside the donor slot, and the block outlives the
        donor's retirement. No device work at all: under the unified
        store, caching a prefix IS bookkeeping. Returns blocks adopted."""
        tokens = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        total = min(len(tokens) // bs, len(block_ids))
        with self._mut:
            node, i = self._root, 0
            t = next(self._clock)
            while i < total:
                child = node.children.get(self._key(tokens, i))
                if child is None:
                    break
                child.last_use = t
                node, i = child, i + 1
            adopted = 0
            for j in range(i, total):
                block = int(block_ids[j])
                self.pool.incref(block)
                node = self._link(node, self._key(tokens, j), block, t)
                adopted += 1
            if adopted:
                self._queue(node)
        if adopted:
            self.inserted_blocks += adopted
            self._c_inserted.inc(adopted)
            self._events.emit("prefix_insert", blocks=adopted, depth=total,
                              used=self.used_blocks, shared=True)
        return adopted

    # ------------------------------------------------------------------ #
    # eviction / capacity                                                 #
    # ------------------------------------------------------------------ #

    def _attached(self, node) -> bool:
        """False for a node of a trie that ``clear()`` dropped under a
        holder's match or plan, or that was evicted."""
        return node.parent is not None or node is self._root

    def _link(self, parent, key, block, t):
        child = _Node(key, block, parent)
        child.last_use = t
        parent.children[key] = child
        self._idle[block] += 1            # fresh: nothing pins it yet
        self._n_nodes += 1
        return child

    def _pin(self, node, by: int) -> None:
        """``node.refs += by`` and what follows from it: a pin counts at
        the node and at every ancestor (a pinned tail protects its whole
        chain), so their blocks leave the idle count with the first pin
        below them and come back with the last."""
        node.refs += by
        if not self._attached(node):
            return
        root, at = self._root, node
        while at is not root:
            was = at.pins
            at.pins = was + by
            if was == 0:
                self._idle[at.block] -= 1
            elif at.pins == 0:
                self._idle[at.block] += 1
            at = at.parent
        if by < 0:
            self._queue(node)

    def _queue(self, node) -> None:
        """Enter ``node`` into the eviction order if it is a ref-zero
        leaf — called wherever a node may have become one: the end of a
        linked chain, an unpin, the eviction of a parent's last child."""
        if (node is not self._root and not node.children and not node.refs
                and not node.queued):
            node.queued = True
            heapq.heappush(self._lru,
                           (node.last_use, next(self._pushes), node))

    def _coldest(self):
        """The least-recently-used ref-zero leaf, the next to be evicted
        (``None`` when there is none), left at the top of the heap. An
        entry whose node is no longer a ref-zero leaf is dropped (the
        node is queued again when it next becomes one); one whose node
        was touched since is put back under its ``last_use``, which only
        ever grows, so nothing colder can lie below it."""
        lru = self._lru
        while lru:
            t, _, node = lru[0]
            if node.children or node.refs:
                heapq.heappop(lru)
                node.queued = False
            elif t != node.last_use:
                heapq.heapreplace(
                    lru, (node.last_use, next(self._pushes), node))
            else:
                return node
        return None

    def alloc_blocks(self, n: int) -> list:
        """Up to ``n`` blocks from the pool, evicting LRU ref-zero leaves
        when the free list runs dry (a partial result is fine). Shared by
        trie inserts and — paged mode — the engine's slot admissions and
        lazy block appends, so both compete under the same LRU policy."""
        out = []
        with self._mut:
            while len(out) < n:
                block = self.pool.alloc()
                if block is not None:
                    out.append(block)
                    continue
                victim = self._coldest()
                if victim is None:
                    break                  # partial allocation is fine
                heapq.heappop(self._lru)
                parent = victim.parent
                del parent.children[victim.key]
                victim.parent = None
                self._idle[victim.block] -= 1
                self._n_nodes -= 1
                self._queue(parent)
                # may not free the block immediately: a paged decode slot
                # still referencing it keeps it alive until that slot
                # retires
                self.pool.decref(victim.block)
                self.evictions += 1
                self._c_evictions.inc()
                self._events.emit("prefix_evict", block=victim.block,
                                  age=victim.last_use)
        return out

    def alloc_blocks_atomic(self, n: int) -> Optional[list]:
        """All-or-nothing :meth:`alloc_blocks`: exactly ``n`` blocks, or
        ``None`` with every partially-allocated block already returned to
        the pool. The KV-migration import and chunked-prefill staging
        paths allocate through this — both must leave the pool untouched
        on a shortfall, because their fallback (decode at the source /
        retry the admission next step) assumes nothing was consumed."""
        out = self.alloc_blocks(int(n))
        if len(out) < int(n):
            for block in out:
                self.pool.decref(block)
            return None
        return out

    def evictable_blocks(self) -> int:
        """How many blocks eviction could *actually return to the free
        list* right now: nodes in fully-unpinned subtrees whose block has
        no other holder (pool refcount 1). The scheduler's block-budget
        admission counts these on top of ``pool.free_blocks`` — a cached
        but idle prefix is reclaimable capacity, not spent capacity. The
        pool's refcounts move without the trie hearing of it (a slot
        retires), so they are read here, against the running ``_idle``."""
        if not self._n_nodes:
            return 0
        return int(self._idle[self.pool.ref_counts() == 1].sum())

    def clear(self) -> None:
        """Drop every cached prefix and release every trie-held block —
        the engine calls this from ``restart()`` together with rebuilding
        the device store, because a trie naming blocks of a discarded
        store would hand out KV that no longer exists. A private pool is
        reset wholesale (the legacy behavior — uncommitted plan blocks
        reclaimed too); a shared pool only gives back the trie's own
        references (the engine resets the pool itself after dropping the
        slot tables)."""
        with self._mut:
            # a match or plan that outlives its trie must find its nodes
            # detached (``_attached``): the one walk of every node
            stack = [self._root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                node.parent = None
            self._root = _Node(None, -1, None)
            self._n_nodes = 0
            self._lru.clear()
            self._idle[:] = 0
            if self._pool_private:
                self.pool.reset()

    # ------------------------------------------------------------------ #
    # stats                                                               #
    # ------------------------------------------------------------------ #

    @property
    def used_blocks(self) -> int:
        """Allocated blocks in the pool. With a private pool this is the
        trie's own footprint (legacy meaning); with a shared pool it
        counts decode-slot blocks too (the whole store's occupancy)."""
        return self.pool.used_blocks

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hit_rate, 4),
            "evictions": self.evictions,
            "evictable_blocks": self.evictable_blocks(),
            "inserted_blocks": self.inserted_blocks,
            "used_blocks": self.used_blocks,
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
        }


__all__ = ["BlockPool", "InsertPlan", "PrefixCacheIndex", "PrefixMatch"]
