"""Continuous-batching decode engine over the static KV-cache path.

The offline :func:`chainermn_tpu.models.generate` decodes ONE fixed batch
start-to-finish; a traffic-facing server cannot wait for the slowest
request before admitting the next. This engine owns a fixed pool of
``n_slots`` cache slots inside one persistent static-shape KV cache
(:func:`~chainermn_tpu.models.transformer.init_kv_caches`-backed) and a
small fixed family of compiled device programs:

- ``prefill`` (one program per **bucket**): run up to
  ``prefill_rows(bucket)`` requests' (padded) prompt suffixes through the
  model in ONE call, each batch row writing K/V into its OWN slot at its
  OWN start position (the per-row ``[B, T]`` position form of
  ``TransformerLM.__call__`` over the per-slot
  ``update_cache_and_attend``) and sampling its first token — admission
  cost is one batched suffix prefill, amortized over the group. A program
  holds a budget of tokens, not a count of rows: ``prefill_batch`` is the
  rows at the smallest bucket, and a larger bucket's program has
  ``prefill_batch * prefill_buckets[0] // bucket`` rows (at least one),
  so no program runs more than ``prefill_batch x prefill_buckets[0]``
  padded tokens and a long prompt admitted alone pays for no empty rows;
- ``decode_step``: advance ALL slots one token per call, each at its OWN
  sequence position; retired/free slots ride along masked by ``jnp.where``
  so shapes never change and nothing recompiles;
- ``prefix_insert`` (when the prefix cache is on): copy a freshly
  prefilled prompt's full KV blocks into the device block store backing
  :class:`~chainermn_tpu.serving.prefix_cache.PrefixCacheIndex`, deferred
  off the admission path. The matching *fetch* needs no program of its
  own: each bucket's prefill gathers the matched blocks INSIDE its single
  device call (a hit costs zero extra dispatches), then prefills only the
  uncached suffix.

Prompt padding is **bucketed**: instead of one ``prefill_len``-padded
program, ``prefill_buckets`` is a small ladder (e.g. ``(64, 256, 1024)``)
and each admission group runs the smallest bucket covering its (suffix)
lengths — padding waste shrinks from ``max_len - len`` to the bucket gap
at the cost of ``len(buckets)`` compiles, all performed once by
:meth:`warmup` (``RecompileGuard`` pins zero growth after).

Why this is correct without ever zeroing a slot between requests: the
causal position mask only admits cache rows at positions ``<= q_pos``, and
every such row was either written by THIS request's prefill (rows
``< prompt_len``) or overwritten by one of its decode steps (each step
writes its query row before attending). Stale K/V from a previous tenant
of the slot — the padding rows a short prompt leaves behind, warmup's
dummy rows, and the garbage tail of a copied prefix block span — sit at
positions the mask excludes until the exact step that overwrites them.
Prefix reuse adds one step to the argument: the copied rows ``[0, L)``
were computed from the SAME first ``L`` tokens at the SAME positions
(causality: K/V of a position depends only on tokens at or before it), so
the suffix attends exactly the rows its own full prefill would have
written. The engine-level parity tests (staggered admissions and shared-
prefix admissions vs solo ``generate()``, token-for-token) pin both.

Per-request sampling parity: each slot carries its own PRNG key and draws
through the SAME ``_sampler`` split sequence as a solo ``generate()`` call
(one split at prefill, one per decode step), via a per-slot vmap — so a
request's tokens are independent of which other requests share the batch.

Tensor-parallel decode reuses the ``_generate_tp_fn`` pattern: all
programs are traced inside ``comm.shard_map`` with the cache's (and block
store's) head axis sharded over the mesh (``P(None, None, axis)`` at
rest), and a vocab-parallel head's local logits are ``all_gather``-ed
before sampling — the scheduler drives TP decode through the identical
slot API.

**Paged mode** (``paged=True``) replaces the dense per-slot cache regions
with ONE shared block store — the same store the prefix cache runs on —
and per-slot **block tables** (host mirror + a ``[n_slots, max_blocks]``
int32 operand per decode call). Concurrency is then bound by *tokens
actually resident*, not ``n_slots x cache_len`` worst case: a slot
allocates blocks lazily as its sequence crosses block boundaries
(``append_block``, scheduler-driven), prefix hits become plain
ref-counted table entries (the PR-5 splice-copy collapses into sharing —
a hit costs zero copies, and caching a freshly prefilled prompt is pure
bookkeeping via ``insert_shared``), retirement decrefs the slot's blocks
back to the pool, and ``kv_quant='int8'`` halves resident bytes again
(per-row-per-head scales, dequantized inside the attention gather).
Shared blocks are never written: a match covers only *full* prompt
blocks, and every write position ``>= match.length`` lands in a block
the slot owns exclusively — copy-on-write reduces to "the first partial
block is always private". Still exactly TWO program families (bucketed
prefill + decode), compiled once at warmup: table *contents* change
per call, shapes never do, so the zero-recompile invariant carries over
unchanged. The legacy dense path is preserved byte-for-byte behind
``paged=False`` (the default).

**State that is not rows of K and V.** A model's ``kv_cache_spec()`` may
name, beside its kinds of KV blocks, a kind whose layers keep one state of a
fixed size a sequence (:class:`~chainermn_tpu.models.transformer.
SlotStateKind`: a linear-attention layer's recurrent state and the last
inputs of its short convolution). For such a layer the store holds one array
a key indexed by SLOT, ``n_slots`` rows and one scratch row behind them: no
pool, no table, no trie, nothing to size (``_SlotState``). A prefill program
is told the store row of each of its rows (``slots``; the scratch row for
rows that hold no request) and the real tokens of each (``valid``), and
writes each row's state after its last REAL token there; the decode program's
rows are the slots in their order, it advances the active ones in place
(donated) and leaves the others as they are; a freed slot's row is simply
overwritten by the next prefill into it, which starts from zero and never
reads it. Admission counts the kind as one unit a slot
(:meth:`ServingEngine.blocks_needed`), ``kv_stats()['kinds']`` reports
``slots_live`` and ``bytes``. Such a model cannot continue a prompt at an
offset, so it is refused what window layers are refused (prefix reuse,
speculation, ``decode_window``, chunked prefill, KV migration, tensor
parallelism, ``paged=False``), each with a ``ValueError``;
preempt-and-replay stays whole, a replay being a prefill from position 0.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.extensions.profiling import Watchdog
from chainermn_tpu.models.transformer import (
    SlotStateKind,
    _sampler,
    init_kv_caches,
    init_paged_kv_caches,
)
from chainermn_tpu.dataflow.dispatch import device_fetch
from chainermn_tpu.monitor import RecompileGuard, annotate
from chainermn_tpu.monitor._state import get_event_log, get_registry
from chainermn_tpu.parallel.paged_kernel import kernel_supported
from chainermn_tpu.resilience.cutpoints import (
    SERVING_CHUNK_PREFILL,
    SERVING_DECODE,
    SERVING_KV_APPEND,
    SERVING_PREFILL,
    SERVING_PREFILL_BATCH,
    SERVING_PREFIX_COPY,
    SERVING_SPEC_VERIFY,
)
from chainermn_tpu.resilience.faults import inject
from chainermn_tpu.serving.prefix_cache import (
    BlockPool,
    PrefixCacheIndex,
    PrefixMatch,
)
from chainermn_tpu.serving.speculative import SpeculativeConfig, build_drafter


@dataclass
class AdmitPlan:
    """One request's admission decision: the pinned prefix match (if any),
    the suffix start position, and the prefill bucket its padded suffix
    runs in. Built by :meth:`ServingEngine.plan_admission`; consumed by
    :meth:`ServingEngine.admit_batch` (or discarded via
    :meth:`ServingEngine.cancel_plan`, which unpins the match)."""

    prompt: np.ndarray
    rng: object
    match: Optional[PrefixMatch]
    start: int          # cached tokens reused (0 on miss)
    bucket: int         # padded suffix length (one compiled program per)
    max_new: int = 1    # token budget (paged mode reserves growth blocks)

    @property
    def cached_frac(self) -> float:
        return self.start / len(self.prompt) if len(self.prompt) else 0.0


@dataclass
class ChunkedPrefill:
    """In-progress chunked prefill of ONE slot: the request's prompt and
    rng held host-side, the slot's privately-staged block ids (allocated
    up front, NOT yet visible in the engine's decode table — see
    :meth:`ServingEngine.begin_chunked`), and the precomputed chunk
    schedule ``[(frontier, chunk_len, bucket), ...]`` that
    :meth:`ServingEngine.prefill_chunk` walks one entry per call."""

    prompt: np.ndarray
    rng: object
    start: int                     # cached-prefix tokens (chunk 0 frontier)
    max_new: int
    ids: list = field(default_factory=list)
    chunks: list = field(default_factory=list)
    next_idx: int = 0
    t_begin: float = 0.0

    @property
    def done(self) -> bool:
        return self.next_idx >= len(self.chunks)

    @property
    def frontier(self) -> int:
        """Tokens prefilled so far (cached prefix included)."""
        if self.done:
            return len(self.prompt)
        return self.chunks[self.next_idx][0]


class _KVKind:
    """Host-side block state of one kind of KV cache (an entry of the
    model's ``kv_cache_spec()``): its pool, the per-slot tables into its
    store, and the growth reserved for each slot. A kind that keeps every
    token holds ``ceil(tokens / block_size)`` blocks a slot; a window kind
    at most the window and one block, its table row a ring: the block of
    positions ``[j*bs, (j+1)*bs)`` sits in table entry ``j % width``, the
    block a request writes next takes the place of one that has left its
    window, and nothing has to be freed while it runs."""

    def __init__(self, kind, n_blocks: Optional[int], block_size: int,
                 n_slots: int, cache_len: int) -> None:
        self.kind = kind
        self.name = kind.name
        self.window = kind.window
        self.bs = block_size
        # tokens a slot can have resident, and the table entries for them
        # (the last block may straddle the span: its tail stays masked)
        self.span = cache_len if kind.window is None else min(
            cache_len, kind.window + block_size)
        self.width = -(-self.span // block_size)
        self.n_blocks = int(n_blocks if n_blocks is not None
                            else n_slots * self.width + 1)
        self.pool = BlockPool(self.n_blocks, reserve_scratch=True)
        # the trie allocates from the pool (evicting idle prefixes first);
        # a window kind's stays empty and is its allocator only
        self.index = PrefixCacheIndex(self.n_blocks, block_size,
                                      pool=self.pool)
        self.tables = np.zeros((n_slots, self.width), np.int32)
        self.slot_blocks: list[list[int]] = [[] for _ in range(n_slots)]
        # slots holding each block, and how many blocks have a holder:
        # the ``blocks_live`` gauge, kept where a slot gains and drops
        # blocks so that reading it walks nothing
        self.holders = [0] * self.n_blocks
        self.live = 0
        # worst-case growth blocks each active slot may still append
        # (admission reserves them; append_block draws them down) — what
        # makes block-budget admission preemption-free in the no-fault case
        self.reserved = np.zeros((n_slots,), np.int64)

    def blocks_for(self, tokens: int) -> int:
        """Blocks a slot holds once its sequence is ``tokens`` long."""
        return -(-min(tokens, self.span) // self.bs)

    def entry(self, block: int) -> int:
        """Table entry of the block of positions ``[block*bs, ...)``."""
        return block if self.window is None else block % self.width

    def takes(self, slot: int, blocks) -> None:
        """The slot's table gains ``blocks`` (admission, import, append)."""
        self.slot_blocks[slot].extend(blocks)
        for block in blocks:
            if self.holders[block] == 0:
                self.live += 1
            self.holders[block] += 1

    def drops(self, slot: int, blocks=None) -> None:
        """The slot's table gives ``blocks`` up (rollback), or all it
        holds (release)."""
        if blocks is None:
            blocks, self.slot_blocks[slot] = self.slot_blocks[slot], []
        else:
            for block in blocks:
                self.slot_blocks[slot].remove(block)
        for block in blocks:
            self.holders[block] -= 1
            if self.holders[block] == 0:
                self.live -= 1

    def free_slot(self, slot: int) -> None:
        """Give the slot's block references back: exclusively-owned blocks
        free immediately, trie-shared ones stay resident for the next hit
        (the store, not the slot, owns cached prefixes)."""
        for block in self.slot_blocks[slot]:
            self.pool.decref(block)
        self.drops(slot)
        self.reserved[slot] = 0
        self.tables[slot, :] = 0

    def reset(self) -> None:
        self.index.clear()
        self.pool.reset()
        self.tables[:] = 0
        self.slot_blocks = [[] for _ in range(len(self.slot_blocks))]
        self.holders = [0] * self.n_blocks
        self.live = 0
        self.reserved[:] = 0

    def admittable(self) -> int:
        return (self.pool.free_blocks + self.index.evictable_blocks()
                - int(self.reserved.sum()))

    def stats(self) -> dict:
        return {
            "kv_blocks": self.n_blocks,
            "layers": len(self.kind.layers),
            "window": self.window,
            "blocks_in_use": self.pool.used_blocks,
            "blocks_live": self.live,
            "blocks_free": self.pool.free_blocks,
            "blocks_reserved": int(self.reserved.sum()),
        }


class _SlotState:
    """Host-side account of one kind of state that is a row a slot
    (:class:`~chainermn_tpu.models.transformer.SlotStateKind`): there is
    nothing to allocate, a slot's row is its state while it holds a request
    and is overwritten by the next prefill into it. ``rows`` are the slots
    and one scratch row for program rows that hold no request."""

    def __init__(self, kind, n_slots: int) -> None:
        self.kind = kind
        self.name = kind.name
        self.rows = n_slots + 1
        self.bytes = len(kind.layers) * self.rows * sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for _, shape, dtype in kind.arrays)

    def stats(self, slots_live: int) -> dict:
        return {"slots": self.rows - 1, "layers": len(self.kind.layers),
                "slots_live": slots_live, "bytes": self.bytes}


class EngineStateError(RuntimeError):
    """A device-program failure left the engine's donated buffers in an
    unknown state — containment is impossible; the scheduler must fail all
    in-flight work and warm-restart."""


# the collection a served model may sow counters into (parallel/moe.py)
_STATS = "serving_stats"


def _with_moe_counts(nxt, stats, real):
    """``nxt`` as a paged program hands it back, with two more entries where
    the model's expert layers sowed their per-token counts (a share of the
    experts held here, ``DroplessMoE.held``): the assignments of the
    program's ``real`` tokens ``[B, S]`` to experts held, and all of them,
    summed over the layers. They ride the fetch the tokens make anyway. A
    model that sows nothing gets ``nxt`` as it is."""
    leaves = jax.tree_util.tree_flatten_with_path(stats)[0]
    if not leaves:
        return nxt

    def total(name):
        return sum(jnp.sum(jnp.where(real, leaf, 0)) for path, leaf in leaves
                   if name in jax.tree_util.keystr(path))

    return jnp.concatenate([nxt, jnp.stack(
        [total("moe_local"), total("moe_total")]).astype(nxt.dtype)])


class ServingEngine:
    """Slot-pool KV-cache decode engine (mechanism only — admission policy,
    EOS retirement, and per-request bookkeeping live in
    :class:`~chainermn_tpu.serving.scheduler.FCFSScheduler`).

    Parameters
    ----------
    model : TransformerLM
        Built for inference: ``sequence_axis=None``; MoE via
        ``moe_impl='gshard'``; ``tensor_axis`` set requires ``comm``.
    params : pytree
        Model parameters (the engine never mutates them).
    n_slots : int
        Cache slots == max concurrently-decoding requests. The decode
        program's batch dimension; fixed at construction.
    prefill_len : int, optional
        Maximum admitted prompt length (== the largest bucket). With the
        default single-bucket ladder every prompt is right-padded to this
        length, the PR-1 behavior; padding rows write K/V the causal mask
        hides until decode overwrites them (module docstring).
    prefill_buckets : sequence of int, optional
        Ascending ladder of padded prompt(-suffix) lengths, one compiled
        prefill program each; an admission runs the smallest bucket
        covering it. Default ``(prefill_len,)``. When both are given,
        ``max(prefill_buckets)`` must equal ``prefill_len``.
    prefill_batch : int
        Rows at the smallest bucket: a program holds at most
        ``prefill_batch x prefill_buckets[0]`` padded tokens, so bucket
        ``b``'s program has :meth:`prefill_rows` ``= max(1, prefill_batch
        * prefill_buckets[0] // b)`` rows, and up to that many requests
        admit per device call (rows beyond the group ride along masked).
        With one bucket it is that program's batch dimension. Clamped to
        ``n_slots``. Default 1 (the PR-1 shape).
    prefix_cache_blocks / prefix_block_size : int
        ``prefix_cache_blocks > 0`` enables ref-counted prefix KV reuse: a
        device block store of that many ``prefix_block_size``-token blocks
        plus a host trie (:class:`PrefixCacheIndex`). On admission the
        longest cached prefix is copied slot-locally (compiled-once fetch
        program) and only the suffix prefills; after admission the
        prompt's full blocks are inserted back (compiled-once insert
        program). 0 disables (default).
    prefix_min_insert_blocks : int
        Cost/benefit gate on inserts: skip caching prompts contributing
        fewer than this many new full blocks (an insert is a device copy;
        a unique ragged tail is never re-hit). Default 1 (cache all).
    paged : bool
        Unify decode KV onto ONE shared block store with per-slot block
        tables (module docstring): concurrency bound by resident tokens
        instead of ``n_slots x cache_len``, prefix reuse by sharing
        instead of copying. The prefix trie always runs on the shared
        pool in this mode — ``prefix_cache_blocks`` must stay 0 (its
        legacy store would duplicate the unified one). Default False:
        the dense PR-1..5 path, byte-for-byte.
    kv_blocks : int, optional
        Paged mode: total store blocks, INCLUDING the reserved scratch
        block (id 0 — the write target for inactive rows and
        unallocated table entries). Default ``n_slots *
        ceil(cache_len/kv_block_size) + 1``, the dense-equivalent
        capacity; set smaller to oversubscribe slots against the real
        (short-request) working set — block-budget admission plus
        preemption keep it safe.
    kv_window_blocks : int, optional
        Paged mode, models with window layers only: total blocks of the
        window layers' store (their own pool, scratch block included).
        Default ``n_slots * ceil((window + kv_block_size) /
        kv_block_size) + 1``: every slot's whole ring.
    kv_block_size : int
        Paged mode: tokens per block. Smaller blocks waste fewer rows on
        ragged tails but widen the tables. Default 16.
    kv_quant : {'none', 'int8'}
        Paged mode: quantize resident blocks to int8 with per-row
        per-head scales (~2x less KV memory; dequantized inside the
        attention gather — a small, tested perturbation of logits, NOT
        bit-parity with the f32/bf16 path). Default 'none'.
    cache_len : int, optional
        Per-slot KV capacity (prompt + generated); defaults to
        ``model.max_len``. A request needs ``len(prompt) + max_new <=
        cache_len``.
    speculative : SpeculativeConfig, optional
        Paged + greedy only: draft ``k`` tokens per slot per round with
        the configured drafter (prompt-lookup or a small draft model —
        see :mod:`chainermn_tpu.serving.speculative`) and verify the
        whole window in ONE target-model dispatch, committing 1..k+1
        tokens. Token-for-token identical to the non-speculative greedy
        stream; block-budget admission reserves ``ceil(k/block_size)``
        extra headroom per slot for the window's worst-case writes.
        The scheduler drives this through :meth:`decode_round`.
    decode_window : int
        Non-speculative dispatch amortization: ``decode_window=n > 1``
        compiles the decode step as a ``lax.fori_loop`` over ``n``
        tokens (ONE dispatch commits ``n`` tokens per active slot —
        see :meth:`decode_steps`). Mutually exclusive with
        ``speculative`` (the verify window already amortizes dispatch,
        adaptively). Default 1, the per-token legacy program.
    temperature / top_k / top_p : sampler configuration shared by every
        request (the compiled programs bake it in, exactly like
        ``generate()``'s lru-cache key).
    comm : communicator, optional
        Required iff ``model.tensor_axis`` is set: all programs then run
        inside its ``shard_map`` with head-sharded caches and block store.
    watchdog : Watchdog or float, optional
        Hang detection around every device program call (prefill, decode,
        prefix copies). Default **off**. A float builds a
        ``Watchdog(timeout=...)`` (abort mode — die loudly, the
        ``global_except_hook`` stance); pass a configured ``Watchdog``
        (e.g. ``on_timeout='warn'``) for report-only. On fire it dumps
        thread stacks + the monitor flight recorder (last events incl.
        slot admits/retires, per-device memory), so a wedged collective
        in serving aborts with evidence instead of hanging the client
        thread forever.
    """

    def __init__(self, model, params, *, n_slots: int,
                 prefill_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 prefill_batch: int = 1,
                 prefix_cache_blocks: int = 0,
                 prefix_block_size: int = 16,
                 prefix_min_insert_blocks: int = 1,
                 paged: bool = False,
                 kv_blocks: Optional[int] = None,
                 kv_window_blocks: Optional[int] = None,
                 kv_block_size: int = 16,
                 kv_quant: str = "none",
                 paged_kernel: bool = False,
                 speculative: Optional[SpeculativeConfig] = None,
                 decode_window: int = 1,
                 cache_len: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, comm=None,
                 watchdog: Optional[Union[Watchdog, float]] = None):
        if model.sequence_axis is not None:
            raise ValueError(
                "serving decode does not support sequence-sharded models: "
                "rebuild with sequence_axis=None for inference"
            )
        if getattr(model, "moe_experts", 0) and model.moe_impl != "gshard":
            raise ValueError(
                "serving decode supports MoE only via moe_impl='gshard' — "
                "rebuild the model with moe_impl='gshard' (same params)"
            )
        # what state the model keeps, by layer kind; what follows holds for
        # one kind that keeps every token. Some kinds cannot continue a
        # prompt at an offset (a window layer's ring, a recurrent state
        # kept a row a slot), and a model that has one is refused the
        # options that are not built for it yet; ``_whole_prompts`` then
        # names the kind
        spec = model.kv_cache_spec()
        state_spec = [k for k in spec if isinstance(k, SlotStateKind)]
        spec = [k for k in spec if not isinstance(k, SlotStateKind)]
        if not spec:
            raise ValueError("the engine serves a model that keeps K and V "
                             "rows in at least one layer")
        self._whole_prompts = (
            "recurrent state" if state_spec else
            "window layers" if any(k.window is not None for k in spec)
            else "")
        if self._whole_prompts:
            for bad, what in (
                    (prefix_cache_blocks, "prefix reuse across requests "
                     "(prefix_cache_blocks): the trie indexes one pool"
                     + (", and a hit needs the state at the boundary"
                        if state_spec else "")),
                    (not paged, "paged=False: such layers live in a "
                     "store of their own"),
                    (speculative is not None, "speculative decoding: a "
                     "verify window continues a sequence at an offset"),
                    (decode_window != 1, "decode_window > 1"),
                    (model.tensor_axis is not None, "tensor_axis")):
                if bad:
                    raise ValueError(self._refusal(what))
        if kv_window_blocks is not None and not any(
                k.window is not None for k in spec):
            raise ValueError("kv_window_blocks sizes the window layers' "
                             "pool and this model has none")
        if model.tensor_axis is not None and comm is None:
            raise ValueError(
                "tensor-parallel serving needs comm= (the decode programs "
                "run inside the communicator's shard_map)"
            )
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        cache_len = cache_len or model.max_len
        if prefill_buckets is None:
            if prefill_len is None:
                raise ValueError("pass prefill_len or prefill_buckets")
            if not 0 < prefill_len <= cache_len:
                raise ValueError(
                    f"prefill_len must be in (0, cache_len={cache_len}], "
                    f"got {prefill_len}"
                )
            buckets = (int(prefill_len),)
        else:
            buckets = tuple(sorted({int(b) for b in prefill_buckets}))
            if not buckets:
                raise ValueError("prefill_buckets must be non-empty")
            if prefill_len is not None and int(prefill_len) != buckets[-1]:
                raise ValueError(
                    f"prefill_len {prefill_len} != max(prefill_buckets) "
                    f"{buckets[-1]} — the largest bucket IS the admission "
                    "length limit; pass one or make them agree"
                )
            prefill_len = buckets[-1]
        if not (0 < buckets[0] and buckets[-1] <= cache_len):
            raise ValueError(
                f"prefill buckets must be in (0, cache_len={cache_len}], "
                f"got {buckets}"
            )
        if not 0 < prefill_len <= cache_len:
            raise ValueError(
                f"prefill_len must be in (0, cache_len={cache_len}], got "
                f"{prefill_len}"
            )
        if cache_len > model.max_len:
            raise ValueError(
                f"cache_len {cache_len} exceeds model.max_len "
                f"{model.max_len}"
            )
        if prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {prefill_batch}")
        self.model = model
        self.params = params
        self.n_slots = int(n_slots)
        self.prefill_len = int(prefill_len)
        self.prefill_buckets = buckets
        self.prefill_batch = min(int(prefill_batch), self.n_slots)
        # a program's rows follow from its bucket: the token budget of the
        # smallest bucket's program, spread over longer rows
        self._prefill_rows = {
            b: max(1, self.prefill_batch * buckets[0] // b)
            for b in buckets}
        self.cache_len = int(cache_len)
        self._comm = comm
        self._sample = _sampler(float(temperature), int(top_k), float(top_p))
        self.decode_window = int(decode_window)
        if self.decode_window < 1:
            raise ValueError(
                f"decode_window must be >= 1, got {decode_window}")
        self._spec = speculative
        if speculative is not None:
            speculative.validate()
            if not paged:
                raise ValueError(
                    "speculative decode needs paged=True — the verify "
                    "window scatters through block tables")
            if float(temperature) != 0.0:
                raise ValueError(
                    "speculative decode is greedy-only (temperature=0): "
                    "the verify step recomputes argmax per position")
            if self.decode_window != 1:
                raise ValueError(
                    "speculative= and decode_window> 1 are mutually "
                    "exclusive — the verify window already amortizes "
                    "dispatch (adaptively, by accept length)")
        if watchdog is not None and not isinstance(watchdog, Watchdog):
            watchdog = Watchdog(timeout=float(watchdog))
        self.watchdog = watchdog
        self._events = get_event_log()
        labels = {"engine": "serving"}
        reg = get_registry()
        self._reg = reg
        self._c_prefills = {
            b: reg.counter("serving_prefills_total",
                           dict(labels, prefill_bucket=str(b)))
            for b in buckets
        }
        # serving_decode_steps_total is created AFTER paged parsing below:
        # in paged mode it carries the paged_kernel="on"/"off" label so
        # kernel ON-vs-OFF A/Bs fork the time series instead of mixing
        self._c_restarts = reg.counter("serving_engine_restarts_total",
                                       labels)
        self._c_appends = reg.counter("kv_block_appends_total", labels)
        # chunked prefill + KV migration (the disaggregation spine)
        self._c_chunks = reg.counter("prefill_chunks_total", labels)
        self._h_chunk_tokens = reg.histogram("chunk_tokens", labels)
        self._c_migrations = reg.counter("kv_migrations_total", labels)
        self._c_migrated_blocks = reg.counter("kv_migrated_blocks_total",
                                              labels)
        self._h_migration = reg.histogram("migration_seconds", labels,
                                          unit="s")
        # versioned weights (the deploy layer's hot-swap surface):
        # version 0 is the constructor's params; every successful
        # swap_params bumps it and moves the gauge
        self.weight_version = 0
        self._g_weight_version = reg.gauge("serving_weight_version", labels)
        self._g_weight_version.set(0)

        # paged mode: ONE shared block store (pool + trie on it), per-slot
        # block tables; the dense caches/prefix store are never built
        self.paged = bool(paged)
        self.kv_quant = str(kv_quant)
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8', got {kv_quant!r}")
        if not self.paged and self.kv_quant != "none":
            raise ValueError("kv_quant needs paged=True (the dense cache "
                             "regions are not quantized)")
        # fused Pallas paged-decode kernel (parallel/paged_kernel.py): an
        # OPT-IN replacement for the decode read side only — prefill and
        # every write stay XLA, and paged_kernel=False (the default) is
        # the byte-for-byte XLA trace. Unavailability degrades to the XLA
        # path with an event, never to a construction failure.
        self.paged_kernel = bool(paged_kernel)
        if self.paged_kernel and not self.paged:
            raise ValueError("paged_kernel=True needs paged=True (the "
                             "fused kernel reads the shared block store)")
        if self.paged_kernel:
            ok, why = kernel_supported()
            if not ok:
                self._events.emit("paged_kernel_fallback", reason=why)
                self.paged_kernel = False
        decode_labels = dict(labels)
        if self.paged:
            decode_labels["paged_kernel"] = (
                "on" if self.paged_kernel else "off")
        self._c_decode_steps = reg.counter("serving_decode_steps_total",
                                           decode_labels)
        self.peak_active = 0
        self._moe_counts = np.zeros(2, np.int64)
        self.prefix_cache: Optional[PrefixCacheIndex] = None
        if self.paged:
            if prefix_cache_blocks:
                raise ValueError(
                    "paged mode unifies decode KV and the prefix cache on "
                    "one shared block store — drop prefix_cache_blocks and "
                    "size the store with kv_blocks/kv_block_size"
                )
            if kv_block_size < 1:
                raise ValueError(
                    f"kv_block_size must be >= 1, got {kv_block_size}")
            self.kv_block_size = int(kv_block_size)
            # a pool, tables and a budget per kind of KV state; the first
            # kind's are what the single-kind paths below (chunked
            # prefill, migration, speculation) know as _pool, _tables, ...
            self._kv = [
                _KVKind(kind, kv_blocks if kind.window is None
                        else kv_window_blocks, self.kv_block_size,
                        self.n_slots, self.cache_len)
                for kind in spec]
            self.kv_blocks = self._kv[0].n_blocks
            # and for a kind that is a row a slot, only the account of it
            self._state = [_SlotState(kind, self.n_slots)
                           for kind in state_spec]
            self._state_layers = sum(len(k.layers) for k in state_spec)
            self._state_tokens = 0
            # the chunks a prefill program's whole-prompt form walks (the
            # rows' prompts) and skips (the rest of the bucket), a layer
            # at a time, for the kinds that run in chunks
            self._chunked = [(k.chunk, len(k.layers)) for k in state_spec
                             if k.chunk]
            self._prefill_chunks = np.zeros(2, np.int64)
            # the rows x layers a decode program runs through the kinds'
            # step, in a kernel and in XLA: every slot's row, held or not
            self._decode_rows_step = np.zeros(2, np.int64)
            for k in state_spec:
                self._decode_rows_step[0 if k.decode_kernel else 1] += (
                    self.n_slots * len(k.layers))
            self._decode_rows = np.zeros(2, np.int64)
            # prefix reuse runs on the one pool of a model whose layers
            # are all of a kind; with window layers or a recurrent state
            # nothing is inserted or matched
            if not self._whole_prompts:
                self.prefix_cache = self._kv[0].index
            self._min_insert = max(1, int(prefix_min_insert_blocks))
            self._n_prog_blocks = self._n_max   # match cap for planning
            # multi-token rounds write up to _write_horizon rows past the
            # commit frontier (a verify window's k drafts, or a decode
            # window's n-1 extra steps); admission reserves the matching
            # extra block headroom so mid-round appends can't run dry
            self._write_horizon = (speculative.k if speculative is not None
                                   else self.decode_window - 1)
            self._spec_headroom = -(-self._write_horizon
                                    // self.kv_block_size)
            # in-progress chunked prefills: slot -> ChunkedPrefill. The
            # slot is NOT in free_slots but also NOT _active — decode
            # dispatches mask it out, and its all-scratch table row
            # routes ride-along writes into the scratch block until the
            # final chunk commits the real ids
            self._chunking: dict[int, ChunkedPrefill] = {}
        elif prefix_cache_blocks:
            if not 0 < prefix_block_size <= self.prefill_len:
                raise ValueError(
                    f"prefix_block_size must be in (0, prefill_len="
                    f"{self.prefill_len}], got {prefix_block_size}"
                )
            self.prefix_cache = PrefixCacheIndex(prefix_cache_blocks,
                                                 prefix_block_size)
            # admission cost/benefit knob: an insert is a device copy, so
            # skip prompts contributing fewer than this many NEW blocks
            # (shared-prefix traffic caches the shared part on first
            # sight either way; unique ragged tails are never re-hit)
            self._min_insert = max(1, int(prefix_min_insert_blocks))
            # both copy programs move this many whole blocks (static
            # shapes); junk trailing ids are identity/masked writes
            self._n_prog_blocks = max(1, self.prefill_len // prefix_block_size)

        if model.tensor_axis is not None:
            self._init_tp_caches(comm)
            self._build_tp_fns(comm)
        elif self.paged:
            self.caches = None          # the block store IS the cache
            self._store = self._place(self._init_paged_store())
            self._build_fns()
        else:
            self.caches = self._place(
                init_kv_caches(model, self.n_slots, self.cache_len))
            if self.prefix_cache is not None:
                self._store = self._place(self._init_store())
            self._build_fns()

        # host-side slot mirror: the scheduler reads/writes through the
        # occupy/release API; the decode program consumes these as [B]
        # device operands each step (tiny transfers, static shapes)
        self._token = np.zeros((self.n_slots,), np.int32)
        self._pos = np.zeros((self.n_slots,), np.int32)
        self._active = np.zeros((self.n_slots,), bool)
        self._keys = self._fresh_keys()
        self.free_slots = set(range(self.n_slots))
        self._warm = False
        # deferred trie inserts: (prompt, slot) pairs copied store-side by
        # flush_inserts() — off the TTFT-critical admission path, always
        # flushed before the donor slot can be reused (scheduler end-of-
        # step + the defensive flush at the next admission)
        self._pending_inserts: list[tuple[np.ndarray, int]] = []

        # recompile tracking: the zero-recompile invariant as live
        # telemetry (compile/recompile events + recompiles_total counter),
        # checked after every device call — not only in tests
        self._guard = RecompileGuard()
        for b, fn in self._prefill_fns.items():
            self._guard.watch(f"serving_prefill_{b}", fn)
        self._guard.watch("serving_decode", self._decode_fn)
        if self.migration_supported:
            for w in self._mig_buckets:
                self._guard.watch(f"serving_kv_gather_{w}",
                                  self._kv_gather_fns[w])
                self._guard.watch(f"serving_kv_scatter_{w}",
                                  self._kv_scatter_fns[w])
        if self.prefix_cache is not None and not self.paged:
            self._guard.watch("serving_prefix_insert", self._insert_fn)
        if self.decode_window > 1:
            self._guard.watch("serving_decode_window", self._window_fn)
        # speculative drafter + its accept accounting (cumulative for
        # spec_stats(); per-round for the scheduler's metrics drain)
        self._drafter = None
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._last_spec_window: Optional[tuple] = None
        # cost-attribution mirror of the window: {slot: (kd, a)} for the
        # last verify round (drafts that fit, drafts accepted) — NOT
        # popped with the window, the scheduler's ledger reads it right
        # after decode_round returns
        self._last_spec_slots: dict = {}
        if self._spec is not None:
            self._drafter = build_drafter(self._spec, self)
            self._guard.watch("serving_spec_verify", self._spec_fn)
            for name, fn in self._drafter.watched_fns().items():
                self._guard.watch(name, fn)

    def _fresh_keys(self):
        """Zeroed per-slot sampler keys. Under TP they are committed
        replicated on the mesh up front — the sharding a real admission's
        key writeback produces — so the decode program warmup-compiles on
        the SAME argument shardings it will see forever (sharding is part
        of the jit cache key; an uncommitted warmup key would cost one
        recompile on first traffic)."""
        keys = jnp.zeros((self.n_slots, 2), jnp.uint32)
        if self.model.tensor_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(
                keys, NamedSharding(self._comm.mesh, P()))
        return self._place(keys)

    def _place(self, tree):
        """Put engine-owned device state (KV store, caches, sampler keys)
        where the parameters are. A program's outputs take the placement
        of its committed inputs: with parameters committed to a device —
        a trainer's ``bcast_data``, a restored checkpoint, any
        ``device_put`` — state created uncommitted comes back from its
        first call committed, and every program then meets a second jit
        cache key (one recompile each) on the first traffic after
        :meth:`warmup`. Uncommitted parameters leave everything
        uncommitted; the tensor-parallel path places its own state."""
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        if (self.model.tensor_axis is not None
                or not getattr(leaf, "committed", False)
                or not leaf.sharding.is_fully_replicated):
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = leaf.sharding
        if isinstance(sh, NamedSharding):
            sh = NamedSharding(sh.mesh, P())    # fits any rank
        return jax.device_put(tree, sh)

    def _watched(self, label: str, **ctx):
        """Watchdog context for one device-program call (no-op when hang
        detection is off). ``ctx`` carries request/trace identity from
        the scheduler, so a fire names WHOSE work wedged — the
        flight-recorder dump then joins against exported traces."""
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.step(label, **ctx)

    @property
    def prefix_enabled(self) -> bool:
        return self.prefix_cache is not None

    def _refusal(self, what: str) -> str:
        return (f"not supported for a model with {self._whole_prompts}: "
                + what)

    def prefill_rows(self, bucket: int) -> int:
        """Rows of ``bucket``'s prefill program, the most requests one
        admission group of that bucket holds: ``prefill_batch`` at the
        smallest bucket, fewer as the rows get longer."""
        return self._prefill_rows[bucket]

    # the first kind's block state under the names the single-kind paths
    # (and the tests) know
    _pool = property(lambda self: self._kv[0].pool)
    _tables = property(lambda self: self._kv[0].tables)
    _slot_blocks = property(lambda self: self._kv[0].slot_blocks)
    _slot_reserved = property(lambda self: self._kv[0].reserved)
    _n_max = property(lambda self: self._kv[0].width)

    @property
    def migration_supported(self) -> bool:
        """KV block migration needs the paged store AND a single-device
        layout: under TP the rows live head-sharded across the mesh and
        the host-bounce gather/scatter pair is not built (documented
        limitation — export raises, the router decodes in place)."""
        return (self.paged and self.model.tensor_axis is None
                and not self._whole_prompts)

    # ------------------------------------------------------------------ #
    # program construction                                                #
    # ------------------------------------------------------------------ #

    def _prefill_body(self, bucket: int, vocab_gather=None):
        """Batched suffix-prefill trace for one bucket: gather each group
        row's slot out of the pooled cache, splice each row's cached
        prefix blocks in from the store (prefix cache on — the fetch is
        INSIDE this program: a hit costs zero extra device calls), run the
        padded suffixes at their per-row start positions in ONE model
        call, splice the updated slots back (inactive rows write back
        what was there), and sample each row's first token from its last
        REAL position. Rows without a match carry junk block ids; the
        garbage span they splice sits entirely under rows their own
        prefill overwrites or the causal mask hides."""
        model, sample = self.model, self._sample
        k = self.prefill_rows(bucket)
        prefix = self.prefix_cache is not None
        span = self._n_prog_blocks * self.prefix_cache.block_size \
            if prefix else 0

        def slot_sample(lg, key):
            nxt, key = sample(lg[None], key)
            return nxt[0], key

        def body(params, caches, tokens, slots, starts, last_idx, active,
                 keys, store=None, fetch_ids=None):
            with annotate("chainermn.prefill"):
                return body_inner(params, caches, tokens, slots, starts,
                                  last_idx, active, keys, store, fetch_ids)

        def body_inner(params, caches, tokens, slots, starts, last_idx,
                       active, keys, store, fetch_ids):
            slot_c = [
                {kk: jnp.take(c[kk], slots, axis=0) for kk in ("k", "v")}
                for c in caches
            ]
            if prefix:
                # per-row prefix splice: gather each row's matched blocks
                # and overwrite its gathered slot rows [0, span)
                for sc, st in zip(slot_c, store):
                    for kk in ("k", "v"):
                        rows = jnp.take(st[kk], fetch_ids.reshape(-1),
                                        axis=0)
                        rows = rows.reshape((k, span) + rows.shape[2:])
                        sc[kk] = jnp.concatenate(
                            [rows, sc[kk][:, span:]], axis=1)
            pos = starts[:, None] + jnp.arange(bucket)[None, :]
            # each row's logits at its last PROMPT token, not a padded
            # row: only that position goes through the head
            lg, slot_c = model.apply(params, tokens, pos, kv_caches=slot_c,
                                     logits_at=last_idx)
            if vocab_gather is not None:
                lg = vocab_gather(lg)
            nxt, keys = jax.vmap(slot_sample)(lg, keys)
            nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
            # write back per row; inactive rows re-write the pool's current
            # content (identity), so rows beyond the group never corrupt a
            # slot even if their (junk) slot index collides with a real one
            out = []
            for c, s in zip(caches, slot_c):
                buf = dict(c)
                for kk in ("k", "v"):
                    arr = buf[kk]
                    for i in range(k):
                        cur = lax.dynamic_slice_in_dim(arr, slots[i], 1, 0)
                        new = jnp.where(active[i], s[kk][i][None], cur)
                        arr = lax.dynamic_update_slice_in_dim(
                            arr, new, slots[i], 0)
                    buf[kk] = arr
                out.append(buf)
            return out, nxt, keys

        return body

    def _decode_body(self, vocab_gather=None):
        """Shared decode trace: one token for EVERY slot, per-slot
        positions, per-slot sampler keys (each slot draws exactly like a
        B=1 ``generate()`` so batching never perturbs a request)."""
        model, sample = self.model, self._sample

        def slot_sample(lg, key):
            nxt, key = sample(lg[None], key)
            return nxt[0], key

        def body(params, caches, tokens, pos, active, keys):
            with annotate("chainermn.decode"):
                return body_inner(params, caches, tokens, pos, active, keys)

        def body_inner(params, caches, tokens, pos, active, keys):
            lg, caches = model.apply(params, tokens[:, None], pos[:, None],
                                     kv_caches=caches)
            lg = lg[:, 0]
            if vocab_gather is not None:
                lg = vocab_gather(lg)
            nxt, keys = jax.vmap(slot_sample)(lg, keys)
            # free/retired slots ride along masked — shapes never change
            nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
            return caches, nxt, keys

        return body

    def _paged_prefill_body(self, bucket: int, vocab_gather=None):
        """Paged suffix-prefill trace for one bucket: each group row
        writes its padded suffix THROUGH its block-table row into the
        shared store (scatter), attends its gathered table span, and
        samples its first token from its last REAL position — all inside
        the model's ``[B, T]`` position path via
        ``paged_update_cache_and_attend``. No slot gather/scatter and no
        prefix splice: a cached prefix is just table entries, and
        inactive rows carry all-scratch tables so their writes land in
        the scratch block instead of anyone's KV."""
        model, sample = self.model, self._sample

        def slot_sample(lg, key):
            nxt, key = sample(lg[None], key)
            return nxt[0], key

        def body(params, store, table, tokens, starts, last_idx, active,
                 keys):
            with annotate("chainermn.prefill"):
                # a window layer writes a row's real tokens only, the
                # last ring of them (padding would wrap onto live blocks),
                # and a state layer's state stops at the last of them
                valid = (jnp.where(active, last_idx + 1, 0)
                         if self._whole_prompts else None)
                caches = self._layer_caches(store, table, valid=valid,
                                            real=valid)
                pos = starts[:, None] + jnp.arange(bucket)[None, :]
                (lg, new_store), stats = model.apply(
                    params, tokens, pos, kv_caches=caches,
                    logits_at=last_idx, mutable=[_STATS])
                if vocab_gather is not None:
                    lg = vocab_gather(lg)
                nxt, keys = jax.vmap(slot_sample)(lg, keys)
                nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
                real = active[:, None] & (
                    jnp.arange(bucket)[None, :] <= last_idx[:, None])
                return new_store, _with_moe_counts(nxt, stats, real), keys

        return body

    def _paged_decode_body(self, vocab_gather=None):
        """Paged decode trace: one token for EVERY slot through the
        ``[n_slots, max_blocks]`` table — per-slot positions and sampler
        keys exactly like the dense body; free/retired slots carry
        all-scratch table rows, so their masked ride-along writes land in
        the scratch block. ``paged_kernel=True`` rides into the cache
        dicts as the static ``use_kernel`` flag — a different trace, not
        a different operand; with the flag off this body is byte-for-byte
        the pre-kernel trace (``**{}`` adds nothing)."""
        model, sample = self.model, self._sample
        extra = {"use_kernel": True} if self.paged_kernel else {}

        def slot_sample(lg, key):
            nxt, key = sample(lg[None], key)
            return nxt[0], key

        def body(params, store, table, tokens, pos, active, keys):
            with annotate("chainermn.decode"):
                caches = self._layer_caches(
                    store, table, real=active.astype(jnp.int32), **extra)
                (lg, new_store), stats = model.apply(
                    params, tokens[:, None], pos[:, None], kv_caches=caches,
                    mutable=[_STATS])
                lg = lg[:, 0]
                if vocab_gather is not None:
                    lg = vocab_gather(lg)
                nxt, keys = jax.vmap(slot_sample)(lg, keys)
                nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
                return (new_store,
                        _with_moe_counts(nxt, stats, active[:, None]), keys)

        return body

    def _spec_verify_body(self, vocab_gather=None):
        """Speculative verify trace: score the ``k+1``-token window
        ``[t0, d1..dk]`` per slot at positions ``[p..p+k]`` in ONE model
        call, returning every position's greedy (argmax) choice. The
        host commits the longest draft prefix matching those choices
        plus one correction token. ``valid`` caps each slot's K/V
        writes (rows past it land in the scratch block — see
        ``paged_update_cache_and_attend``): slots near ``cache_len``
        would otherwise clamp their table lookup onto a LIVE row. The
        rejected rows this window writes are garbage only until the
        next window: its span always covers them, and every row is
        rewritten before any query attends it."""
        model = self.model
        window = self._spec.k + 1
        extra = {"use_kernel": True} if self.paged_kernel else {}

        def body(params, store, table, tokens, pos, valid, active):
            with annotate("chainermn.spec_verify"):
                caches = self._layer_caches(store, table, valid=valid,
                                            **extra)
                posm = pos[:, None] + jnp.arange(window)[None, :]
                lg, new_store = model.apply(params, tokens, posm,
                                            kv_caches=caches)
                if vocab_gather is not None:
                    lg = vocab_gather(lg)
                g = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                g = jnp.where(active[:, None], g, jnp.zeros_like(g))
                return new_store, g

        return body

    def _paged_decode_steps_body(self, n: int, vocab_gather=None):
        """Multi-token paged decode: ``n`` chained decode steps inside a
        ``lax.fori_loop`` — ONE dispatch commits ``n`` tokens per active
        slot (the non-speculative dispatch-amortization program; PERF.md
        "Dispatch amortization"). Each iteration samples through the
        same per-slot key splits as ``n`` separate decode steps, so the
        token stream is identical to the per-token program. ``valid``
        masks each iteration's single write for slots that crossed
        ``cache_len`` mid-window (their later rows are discarded by the
        scheduler's retirement anyway)."""
        model, sample = self.model, self._sample
        cache_len = self.cache_len
        extra = {"use_kernel": True} if self.paged_kernel else {}

        def slot_sample(lg, key):
            nxt, key = sample(lg[None], key)
            return nxt[0], key

        def body(params, store, table, tokens, pos, active, keys):
            with annotate("chainermn.decode"):
                def step(i, carry):
                    store, tok, keys, out = carry
                    p = pos + i
                    valid = (active & (p < cache_len)).astype(jnp.int32)
                    caches = self._layer_caches(store, table, valid=valid,
                                                **extra)
                    lg, store = model.apply(params, tok[:, None],
                                            p[:, None], kv_caches=caches)
                    lg = lg[:, 0]
                    if vocab_gather is not None:
                        lg = vocab_gather(lg)
                    nxt, keys = jax.vmap(slot_sample)(lg, keys)
                    nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
                    return store, nxt, keys, out.at[:, i].set(nxt)

                out0 = jnp.zeros((tokens.shape[0], n), jnp.int32)
                store, _, keys, out = lax.fori_loop(
                    0, n, step, (store, tokens, keys, out0))
                return store, out, keys

        return body

    def _decode_steps_body(self, n: int, vocab_gather=None):
        """Dense twin of :meth:`_paged_decode_steps_body`: the same
        fori_loop over the pooled per-slot cache regions. Overshooting
        writes clamp to a slot's own last row — stale-rows masking
        covers them exactly like warmup garbage."""
        model, sample = self.model, self._sample

        def slot_sample(lg, key):
            nxt, key = sample(lg[None], key)
            return nxt[0], key

        def body(params, caches, tokens, pos, active, keys):
            with annotate("chainermn.decode"):
                def step(i, carry):
                    caches, tok, keys, out = carry
                    lg, caches = model.apply(params, tok[:, None],
                                             (pos + i)[:, None],
                                             kv_caches=caches)
                    lg = lg[:, 0]
                    if vocab_gather is not None:
                        lg = vocab_gather(lg)
                    nxt, keys = jax.vmap(slot_sample)(lg, keys)
                    nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
                    return caches, nxt, keys, out.at[:, i].set(nxt)

                out0 = jnp.zeros((tokens.shape[0], n), jnp.int32)
                caches, _, keys, out = lax.fori_loop(
                    0, n, step, (caches, tokens, keys, out0))
                return caches, out, keys

        return body

    def _init_paged_store(self, local_heads: Optional[int] = None):
        counts = {k.name: k.n_blocks for k in self._kv}
        counts.update((st.name, st.rows) for st in self._state)
        return init_paged_kv_caches(
            self.model,
            tuple(counts[k.name] for k in self.model.kv_cache_spec()),
            self.kv_block_size, local_heads=local_heads,
            quant=self.kv_quant)

    def _layer_caches(self, store, tables, valid=None, real=None, **extra):
        """Inside a program: the cache dict of every layer, its store
        beside its kind's table. ``tables`` is what :meth:`_table_args`
        builds, one dict a kind; ``valid`` goes to every layer where the program caps a
        row's writes for all of them, and otherwise (a prefill) to window
        layers alone; ``extra`` are static entries for all layers that keep
        K and V. A layer whose state is a row a slot gets ``real`` as its
        ``valid``: the tokens of each row that advance its state."""
        caches = [None] * len(store)
        for kv, ops in zip(self._kv, tables):
            static = dict(extra)
            if kv.window is not None:
                static["window"] = kv.window
            if valid is not None and (kv.window is not None
                                      or not self._whole_prompts):
                static["valid"] = valid
            for i in kv.kind.layers:
                caches[i] = dict(store[i], **ops, **static)
        for st, ops in zip(self._state, tables[len(self._kv):]):
            for i in st.kind.layers:
                caches[i] = dict(store[i], **ops, valid=real)
        return caches

    def _table_args(self, rows: Optional[int] = None) -> tuple:
        """The per-kind table operand of a program: the decode step's
        (every slot's row), or all-scratch tables of ``rows`` rows for a
        prefill to fill in. A kind that is a row a slot has no table: a
        decode step's rows are the slots in their order, and a prefill
        takes ``slots``, the store row of each of its rows (the scratch
        row until filled in)."""
        out = []
        for kv in self._kv:
            out.append({"table": jnp.asarray(kv.tables) if rows is None
                        else np.zeros((rows, kv.width), np.int32)})
        for st in self._state:
            out.append({} if rows is None else
                       {"slots": np.full((rows,), st.rows - 1, np.int32)})
        return tuple(out)

    def _insert_body(self):
        """Prefix insert: copy each NEW full block's rows out of the donor
        slot into its allocated store block. Sequential per-block updates;
        blocks past ``n_used`` re-write the store's current content
        (identity), so junk trailing ids never clobber a live block."""
        bs = self.prefix_cache.block_size
        n_prog = self._n_prog_blocks

        def body(store, caches, slot, block_ids, row_starts, n_used):
            with annotate("chainermn.prefix_insert"):
                out = []
                for st, c in zip(store, caches):
                    buf = dict(st)
                    for kk in ("k", "v"):
                        arr = buf[kk]
                        h, dh = c[kk].shape[2], c[kk].shape[3]
                        for j in range(n_prog):
                            blk = lax.dynamic_slice(
                                c[kk], (slot, row_starts[j], 0, 0),
                                (1, bs, h, dh))[0]
                            cur = lax.dynamic_slice_in_dim(
                                arr, block_ids[j], 1, 0)[0]
                            new = jnp.where(j < n_used, blk, cur)
                            arr = lax.dynamic_update_slice_in_dim(
                                arr, new[None], block_ids[j], 0)
                        buf[kk] = arr
                    out.append(buf)
                return out

        return body

    def _init_store(self, local_heads: Optional[int] = None):
        pc = self.prefix_cache
        h = local_heads or self.model.n_heads
        dh = self.model.d_model // self.model.n_heads
        z = lambda: jnp.zeros((pc.n_blocks, pc.block_size, h, dh),
                              self.model.compute_dtype)
        return [{"k": z(), "v": z()} for _ in range(self.model.n_layers)]

    def _kv_gather_body(self):
        """Migration read side: pull one bucket's worth of block rows
        (every array in each layer dict — int8 rows AND their scales move
        as stored, no dequant round-trip) out of the store by id, in ONE
        dispatch. The block-id operand is data, not a trace constant, so
        each warmup-bucketed width compiles exactly once and covers every
        block list of that size — the same scalar-operand trick as the
        paged decode path. Junk trailing ids gather scratch content the
        importer's ``n_used`` mask discards. Compiled WITHOUT donation:
        export must leave the source store intact so a failed handover
        can keep decoding in place."""
        def body(store, ids):
            with annotate("chainermn.kv_gather"):
                return [{kk: jnp.take(layer[kk], ids, axis=0)
                         for kk in layer} for layer in store]

        return body

    def _kv_scatter_body(self, width: int):
        """Migration write side: land ``n_used`` gathered block rows into
        freshly allocated ids of THIS store (donated — the store is
        consumed and returned like every other program), one compiled
        program per warmup bucket ``width``. Rows past ``n_used`` carry
        the scratch id 0 and re-write scratch's current content
        (identity), so each bucket's program covers every migration size
        it pads to and duplicate padding ids stay deterministic."""
        def body(store, ids, rows, n_used):
            with annotate("chainermn.kv_scatter"):
                valid = jnp.arange(width) < n_used
                out = []
                for layer, lrows in zip(store, rows):
                    buf = dict(layer)
                    for kk in layer:
                        cur = jnp.take(buf[kk], ids, axis=0)
                        mask = valid.reshape(
                            (-1,) + (1,) * (cur.ndim - 1))
                        buf[kk] = buf[kk].at[ids].set(
                            jnp.where(mask, lrows[kk], cur))
                    out.append(buf)
                return out

        return body

    def _migration_bucket_widths(self) -> tuple:
        """Warmup bucket widths for the fused migration transfer: powers
        of two up to ``n_max`` plus ``n_max`` itself, always including 1
        (the per-block reference path rides the width-1 program). A
        transfer pads its block list to the smallest covering bucket —
        at most 2x the live blocks move, and no block count ever
        compiles a new program."""
        widths = {1, self._n_max}
        w = 2
        while w < self._n_max:
            widths.add(w)
            w *= 2
        return tuple(sorted(widths))

    def _mig_bucket(self, n: int) -> int:
        """Smallest warmup bucket covering ``n`` blocks."""
        for w in self._mig_buckets:
            if w >= n:
                return w
        raise RuntimeError(
            f"{n} blocks exceed the largest migration bucket "
            f"{self._mig_buckets[-1]}")

    def _build_fns(self):
        if self.paged:
            self._prefill_fns = {
                b: jax.jit(self._paged_prefill_body(b), donate_argnums=(1,))
                for b in self.prefill_buckets
            }
            self._decode_fn = jax.jit(self._paged_decode_body(),
                                      donate_argnums=(1,))
            self._mig_buckets = self._migration_bucket_widths()
            self._kv_gather_fns = {
                w: jax.jit(self._kv_gather_body())
                for w in self._mig_buckets
            }
            self._kv_scatter_fns = {
                w: jax.jit(self._kv_scatter_body(w), donate_argnums=(0,))
                for w in self._mig_buckets
            }
            if self._spec is not None:
                self._spec_fn = jax.jit(self._spec_verify_body(),
                                        donate_argnums=(1,))
            if self.decode_window > 1:
                self._window_fn = jax.jit(
                    self._paged_decode_steps_body(self.decode_window),
                    donate_argnums=(1,))
            return
        self._prefill_fns = {
            b: jax.jit(self._prefill_body(b), donate_argnums=(1,))
            for b in self.prefill_buckets
        }
        self._decode_fn = jax.jit(self._decode_body(), donate_argnums=(1,))
        if self.decode_window > 1:
            self._window_fn = jax.jit(
                self._decode_steps_body(self.decode_window),
                donate_argnums=(1,))
        if self.prefix_cache is not None:
            self._insert_fn = jax.jit(self._insert_body(),
                                      donate_argnums=(0,))

    def _init_tp_caches(self, comm):
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = self.model.tensor_axis
        n_tp = comm.mesh.shape[axis]
        if self.model.n_heads % n_tp:
            raise ValueError(
                f"n_heads {self.model.n_heads} not divisible by "
                f"tensor-axis size {n_tp}"
            )
        shard = NamedSharding(comm.mesh, P(None, None, axis))
        if self.paged:
            # the store's head axis (2) shards like the dense caches', and
            # the tiny tables stay replicated. A rank's scale arrays are
            # [N, 1, W] of ITS heads (column t * H_local + h), so the same
            # spec splits them, and the whole array is the ranks' side by
            # side along the columns: made so here (all zeros), and only
            # ever read inside shard_map
            self.caches = None
            local = self._init_paged_store(self.model.n_heads // n_tp)
            self._store = jax.device_put(jax.tree.map(
                lambda x: jnp.concatenate([x] * n_tp, axis=2), local), shard)
            return
        self.caches = jax.device_put(
            init_kv_caches(self.model, self.n_slots, self.cache_len), shard)
        if self.prefix_cache is not None:
            # full-head store buffers; device_put splits the head axis
            # over the mesh exactly like the pooled caches
            self._store = jax.device_put(self._init_store(), shard)

    def _build_tp_fns(self, comm):
        from jax.sharding import PartitionSpec as P

        axis = self.model.tensor_axis
        gather = None
        if self.model.vocab_parallel_head:
            def gather(lg):
                return lax.all_gather(lg, axis, axis=-1, tiled=True)

        if self.paged:
            layer_spec = {"k": P(None, None, axis), "v": P(None, None, axis)}
            if self.kv_quant == "int8":
                layer_spec.update(k_scale=P(None, None, axis),
                                  v_scale=P(None, None, axis))
            store_spec = [dict(layer_spec)
                          for _ in range(self.model.n_layers)]
            self._prefill_fns = {
                b: jax.jit(comm.shard_map(
                    self._paged_prefill_body(b, gather),
                    in_specs=(P(), store_spec, P(), P(), P(), P(), P(),
                              P()),
                    out_specs=(store_spec, P(), P()),
                    check_vma=False,
                ), donate_argnums=(1,))
                for b in self.prefill_buckets
            }
            self._decode_fn = jax.jit(comm.shard_map(
                self._paged_decode_body(gather),
                in_specs=(P(), store_spec, P(), P(), P(), P(), P()),
                out_specs=(store_spec, P(), P()),
                check_vma=False,
            ), donate_argnums=(1,))
            if self._spec is not None:
                self._spec_fn = jax.jit(comm.shard_map(
                    self._spec_verify_body(gather),
                    in_specs=(P(), store_spec, P(), P(), P(), P(), P()),
                    out_specs=(store_spec, P()),
                    check_vma=False,
                ), donate_argnums=(1,))
            if self.decode_window > 1:
                self._window_fn = jax.jit(comm.shard_map(
                    self._paged_decode_steps_body(self.decode_window,
                                                  gather),
                    in_specs=(P(), store_spec, P(), P(), P(), P(), P()),
                    out_specs=(store_spec, P(), P()),
                    check_vma=False,
                ), donate_argnums=(1,))
            return

        cache_spec = [{"k": P(None, None, axis), "v": P(None, None, axis)}
                      for _ in range(self.model.n_layers)]
        prefill_specs = (P(), cache_spec, P(), P(), P(), P(), P(), P())
        if self.prefix_cache is not None:
            prefill_specs = prefill_specs + (cache_spec, P())
        self._prefill_fns = {
            b: jax.jit(comm.shard_map(
                self._prefill_body(b, gather),
                in_specs=prefill_specs,
                out_specs=(cache_spec, P(), P()),
                check_vma=False,
            ), donate_argnums=(1,))
            for b in self.prefill_buckets
        }
        self._decode_fn = jax.jit(comm.shard_map(
            self._decode_body(gather),
            in_specs=(P(), cache_spec, P(), P(), P(), P()),
            out_specs=(cache_spec, P(), P()),
            check_vma=False,
        ), donate_argnums=(1,))
        if self.decode_window > 1:
            self._window_fn = jax.jit(comm.shard_map(
                self._decode_steps_body(self.decode_window, gather),
                in_specs=(P(), cache_spec, P(), P(), P(), P()),
                out_specs=(cache_spec, P(), P()),
                check_vma=False,
            ), donate_argnums=(1,))
        if self.prefix_cache is not None:
            self._insert_fn = jax.jit(comm.shard_map(
                self._insert_body(),
                in_specs=(cache_spec, cache_spec, P(), P(), P(), P()),
                out_specs=cache_spec,
                check_vma=False,
            ), donate_argnums=(0,))

    # ------------------------------------------------------------------ #
    # admission planning (host side, cheap)                               #
    # ------------------------------------------------------------------ #

    def bucket_for(self, suffix_len: int, start: int = 0) -> Optional[int]:
        """Smallest bucket covering a ``suffix_len``-token prefill that
        starts at row ``start`` and must stay inside ``cache_len``;
        ``None`` when no bucket fits."""
        for b in self.prefill_buckets:
            if b >= suffix_len and start + b <= self.cache_len:
                return b
        return None

    def plan_admission(self, prompt, rng=None,
                       max_new: int = 1) -> AdmitPlan:
        """Decide how a prompt admits: match (and pin) the longest cached
        prefix that still leaves a bucket fitting inside the slot, and
        pick that bucket. Pure host work — no device call. The caller owns
        the plan: feed it to :meth:`admit_batch` or return the pin with
        :meth:`cancel_plan`. ``max_new`` is the request's token budget —
        paged admission reserves its worst-case growth blocks from it."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.validate_request(len(prompt), max_new)
        match = None
        if self.prefix_cache is not None:
            max_blocks = self._n_prog_blocks
            while True:
                match = (self.prefix_cache.match(prompt, max_blocks)
                         if max_blocks > 0 else None)
                if match is None:
                    break
                if self.bucket_for(len(prompt) - match.length,
                                   match.length) is not None:
                    break
                # a max-length match can leave no room for a bucket inside
                # cache_len — shrink and retry (rare: near-capacity slots)
                max_blocks = len(match.nodes) - 1
                self.prefix_cache.release(match)
        start = match.length if match is not None else 0
        bucket = self.bucket_for(len(prompt) - start, start)
        assert bucket is not None  # start=0 always fits (validate_request)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return AdmitPlan(prompt=prompt, rng=rng, match=match, start=start,
                         bucket=bucket, max_new=int(max_new))

    def cancel_plan(self, plan: AdmitPlan) -> None:
        """Discard an unused plan, unpinning its prefix match."""
        if plan.match is not None and self.prefix_cache is not None:
            self.prefix_cache.release(plan.match)

    # ------------------------------------------------------------------ #
    # slot API (host side)                                                #
    # ------------------------------------------------------------------ #

    @property
    def active_slots(self) -> int:
        return int(self._active.sum())

    def validate_request(self, prompt_len: int, max_new_tokens: int) -> None:
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if prompt_len > self.prefill_len:
            raise ValueError(
                f"prompt of {prompt_len} tokens exceeds prefill_len="
                f"{self.prefill_len}"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt_len + max_new_tokens > self.cache_len:
            raise ValueError(
                f"{prompt_len} prompt + {max_new_tokens} new tokens exceed "
                f"cache_len={self.cache_len}"
            )
        if self.paged:
            need = self.blocks_needed(prompt_len, max_new_tokens)
            for kv, n in zip(self._kv, need):
                if n > kv.pool.capacity:
                    raise ValueError(
                        f"request needs {n} KV blocks worst-case but the "
                        f"{kv.name} pool holds {kv.pool.capacity} — raise "
                        "kv_blocks or shrink the request"
                    )

    def warmup(self) -> None:
        """Compile every device program once, on dummy no-op inputs (all
        rows inactive — semantically identity; the garbage K/V rows they
        write are covered by the stale-rows masking argument). After this,
        NOTHING recompiles: the zero-recompile invariant holds across
        every bucket, the decode step, and both prefix-copy programs —
        asserted by tests and carried live by the ``RecompileGuard``."""
        if self._warm:
            return
        if self.active_slots:
            raise RuntimeError("warmup needs an idle engine")
        if self.paged:
            # all-scratch tables: every warmup write lands in the scratch
            # block, no allocation and no real KV touched
            for b in self.prefill_buckets:
                k = self.prefill_rows(b)
                zeros_i = jnp.zeros((k,), jnp.int32)
                with self._watched(f"serving warmup prefill[{b}]"):
                    self._store, _, _ = self._prefill_fns[b](
                        self.params, self._store,
                        self._table_args(rows=k),
                        jnp.zeros((k, b), jnp.int32), zeros_i, zeros_i,
                        jnp.zeros((k,), bool),
                        jnp.zeros((k, 2), jnp.uint32))
            with self._watched("serving warmup decode"):
                self._store, _, _ = self._decode_fn(*self._decode_args())
            if self.migration_supported:
                # all-scratch ids + n_used=0 at EVERY bucket width: the
                # gather reads scratch, the scatter re-writes scratch's
                # own content — one compile per bucket covers every
                # future migration size that pads to it
                for w in self._mig_buckets:
                    mig_ids = jnp.zeros((w,), jnp.int32)
                    with self._watched(f"serving warmup kv_gather[{w}]"):
                        rows = self._kv_gather_fns[w](self._store, mig_ids)
                    with self._watched(f"serving warmup kv_scatter[{w}]"):
                        self._store = self._kv_scatter_fns[w](
                            self._store, mig_ids, rows, jnp.int32(0))
            if self.decode_window > 1:
                with self._watched("serving warmup decode_window"):
                    self._store, _, _ = self._window_fn(
                        *self._decode_args())
            if self._spec is not None:
                # all rows inactive + valid=0: every verify-window write
                # lands in the scratch block — the one compile covers
                # EVERY accept length (accept is host-side bookkeeping;
                # the program's shapes never depend on it)
                with self._watched("serving warmup spec_verify"):
                    self._store, _ = self._spec_fn(
                        self.params, self._store, self._table_args(),
                        jnp.zeros((self.n_slots, self._spec.k + 1),
                                  jnp.int32),
                        jnp.asarray(self._pos),
                        jnp.zeros((self.n_slots,), jnp.int32),
                        jnp.asarray(self._active))
                self._drafter.warmup()
        else:
            for b in self.prefill_buckets:
                k = self.prefill_rows(b)
                zeros_i = jnp.zeros((k,), jnp.int32)
                extra = ()
                if self.prefix_cache is not None:
                    extra = (self._store, jnp.zeros(
                        (k, self._n_prog_blocks), jnp.int32))
                with self._watched(f"serving warmup prefill[{b}]"):
                    self.caches, _, _ = self._prefill_fns[b](
                        self.params, self.caches,
                        jnp.zeros((k, b), jnp.int32), zeros_i, zeros_i,
                        zeros_i, jnp.zeros((k,), bool),
                        jnp.zeros((k, 2), jnp.uint32), *extra)
            with self._watched("serving warmup decode"):
                self.caches, _, _ = self._decode_fn(*self._decode_args())
            if self.prefix_cache is not None:
                ids = jnp.zeros((self._n_prog_blocks,), jnp.int32)
                with self._watched("serving warmup prefix"):
                    self._store = self._insert_fn(self._store, self.caches,
                                                  jnp.int32(0), ids, ids,
                                                  jnp.int32(0))
        self._warm = True
        self._guard.check()
        self._events.emit("serving_warmup",
                          buckets=list(self.prefill_buckets),
                          prefill_batch=self.prefill_batch,
                          prefill_rows=[self.prefill_rows(b)
                                        for b in self.prefill_buckets],
                          paged=self.paged,
                          prefix=self.prefix_cache is not None)

    def prefill(self, prompt: np.ndarray, rng,
                ctx: Optional[dict] = None) -> tuple[int, int]:
        """Admit one prompt into a free slot (no prefix reuse — the PR-1
        surface): runs the smallest covering bucket's compiled prefill,
        returns ``(slot, first_token)``. ``rng`` is the request's own PRNG
        key (its sampler split sequence matches a solo ``generate()``).
        Raises ``RuntimeError`` when no slot is free — admission control
        is the scheduler's job, not a silent queue here."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.validate_request(len(prompt), 1)
        bucket = self.bucket_for(len(prompt))
        plan = AdmitPlan(prompt=prompt, rng=rng, match=None, start=0,
                         bucket=bucket)
        return self.admit_batch([plan], point=SERVING_PREFILL,
                                ctx=ctx)[0]

    def admit_batch(self, plans: Sequence[AdmitPlan], *,
                    point: str = SERVING_PREFILL_BATCH,
                    ctx: Optional[dict] = None
                    ) -> list[tuple[int, int]]:
        """Admit a same-bucket group in ONE batched prefill call (plus one
        prefix-fetch copy per cached member, before): returns ``[(slot,
        first_token), ...]`` in plan order. Slot mirrors commit only after
        the device calls succeed, so a raise BEFORE device execution (the
        fault cut-points) leaves the engine intact — the scheduler then
        errors only this group. A failure that consumed the donated cache
        buffers re-raises as :class:`EngineStateError` (full restart).

        After commit, each member's full prompt blocks are inserted into
        the prefix trie (best effort — an insert failure never un-admits
        a request; a store-corrupting one resets the prefix cache)."""
        if not plans:
            return []
        buckets = {p.bucket for p in plans}
        if len(buckets) != 1:
            raise ValueError(
                f"admission group mixes buckets {sorted(buckets)} — one "
                "compiled program per call"
            )
        bucket = plans[0].bucket
        k = self.prefill_rows(bucket)
        if len(plans) > k:
            raise ValueError(
                f"group of {len(plans)} exceeds the {k} rows of bucket "
                f"{bucket}'s program (prefill_batch={self.prefill_batch} "
                f"rows at bucket {self.prefill_buckets[0]})"
            )
        if len(plans) > len(self.free_slots):
            raise RuntimeError("no free slot (scheduler admitted too many)")
        if self.paged:
            return self._paged_admit(plans, point=point, ctx=ctx)
        if self._pending_inserts:
            self.flush_inserts()   # before slots are picked: never insert
        slots = sorted(self.free_slots)[:len(plans)]  # deterministic pick
        n_cached = sum(p.match is not None for p in plans)
        try:
            try:
                with self._watched("serving prefill", **(ctx or {})), \
                        annotate("chainermn.serving_prefill",
                                 rows=len(plans), of=k, bucket=bucket):
                    if n_cached:
                        inject(SERVING_PREFIX_COPY, op="fetch",
                               hits=n_cached, batch=len(plans))
                    # fault cut-point INSIDE the watchdog window: an
                    # injected hang here exercises exactly the wedge hang
                    # detection exists for
                    inject(point, batch=len(plans), bucket=bucket,
                           slots=slots)
                    with annotate("chainermn.serving_prefill_args"):
                        tokens = np.zeros((k, bucket), np.int32)
                        starts = np.zeros((k,), np.int32)
                        last = np.zeros((k,), np.int32)
                        active = np.zeros((k,), bool)
                        slot_ids = np.zeros((k,), np.int32)
                        keys = [jnp.zeros((2,), jnp.uint32)] * k
                        extra = ()
                        if self.prefix_cache is not None:
                            fetch_ids = np.zeros((k, self._n_prog_blocks),
                                                 np.int32)
                        for i, (plan, slot) in enumerate(zip(plans, slots)):
                            suffix = plan.prompt[plan.start:]
                            tokens[i, : len(suffix)] = suffix
                            starts[i] = plan.start
                            last[i] = len(suffix) - 1
                            active[i] = True
                            slot_ids[i] = slot
                            keys[i] = plan.rng
                            if plan.match is not None:
                                fetch_ids[i, : len(plan.match.block_ids)] = \
                                    plan.match.block_ids
                        if self.prefix_cache is not None:
                            extra = (self._store, jnp.asarray(fetch_ids))
                        args = (jnp.asarray(tokens), jnp.asarray(slot_ids),
                                jnp.asarray(starts), jnp.asarray(last),
                                jnp.asarray(active), jnp.stack(keys), *extra)
                    with annotate("chainermn.serving_prefill_dispatch"):
                        self.caches, firsts, keys_out = \
                            self._prefill_fns[bucket](
                                self.params, self.caches, *args)
                    with annotate("chainermn.serving_prefill_fetch"):
                        firsts = device_fetch(firsts)
            except Exception as e:
                if not self._state_ok():
                    raise EngineStateError(
                        f"admission failed mid-device-call "
                        f"({type(e).__name__}: {e}); donated cache buffers "
                        "are gone — restart required"
                    ) from e
                raise
        finally:
            for plan in plans:
                self.cancel_plan(plan)   # pins served their purpose
        out = []
        for i, (plan, slot) in enumerate(zip(plans, slots)):
            first = int(firsts[i])
            self.free_slots.discard(slot)
            self._token[slot] = first
            self._pos[slot] = len(plan.prompt)
            self._active[slot] = True
            self._keys = self._keys.at[slot].set(keys_out[i])
            self._c_prefills[bucket].inc()
            self._events.emit("prefill", slot=slot,
                              prompt_len=len(plan.prompt), bucket=bucket,
                              cached=plan.start, batch=len(plans))
            out.append((slot, first))
            if self.prefix_cache is not None:
                self._pending_inserts.append((plan.prompt, slot))
        self.peak_active = max(self.peak_active, self.active_slots)
        self._guard.check()
        return out

    # ------------------------------------------------------------------ #
    # paged admission + block management                                   #
    # ------------------------------------------------------------------ #

    def _paged_alloc_slot(self, plan: AdmitPlan, slot: int) -> list:
        """Allocate, in every kind's pool, the blocks a plan's prefill
        writes into ([start, len(prompt)) — shared prefix blocks are
        referenced, not copied; a window kind takes the last ring of the
        prompt's blocks), write the slot's table mirrors, and reserve the
        worst-case decode growth. Returns the ids per kind. Raises
        ``RuntimeError`` when a pool (plus trie eviction) cannot cover it
        — the scheduler's block-budget gate makes that unreachable in the
        no-fault case — with nothing left allocated."""
        bs = self.kv_block_size
        plen = len(plan.prompt)
        n_prompt = -(-plen // bs)
        shared = list(plan.match.block_ids) if plan.match is not None else []
        got: list[list] = []
        try:
            for kv in self._kv:
                most = kv.blocks_for(plen + plan.max_new)
                held = min(n_prompt, most)
                need_now = held - len(shared)
                new = kv.index.alloc_blocks(need_now)
                if len(new) < need_now:
                    for block in new:
                        kv.pool.decref(block)
                    raise RuntimeError(
                        f"kv block pool exhausted: slot {slot} needs "
                        f"{need_now} {kv.name} blocks, {len(new)} "
                        f"allocatable (free={kv.pool.free_blocks})"
                    )
                for block in shared:
                    kv.pool.incref(block)    # the slot co-owns its prefix
                ids = shared + new
                kv.tables[slot, :] = 0
                for j, block in enumerate(ids, n_prompt - held):
                    kv.tables[slot, kv.entry(j)] = block
                kv.reserved[slot] = most - held + self._spec_headroom
                got.append(ids)
        except Exception:
            self._paged_unalloc_slot(slot, got)
            raise
        return got

    def _paged_unalloc_slot(self, slot: int, ids_by_kind: list) -> None:
        """Undo :meth:`_paged_alloc_slot`: nothing was admitted."""
        for kv, ids in zip(self._kv, ids_by_kind):
            for block in ids:
                kv.pool.decref(block)
            kv.reserved[slot] = 0
            kv.tables[slot, :] = 0

    # graftlint: hot — the paged-path body of admit_batch
    def _paged_admit(self, plans: Sequence[AdmitPlan], *, point: str,
                     ctx: Optional[dict] = None) -> list[tuple[int, int]]:
        """Paged twin of the dense ``admit_batch`` body: allocate block
        tables (prefix hits = shared entries, zero copies), run the ONE
        bucketed prefill program through them, then commit mirrors and
        adopt each prompt's full blocks into the trie (``insert_shared``
        — pure bookkeeping, nothing device-side). A failure before the
        device call rolls the allocations back and errors only this
        group; one that consumed the donated store re-raises as
        :class:`EngineStateError`."""
        bucket = plans[0].bucket
        k = self.prefill_rows(bucket)
        slots = sorted(self.free_slots)[:len(plans)]  # deterministic pick
        n_cached = sum(p.match is not None for p in plans)
        alloc_records: list[tuple[int, list]] = []
        try:
            try:
                with self._watched("serving prefill", **(ctx or {})), \
                        annotate("chainermn.serving_prefill",
                                 rows=len(plans), of=k, bucket=bucket):
                    if n_cached:
                        inject(SERVING_PREFIX_COPY, op="share",
                               hits=n_cached, batch=len(plans))
                    inject(point, batch=len(plans), bucket=bucket,
                           slots=slots)
                    with annotate("chainermn.serving_prefill_args"):
                        tokens = np.zeros((k, bucket), np.int32)
                        starts = np.zeros((k,), np.int32)
                        last = np.zeros((k,), np.int32)
                        active = np.zeros((k,), bool)
                        table = self._table_args(rows=k)
                        keys = [jnp.zeros((2,), jnp.uint32)] * k
                        for i, (plan, slot) in enumerate(zip(plans, slots)):
                            ids = self._paged_alloc_slot(plan, slot)
                            alloc_records.append((slot, ids))
                            for kv, ops in zip(self._kv, table):
                                ops["table"][i] = kv.tables[slot]
                            for ops in table[len(self._kv):]:
                                ops["slots"][i] = slot
                            suffix = plan.prompt[plan.start:]
                            tokens[i, : len(suffix)] = suffix
                            starts[i] = plan.start
                            last[i] = len(suffix) - 1
                            active[i] = True
                            keys[i] = plan.rng
                        args = (jnp.asarray(tokens), jnp.asarray(starts),
                                jnp.asarray(last), jnp.asarray(active),
                                jnp.stack(keys))
                    with annotate("chainermn.serving_prefill_dispatch"):
                        self._store, firsts, keys_out = \
                            self._prefill_fns[bucket](
                                self.params, self._store, table, *args)
                    with annotate("chainermn.serving_prefill_fetch"):
                        firsts = self._take_moe_counts(device_fetch(firsts),
                                                       k)
            except Exception as e:
                for slot, ids in alloc_records:   # undo: nothing admitted
                    self._paged_unalloc_slot(slot, ids)
                if not self._state_ok():
                    raise EngineStateError(
                        f"admission failed mid-device-call "
                        f"({type(e).__name__}: {e}); donated store buffers "
                        "are gone — restart required"
                    ) from e
                raise
        finally:
            for plan in plans:
                self.cancel_plan(plan)   # pins served their purpose
        out = []
        for (plan, slot), (_, ids) in zip(zip(plans, slots), alloc_records):
            first = int(firsts[len(out)])
            self.free_slots.discard(slot)
            self._token[slot] = first
            self._pos[slot] = len(plan.prompt)
            self._active[slot] = True
            self._keys = self._keys.at[slot].set(keys_out[len(out)])
            for kv, kind_ids in zip(self._kv, ids):
                kv.takes(slot, kind_ids)
            self._c_prefills[bucket].inc()
            self._events.emit("prefill", slot=slot,
                              prompt_len=len(plan.prompt), bucket=bucket,
                              cached=plan.start, batch=len(plans),
                              blocks=sum(len(i) for i in ids))
            out.append((slot, first))
            self._state_tokens += len(plan.prompt) * self._state_layers
            if self._drafter is not None:
                self._drafter.on_admit(slot, plan.prompt, first)
            # zero-copy trie insert: the slot's blocks already hold the
            # prompt's KV — adopting them IS the cache insert
            if (self.prefix_cache is not None
                    and self.prefix_cache.missing_blocks(plan.prompt)
                    >= self._min_insert):
                self.prefix_cache.insert_shared(plan.prompt, ids[0])
        for chunk, layers in self._chunked:
            live = layers * sum(-(-(len(p.prompt) - p.start) // chunk)
                                for p in plans)
            self._prefill_chunks += (live,
                                     layers * k * -(-bucket // chunk) - live)
        self.peak_active = max(self.peak_active, self.active_slots)
        self._guard.check()
        return out

    # ------------------------------------------------------------------ #
    # chunked prefill (paged only)                                        #
    # ------------------------------------------------------------------ #

    def plan_chunks(self, plan: AdmitPlan,
                    chunk_tokens: int) -> Optional[list]:
        """Chunk schedule for a plan's suffix: split ``[start, len(prompt))``
        into ``chunk_tokens``-sized pieces and pick each piece's bucket at
        its own frontier. Returns ``[(frontier, chunk_len, bucket), ...]``
        or ``None`` when chunking doesn't apply — non-paged engines, a
        suffix that already fits one chunk (the one-shot path is strictly
        better), or a chunk whose frontier leaves no bucket inside
        ``cache_len`` (``bucket_for``'s ``start + b <= cache_len``
        constraint; an out-of-range bucket would clamp table lookups onto
        live blocks). ``None`` means: admit unchunked."""
        if not self.paged or self._whole_prompts:
            return None     # such a layer's prefill takes whole prompts
        chunk_tokens = int(chunk_tokens)
        if chunk_tokens < 1:
            return None
        plen = len(plan.prompt)
        if plen - plan.start <= chunk_tokens:
            return None
        from chainermn_tpu.parallel.sequence import chunk_spans

        chunks = []
        for frontier, clen in chunk_spans(plan.start, plen, chunk_tokens):
            bucket = self.bucket_for(clen, frontier)
            if bucket is None:
                return None
            chunks.append((frontier, clen, bucket))
        return chunks

    def begin_chunked(self, plan: AdmitPlan, chunks: list) -> int:
        """Stage a chunked admission: claim a free slot, allocate ALL the
        prompt's blocks up front (shared prefix blocks referenced, not
        copied — exactly :meth:`_paged_alloc_slot`'s accounting) and
        reserve decode growth, but leave the slot's decode-table row
        **all-scratch**: decode rounds interleaving with the chunks still
        pass the full ``[n_slots]`` table, and the masked ride-along
        write at this inactive slot's stale position must land in the
        scratch block, never in a real (possibly trie-shared) block. The
        real ids live privately in the :class:`ChunkedPrefill` until the
        final chunk commits them. Consumes the plan (its match pin
        converts into refcounts). Returns the claimed slot."""
        if not self.paged:
            raise RuntimeError("chunked prefill needs paged=True")
        if self._whole_prompts:
            raise ValueError(self._refusal(
                "chunked prefill (a chunk continues a prompt at an offset)"))
        if not self.free_slots:
            raise RuntimeError("no free slot for chunked prefill")
        slot = min(self.free_slots)
        bs = self.kv_block_size
        plen = len(plan.prompt)
        shared = (list(plan.match.block_ids)
                  if plan.match is not None else [])
        need_now = -(-plen // bs) - len(shared)
        try:
            new = self.prefix_cache.alloc_blocks_atomic(need_now)
            if new is None:
                raise RuntimeError(
                    f"kv block pool exhausted: chunked slot {slot} needs "
                    f"{need_now} blocks (free={self._pool.free_blocks})")
            for block in shared:
                self._pool.incref(block)   # the slot co-owns its prefix
        finally:
            self.cancel_plan(plan)
        ids = shared + new
        self._tables[slot, :] = 0          # stays scratch until commit
        self._slot_reserved[slot] = (
            -(-(plen + plan.max_new) // bs) - (-(-plen // bs))
            + self._spec_headroom)
        self._slot_takes(slot, ids)
        self.free_slots.discard(slot)
        self._chunking[slot] = ChunkedPrefill(
            prompt=plan.prompt, rng=plan.rng, start=plan.start,
            max_new=int(plan.max_new), ids=ids, chunks=list(chunks),
            t_begin=time.perf_counter())
        return slot

    def chunk_state(self, slot: int) -> Optional[ChunkedPrefill]:
        return self._chunking.get(slot)

    def prefill_chunk(self, slot: int,
                      ctx: Optional[dict] = None) -> Optional[int]:
        """Run ONE staged chunk through its bucket's compiled prefill
        program (row 0 carries the chunk at ``starts=frontier``; the
        other rows ride inactive on all-scratch tables — the warmup
        shapes, so nothing recompiles). Intermediate chunks discard the
        sampled output and consume NO rng (their pad-tail garbage rows
        are overwritten by the next chunk's writes before anything
        attends them — the module's stale-rows induction, unchanged);
        the FINAL chunk samples with the request's own rng (the one
        admission split, sampler parity with a solo ``generate()``),
        commits the slot's table/mirrors, and returns the first token.
        Returns ``None`` after an intermediate chunk.

        A raise before the device call leaves the staged state intact
        (the scheduler may retry or release the slot); one that consumed
        the donated store re-raises as :class:`EngineStateError`."""
        st = self._chunking[slot]
        frontier, clen, bucket = st.chunks[st.next_idx]
        final = st.next_idx == len(st.chunks) - 1
        k = self.prefill_rows(bucket)
        try:
            with self._watched("serving chunk_prefill", **(ctx or {})), \
                    annotate("chainermn.serving_chunk_prefill"):
                inject(SERVING_CHUNK_PREFILL, slot=slot,
                       chunk=st.next_idx, of=len(st.chunks),
                       bucket=bucket, frontier=frontier)
                with annotate("chainermn.serving_prefill_args"):
                    tokens = np.zeros((k, bucket), np.int32)
                    starts = np.zeros((k,), np.int32)
                    last = np.zeros((k,), np.int32)
                    active = np.zeros((k,), bool)
                    table = np.zeros((k, self._n_max), np.int32)
                    keys = [jnp.zeros((2,), jnp.uint32)] * k
                    tokens[0, :clen] = st.prompt[frontier:frontier + clen]
                    starts[0] = frontier
                    last[0] = clen - 1
                    active[0] = True
                    table[0, : len(st.ids)] = st.ids
                    if final:
                        keys[0] = st.rng
                    args = (jnp.asarray(tokens), jnp.asarray(starts),
                            jnp.asarray(last), jnp.asarray(active),
                            jnp.stack(keys))
                with annotate("chainermn.serving_prefill_dispatch"):
                    self._store, nxt, keys_out = self._prefill_fns[bucket](
                        self.params, self._store, ({"table": table},), *args)
                with annotate("chainermn.serving_prefill_fetch"):
                    first = int(device_fetch(nxt)[0]) if final else None
        except Exception as e:
            if not self._state_ok():
                raise EngineStateError(
                    f"chunked prefill failed mid-device-call "
                    f"({type(e).__name__}: {e}); donated store buffers "
                    "are gone — restart required") from e
            raise
        st.next_idx += 1
        self._c_chunks.inc()
        self._c_prefills[bucket].inc()
        self._h_chunk_tokens.observe(clen)
        self._events.emit("prefill_chunk", slot=slot, chunk=st.next_idx,
                          of=len(st.chunks), tokens=clen, bucket=bucket,
                          frontier=frontier, final=final)
        self._guard.check()
        if not final:
            return None
        # final-chunk commit: the staged ids become the slot's decode
        # table and the slot joins the active set — from here on it is
        # indistinguishable from an unchunked admission
        plen = len(st.prompt)
        self._tables[slot, : len(st.ids)] = st.ids
        self._token[slot] = first
        self._pos[slot] = plen
        self._active[slot] = True
        self._keys = self._keys.at[slot].set(keys_out[0])
        self._chunking.pop(slot)
        self._events.emit("prefill", slot=slot, prompt_len=plen,
                          bucket=bucket, cached=st.start, batch=1,
                          blocks=len(st.ids), chunks=len(st.chunks))
        if self._drafter is not None:
            self._drafter.on_admit(slot, st.prompt, first)
        if (self.prefix_cache.missing_blocks(st.prompt)
                >= self._min_insert):
            self.prefix_cache.insert_shared(st.prompt, st.ids)
        self.peak_active = max(self.peak_active, self.active_slots)
        return first

    # ------------------------------------------------------------------ #
    # KV block migration (paged, single-device)                           #
    # ------------------------------------------------------------------ #

    def _gather_block_rows(self, ids: list, ctx: Optional[dict],
                           fused: bool) -> list:
        """Pull ``ids``' block rows to the host. Fused: pad the block
        list to the smallest warmup bucket and run ONE gather dispatch.
        Per-block (the pre-round-20 reference path, kept for the
        bit-equality pin and the PERF.md phase model): one width-1
        gather per block — N dispatches + N host bounces. Both return
        the identical layers structure."""
        n = len(ids)
        if fused:
            w = self._mig_bucket(n)
            ids_op = np.zeros((w,), np.int32)
            ids_op[:n] = ids
            with self._watched(f"serving kv_gather[{w}]", **(ctx or {})), \
                    annotate("chainermn.kv_gather"):
                rows = self._kv_gather_fns[w](self._store,
                                              jnp.asarray(ids_op))
            self._guard.check()
            return [{kk: np.asarray(layer[kk])[:n] for kk in layer}
                    for layer in rows]
        per_block = []
        for b in ids:
            one = np.asarray([b], np.int32)
            with self._watched("serving kv_gather[1]", **(ctx or {})), \
                    annotate("chainermn.kv_gather"):
                rows = self._kv_gather_fns[1](self._store,
                                              jnp.asarray(one))
            self._guard.check()
            per_block.append([{kk: np.asarray(layer[kk])
                               for kk in layer} for layer in rows])
        return [{kk: np.concatenate([blk[li][kk] for blk in per_block])
                 for kk in per_block[0][li]}
                for li in range(len(per_block[0]))]

    def _scatter_block_rows(self, new: list, layers: list,
                            ctx: Optional[dict], fused: bool) -> None:
        """Land host ``layers`` rows into blocks ``new`` of THIS store.
        Fused: one scatter dispatch at the covering bucket width.
        Per-block: one width-1 scatter per block (reference path). Any
        raise leaves rollback to the caller."""
        n = len(new)
        if fused:
            w = self._mig_bucket(n)
            ids_op = np.zeros((w,), np.int32)
            ids_op[:n] = new
            rows = []
            for layer in layers:
                full = {}
                for kk, arr in layer.items():
                    pad = np.zeros((w,) + tuple(arr.shape[1:]), arr.dtype)
                    pad[:n] = arr
                    full[kk] = jnp.asarray(pad)
                rows.append(full)
            with self._watched(f"serving kv_scatter[{w}]", **(ctx or {})), \
                    annotate("chainermn.kv_scatter"):
                self._store = self._kv_scatter_fns[w](
                    self._store, jnp.asarray(ids_op), rows, jnp.int32(n))
            return
        for j in range(n):
            one = np.asarray([new[j]], np.int32)
            rows = [{kk: jnp.asarray(arr[j:j + 1])
                     for kk, arr in layer.items()} for layer in layers]
            with self._watched("serving kv_scatter[1]", **(ctx or {})), \
                    annotate("chainermn.kv_scatter"):
                self._store = self._kv_scatter_fns[1](
                    self._store, jnp.asarray(one), rows, jnp.int32(1))

    def _refuse_migration(self) -> None:
        if self._whole_prompts:
            raise ValueError(self._refusal(
                "KV migration (a payload carries one store's blocks)"))

    def export_slot_kv(self, slot: int,
                       ctx: Optional[dict] = None, *,
                       fused: bool = True) -> dict:
        """Read an active slot's entire KV state out to the host: ONE
        compiled gather dispatch at the covering warmup bucket (no
        donation — the source store is untouched, so a failed handover
        keeps decoding in place) pulls the slot's block rows, then the
        host slices exactly ``n_blocks`` rows per layer array — bytes
        moved = bucket(n) x block_bytes, int8 rows + scales as stored,
        no dequant round-trip. ``fused=False`` keeps the per-block
        reference path (one dispatch per block) for parity pins. The
        payload plus the slot's host mirrors (position, last token,
        sampler key) is everything a decode-tier engine needs to
        continue the request token-exactly via :meth:`import_slot_kv`.
        Read-only: the slot stays active here; the caller releases it
        only after the import commits."""
        self._refuse_migration()
        if not self.migration_supported:
            raise RuntimeError(
                "KV migration needs paged=True on a single-device engine "
                "(TP stores are head-sharded across the mesh)")
        if not self._active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        t0 = time.perf_counter()
        ids = list(self._slot_blocks[slot])
        n = len(ids)
        layers = self._gather_block_rows(ids, ctx, fused)
        return {
            "n_blocks": n,
            "block_size": self.kv_block_size,
            "kv_quant": self.kv_quant,
            "n_layers": self.model.n_layers,
            "pos": int(self._pos[slot]),
            "token": int(self._token[slot]),
            "key": np.asarray(self._keys[slot]),
            "layers": layers,
            "t_start": t0,
        }

    def can_import(self, payload: dict, max_new: int = 1, *,
                   static_only: bool = False) -> bool:
        """Cheap host-side pre-check that :meth:`import_slot_kv` would
        succeed here: layout agreement (block size / quant / layers /
        row shapes), a free slot, and block budget for the resident
        blocks plus remaining decode growth. ``static_only`` checks the
        layout/position constraints alone — a False there means the
        import can NEVER succeed on this engine (structural mismatch),
        while a transient False (slots/blocks busy) clears on its own."""
        if not self.migration_supported:
            return False
        if not static_only and not (self._warm and self.free_slots):
            return False
        if (int(payload["block_size"]) != self.kv_block_size
                or str(payload["kv_quant"]) != self.kv_quant
                or int(payload["n_layers"]) != self.model.n_layers):
            return False
        n = int(payload["n_blocks"])
        if not 0 < n <= self._n_max:
            return False
        for kk, arr in payload["layers"][0].items():
            if tuple(arr.shape[1:]) != tuple(self._store[0][kk].shape[1:]):
                return False
        pos = int(payload["pos"])
        if pos + int(max_new) > self.cache_len:
            return False
        if static_only:
            return True
        bs = self.kv_block_size
        need = (n + max(0, -(-(pos + int(max_new)) // bs) - n)
                + self._spec_headroom)
        return bool((need <= self.kv_blocks_admittable()).all())

    def import_slot_kv(self, payload: dict, *,
                       prompt: Optional[np.ndarray] = None,
                       max_new: int = 1,
                       ctx: Optional[dict] = None,
                       fused: bool = True) -> int:
        """Land a migrated request into THIS engine: allocate fresh
        blocks, scatter the host rows in with the compiled-once pair's
        write side (one dispatch at the covering warmup bucket — the pad
        tail carries scratch ids and identity content), and
        commit the slot mirrors (position/token/sampler key) so the next
        decode round continues the request token-exactly. When
        ``prompt`` is given, its full blocks are adopted into this
        engine's prefix trie (``insert_shared`` — the migrated prefix
        becomes ground truth here, not router belief). Returns the slot.
        Raises ``RuntimeError`` (layout/budget) with the engine intact —
        the caller's fallback is decoding in place at the source."""
        self._refuse_migration()
        if not self.migration_supported:
            raise RuntimeError(
                "KV migration needs paged=True on a single-device engine")
        if (int(payload["block_size"]) != self.kv_block_size
                or str(payload["kv_quant"]) != self.kv_quant
                or int(payload["n_layers"]) != self.model.n_layers):
            raise RuntimeError(
                "migration layout mismatch: source/dest engines disagree "
                "on block_size/kv_quant/n_layers")
        if not self.free_slots:
            raise RuntimeError("no free slot for migration import")
        n = int(payload["n_blocks"])
        if not 0 < n <= self._n_max:
            raise RuntimeError(
                f"migration carries {n} blocks; this engine's tables "
                f"hold at most {self._n_max}")
        pos = int(payload["pos"])
        if pos + int(max_new) > self.cache_len:
            raise RuntimeError(
                f"migrated position {pos} + {max_new} new tokens exceed "
                f"cache_len={self.cache_len}")
        new = self.prefix_cache.alloc_blocks_atomic(n)
        if new is None:
            raise RuntimeError(
                f"kv block pool exhausted: import needs {n} blocks "
                f"(free={self._pool.free_blocks})")
        slot = min(self.free_slots)
        bs = self.kv_block_size
        try:
            self._scatter_block_rows(new, payload["layers"], ctx, fused)
        except Exception as e:
            for block in new:
                self._pool.decref(block)
            if not self._state_ok():
                raise EngineStateError(
                    f"migration import failed mid-device-call "
                    f"({type(e).__name__}: {e}); donated store buffers "
                    "are gone — restart required") from e
            raise
        self._guard.check()
        self.free_slots.discard(slot)
        self._tables[slot, :] = 0
        self._tables[slot, :n] = new
        self._slot_takes(slot, new)
        self._slot_reserved[slot] = (
            max(0, -(-(pos + int(max_new)) // bs) - n)
            + self._spec_headroom)
        self._pos[slot] = pos
        self._token[slot] = int(payload["token"])
        self._active[slot] = True
        self._keys = self._keys.at[slot].set(jnp.asarray(payload["key"]))
        seconds = time.perf_counter() - float(payload.get("t_start", 0.0)) \
            if payload.get("t_start") else 0.0
        self._c_migrations.inc()
        self._c_migrated_blocks.inc(n)
        if seconds > 0.0:
            self._h_migration.observe(seconds)
        self._events.emit("kv_migrate", slot=slot, blocks=n, pos=pos,
                          seconds=round(seconds, 6))
        if self._drafter is not None and prompt is not None:
            self._drafter.on_admit(slot, np.asarray(prompt, np.int32),
                                   int(payload["token"]))
        if prompt is not None:
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            if (self.prefix_cache.missing_blocks(prompt)
                    >= self._min_insert):
                self.prefix_cache.insert_shared(prompt, new)
        self.peak_active = max(self.peak_active, self.active_slots)
        return slot

    # ------------------------------------------------------------------ #
    # cross-replica prefix sharing (paged, single-device)                 #
    # ------------------------------------------------------------------ #

    def export_prefix_kv(self, tokens, ctx: Optional[dict] = None, *,
                         min_blocks: int = 1) -> Optional[dict]:
        """Read this engine's cached prefix of ``tokens`` out to the
        host through the fused migration gather — the share payload
        another replica imports via :meth:`import_prefix_kv` instead of
        re-prefilling blocks the fleet already paid for. Returns ``None``
        (never raises on a cold cache) when sharing is unsupported, the
        trie holds fewer than ``min_blocks`` of the prompt, or the
        engine is not warm — the caller's fallback is a plain prefill.
        Read-only on the store; the matched blocks are pinned only for
        the duration of the gather."""
        if not (self.migration_supported and self._warm
                and self.prefix_cache is not None):
            return None
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        m = self.prefix_cache.match(tokens)
        if m is None:
            return None
        try:
            n = len(m.block_ids)
            if n < max(1, int(min_blocks)):
                return None
            t0 = time.perf_counter()
            layers = self._gather_block_rows(list(m.block_ids), ctx, True)
            return {
                "n_blocks": n,
                "block_size": self.kv_block_size,
                "kv_quant": self.kv_quant,
                "n_layers": self.model.n_layers,
                "tokens": tokens[:m.length].copy(),
                "layers": layers,
                "t_start": t0,
            }
        finally:
            self.prefix_cache.release(m)

    def can_import_prefix(self, payload: dict, *,
                          static_only: bool = False) -> bool:
        """Pre-check that :meth:`import_prefix_kv` would succeed here:
        layout agreement and (non-static) warm programs plus block
        budget. Same static/transient split as :meth:`can_import`."""
        if not (self.migration_supported and self.prefix_cache
                is not None):
            return False
        if (int(payload["block_size"]) != self.kv_block_size
                or str(payload["kv_quant"]) != self.kv_quant
                or int(payload["n_layers"]) != self.model.n_layers):
            return False
        n = int(payload["n_blocks"])
        if not 0 < n <= self._n_max:
            return False
        for kk, arr in payload["layers"][0].items():
            if tuple(arr.shape[1:]) != tuple(self._store[0][kk].shape[1:]):
                return False
        if static_only:
            return True
        return self._warm and bool((n <= self.kv_blocks_admittable()).all())

    def import_prefix_kv(self, payload: dict,
                         ctx: Optional[dict] = None) -> int:
        """Adopt a shared prefix payload into THIS engine's trie:
        allocate blocks all-or-nothing, scatter the rows in through the
        fused write side, then ``insert_shared`` hands ownership to the
        trie (each adopted block settles at refcount 1, trie-owned; a
        block whose trie position was cached concurrently drops straight
        back to the free list). The next admission matching this prefix
        prefills ZERO of its shared blocks. Returns blocks adopted (0 =
        already resident, nothing to do); raises ``RuntimeError`` with
        the engine intact on layout mismatch or pool exhaustion — the
        caller's fallback is a plain prefill."""
        if not self.migration_supported or self.prefix_cache is None:
            raise RuntimeError(
                "prefix sharing needs paged=True on a single-device "
                "engine")
        if (int(payload["block_size"]) != self.kv_block_size
                or str(payload["kv_quant"]) != self.kv_quant
                or int(payload["n_layers"]) != self.model.n_layers):
            raise RuntimeError(
                "share layout mismatch: source/dest engines disagree "
                "on block_size/kv_quant/n_layers")
        n = int(payload["n_blocks"])
        if not 0 < n <= self._n_max:
            raise RuntimeError(
                f"shared prefix carries {n} blocks; this engine's "
                f"tables hold at most {self._n_max}")
        tokens = np.asarray(payload["tokens"], np.int32).reshape(-1)
        if self.prefix_cache.missing_blocks(tokens) == 0:
            return 0                       # already ground truth here
        new = self.prefix_cache.alloc_blocks_atomic(n)
        if new is None:
            raise RuntimeError(
                f"kv block pool exhausted: share import needs {n} "
                f"blocks (free={self._pool.free_blocks})")
        try:
            self._scatter_block_rows(new, payload["layers"], ctx, True)
        except Exception as e:
            for block in new:
                self._pool.decref(block)
            if not self._state_ok():
                raise EngineStateError(
                    f"share import failed mid-device-call "
                    f"({type(e).__name__}: {e}); donated store buffers "
                    "are gone — restart required") from e
            raise
        self._guard.check()
        adopted = self.prefix_cache.insert_shared(tokens, new)
        for block in new:
            self._pool.decref(block)
        return adopted

    def blocks_needed(self, prompt_len: int, max_new: int,
                      start: int = 0) -> np.ndarray:
        """Worst-case NEW blocks a request admits with, one count per kind
        of KV state: blocks covering ``[start, prompt_len + max_new)``
        (``start`` = cached-prefix tokens, whose blocks are shared, not
        allocated), a window kind's capped at its ring, and one unit (its
        slot's row) of a kind that is a row a slot. The scheduler's
        block-budget admission compares this against
        :meth:`kv_blocks_admittable`, kind by kind. Multi-token rounds add
        ``ceil(write_horizon / block_size)`` headroom: a verify window
        writes up to ``k`` draft rows past the commit frontier, and those
        writes must never find the pool dry mid-round."""
        bs = self.kv_block_size
        return np.array(
            [kv.blocks_for(prompt_len + max_new) - start // bs
             + self._spec_headroom for kv in self._kv]
            + [1] * len(self._state), np.int64)

    def kv_blocks_admittable(self) -> np.ndarray:
        """Blocks an admission may claim without ever starving a decode,
        per kind: free pool blocks, plus trie blocks eviction could
        reclaim, minus the growth already reserved by active slots; of a
        kind that is a row a slot, the free slots."""
        return np.array([kv.admittable() for kv in self._kv]
                        + [len(self.free_slots)] * len(self._state),
                        np.int64)

    def _horizon_block_range(self, slot: int) -> range:
        """Blocks the slot's next round may write: those covering
        ``[pos, pos + write_horizon]`` clipped to ``cache_len``. Horizon
        0 (the legacy per-token path) is exactly the next write's block."""
        bs = self.kv_block_size
        p = int(self._pos[slot])
        if p >= self.cache_len:
            return range(0)   # no further real writes (valid masks them)
        hi = min(p + self._write_horizon, self.cache_len - 1)
        return range(p // bs, hi // bs + 1)

    def slot_needs_block(self, slot: int) -> bool:
        """True when a write inside the slot's next decode round crosses
        into a block it has not allocated yet (a table entry in the
        horizon span still points at scratch), in any kind's table.
        Multi-token rounds (speculative window / decode_window) widen the
        span checked."""
        if not self.paged or not self._active[slot]:
            return False
        blocks = self._horizon_block_range(slot)
        return any(kv.tables[slot, kv.entry(j)] == 0
                   for kv in self._kv for j in blocks)

    def append_block(self, slot: int) -> bool:
        """Lazily allocate the slot's next block (evicting idle trie
        prefixes if the free list is dry) — the FIRST unallocated entry
        in the next round's write span, in each kind's table that has
        one. Returns False when a pool is truly exhausted — the scheduler
        then preempts the lowest-priority request and retries. Carries
        the ``serving.kv_append`` fault cut-point: an injected failure
        here is contained by preempting ONLY this slot (no engine
        restart)."""
        inject(SERVING_KV_APPEND, slot=slot, pos=int(self._pos[slot]))
        blocks = self._horizon_block_range(slot)
        for kv in self._kv:
            idx = next((kv.entry(j) for j in blocks
                        if kv.tables[slot, kv.entry(j)] == 0), None)
            if idx is None:
                continue      # span fully allocated — nothing to do
            got = kv.index.alloc_blocks(1)
            if not got:
                return False
            block = got[0]
            kv.tables[slot, idx] = block
            kv.takes(slot, [block])
            if kv.reserved[slot] > 0:
                kv.reserved[slot] -= 1
            self._c_appends.inc()
            self._events.emit("kv_append", slot=slot, block=block,
                              pos=int(self._pos[slot]))
        return True

    def _slot_takes(self, slot: int, blocks) -> None:
        self._kv[0].takes(slot, blocks)

    def _slot_drops(self, slot: int, blocks=None) -> None:
        self._kv[0].drops(slot, blocks)

    def slot_block_count(self, slot: int) -> int:
        """Blocks the slot's table currently references (0 in dense
        mode) — the per-request block-count series at retirement."""
        if not self.paged:
            return 0
        return sum(len(kv.slot_blocks[slot]) for kv in self._kv)

    def slot_block_shares(self) -> np.ndarray:
        """Refcount-weighted block count every slot holds RIGHT NOW,
        ``[n_slots]`` (zeros in dense mode): a private block counts 1, a
        prefix block shared by ``r`` live holders counts ``1/r`` — so
        summing this over all holders always reproduces the pool's true
        occupancy. The cost ledger integrates it into per-tenant KV
        block-seconds, once a step: one pass over the tables, no call a
        block."""
        if not self.paged:
            return np.zeros(self.n_slots)
        if self.prefix_cache is None:
            # blocks are shared through the trie alone: without one every
            # block has the one holder, and counting them walks nothing
            return np.sum([[len(held) for held in kv.slot_blocks]
                           for kv in self._kv], axis=0, dtype=np.float64)
        # a trie means one kind of KV state, and a slot's table names the
        # blocks it holds and scratch — but for a chunked prefill's,
        # staged beside the table until its last chunk
        tables = self._tables
        if self._chunking:
            tables = tables.copy()
            for slot, st in self._chunking.items():
                tables[slot, : len(st.ids)] = st.ids
        return self._pool.shares(tables)

    def kv_pool_stats(self) -> tuple[int, int, int]:
        """(blocks in use, blocks free, blocks live) — the scheduler
        samples these into the ``kv_blocks_in_use``/``kv_blocks_free``/
        ``kv_blocks_live`` gauges. In use = every block off the free list,
        whoever holds it: live slots AND finished prompts the prefix trie
        keeps until evicted (so it reads near the pool's size on a busy
        server whatever is live). Live = blocks some slot's table
        references, a running count."""
        return (sum(kv.pool.used_blocks for kv in self._kv),
                sum(kv.pool.free_blocks for kv in self._kv),
                sum(kv.live for kv in self._kv))

    def kv_stats(self) -> dict:
        """The paged stores' configuration and occupancy (blocks live,
        reserved and in use, per layer kind under ``kinds``); an empty
        dict in dense mode."""
        if not self.paged:
            return {}
        first = self._kv[0].stats()
        return {
            "kv_blocks": self.kv_blocks,
            "kv_block_size": self.kv_block_size,
            "kv_quant": self.kv_quant,
            # of the first kind's pool, as before there were kinds: off
            # the free list, trie-held (evictable) prompts included;
            "blocks_in_use": first["blocks_in_use"],
            # referenced by some live slot's table;
            "blocks_live": first["blocks_live"],
            "blocks_free": first["blocks_free"],
            "blocks_reserved": first["blocks_reserved"],
            "peak_active": self.peak_active,
            # and the same of every kind's, by the spec's names; of a
            # kind that is a row a slot, the slots holding a request and
            # the bytes of its arrays
            "kinds": dict(
                {kv.name: kv.stats() for kv in self._kv},
                **{st.name: st.stats(self.active_slots)
                   for st in self._state}),
        }

    def pop_state_stats(self) -> Optional[tuple]:
        """``(slots live, bytes, tokens, live chunks, padding chunks,
        kernel rows, xla rows)`` of the state kept a row a slot: rows
        holding a request now, the bytes of all such arrays, the tokens x
        layers whose state the programs advanced, the chunks x layers that
        the prefill programs' whole-prompt form walked and skipped
        (``SlotStateKind.chunk``), and the rows x layers the decode
        programs ran through the step in a kernel and in XLA
        (``SlotStateKind.decode_kernel``), each since the last call
        (cleared on read); ``None`` for a model that keeps none. The
        scheduler drains it into
        :class:`~chainermn_tpu.serving.metrics.ServingMetrics`."""
        if not (self.paged and self._state):
            return None
        tokens, self._state_tokens = self._state_tokens, 0
        live, padding = (int(x) for x in self._prefill_chunks)
        self._prefill_chunks[:] = 0
        kernel, xla = (int(x) for x in self._decode_rows)
        self._decode_rows[:] = 0
        return (self.active_slots, sum(st.bytes for st in self._state),
                tokens, live, padding, kernel, xla)

    def flush_inserts(self) -> None:
        """Run the deferred trie inserts (one compiled copy per prompt
        with new full blocks). Deferral keeps the insert copies off the
        TTFT-critical admission path; the scheduler flushes at the end of
        every step and :meth:`admit_batch` flushes defensively before
        picking slots, so a donor's rows are always copied out before its
        slot can be reused by a later tenant."""
        if self.paged:
            return   # paged inserts are zero-copy, done at admission
        pending, self._pending_inserts = self._pending_inserts, []
        for prompt, slot in pending:
            self._insert_prefix(prompt, slot)

    def _insert_prefix(self, prompt: np.ndarray, slot: int) -> None:
        """Cache a freshly-prefilled prompt's full blocks (best effort:
        never fails the admitted request; a store-corrupting failure
        resets the prefix cache to a consistent empty state)."""
        if self.prefix_cache.missing_blocks(prompt) < self._min_insert:
            return
        plan = self.prefix_cache.plan_insert(prompt)
        if plan is None:
            return
        try:
            inject(SERVING_PREFIX_COPY, op="insert", slot=slot,
                   blocks=len(plan.block_ids))
            ids = np.zeros((self._n_prog_blocks,), np.int32)
            ids[: len(plan.block_ids)] = plan.block_ids
            rows = np.zeros((self._n_prog_blocks,), np.int32)
            rows[: len(plan.row_starts)] = plan.row_starts
            with self._watched("serving prefix insert"), \
                    annotate("chainermn.serving_prefix_copy"):
                self._store = self._insert_fn(
                    self._store, self.caches, jnp.int32(slot),
                    jnp.asarray(ids), jnp.asarray(rows),
                    jnp.int32(len(plan.block_ids)))
            self.prefix_cache.commit_insert(plan)
            self._guard.check()
        except Exception as e:  # noqa: BLE001 — insertion is best-effort
            self.prefix_cache.abort_insert(plan)
            if not self._state_ok():
                self._reset_prefix()
            self._events.emit("prefix_insert_error",
                              error=type(e).__name__, detail=str(e)[:200])

    def _state_ok(self) -> bool:
        """True when the donated device buffers are still alive (an
        exception fired BEFORE the device call consumed them) — the
        scheduler's containment test: intact state means only the group
        being admitted failed, everything decoding is untouched."""
        try:
            leaves = jax.tree_util.tree_leaves(
                self._store if self.paged else self.caches)
            if self.prefix_cache is not None and not self.paged:
                leaves += jax.tree_util.tree_leaves(self._store)
            return not any(leaf.is_deleted() for leaf in leaves)
        except Exception:  # noqa: BLE001 — can't tell: assume the worst
            return False

    def _reset_prefix(self) -> None:
        """Fresh (empty) prefix store + cleared trie, together — a trie
        naming blocks of a dead store would hand out KV that no longer
        exists (same shapes/shardings: nothing recompiles)."""
        if self.prefix_cache is None:
            return
        if self.model.tensor_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axis = self.model.tensor_axis
            shard = NamedSharding(self._comm.mesh, P(None, None, axis))
            self._store = jax.device_put(self._init_store(), shard)
        else:
            self._store = self._init_store()
        self.prefix_cache.clear()

    def _decode_args(self) -> tuple:
        """The operands of the decode program (and of the window program,
        which takes the same): parameters, the KV state it hands back
        updated — the block store and its tables, or the dense caches —
        and the host-side slot mirror. Warm-up, every decode call and
        :meth:`decode_program_text` build them here, so they cannot drift
        apart."""
        kv = ((self._store, self._table_args()) if self.paged
              else (self.caches,))
        return (self.params, *kv, jnp.asarray(self._token),
                jnp.asarray(self._pos), jnp.asarray(self._active),
                self._keys)

    def _decode_stats(self) -> dict:
        """Counts a decode span carries into the profiler's trace: slots
        the step advances and the tokens their contexts hold, both from
        the host-side slot mirror."""
        return {"active": int(self._active.sum()),
                "live_tokens": int(self._pos[self._active].sum())}

    def _set_kv_state(self, state) -> None:
        if self.paged:
            self._store = state
        else:
            self.caches = state

    def decode_step(self, ctx: Optional[dict] = None) -> dict[int, int]:
        """Advance every active slot one token (ONE compiled call for the
        whole pool); returns ``{slot: token}`` for the active slots. No-op
        ({}) when nothing is active. ``ctx`` (request/trace ids from the
        scheduler) labels the watchdog window."""
        if not self._active.any():
            return {}
        # the fetch (np.asarray) is inside the watchdog window on purpose:
        # a wedged collective hangs exactly there, and that is the hang
        # the serving watchdog exists to turn into a loud abort
        with self._watched("serving decode_step", **(ctx or {})), \
                annotate("chainermn.serving_decode", **self._decode_stats()):
            inject(SERVING_DECODE, active=int(self._active.sum()))
            with annotate("chainermn.serving_decode_args"):
                args = self._decode_args()
            with annotate("chainermn.serving_decode_dispatch"):
                state, nxt, self._keys = self._decode_fn(*args)
                self._set_kv_state(state)
            with annotate("chainermn.serving_decode_fetch"):
                nxt = self._take_moe_counts(device_fetch(nxt), self.n_slots)
        with annotate("chainermn.serving_decode_post"):
            self._c_decode_steps.inc()
            self._events.emit("decode_step", active=int(self._active.sum()))
            self._guard.check()
            if self.paged:
                self._state_tokens += (int(self._active.sum())
                                       * self._state_layers)
                self._decode_rows += self._decode_rows_step
            out = {}
            for slot in np.flatnonzero(self._active):
                slot = int(slot)
                tok = int(nxt[slot])
                self._token[slot] = tok
                self._pos[slot] += 1
                out[slot] = tok
        return out

    def decode_steps(self, ctx: Optional[dict] = None
                     ) -> dict[int, list[int]]:
        """Advance every active slot ``decode_window`` tokens in ONE
        device dispatch (the fori_loop program — PERF.md "Dispatch
        amortization"); returns ``{slot: [tokens...]}`` in generation
        order. The token stream is identical to ``decode_window`` calls
        of :meth:`decode_step` (same per-slot key splits); the scheduler
        retires mid-window and discards the tail past EOS/budget."""
        if self.decode_window < 2:
            raise RuntimeError(
                "decode_steps needs ServingEngine(decode_window=n>1)")
        if not self._active.any():
            return {}
        n = self.decode_window
        with self._watched("serving decode_steps", **(ctx or {})), \
                annotate("chainermn.serving_decode", **self._decode_stats()):
            inject(SERVING_DECODE, active=int(self._active.sum()), window=n)
            with annotate("chainermn.serving_decode_args"):
                args = self._decode_args()
            with annotate("chainermn.serving_decode_dispatch"):
                state, out, self._keys = self._window_fn(*args)
                self._set_kv_state(state)
            with annotate("chainermn.serving_decode_fetch"):
                out = device_fetch(out)
        with annotate("chainermn.serving_decode_post"):
            self._c_decode_steps.inc()
            self._events.emit("decode_step", active=int(self._active.sum()),
                              window=n)
            self._guard.check()
            res = {}
            for slot in np.flatnonzero(self._active):
                slot = int(slot)
                toks = [int(t) for t in out[slot]]
                self._token[slot] = toks[-1]
                self._pos[slot] += n
                res[slot] = toks
        return res

    def spec_decode_step(self, ctx: Optional[dict] = None
                         ) -> dict[int, list[int]]:
        """One speculative round for every active slot: draft ``k``
        tokens per slot (host-side drafter), verify the ``k+1``-token
        window in ONE target dispatch, and commit each slot's longest
        matching draft prefix plus the correction token (1..k+1 tokens —
        exactly the greedy stream, by the module's induction argument).
        Returns ``{slot: [tokens...]}``; blocks appended for rejected
        rows are rolled back so a mispredicted window never holds pool
        capacity."""
        if self._spec is None:
            raise RuntimeError(
                "spec_decode_step needs ServingEngine(speculative=...)")
        if not self._active.any():
            return {}
        k = self._spec.k
        drafts = self._drafter.propose(k)          # [n_slots, k] host int32
        tokens = np.concatenate([self._token[:, None], drafts], axis=1)
        # rows past valid land in the scratch block: a slot nearing
        # cache_len must not let the clamped table lookup hit a live row
        valid = np.where(self._active,
                         np.clip(self.cache_len - self._pos, 0, k + 1),
                         0).astype(np.int32)
        with self._watched("serving spec_verify", **(ctx or {})), \
                annotate("chainermn.serving_spec_verify"):
            inject(SERVING_SPEC_VERIFY, active=int(self._active.sum()), k=k)
            with annotate("chainermn.serving_decode_args"):
                args = (self._table_args(), jnp.asarray(tokens),
                        jnp.asarray(self._pos), jnp.asarray(valid),
                        jnp.asarray(self._active))
            with annotate("chainermn.serving_decode_dispatch"):
                self._store, g = self._spec_fn(self.params, self._store,
                                               *args)
            with annotate("chainermn.serving_decode_fetch"):
                g = device_fetch(g)
        with annotate("chainermn.serving_decode_post"):
            self._c_decode_steps.inc()
            self._events.emit("decode_step", active=int(self._active.sum()),
                              window=k + 1)
            self._guard.check()
            res = {}
            proposed = accepted = 0
            lengths = []
            spec_slots = {}
            for slot in np.flatnonzero(self._active):
                slot = int(slot)
                kd = min(k, int(valid[slot]) - 1)   # drafts that fit the slot
                a = 0
                while a < kd and int(drafts[slot, a]) == int(g[slot, a]):
                    a += 1
                toks = ([int(t) for t in drafts[slot, :a]]
                        + [int(g[slot, a])])
                self._token[slot] = toks[-1]
                self._pos[slot] += len(toks)
                self._drafter.on_commit(slot, toks)
                self._rollback_spec_blocks(slot)
                proposed += kd
                accepted += a
                lengths.append(a)
                spec_slots[slot] = (kd, a)
                res[slot] = toks
            self._spec_proposed_total += proposed
            self._spec_accepted_total += accepted
            self._last_spec_window = (proposed, accepted, lengths)
            self._last_spec_slots = spec_slots
        return res

    def _rollback_spec_blocks(self, slot: int) -> None:
        """Free blocks the verify window appended for rows that got
        rejected: keep the block the slot's NEXT write lands in, free
        every allocated entry strictly beyond it (back into the slot's
        reserved headroom, keeping ``reserved = worst-case remaining −
        held``). Shared prefix blocks are out of reach by construction —
        they cover only rows ``< len(prompt) <= pos``."""
        keep = min(int(self._pos[slot]) // self.kv_block_size + 1,
                   self._n_max)
        freed = 0
        for idx in range(keep, self._n_max):
            block = int(self._tables[slot, idx])
            if block == 0:
                continue
            self._pool.decref(block)
            self._slot_drops(slot, [block])
            self._tables[slot, idx] = 0
            self._slot_reserved[slot] += 1
            freed += 1
        if freed:
            self._events.emit("spec_rollback", slot=slot, blocks=freed,
                              pos=int(self._pos[slot]))

    def decode_round(self, ctx: Optional[dict] = None
                     ) -> dict[int, list[int]]:
        """One decode dispatch under whatever mode the engine was built
        with — the scheduler's single entry point. Speculative engines
        verify a draft window, ``decode_window`` engines run the
        fori_loop program, and the legacy engine wraps its single token
        in a one-element list."""
        if self._spec is not None:
            return self.spec_decode_step(ctx=ctx)
        if self.decode_window > 1:
            return self.decode_steps(ctx=ctx)
        return {slot: [tok]
                for slot, tok in self.decode_step(ctx=ctx).items()}

    @property
    def spec_enabled(self) -> bool:
        return self._spec is not None

    @property
    def last_spec_slots(self) -> dict:
        """``{slot: (kd, a)}`` of the last verify round (drafts that fit,
        drafts accepted) — the per-slot attribution the cost ledger
        splits accepted-vs-wasted verify work with. Unlike
        :meth:`pop_spec_window` this is NOT cleared on read."""
        return self._last_spec_slots

    def _take_moe_counts(self, fetched, rows: int):
        """``fetched[:rows]``, the tokens; what a program appended past them
        (:func:`_with_moe_counts`) is added to the counts
        :meth:`pop_moe_counts` hands on."""
        if len(fetched) > rows:
            self._moe_counts += fetched[rows:rows + 2]
        return fetched[:rows]

    def pop_moe_counts(self) -> Optional[tuple]:
        """``(local, total)``: expert assignments of the tokens processed
        since the last call, to experts held here and in all, cleared on
        read; ``None`` where no program counted any (a model whose expert
        layers hold every expert, or none). The scheduler drains it into
        :class:`~chainermn_tpu.serving.metrics.ServingMetrics`."""
        if not self._moe_counts[1]:
            return None
        local, total = (int(c) for c in self._moe_counts)
        self._moe_counts[:] = 0
        return local, total

    def pop_spec_window(self) -> Optional[tuple]:
        """``(proposed, accepted, accept_lengths)`` of the last verify
        round, cleared on read — the scheduler drains it into
        :class:`~chainermn_tpu.serving.metrics.ServingMetrics` right
        after delivering the round's tokens."""
        win, self._last_spec_window = self._last_spec_window, None
        return win

    def spec_stats(self) -> dict:
        """Cumulative speculative counters (empty dict when speculation
        is off)."""
        if self._spec is None:
            return {}
        prop = self._spec_proposed_total
        acc = self._spec_accepted_total
        return {
            "drafter": self._spec.drafter,
            "spec_k": self._spec.k,
            "spec_tokens_proposed": prop,
            "spec_tokens_accepted": acc,
            "accept_rate": (acc / prop) if prop else 0.0,
        }

    def slot_tokens_used(self, slot: int) -> int:
        """Current sequence depth of a slot (prompt + generated so far)."""
        return int(self._pos[slot]) + 1 if self._active[slot] else 0

    def release(self, slot: int) -> None:
        """Retire a slot (EOS / length / cancellation). The cache is NOT
        zeroed: the causal position mask makes stale rows unreachable to
        the next tenant (module docstring — pinned by the slot-reuse
        parity test)."""
        if slot in self.free_slots:
            return
        if self.paged:
            # give the slot's block references back: exclusively-owned
            # blocks free immediately, trie-shared ones stay resident for
            # the next hit (the store, not the slot, owns cached prefixes)
            for kv in self._kv:
                kv.free_slot(slot)
            # a half-prefilled chunked slot releases the same way: its
            # staged ids ARE _slot_blocks, so cancel/preempt/deadline
            # mid-chunk leaks nothing (replay reproduces the tokens from
            # the same prompt + rng)
            self._chunking.pop(slot, None)
        if self._drafter is not None:
            self._drafter.on_release(slot)
        self._active[slot] = False
        self.free_slots.add(slot)

    def restart(self) -> None:
        """Warm restart after an engine-side failure: fresh KV caches,
        cleared host slot mirrors, AND a fresh prefix store + emptied trie
        — all rebuilt together, with the SAME compiled programs (the new
        arrays have identical shapes/shardings, so nothing recompiles —
        pinned by the restart tests). The prefix index must reset with the
        store: a warm restart keeping a stale trie would "hit" on KV
        blocks that no longer exist and serve a new request another
        prompt's attention state. Needed because a failed call may have
        consumed the donated cache buffers; params are never donated and
        survive. The scheduler drives this from its exception boundary;
        every restart is a counted, event-logged recovery."""
        if self.model.tensor_axis is not None:
            self._init_tp_caches(self._comm)
        elif self.paged:
            self._store = self._place(self._init_paged_store())
        else:
            self.caches = self._place(init_kv_caches(
                self.model, self.n_slots, self.cache_len))
            if self.prefix_cache is not None:
                self._store = self._place(self._init_store())
        if self.paged:
            # drop the trie, the slot tables' references and reset the
            # pool wholesale — a stale table pinning blocks of a dead
            # store would leak capacity forever (and a stale ENTRY would
            # read KV that no longer exists)
            for kv in self._kv:
                kv.reset()
            self._chunking.clear()
        elif self.prefix_cache is not None:
            self.prefix_cache.clear()
        self._pending_inserts = []
        self._token[:] = 0
        self._pos[:] = 0
        self._active[:] = False
        self._keys = self._fresh_keys()
        self.free_slots = set(range(self.n_slots))
        if self._drafter is not None:
            self._drafter.reset()
        self._c_restarts.inc()
        self._events.emit("engine_restart")

    # ------------------------------------------------------------------ #
    # versioned weights (the deploy layer's swap surface)                 #
    # ------------------------------------------------------------------ #

    def swap_params(self, new_params, *, version: Optional[int] = None) -> int:
        """Commit a new param pytree in place; returns the new version.

        The caller (normally :class:`~chainermn_tpu.deploy.publish
        .WeightPublisher`, via the scheduler's swap fence) must hand over
        a tree with the EXACT structure, per-leaf shape/dtype, and
        shardings of the current params — sharding is part of the jit
        cache key, so an identically-committed tree makes the swap a
        pure pointer exchange: the compiled prefill/decode programs next
        run on the new weights with ZERO recompiles. Validation happens
        BEFORE anything is assigned, so a rejected swap leaves the
        engine bit-for-bit on its prior weights (never a half-written
        engine). Params are never donated (see :meth:`restart`), so the
        old tree stays alive for any caller-held reference.
        """
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        if new_def != old_def:
            raise EngineStateError(
                f"swap_params: tree structure mismatch — engine has "
                f"{old_def}, got {new_def}")
        for i, (old, new) in enumerate(zip(old_leaves, new_leaves)):
            if getattr(new, "shape", None) != old.shape or \
                    getattr(new, "dtype", None) != old.dtype:
                raise EngineStateError(
                    f"swap_params: leaf {i} is "
                    f"{getattr(new, 'shape', None)}/"
                    f"{getattr(new, 'dtype', None)}, engine compiled "
                    f"against {old.shape}/{old.dtype}")
            old_sh = getattr(old, "sharding", None)
            new_sh = getattr(new, "sharding", None)
            if old_sh is not None and (
                    new_sh is None
                    or not new_sh.is_equivalent_to(old_sh, old.ndim)):
                raise EngineStateError(
                    f"swap_params: leaf {i} sharding {new_sh} is not "
                    f"equivalent to the warmup-compiled {old_sh} — "
                    "device_put against engine.params shardings first "
                    "(jit cache key discipline)")
        self.params = new_params
        self.weight_version = (int(version) if version is not None
                               else self.weight_version + 1)
        self._g_weight_version.set(self.weight_version)
        self._events.emit("weight_swap", version=self.weight_version)
        return self.weight_version

    # ------------------------------------------------------------------ #
    # observability                                                       #
    # ------------------------------------------------------------------ #

    def compile_counts(self) -> dict[str, int]:
        """Executable counts of the prefill family (summed over buckets)
        and the decode program — the zero-recompile invariant is
        ``{'prefill': len(buckets), 'decode': 1}`` after warmup, asserted
        by tests and logged by the benchmark's serving harness."""
        return {
            "prefill": sum(int(fn._cache_size())
                           for fn in self._prefill_fns.values()),
            "decode": int(self._decode_fn._cache_size()),
        }

    def compile_counts_detailed(self) -> dict[str, int]:
        """Per-program executable counts (every bucket + decode + the
        prefix-copy pair) — each must be exactly 1 after :meth:`warmup`."""
        out = {f"prefill_{b}": int(fn._cache_size())
               for b, fn in self._prefill_fns.items()}
        out["decode"] = int(self._decode_fn._cache_size())
        if self.migration_supported:
            for w in self._mig_buckets:
                out[f"kv_gather_{w}"] = int(
                    self._kv_gather_fns[w]._cache_size())
                out[f"kv_scatter_{w}"] = int(
                    self._kv_scatter_fns[w]._cache_size())
        if self.prefix_cache is not None and not self.paged:
            out["prefix_insert"] = int(self._insert_fn._cache_size())
        if self._spec is not None:
            out["spec_verify"] = int(self._spec_fn._cache_size())
            out.update(self._drafter.compile_counts())
        if self.decode_window > 1:
            out["decode_window"] = int(self._window_fn._cache_size())
        return out

    @property
    def recompiles(self) -> dict[str, int]:
        """Recompiles observed past each program's warmup compile (the
        guard's live count; empty == the invariant holds)."""
        return self._guard.recompiles

    def decode_program_text(self) -> str:
        """The decode step as lowered for this backend (StableHLO text):
        what an on-chip check reads to see which attention read path the
        program really holds — a Mosaic ``tpu_custom_call``, the
        interpreted kernel, or the XLA gather. Lowering traces but
        compiles and runs nothing, so ``compile_counts`` do not move."""
        return self._decode_fn.lower(*self._decode_args()).as_text()

    def prefix_stats(self) -> dict:
        """The prefix cache's hit/eviction/occupancy numbers (empty dict
        when disabled)."""
        return self.prefix_cache.stats() if self.prefix_cache else {}

    def occupancy(self) -> dict:
        """Cheap host-side occupancy snapshot — the fleet router's
        occupancy-aware-admission input (no device call, no locks beyond
        numpy reads): slot fill, free-KV fraction (paged engines count
        blocks; dense engines count free slots), and whether the prefix
        trie is live on this engine."""
        active = self.active_slots
        if self.paged:
            kv_free_frac = min(
                kv.pool.free_blocks / max(kv.pool.capacity, 1)
                for kv in self._kv)
        else:
            kv_free_frac = len(self.free_slots) / max(self.n_slots, 1)
        return {
            "n_slots": self.n_slots,
            "active_slots": active,
            "free_slots": len(self.free_slots),
            "kv_free_frac": round(float(kv_free_frac), 4),
            "prefix_enabled": self.prefix_enabled,
            "paged": self.paged,
            "warm": self._warm,
            "weight_version": self.weight_version,
        }


__all__ = ["AdmitPlan", "ChunkedPrefill", "EngineStateError",
           "ServingEngine"]
