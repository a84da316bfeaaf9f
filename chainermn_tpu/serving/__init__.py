"""``chainermn_tpu.serving`` — continuous-batching inference over the
static KV-cache decode path.

The training side of the framework ends at offline decoding
(:func:`chainermn_tpu.models.generate`: one fixed batch, start to finish).
This package is the traffic-facing counterpart — the ROADMAP's
"serving heavy traffic" axis — built from four layers:

- :class:`~chainermn_tpu.serving.engine.ServingEngine` — mechanism: a
  fixed pool of cache slots in one persistent static-shape KV cache, a
  small fixed family of compiled programs (bucketed batched ``prefill``
  — one program per padded-length bucket; ``prefill_batch`` is its rows
  at the smallest bucket, and a program holds at most ``prefill_batch x
  prefill_buckets[0]`` tokens, so longer buckets have fewer rows
  (``prefill_rows``) — the all-slots ``decode_step``,
  and the prefix-copy pair), zero recompiles after :meth:`warmup`,
  tensor-parallel via ``comm.shard_map``;
- :class:`~chainermn_tpu.serving.prefix_cache.PrefixCacheIndex` — prefix
  KV reuse: a host-side ref-counted trie over token blocks backed by a
  device block store; on admission the longest cached prefix is copied
  slot-locally and only the uncached suffix prefills (LRU eviction on
  ref-zero leaves). With ``ServingEngine(paged=True)`` the SAME store
  becomes the single KV substrate (:class:`~chainermn_tpu.serving.
  prefix_cache.BlockPool`): decode slots address it through block
  tables, hits are zero-copy shared entries, and admission is budgeted
  in blocks instead of worst-case slot regions;
- :class:`~chainermn_tpu.serving.scheduler.FCFSScheduler` — policy: FCFS
  admission into freed slots between decode steps (cost-aware grouping:
  same-bucket batches preferring shared cached prefixes, bounded prefill
  interleave per decode step), request state machine, EOS/length
  retirement, cancellation;
- :class:`~chainermn_tpu.serving.metrics.ServingMetrics` — observability:
  TTFT/TPOT percentiles, tokens/s, queue depth, slot occupancy (the same
  reporting convention as ``extensions.StepTimer``);
- :class:`~chainermn_tpu.serving.client.ServingClient` — the in-process
  front: background engine thread, blocking and per-token streaming APIs.

Correctness invariant (pinned in ``tests/serving_tests``): requests
admitted at staggered times into the shared slot pool produce
token-for-token the same outputs as isolated ``generate()`` calls with
the same params and rng.

Everything here is ONE engine — one slot pool, one mesh, one failure
domain. The multi-replica tier (N engines behind a prefix-affinity,
occupancy-aware router with replica-level failover) is
:mod:`chainermn_tpu.fleet`, which drives these classes unchanged.
"""

from chainermn_tpu.serving.client import ServingClient
from chainermn_tpu.serving.engine import (
    AdmitPlan,
    EngineStateError,
    ServingEngine,
)
from chainermn_tpu.serving.fairness import (
    BrownoutPolicy,
    FairAdmission,
)
from chainermn_tpu.serving.metrics import ServingMetrics
from chainermn_tpu.serving.prefix_cache import (
    BlockPool,
    PrefixCacheIndex,
    PrefixMatch,
)
from chainermn_tpu.serving.scheduler import (
    DeadlineExceededError,
    EngineFailed,
    FCFSScheduler,
    QueueFullError,
    Request,
    RequestState,
)
from chainermn_tpu.serving.speculative import SpeculativeConfig

__all__ = [
    "AdmitPlan",
    "BlockPool",
    "BrownoutPolicy",
    "DeadlineExceededError",
    "EngineFailed",
    "EngineStateError",
    "FCFSScheduler",
    "FairAdmission",
    "PrefixCacheIndex",
    "PrefixMatch",
    "QueueFullError",
    "Request",
    "RequestState",
    "ServingClient",
    "ServingEngine",
    "ServingMetrics",
    "SpeculativeConfig",
]
