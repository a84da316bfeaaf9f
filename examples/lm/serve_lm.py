#!/usr/bin/env python
"""Continuous-batching LM serving demo — the traffic-facing counterpart of
``train_lm.py``.

Builds a small TransformerLM, stands up the in-process serving stack
(:mod:`chainermn_tpu.serving`: slot-pool KV-cache engine + FCFS scheduler +
background client thread), and pushes a burst of ragged random prompts
through it: some blocking, one streamed token-by-token. Prints the serving
metrics (TTFT/TPOT percentiles, tokens/s, slot occupancy) at the end.

And the continuous-telemetry demo (ISSUE 15): ``--health`` runs a
background collector sampling every registry instrument into ring-buffer
time series at ``--ts-cadence``, scores each replica
healthy/degraded/critical through the standard detector set (TTFT p99
drift, queue-depth threshold, decode-stall deadman), prints the verdict
at the end, and — with ``--http-port`` — serves live ``/timeseries`` and
``/health`` JSON it then scrapes back over the real socket.

Also the telemetry demo: the burst runs inside a
:func:`chainermn_tpu.monitor.annotate` profiler scope (capture with
``jax.profiler.trace`` and the span shows up named in XProf/Perfetto),
``--watchdog SECONDS`` arms the engine's hang watchdog (a wedged
collective dumps the flight recorder + thread stacks instead of hanging
the client), and ``--prometheus`` prints the process-wide
:func:`chainermn_tpu.monitor.exposition` text — the same series a
Prometheus scraper would pull.

And the graceful-degradation demo: ``--max-queue N`` bounds the admission
queue (overflow submissions are rejected with ``QueueFullError`` —
backpressure at the submitter) and ``--deadline SECONDS`` sheds requests
still queued past their deadline (``wait()`` raises
``DeadlineExceededError`` instead of blocking on work that will never
start). See README "Fault tolerance".

And the admission fast path (PR 5): ``--prefill-buckets`` pads prompts to
a small ladder of bucketed lengths instead of one ``--prefill-len``,
``--prefill-batch N`` admits up to N smallest-bucket requests per compiled
prefill call (a longer bucket's program has N x smallest bucket / bucket
rows), and ``--prefix-blocks N`` turns on ref-counted prefix KV
reuse — with ``--shared-prefix M`` every burst prompt shares an M-token
system prompt, so admissions prefill only their ragged tails (the prefix
stats print at the end: hit rate, evictions, store occupancy).

And the paged KV store (PR 7): ``--paged-kv`` runs decode on one shared
block store with per-slot block tables — slots stop reserving worst-case
``cache_len`` regions, so the same device memory serves 4x+ more
concurrent requests, prefix hits become zero-copy shared table entries,
and ``--kv-quant int8`` halves resident KV bytes again; ``--kv-blocks``
caps the pool (admission then defers to the queue, and a dry pool
preempts+requeues the newest request instead of failing it).

And the serving fleet (ISSUE 8): ``--replicas N`` runs N engine replicas
(each with its own warmup'd programs, slot pool, and prefix store) behind
a ``FleetRouter`` — prefix-affinity + occupancy-aware routing
(``--no-affinity`` for pure least-loaded), a global ``--max-queue`` shed
at the fleet edge, and replica-level failover; the fleet report (replica
states, affinity hit rate, fleet-pooled TTFT percentiles) prints at the
end, and ``--verify-parity`` checks the first few outputs token-for-token
against solo ``generate()``.

And speculative decode (PR 12): ``--speculate ngram`` drafts k tokens per
round from the request's own prefix (prompt-lookup n-grams — no second
model) and verifies them in ONE target call, committing every leading
match plus the free correction token; ``--speculate draft`` drafts with a
small TransformerLM instead. Greedy-only (``--temperature 0``) and paged
(``--paged-kv``): accepted tokens commit straight into shared block-store
blocks, rejected rows roll back. ``--spec-k`` sets the draft window; the
accept rate and proposed/accepted totals print at the end.

And the weight lifecycle (ISSUE 10): ``--reshard-from <dir>`` restores
the serving params from a ``ShardedCheckpointer`` snapshot directory
through ``deploy.elastic_restore`` — a snapshot saved while training at
one mesh shape / TP degree serves at another (the manifest's save-time
geometry drives the fused-qkv layout permutation); pair with
``train_lm.py --snapshot-to`` for the train→reshard→serve chain, or with
``train_lm.py --publish-to engine`` for the online hot-swap variant.

And the closed-loop control plane (ISSUE 16): ``--autoscale`` runs a
background :class:`~chainermn_tpu.fleet.control.FleetController` over
the fleet — sustained queue pressure spawns replicas (up to
``--max-replicas``), sustained idleness retires them (down to
``--min-replicas``), and ``--canary`` then demonstrates an SLO-guarded
canary deploy end to end: bumped weights swap onto ONE replica, bake,
and promote fleet-wide (or auto-rollback on regression), with the
controller's decision ring and version history printed at the end and
served live at ``/control`` with ``--http-port``.

And overload robustness (ISSUE 18): ``--priority mixed`` labels every
other burst request ``batch`` (``batch`` runs only when the interactive
queue is drained, and is preempted FIRST when the KV pool runs dry),
``--tenant-weights "tenant0=4,tenant1=1"`` turns on weighted
deficit-round-robin admission over the ``--tenants`` labels (weights
shrink automatically for tenants over their measured device-second
share), and ``--brownout N`` arms the degradation ladder up to level N —
sustained interactive backlog steps pause-batch -> single-token decode ->
max-new cap -> shed-lowest-weight-tenant, each step edge-logged and fully
reversible once the queue drains; the episode (levels hit, steps, final
level) prints at the end next to the per-tenant cost table.

And chunked prefill + disaggregated tiers (ISSUE 19): ``--chunk-tokens
N`` (with ``--paged-kv``) prefills long prompts N tokens per scheduler
step interleaved with decode — resident streams stop stalling for whole
long prefills; ``--prefill-replicas P --decode-replicas D`` splits the
fleet into tiers: new requests prefill on the first P replicas, then
their KV blocks migrate host-bounce to a decode replica (same token
stream, rng and position ride along; a failed migration just decodes in
place). The migration counters print with the fleet report.

And fleet-wide KV reuse (ISSUE 20): ``--share-prefixes`` (paged fleet,
affinity on) turns an affinity MISS on a prompt whose prefix another
replica holds into a prefix hit — the holder exports the cached blocks
once through the fused migration gather, a host-side payload LRU serves
every later adopter, and the routed replica imports them before the
request admits, prefilling only the uncached suffix; ``--rebalance``
probes mid-stream decode rebalancing — while the burst is in flight the
router migrates one live decode from the busiest replica to the least
loaded, and the victim finishes token-exactly on its new home. The
share/rebalance counters and payload-cache stats print with the fleet
report.

Run (CPU mesh; any accelerator works the same)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --requests 16 --slots 4 --prometheus

    # two replicas behind the prefix-affinity router:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --replicas 2 --shared-prefix 4 \
        --prefix-blocks 16 --prefix-block-size 2 --verify-parity

    # shared-system-prompt traffic through the prefix-cached fast path:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --shared-prefix 12 \
        --prefill-buckets 4,16 --prefill-batch 4 --prefix-blocks 32 \
        --prefix-block-size 2

    # tensor-parallel decode through the same scheduler:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --tensor-parallel

    # speculative decode on the paged store (prompt-lookup drafting):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --paged-kv --temperature 0 \
        --speculate ngram --spec-k 4

    # closed-loop autoscaling + a canary deploy through the controller:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --autoscale --min-replicas 1 \
        --max-replicas 3 --slots 1 --requests 24 --canary

    # chunked prefill + disaggregated prefill/decode tiers:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --paged-kv --chunk-tokens 4 \
        --prefill-replicas 1 --decode-replicas 1 --verify-parity

    # fleet-wide KV reuse: cross-replica prefix sharing + a mid-stream
    # decode-rebalance probe:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/serve_lm.py --replicas 2 --paged-kv \
        --kv-block-size 2 --shared-prefix 12 --share-prefixes \
        --rebalance --verify-parity
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import chainermn_tpu
from chainermn_tpu import monitor
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.serving import (
    QueueFullError,
    ServingClient,
    ServingEngine,
)
from chainermn_tpu.utils import enable_compilation_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slots", type=int, default=4,
                    help="cache slots = max concurrent decodes")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prefill-len", type=int, default=16,
                    help="prompts are padded to this length (one compile)")
    ap.add_argument("--prefill-buckets", default="",
                    help="comma-separated padded-length ladder (e.g. "
                         "'4,16'): each admission runs the smallest "
                         "bucket covering its (suffix) length — less "
                         "padding waste for one extra compile per bucket "
                         "(empty: single prefill-len bucket)")
    ap.add_argument("--prefill-batch", type=int, default=1,
                    help="rows of the smallest bucket's prefill program; "
                         "a program holds at most this x the smallest "
                         "bucket in tokens, so longer buckets admit fewer "
                         "requests per device call")
    ap.add_argument("--prefix-blocks", type=int, default=0,
                    help="enable ref-counted prefix KV reuse with this "
                         "many device store blocks: requests sharing a "
                         "cached prompt prefix prefill only their suffix "
                         "(0: off)")
    ap.add_argument("--prefix-block-size", type=int, default=4,
                    help="tokens per prefix-cache block (matches are "
                         "multiples of this)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every burst prompt a shared system-prompt "
                         "prefix of this many tokens — the workload "
                         "prefix caching exists for (0: fully ragged)")
    ap.add_argument("--paged-kv", action="store_true",
                    help="paged KV decode: one shared block store under "
                         "every slot (block-table indexed), admission by "
                         "free blocks instead of worst-case slot "
                         "regions — 4x+ more concurrent requests at the "
                         "same device KV memory; prefix hits become "
                         "zero-copy shared table entries")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged: total store blocks incl. the scratch "
                         "block (0: dense-equivalent capacity, "
                         "slots x ceil(cache_len/block))")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged: tokens per KV block")
    ap.add_argument("--kv-quant", choices=("none", "int8"), default="none",
                    help="paged: int8-quantize resident blocks (per-row "
                         "per-head scales, ~2x less KV memory; small "
                         "tested logit perturbation)")
    ap.add_argument("--speculate", choices=("off", "ngram", "draft"),
                    default="off",
                    help="speculative decode on the paged store: draft k "
                         "tokens per round (ngram: prompt-lookup from the "
                         "request's own prefix, no second model; draft: a "
                         "small draft TransformerLM), verify them in ONE "
                         "target call, commit every leading match + the "
                         "correction token. Needs --paged-kv and "
                         "--temperature 0 (greedy-only)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative draft window: tokens proposed per "
                         "verify round")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked prefill (ISSUE 19, needs --paged-kv): "
                         "prefill long prompts this many tokens per "
                         "scheduler step, interleaved with decode of "
                         "resident slots, instead of one monolithic "
                         "bucket call (0: off)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run this many engine replicas behind the fleet "
                         "router (1: the plain single-engine client)")
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="disaggregated tiers (ISSUE 19, needs "
                         "--paged-kv and --decode-replicas): the first P "
                         "replicas take every new request's prefill; on "
                         "completion the KV blocks migrate host-bounce "
                         "to a decode-tier replica (0: symmetric fleet)")
    ap.add_argument("--decode-replicas", type=int, default=0,
                    help="disaggregated tiers: replicas that only take "
                         "migrated-in decode work (give with "
                         "--prefill-replicas; the fleet size is P+D)")
    ap.add_argument("--share-prefixes", action="store_true",
                    help="cross-replica prefix sharing (ISSUE 20, needs "
                         "a --paged-kv fleet with affinity): an affinity "
                         "miss on a prompt whose prefix another replica "
                         "holds exports those blocks through the fused "
                         "migration path (cached host-side, LRU) and "
                         "imports them into the routed replica BEFORE "
                         "admission — only the uncached suffix prefills")
    ap.add_argument("--rebalance", action="store_true",
                    help="mid-stream decode rebalancing probe (ISSUE 20, "
                         "needs a --paged-kv fleet): while the burst is "
                         "in flight, migrate one live decode from the "
                         "busiest replica to the least loaded — the "
                         "victim finishes token-exactly on its new home")
    ap.add_argument("--affinity", dest="affinity", action="store_true",
                    default=True,
                    help="prefix-affinity routing (default): requests "
                         "sharing a cached prefix go to the replica whose "
                         "trie holds it, within the load-imbalance bound")
    ap.add_argument("--no-affinity", dest="affinity", action="store_false",
                    help="pure occupancy-aware least-loaded routing")
    ap.add_argument("--autoscale", action="store_true",
                    help="closed-loop fleet control (ISSUE 16): a "
                         "background FleetController scales the fleet "
                         "between --min-replicas and --max-replicas on "
                         "sustained queue pressure / idleness (implies "
                         "fleet mode and the --health telemetry wiring)")
    ap.add_argument("--min-replicas", type=int, default=1,
                    help="autoscale floor (also the starting fleet size "
                         "when --autoscale is given without --replicas)")
    ap.add_argument("--max-replicas", type=int, default=3,
                    help="autoscale ceiling")
    ap.add_argument("--canary", action="store_true",
                    help="after the burst, deploy bumped weights through "
                         "the controller's canary path: one replica "
                         "takes them, bakes for --canary-bake seconds "
                         "against the fleet health/SLO baseline, then "
                         "promotes fleet-wide (or auto-rollbacks on "
                         "regression); needs --autoscale")
    ap.add_argument("--canary-bake", type=float, default=1.0,
                    help="canary bake window in seconds (--canary)")
    ap.add_argument("--reshard-from", default="",
                    help="restore the serving params from a "
                         "ShardedCheckpointer snapshot directory through "
                         "deploy.elastic_restore: the manifest's "
                         "save-time TP degree is resharded onto THIS "
                         "run's layout (dense or --tensor-parallel at "
                         "any degree), so a training snapshot serves "
                         "directly — see train_lm.py --snapshot-to")
    ap.add_argument("--verify-parity", action="store_true",
                    help="after the burst, check the first few completed "
                         "requests token-for-token against solo "
                         "generate() with the same rng")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--eos-id", type=int, default=1,
                    help="token retiring a request early (-1: disabled)")
    ap.add_argument("--tensor-parallel", action="store_true",
                    help="shard heads over the mesh; decode runs inside "
                         "the communicator's shard_map")
    ap.add_argument("--watchdog", type=float, default=0.0,
                    help="arm the engine hang watchdog: a decode step "
                         "exceeding this many seconds dumps the flight "
                         "recorder + thread stacks and aborts (0: off)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: submissions beyond this "
                         "many queued requests are rejected with "
                         "QueueFullError — backpressure instead of "
                         "unbounded queueing (0: unbounded)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds: work still "
                         "queued past it is shed (terminal ERRORED, "
                         "wait() raises DeadlineExceededError) instead of "
                         "occupying a slot too late to matter (0: off)")
    ap.add_argument("--prometheus", action="store_true",
                    help="print the Prometheus text exposition of the "
                         "process metrics registry at the end")
    ap.add_argument("--trace", type=int, default=1, metavar="N",
                    help="trace every Nth request (span tree: queue -> "
                         "admit -> prefill -> decode -> retire; "
                         "shed/errored requests are always kept). "
                         "0 disables tracing")
    ap.add_argument("--trace-out", default="",
                    help="write retained traces as Chrome trace-event "
                         "JSON to this path — load it in "
                         "chrome://tracing or ui.perfetto.dev")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="declare a TTFT p99 SLO at this many ms and "
                         "print the multi-window burn-rate evaluation "
                         "at the end (0: no SLO)")
    ap.add_argument("--http-port", type=int, default=-1,
                    help="serve the monitor scrape endpoints (/metrics "
                         "/traces /slo /events) on this port for the "
                         "duration of the burst (0: ephemeral; -1: off)")
    ap.add_argument("--health", action="store_true",
                    help="continuous telemetry (ISSUE 15): a background "
                         "collector samples every registry instrument "
                         "into ring-buffer time series, the standard "
                         "detector set (TTFT drift, queue threshold, "
                         "decode-stall deadman) scores each replica "
                         "healthy/degraded/critical, and the verdict "
                         "prints at the end; with --http-port the "
                         "/timeseries and /health endpoints serve live "
                         "JSON")
    ap.add_argument("--ts-cadence", type=float, default=0.05,
                    help="collector sampling cadence in seconds "
                         "(--health)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="cost accounting (ISSUE 17): label the burst's "
                         "requests with this many synthetic tenants "
                         "(round-robin) and print the per-tenant cost "
                         "table — device seconds by kind, KV "
                         "block-seconds, queue wait — plus the fleet "
                         "goodput breakdown at the end; with "
                         "--http-port the /costs endpoint serves the "
                         "same JSON live (1: everything bills to "
                         "'default')")
    ap.add_argument("--priority", choices=("interactive", "batch", "mixed"),
                    default="interactive",
                    help="admission class for the burst's requests "
                         "(ISSUE 18): 'batch' marks them all "
                         "best-effort (admitted only when the "
                         "interactive queue is drained, preempted first "
                         "when KV runs dry), 'mixed' alternates the two "
                         "classes request by request")
    ap.add_argument("--tenant-weights", default="",
                    help="weighted-fair tenant admission (ISSUE 18): "
                         "comma-separated 'name=weight' pairs over the "
                         "--tenants labels (e.g. 'tenant0=4,tenant1=1') "
                         "— admission runs deficit-round-robin over "
                         "per-tenant token budgets, and a tenant over "
                         "its measured device-second share has its "
                         "effective weight shrunk (empty: FIFO within "
                         "each class)")
    ap.add_argument("--brownout", type=int, default=0,
                    help="arm the brownout degradation ladder up to "
                         "this level (1: pause batch, 2: +single-token "
                         "decode, 3: +max-new cap, 4: +shed lowest-"
                         "weight tenant); sustained interactive backlog "
                         "steps up, a drained queue steps back down, "
                         "and the episode prints at the end (0: off)")
    args = ap.parse_args()
    enable_compilation_cache()

    comm = chainermn_tpu.create_communicator("tpu") if args.tensor_parallel \
        else None
    model = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_layers=args.layers, max_len=args.prefill_len + args.max_new,
        tensor_axis=comm.axis_name if comm else None,
    )
    rng = np.random.RandomState(0)
    init_tok = jnp.zeros((1, args.prefill_len), jnp.int32)
    if comm is not None:
        from jax.sharding import PartitionSpec as P

        params = jax.jit(comm.shard_map(
            lambda t: model.init(jax.random.PRNGKey(0), t),
            in_specs=P(), out_specs=P(),
        ))(init_tok)
    else:
        params = model.init(jax.random.PRNGKey(0), init_tok)

    if args.reshard_from:
        # elastic restore (ISSUE 10): the fresh-init params are only the
        # restore TEMPLATE (structure + target shardings); a snapshot
        # saved on a different mesh shape or TP degree is gathered,
        # qkv-permuted per the manifest, and re-sliced onto this layout
        from chainermn_tpu.deploy import elastic_restore
        from chainermn_tpu.extensions.sharded_checkpoint import (
            ShardedCheckpointer,
        )

        with ShardedCheckpointer(args.reshard_from) as cp:
            mf = cp.manifest() or {}
            restored, step = elastic_restore(
                cp, {"params": params}, comm=comm, model=model)
        if restored is None:
            raise SystemExit(
                f"--reshard-from {args.reshard_from}: no snapshot found")
        params = restored["params"]
        print(f"resharded snapshot step {step}: save-time tp_degree="
              f"{mf.get('tp_degree', 1)} -> serving tp_degree="
              f"{comm.size if comm else 1}")

    buckets = (tuple(int(b) for b in args.prefill_buckets.split(","))
               if args.prefill_buckets else None)
    paged_kw = {}
    if args.paged_kv:
        paged_kw = dict(paged=True, kv_blocks=args.kv_blocks or None,
                        kv_block_size=args.kv_block_size,
                        kv_quant=args.kv_quant)
        if args.prefix_blocks:
            raise SystemExit("--paged-kv unifies the prefix cache onto the "
                             "shared block store; drop --prefix-blocks and "
                             "size it with --kv-blocks/--kv-block-size")
    spec_cfg = None
    if args.speculate != "off":
        from chainermn_tpu.serving import SpeculativeConfig

        if not args.paged_kv:
            raise SystemExit("--speculate commits accepted tokens into "
                             "shared block-store blocks; add --paged-kv")
        if args.temperature != 0.0:
            raise SystemExit("--speculate verifies drafts against the "
                             "greedy argmax; pass --temperature 0")
        if args.speculate == "draft":
            draft_model = TransformerLM(
                vocab_size=args.vocab, d_model=max(16, args.d_model // 2),
                n_heads=max(1, args.heads // 2), n_layers=1,
                max_len=args.prefill_len + args.max_new,
            )
            draft_params = draft_model.init(jax.random.PRNGKey(2),
                                            init_tok)
            spec_cfg = SpeculativeConfig(k=args.spec_k, drafter="draft",
                                         draft_model=draft_model,
                                         draft_params=draft_params)
        else:
            spec_cfg = SpeculativeConfig(k=args.spec_k)
    engine_kw = dict(
        speculative=spec_cfg,
        n_slots=args.slots, prefill_len=args.prefill_len,
        prefill_buckets=buckets, prefill_batch=args.prefill_batch,
        prefix_cache_blocks=args.prefix_blocks,
        prefix_block_size=args.prefix_block_size,
        temperature=args.temperature, comm=comm,
        watchdog=args.watchdog or None, **paged_kw,
    )
    if args.canary and not args.autoscale:
        raise SystemExit("--canary deploys through the controller; add "
                         "--autoscale")
    # overload robustness (ISSUE 18): weighted-fair admission + the
    # brownout ladder ride the same scheduler kwargs in both the
    # single-engine client and every fleet replica
    fair_kw = {}
    if args.tenant_weights:
        weights = {}
        for pair in args.tenant_weights.split(","):
            name, _, w = pair.partition("=")
            if not w:
                raise SystemExit(f"--tenant-weights: '{pair}' is not "
                                 "name=weight")
            weights[name.strip()] = float(w)
        fair_kw = dict(fair=True, tenant_weights=weights)
    brownout_policy = None
    if args.brownout:
        from chainermn_tpu.serving.fairness import BrownoutPolicy

        brownout_policy = BrownoutPolicy(
            max_level=args.brownout, queue_high=float(args.slots),
            up_after_s=0.05, down_after_s=0.2, cooldown_s=0.1)
        fair_kw["brownout"] = brownout_policy
    if args.chunk_tokens and not args.paged_kv:
        raise SystemExit("--chunk-tokens stages chunks on the shared "
                         "block store; add --paged-kv")
    tiered = bool(args.prefill_replicas or args.decode_replicas)
    if tiered:
        if not (args.prefill_replicas and args.decode_replicas):
            raise SystemExit("disaggregated tiers need BOTH "
                             "--prefill-replicas and --decode-replicas")
        if not args.paged_kv:
            raise SystemExit("KV migration moves block-store rows; the "
                             "tiers need --paged-kv")
        if args.autoscale:
            raise SystemExit("--autoscale resizes a symmetric fleet; "
                             "static tiers don't mix with it")
    fleet_mode = args.replicas > 1 or args.autoscale or tiered
    if args.share_prefixes or args.rebalance:
        if not args.paged_kv:
            raise SystemExit("--share-prefixes/--rebalance move "
                             "block-store rows; add --paged-kv")
        if not fleet_mode:
            raise SystemExit("--share-prefixes/--rebalance need a fleet; "
                             "add --replicas 2 (or more)")
        if args.share_prefixes and not args.affinity:
            raise SystemExit("--share-prefixes finds holders through the "
                             "affinity trie; drop --no-affinity")
    n_start = (args.prefill_replicas + args.decode_replicas if tiered
               else max(args.replicas, args.min_replicas)
               if args.autoscale else args.replicas)
    eos = None if args.eos_id < 0 else args.eos_id
    if fleet_mode:
        from chainermn_tpu.fleet import FleetRouter

        engines = [ServingEngine(model, params, **engine_kw)
                   for _ in range(n_start)]
        engine = engines[0]
        tier_kw = dict(prefill_replicas=args.prefill_replicas,
                       decode_replicas=args.decode_replicas) if tiered \
            else {}
        front = FleetRouter(engines, eos_id=eos, affinity=args.affinity,
                            max_queue=args.max_queue or None,
                            default_deadline_s=args.deadline or None,
                            chunk_tokens_per_step=args.chunk_tokens
                            or None,
                            share_prefixes=args.share_prefixes,
                            **tier_kw, **fair_kw)
        front.wait_ready(600)   # every replica warm, off the burst clock
    else:
        engine = ServingEngine(model, params, **engine_kw)
        engine.warmup()   # every bucket + decode compile once, off the burst
        front = ServingClient(engine, eos_id=eos,
                              max_queue=args.max_queue or None,
                              default_deadline_s=args.deadline or None,
                              chunk_tokens_per_step=args.chunk_tokens
                              or None, **fair_kw)

    collector = None
    if args.health or args.autoscale:
        from chainermn_tpu.monitor.health import (
            HealthMonitor,
            fleet_health,
            standard_replica_sensors,
        )
        from chainermn_tpu.monitor.timeseries import Collector

        if fleet_mode:
            # per-replica sensors + lifecycle probes + routing penalty,
            # wired in one call
            collector = fleet_health(front, cadence_s=args.ts_cadence,
                                     stall_timeout_s=30.0)
        else:
            collector = Collector(cadence_s=args.ts_cadence)
            inst = front.metrics.instance
            sigs, dets = standard_replica_sensors(
                inst, stall_timeout_s=30.0, tag="0")
            for sg in sigs:
                collector.add_signal(sg)
            for dt in dets:
                collector.add_detector(dt)
            health_mon = HealthMonitor(store=collector.store)
            health_mon.watch("0", detectors=dets)
            collector.attach_health(health_mon)
            front.metrics.attach_health(
                lambda m=health_mon: m.score_json("0"))
        collector.start()

    controller = None
    if args.autoscale:
        from chainermn_tpu.fleet import (
            AutoscalePolicy,
            CanaryPolicy,
            FleetController,
        )

        controller = FleetController(
            front, collector,
            engine_factory=lambda: ServingEngine(model, params,
                                                 **engine_kw),
            autoscale=AutoscalePolicy(
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas,
                queue_high=1.0, idle_low=0.25, up_after_s=0.2,
                down_after_s=1.0, cooldown_s=0.3),
            canary=CanaryPolicy(bake_s=args.canary_bake),
            cadence_s=0.05, sensor_kw=dict(stall_timeout_s=30.0))
        controller.start()

    monitor.get_tracer().configure(sample=args.trace)
    slo_engine = None
    if args.slo_ttft_ms:
        slo_engine = monitor.SLOEngine()
        slo_engine.add(monitor.LatencyObjective(
            "ttft_p99", "serving_ttft_seconds",
            threshold_s=args.slo_ttft_ms / 1e3, windows=(30.0, 120.0)))
    server = None
    if args.http_port >= 0:
        server = monitor.http.serve(
            port=args.http_port, slo=slo_engine,
            fleet=front if fleet_mode else None,
            timeseries=collector,
            health=collector.health if collector is not None else None,
            controller=controller,
            costs=None if fleet_mode else front.metrics.costs)
        print(f"monitor endpoints at {server.url} "
              "(/metrics /traces /slo /events /fleet /timeseries "
              "/health /control /costs)")
    shared = (rng.randint(2, args.vocab, args.shared_prefix)
              .astype(np.int32) if args.shared_prefix else
              np.zeros((0,), np.int32))
    t0 = time.time()
    rejected = shed_or_failed = 0
    parity_jobs = []
    with monitor.annotate("chainermn.serve_lm_burst"), front as client:
        # one streaming request: tokens arrive as they are decoded
        tail_max = max(1, args.prefill_len - len(shared))
        stream_toks: list[int] = []
        streamed = client.submit(
            np.concatenate([shared,
                            rng.randint(2, args.vocab, min(5, tail_max))
                            .astype(np.int32)]),
            args.max_new,
            rng=jax.random.PRNGKey(1), stream_cb=stream_toks.append)
        # a burst of blocking requests with ragged prompt (tail) lengths;
        # with --shared-prefix they all share a system prompt, so a
        # prefix-cached engine prefills only the ragged tails. With
        # --max-queue the bounded queue may bounce some (backpressure is
        # the submitter's signal — a real client would retry later)
        handles = []
        tenants = [f"tenant{j}" for j in range(max(args.tenants, 1))] \
            if args.tenants > 1 else ["default"]
        for i in range(args.requests - 1):
            prompt = np.concatenate([shared, rng.randint(
                2, args.vocab, rng.randint(1, tail_max + 1))
                .astype(np.int32)])
            n_new = int(rng.randint(1, args.max_new + 1))
            key = jax.random.PRNGKey(100 + i)
            prio = ("batch" if args.priority == "batch"
                    or (args.priority == "mixed" and i % 2 == 1)
                    else "interactive")
            try:
                h = client.submit(prompt, n_new, rng=key,
                                  tenant=tenants[i % len(tenants)],
                                  priority=prio)
                handles.append(h)
                parity_jobs.append((h, prompt, n_new, key))
            except QueueFullError:
                rejected += 1
        rebalanced = None
        if args.rebalance:
            # the probe: pick the busiest replica while the burst is in
            # flight and ask the router to move one live decode off it
            # (a False just means nothing was mid-decode to move — the
            # demo burst may drain faster than the handshake)
            snaps = [r.snapshot() for r in client.replicas]
            busy = [s for s in snaps if s.active_slots > 0]
            src = max(busy or snaps,
                      key=lambda s: s.active_slots).replica_id
            ticket = client.rebalance_decode(src)
            rebalanced = (bool(ticket.wait(30))
                          if ticket is not None else False)
        for h in handles + [streamed]:
            try:
                h.wait(timeout=600)
            except Exception as e:  # shed past --deadline, or engine-failed
                shed_or_failed += 1
                print(f"request {h.id}: {type(e).__name__}: {e}")
        if controller is not None and args.canary:
            # the canary path end to end: bumped weights onto ONE
            # replica, bake against the fleet baseline, promote (or
            # auto-rollback) — driven entirely by the background loop
            new_params = jax.tree_util.tree_map(
                lambda a: a + jnp.asarray(0.01, a.dtype), params)
            controller.deploy(new_params, step=1)
            deadline = time.time() + 120
            outcome = None
            while time.time() < deadline:
                crep = controller.report()
                outcome = (crep["canary"] or {}).get("last_outcome")
                if outcome is not None and crep["phase"] == "idle":
                    break
                time.sleep(0.05)
            assert outcome is not None, "canary deploy never resolved"
            print(f"canary deploy: {outcome['action']} "
                  f"(replica {outcome['replica']}, "
                  f"version {outcome.get('version')})")
        if controller is not None:
            crep = controller.report()
            cur = crep["versions"]["current"]
            print(f"controller: capacity={crep['capacity']} "
                  f"target={crep['target_replicas']} "
                  f"scale_ups={crep['autoscale']['scale_ups']} "
                  f"scale_downs={crep['autoscale']['scale_downs']}")
            for d in crep["decisions"]:
                print(f"  decision: {d}")
            print(f"weights: version={cur['version']} ({cur['source']}) "
                  f"history={[(h['version'], h['source']) for h in crep['versions']['history']]}")
            controller.stop()
        if fleet_mode:
            fleet_rep = client.fleet_report()
            pooled_ttft = fleet_rep["pooled"]["histograms"].get(
                "serving_ttft_seconds", {})
            report = {
                "fleet_requests_total": fleet_rep["requests_total"],
                "fleet_reroutes_total": fleet_rep["reroutes_total"],
                "fleet_shed_total": fleet_rep["shed_total"],
                "fleet_capacity": fleet_rep["capacity"],
                "affinity_hit_rate": fleet_rep["affinity"]["hit_rate"],
                "ttft_p50_s": pooled_ttft.get("p50_s"),
                "ttft_p99_s": pooled_ttft.get("p99_s"),
                "tokens_generated": fleet_rep["pooled"]["counters"].get(
                    "serving_tokens_total", 0),
            }
            cost_rep = fleet_rep.get("costs")
        else:
            report = client.metrics.report()
            # printed as its own table below, not as one mega-line
            cost_rep = report.pop("costs", None)

    print(f"streamed request: {len(stream_toks)} tokens "
          f"(first few: {stream_toks[:8]})")
    done = sum(1 for h in handles if h.state.value == "done") \
        + (streamed.state.value == "done")
    print(f"{done}/{args.requests} requests served in "
          f"{time.time() - t0:.2f}s through {args.slots} slots "
          f"({rejected} rejected at admission, {shed_or_failed} "
          "shed/failed)")
    for k, v in sorted(report.items()):
        print(f"  {k}: {v}")
    if cost_rep:
        # the tenant bill: who consumed the device, and how much of the
        # measured time did useful work (the goodput breakdown)
        dt = cost_rep["device_time"]
        gp = cost_rep["goodput"]
        print(f"cost accounting: measured={dt['measured_s']}s "
              f"attributed={dt['attributed_s']}s over "
              f"{dt['dispatches']} dispatches "
              f"(conservation_error={dt['conservation_error']})")
        print("  goodput: " + ", ".join(
            f"{k}={v}" for k, v in gp.items()))
        for tenant, row in sorted(cost_rep["tenants"].items()):
            print(f"  tenant {tenant}: device={row['device_total_s']}s "
                  f"{row['device_s']} kv_block_s={row['kv_block_s']} "
                  f"queue_wait_s={row['queue_wait_s']}")
    if brownout_policy is not None:
        bj = brownout_policy.to_json()
        print(f"brownout episode: steps={bj['steps']} "
              f"final_level={bj['level']} ({bj['action']}) "
              f"last_reason={bj['last_reason']}")
    if args.verify_parity:
        from chainermn_tpu.models import generate as solo_generate

        checked = 0
        for h, prompt, n_new, key in parity_jobs:
            if h.state.value != "done" or checked >= 3:
                continue
            ref = np.asarray(solo_generate(
                model, params, jnp.asarray(prompt)[None], n_new,
                temperature=args.temperature, rng=key, eos_id=eos,
                comm=comm)[0])
            out = h.output
            assert np.array_equal(out, ref[:len(out)]), (
                f"request {h.id} diverged from solo generate()")
            checked += 1
        print(f"parity vs solo generate: OK ({checked} requests)")
    if fleet_mode:
        for r in front.replicas:
            print(f"replica {r.replica_id}: state={r.state.value} "
                  f"served={r.metrics.requests_completed} "
                  f"executables={r.engine.compile_counts_detailed()} "
                  "(zero recompiles after warmup)")
        print("fleet: " + ", ".join(
            f"{k}={v}" for k, v in fleet_rep["affinity"].items()))
        if args.share_prefixes or args.rebalance:
            kr = fleet_rep["kv_reuse"]
            pc = kr.get("payload_cache") or {}
            print(f"kv reuse: share_enabled={kr['share_enabled']} "
                  f"shares={kr['shares']} rebalances={kr['rebalances']} "
                  f"payload_cache_hits={pc.get('hits', 0)} "
                  f"payload_cache_entries={pc.get('entries', 0)} "
                  f"payload_cache_imports={pc.get('imports', 0)}")
        if args.rebalance:
            print(f"rebalance probe: moved={rebalanced}")
        if fleet_rep.get("tiers"):
            from chainermn_tpu.monitor._state import get_registry

            mig = sum(v for k, v in
                      get_registry().snapshot()["counters"].items()
                      if k.startswith("kv_migrations_total"))
            print(f"tiers: prefill={fleet_rep['tiers']['prefill']} "
                  f"decode={fleet_rep['tiers']['decode']} "
                  f"kv_migrations_total={mig}")
    else:
        if engine.prefix_enabled:
            print("prefix cache: " + ", ".join(
                f"{k}={v}" for k, v in engine.prefix_stats().items()))
        if engine.paged:
            print("paged KV: " + ", ".join(
                f"{k}={v}" for k, v in engine.kv_stats().items()))
        if engine.spec_enabled:
            print("speculative: " + ", ".join(
                f"{k}={v}" for k, v in engine.spec_stats().items()))
        print(f"engine executables: {engine.compile_counts_detailed()} "
              "(zero recompiles after warmup)")
    if slo_engine is not None:
        import json

        ev = slo_engine.evaluate()
        for name, entry in ev.items():
            print(f"SLO {name}: compliant={entry['compliant']} "
                  f"max_burn_rate={entry['max_burn_rate']} "
                  f"windows={json.dumps(entry['windows'])}")
    if args.trace_out:
        tracer = monitor.get_tracer()
        n = len(tracer.finished())
        tracer.export_chrome(args.trace_out)
        print(f"wrote {n} trace(s) to {args.trace_out} "
              "(load in chrome://tracing or ui.perfetto.dev)")
    if collector is not None:
        collector.stop()
        hm = collector.health
        hrep = hm.report() if hm is not None else {}
        print(f"health: worst={hrep.get('worst')} over "
              f"{hrep.get('n_watched', 0)} replica(s), "
              f"{len(collector.store.names())} series, "
              f"{collector.ticks} ticks")
        for key, score in sorted(hrep.get("replicas", {}).items()):
            print(f"  replica {key}: {score['state']} "
                  f"(contributing: {score['contributing'] or 'none'})")
        if server is not None:
            # scrape our own endpoints over the real socket — the same
            # JSON any external prober would see
            import json as _json
            from urllib.request import urlopen

            with urlopen(f"{server.url}/health", timeout=10) as r:
                scraped = _json.loads(r.read())
            with urlopen(f"{server.url}/timeseries?last=8",
                         timeout=10) as r:
                ts_scraped = _json.loads(r.read())
            print(f"scraped /health: worst={scraped.get('worst')}; "
                  f"/timeseries: {ts_scraped.get('n_series', 0)} series")
    if server is not None:
        import json as _json
        from urllib.request import urlopen

        with urlopen(f"{server.url}/costs", timeout=10) as r:
            cost_scraped = _json.loads(r.read())
        if cost_scraped:
            print(f"scraped /costs: {len(cost_scraped['tenants'])} "
                  "tenant(s), conservation_error="
                  f"{cost_scraped['device_time']['conservation_error']}")
    if server is not None:
        server.close()
    if args.prometheus:
        print("\n# process metrics registry (Prometheus exposition)")
        print(monitor.exposition(), end="")


if __name__ == "__main__":
    main()
