#!/usr/bin/env python
"""Benchmark: ResNet-50 ImageNet-shape training throughput (images/sec/chip)
plus the communicator-strategy x wire-dtype x double-buffering sweep.

Mirrors the reference's headline workload (BASELINE.md: ChainerMN ResNet-50
ImageNet; the 15-min/1024-GPU run sustained ~125 images/sec/GPU on P100).
Prints one JSON line per completed batch rung and the full record LAST:
{"metric", "value", "unit", "vs_baseline", ...,
"sweep": [...], "allreduce_gbps": N} where vs_baseline is images/sec/chip
divided by the reference's 125 img/s/GPU, and "sweep" carries one record per
{tpu-f32, tpu-bf16, flat, hierarchical, two_dimensional} x {double buffering
on/off} configuration with its step time and HLO-derived per-step collective
traffic (SURVEY.md S6/S7 hard-part 4: does double buffering still win when
XLA already overlaps?).

NOTE on single-chip runs: with one device the mesh collectives are identity
and per-step collective bytes are ~0 — the sweep then measures strategy
*overhead* (it should be ~zero) and the record says "n_chips": 1 so the
numbers aren't over-read. On a real multi-chip slice the same harness
produces true allreduce bandwidth.

One process: ``python bench.py`` runs the selected mode and exits non-zero
with a traceback when it fails. A mode that finds no TPU fails too, unless the
harness smoke hook ``CHAINERMN_TPU_BENCH_PLATFORM`` names another platform
(tests set it to ``cpu``); every record carries the ``device_kind`` it ran on.
Synthetic data — this measures the training step, not input pipelines.
"""

import json
import os
import signal
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 125.0  # BASELINE.md derived P100 number

# bf16 peak FLOP/s of one JAX device, keyed by the exact ``device_kind``
# JAX reports. Source: Google Cloud documentation, "TPU v5e" system
# architecture (197 TFLOP/s bf16 per chip; ``jax.devices()[0].device_kind``
# on a v5e is "TPU v5 lite"). A kind that is not here is an error, not a
# default: add it with its source.
CHIP_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def chip_peak(device_kind: str) -> float:
    """Peak bf16 FLOP/s of a device of this kind; raises on an unknown kind
    so that no record silently loses its ``mfu``."""
    try:
        return CHIP_PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {device_kind!r}: add "
            "it to bench.CHIP_PEAK_FLOPS with its source") from None


def _start_backend():
    """Bring JAX up for one bench mode and return its devices: place the
    persistent compile cache, and refuse to run anywhere but on a TPU unless
    the harness smoke hook names the platform to use instead."""
    import jax

    from chainermn_tpu.utils import enable_compilation_cache

    plat = os.environ.get("CHAINERMN_TPU_BENCH_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    enable_compilation_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" and not plat:
        raise SystemExit(
            f"bench.py measures the TPU but JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind!r}); nothing was "
            "run. (CHAINERMN_TPU_BENCH_PLATFORM=cpu runs the harness smoke.)")
    return devs


def _measure(model, comm, batch, *, double_buffering, n_steps, warmup=3,
             commstats=True, image_size=224):
    """Compile + time one configuration; returns a result dict.

    Shared by the headline measurement and the sweep so every number comes
    from the same code path."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu.training import jit_train_step

    from chainermn_tpu.monitor import instrument

    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(
        rng, (batch, image_size, image_size, 3), jnp.bfloat16
    )
    labels = jnp.zeros((batch,), jnp.int32)
    t_init = time.time()
    variables = comm.bcast_data(model.init(rng, images[:2], train=True))
    log(f"model.init done in {time.time() - t_init:.1f}s (batch={batch})")
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm, double_buffering=double_buffering
    )
    opt_state = jax.device_put(opt.init(variables["params"]), comm.named_sharding())
    jitted = jit_train_step(model, opt, comm)
    # One AOT compile serves execution, the MFU estimate, and commstats (a
    # separate lower().compile() would not share the jit cache and would
    # double the multi-minute ResNet compile).
    t0 = time.time()
    step = jitted.lower(variables, opt_state, images, labels).compile()
    compile_s = time.time() - t0
    step_flops = None
    try:
        ca = step.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        # per-DEVICE per-step FLOPs from the compiled (post-SPMD-partitioning)
        # module — already each chip's share; don't divide by n_chips again.
        step_flops = float(ca.get("flops", 0.0)) or None
    except Exception as e:
        log(f"cost_analysis unavailable: {e}")
    cs = {"total_bytes": 0}
    if commstats:
        try:
            from chainermn_tpu.extensions import parse_hlo_collectives

            cs = parse_hlo_collectives(step.as_text())
        except Exception as e:
            log(f"collective_stats unavailable: {e}")
    # The AOT-compiled executable bypasses jit_train_step's own monitored
    # wrapper, so instrument it here: the measured loop feeds the step
    # counter/histogram every record embeds as its "monitor" block. (Wrapper
    # cost is host-side dict/deque ops — noise against a real step.)
    step = instrument(step, "bench_train_step")
    # Timing closes with a device->host fetch of the loss: the window ends
    # when the last step's value is on the host.
    for _ in range(warmup):
        variables, opt_state, loss = step(variables, opt_state, images, labels)
        float(loss)
    t0 = time.time()
    for _ in range(n_steps):
        variables, opt_state, loss = step(variables, opt_state, images, labels)
    loss_val = float(loss)
    dt = time.time() - t0
    step_time = dt / n_steps
    return {
        "loss": loss_val,
        "compile_s": round(compile_s, 1),
        "step_time_ms": round(step_time * 1e3, 2),
        "img_per_sec": batch * n_steps / dt,
        "step_flops_per_device": step_flops,
        "collective_bytes_per_step": int(cs.get("total_bytes", 0)),
        # effective collective bandwidth: HLO bytes/step over measured step
        # time (0 on a single chip — collectives are identity there)
        "allreduce_gbps": round(
            cs.get("total_bytes", 0) / step_time / 1e9, 3
        ),
    }


# The sweep grid: reference strategy names x double buffering. tpu-bf16 is
# the flagship (reference pure_nccl + fp16 allreduce analog).
_SWEEP_GRID = [
    ("tpu_f32", "tpu", {}),
    ("tpu_bf16", "tpu", {"allreduce_grad_dtype": "bfloat16"}),
    ("flat", "flat", {}),
    ("hierarchical", "hierarchical", {}),
    ("two_dimensional", "two_dimensional", {}),
]


def train_main() -> None:
    # SIGTERM (``timeout``) unwinds the interpreter instead of killing it
    # mid-call, so the PJRT client is torn down in order
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    devs = _start_backend()

    import chainermn_tpu
    from chainermn_tpu.models import ResNet50

    log(f"devices: {devs} (kind={devs[0].device_kind!r})")
    n_chips = len(devs)

    stem = os.environ.get("CHAINERMN_TPU_BENCH_STEM", "conv7")
    # Smoke-test hook (CI only; the driver never sets it): a tiny model +
    # small images exercise the whole harness — ladder, sweep, commstats —
    # in seconds on CPU.
    tiny = bool(os.environ.get("CHAINERMN_TPU_BENCH_TINY"))
    image_size = 32 if tiny else 224
    if tiny:
        from chainermn_tpu.models import ResNet

        model = ResNet(stage_sizes=[1, 1], width=8, num_classes=10, stem=stem)
    else:
        model = ResNet50(num_classes=1000, stem=stem)
    n_steps = int(os.environ.get("CHAINERMN_TPU_BENCH_STEPS", "50"))
    sweep_steps = int(os.environ.get("CHAINERMN_TPU_BENCH_SWEEP_STEPS", "20"))
    comm = chainermn_tpu.create_communicator("tpu", allreduce_grad_dtype="bfloat16")

    deadline = time.time() + float(
        os.environ.get("CHAINERMN_TPU_BENCH_CHILD_BUDGET", "1200")
    )
    # 256/chip, not 128: by the compiler's accounting the step does more
    # FLOPs per byte accessed at a larger batch (64.9 at 128, 75.1 at 256,
    # 84.6 at 512; scripts/mfu_aot.jsonl, chip-free). The halving loop
    # below degrades gracefully on OOM — EXCEPT when the batch was set
    # explicitly (CHAINERMN_TPU_BENCH_BATCH): a sweep cell labeled
    # batch=512 must fail on OOM rather than silently measure 256 under
    # the wrong label (the next cell measures 256 on purpose).
    explicit_batch = int(os.environ.get("CHAINERMN_TPU_BENCH_BATCH", "0"))

    def _headline_record(h, b):
        per_chip = h["img_per_sec"] / n_chips
        rec = {
            "metric": "resnet50_imagenet_train_throughput",
            "value": round(per_chip, 2),
            "unit": "images/sec/chip",
            "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 3),
            "step_time_ms": h["step_time_ms"],
            "batch_per_chip": b // n_chips,
            "n_chips": n_chips,
            "stem": stem,
            "device_kind": devs[0].device_kind,
            "collective_bytes_per_step": h["collective_bytes_per_step"],
            "allreduce_gbps": h["allreduce_gbps"],
        }
        if tiny:
            rec["tiny"] = True  # CI smoke run, not a real measurement
        # acceptance: every mode's record carries the registry snapshot
        # (step counters, step-time percentiles, device-memory gauges)
        try:
            from chainermn_tpu.monitor import snapshot as monitor_snapshot

            rec["monitor"] = monitor_snapshot()
        except Exception as e:
            log(f"monitor snapshot unavailable: {e}")
        if h["step_flops_per_device"]:
            achieved = h["step_flops_per_device"] / (h["step_time_ms"] / 1e3)
            rec["achieved_tflops_per_chip"] = round(achieved / 1e12, 2)
            if devs[0].platform == "tpu":
                peak = chip_peak(devs[0].device_kind)
                rec["mfu"] = round(achieved / peak, 4)
                log(f"MFU: {achieved / peak:.1%} of "
                    f"{peak / 1e12:.0f} TFLOP/s peak")
        return rec

    # Batch LADDER, small to large: a larger batch does more FLOPs per byte
    # accessed (comment above) and compiles longer. So: land the quickest
    # record first, then climb; every completed rung is printed BEFORE the
    # next compile starts, so a run that is cut short mid-climb has still
    # printed the best rung it measured. With a warm compilation cache the lower rungs cost seconds.
    # An explicit batch (sweep cells) is a single rung and must fail rather
    # than substitute a different batch.
    if explicit_batch:
        ladder = [explicit_batch]
    elif tiny:
        ladder = [256 * n_chips]
    else:
        ladder = [128 * n_chips, 256 * n_chips, 512 * n_chips]

    headline, batch, record = None, None, None
    prev_wall = prev_compile = None
    # Pessimistic cost of a COLD rung: never START a compile that might not
    # fit what is left of the time budget. A warm previous rung (compile hit
    # the persistent cache) predicts warm neighbors: the same earlier
    # process that cached this rung's graph ran the same ladder.
    climb_floor = float(os.environ.get("CHAINERMN_TPU_BENCH_CLIMB_FLOOR",
                                       "1500"))
    ladder = list(ladder)
    while ladder:
        rung = ladder.pop(0)
        if headline is not None:
            remaining = deadline - time.time()
            warm = prev_compile is not None and prev_compile < 60
            need = max(3 * prev_wall, 120.0) if warm else climb_floor
            if remaining < need:
                log(f"ladder: skipping batch {rung} ({remaining:.0f}s left "
                    f"< {need:.0f}s needed; prev rung {prev_wall:.0f}s, "
                    f"compile {'warm' if warm else 'cold'})")
                break
        rung_start = time.time()
        try:
            h = _measure(
                model, comm, rung, double_buffering=False, n_steps=n_steps,
                image_size=image_size,
            )
            prev_wall = time.time() - rung_start
            prev_compile = h["compile_s"]
            log(f"headline rung: batch={rung} "
                f"step={h['step_time_ms']}ms "
                f"{h['img_per_sec']:.0f} img/s "
                f"(compile {h['compile_s']}s, total {prev_wall:.0f}s)")
        except Exception as e:  # OOM / shape limits on this rung
            log(f"batch {rung} failed: {type(e).__name__}: {e}"[:320])
            if explicit_batch:
                raise SystemExit(
                    f"explicit batch {explicit_batch} failed; not "
                    "substituting another (the measurement label must "
                    "match the measured batch)")
            if headline is None:
                # no record yet: the smallest planned rung doesn't fit —
                # descend by halving (replaces the climb; a bigger rung
                # cannot fit where a smaller one OOM'd)
                if rung >= 16:
                    ladder = [rung // 2]
                continue
            break  # OOM above a working rung: larger rungs won't fit either
        if headline is None or h["img_per_sec"] > headline["img_per_sec"]:
            headline, batch = h, rung
        # emit the best record so far NOW: the last line printed is then a
        # record of THIS run whenever the run is cut short
        record = _headline_record(headline, batch)
        print(json.dumps(record), flush=True)
    if headline is None:
        raise SystemExit("benchmark could not run at any batch size")
    per_chip = headline["img_per_sec"] / n_chips

    # ---- strategy x double-buffering sweep (BASELINE.md metric 2) -------- #
    sweep = []
    if os.environ.get("CHAINERMN_TPU_BENCH_SWEEP", "1") != "0":
        for name, strategy, kwargs in _SWEEP_GRID:
            for db in (False, True):
                label = f"{name}{'+db' if db else ''}"
                if name == "tpu_bf16" and not db:
                    # exactly the headline configuration — reuse its numbers
                    # instead of burning a second multi-minute compile
                    sweep.append({
                        "config": label,
                        "step_time_ms": headline["step_time_ms"],
                        "img_per_sec_per_chip": round(per_chip, 1),
                        "collective_bytes_per_step":
                            headline["collective_bytes_per_step"],
                        "allreduce_gbps": headline["allreduce_gbps"],
                        "from_headline": True,
                    })
                    continue
                if time.time() > deadline:
                    sweep.append({"config": label, "skipped": "time budget"})
                    continue
                try:
                    c = chainermn_tpu.create_communicator(strategy, **kwargs)
                    r = _measure(model, c, batch, double_buffering=db,
                                 n_steps=sweep_steps, image_size=image_size)
                    sweep.append({
                        "config": label,
                        "step_time_ms": r["step_time_ms"],
                        "img_per_sec_per_chip": round(
                            r["img_per_sec"] / n_chips, 1
                        ),
                        "collective_bytes_per_step":
                            r["collective_bytes_per_step"],
                        "allreduce_gbps": r["allreduce_gbps"],
                    })
                    log(f"sweep {label}: {r['step_time_ms']}ms/step, "
                        f"{r['collective_bytes_per_step'] / 1e6:.1f} MB/step, "
                        f"{r['allreduce_gbps']} GB/s")
                except Exception as e:
                    sweep.append({
                        "config": label,
                        "error": f"{type(e).__name__}: {e}"[:200],
                    })
                    log(f"sweep {label} failed: {type(e).__name__}: {e}")
        record["sweep"] = sweep
        db_pairs = {
            s["config"]: s["step_time_ms"] for s in sweep
            if "step_time_ms" in s
        }
        base, db = db_pairs.get("tpu_bf16"), db_pairs.get("tpu_bf16+db")
        if base and db:
            # the SURVEY S7 hard-part-4 answer, as data
            record["double_buffering_speedup"] = round(base / db, 4)

    print(json.dumps(record))


def serving_main() -> None:
    """``bench.py --mode serving``: continuous-batching decode benchmark
    over :mod:`chainermn_tpu.serving` — the serving-side counterpart of the
    ResNet training headline. Prints ONE JSON line:
    ``{"metric": "serving_decode_throughput", "value": tokens/sec, ...,
    "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "slot_occupancy", ...}``.

    Workload: a burst of ragged random prompts (the arrival pattern that
    exercises admission + slot reuse) through a fixed slot pool; one
    warmup request compiles the two engine programs, then the measured
    run counts only steady-state work. The zero-recompile invariant is
    carried in the record (``"recompiles"``) so a regression shows up in
    the perf artifact, not just in tests. Runs on the TPU; under the
    harness smoke hook (``CHAINERMN_TPU_BENCH_PLATFORM=cpu``) the CPU mesh
    checks the record's shape and invariants only (records say so via
    ``device_kind``). A failure prints a parseable error record and exits
    non-zero.
    """
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    import numpy as np

    import jax

    devs = _start_backend()

    import jax.numpy as jnp

    from chainermn_tpu.models import TransformerLM, generate
    from chainermn_tpu.serving import FCFSScheduler, ServingEngine

    e = os.environ.get
    n_slots = int(e("CHAINERMN_TPU_SERVE_SLOTS", "8"))
    n_requests = int(e("CHAINERMN_TPU_SERVE_REQUESTS", "32"))
    prefill_len = int(e("CHAINERMN_TPU_SERVE_PREFILL_LEN", "32"))
    max_new = int(e("CHAINERMN_TPU_SERVE_MAX_NEW", "32"))
    vocab = int(e("CHAINERMN_TPU_SERVE_VOCAB", "256"))
    d_model = int(e("CHAINERMN_TPU_SERVE_DMODEL", "128"))
    n_layers = int(e("CHAINERMN_TPU_SERVE_LAYERS", "4"))
    n_heads = int(e("CHAINERMN_TPU_SERVE_HEADS", "8"))
    skip_sections = {s for s in e(
        "CHAINERMN_TPU_SERVE_SKIP_SECTIONS", "").split(",") if s}
    # the kernel + speculative sections reuse the paged
    # section's workload/engine parameters
    if "paged_serving" in skip_sections:
        skip_sections |= {"paged_kernel_serving",
                          "speculative_serving"}

    log(f"serving bench: devices={len(devs)} kind={devs[0].device_kind!r} "
        f"slots={n_slots} requests={n_requests}")
    try:
        model = TransformerLM(
            vocab_size=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, max_len=prefill_len + max_new,
        )
        rng = np.random.RandomState(0)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, prefill_len), jnp.int32))
        engine = ServingEngine(model, params, n_slots=n_slots,
                               prefill_len=prefill_len)

        # warmup: compile prefill + decode once, off the measured clock
        warm = FCFSScheduler(engine)
        warm.submit(rng.randint(1, vocab, 4).astype(np.int32), 2)
        warm.run_until_idle()

        sched = FCFSScheduler(engine)  # fresh metrics for the measured run
        t0 = time.time()
        for _ in range(n_requests):
            prompt = rng.randint(1, vocab,
                                 rng.randint(1, prefill_len + 1))
            sched.submit(prompt.astype(np.int32),
                         int(rng.randint(1, max_new + 1)))
        sched.run_until_idle()
        wall = time.time() - t0
        m = sched.metrics.report()
        record = {
            "metric": "serving_decode_throughput",
            "value": m["tokens_per_sec"],
            "unit": "tokens/sec",
            "mode": "serving",
            "n_chips": len(devs),
            "device_kind": devs[0].device_kind,
            "n_slots": n_slots,
            "n_requests": n_requests,
            "prefill_len": prefill_len,
            "max_new": max_new,
            "model": {"vocab": vocab, "d_model": d_model,
                      "n_layers": n_layers, "n_heads": n_heads},
            "tokens_generated": m["tokens_generated"],
            "wall_s": round(wall, 3),
            "ttft_p50_ms": round(m["ttft_p50_s"] * 1e3, 3),
            "ttft_p99_ms": round(m["ttft_p99_s"] * 1e3, 3),
            "ttft_mean_ms": round(m["ttft_mean_s"] * 1e3, 3),
            "tpot_p50_ms": round(m["tpot_p50_s"] * 1e3, 3),
            "tpot_p99_ms": round(m["tpot_p99_s"] * 1e3, 3),
            "slot_occupancy": m["slot_occupancy_mean"],
            "slot_occupancy_p99": m["slot_occupancy_p99"],
            "queue_depth_mean": m["queue_depth_mean"],
            "queue_depth_p99": m["queue_depth_p99"],
            "recompiles": engine.compile_counts(),
        }

        # ---- continuous telemetry: collector ON vs OFF, warm engine --- #
        # ISSUE 15 acceptance: the background collector + detector graph
        # must cost <2% of serving throughput. The SAME job list runs
        # twice through fresh schedulers on the already-warm engine — OFF
        # first, then ON with a Collector sampling every registry
        # instrument at ts_cadence plus the standard per-instance sensor
        # set and a HealthMonitor — and the record carries the overhead
        # fraction, ON-vs-OFF token parity, the zero-recompile invariant,
        # and the health verdict the run ended on.
        from chainermn_tpu.monitor.health import (
            HealthMonitor,
            standard_replica_sensors,
        )
        from chainermn_tpu.monitor.timeseries import Collector

        ts_cadence = float(e("CHAINERMN_TPU_SERVE_TS_CADENCE", "0.05"))
        ts_jobs = [
            (rng.randint(1, vocab,
                         rng.randint(1, prefill_len + 1)).astype(np.int32),
             int(rng.randint(1, max_new + 1)))
            for _ in range(n_requests)
        ]
        ts_counts = engine.compile_counts_detailed()

        def run_ts_workload(ts_on):
            s = FCFSScheduler(engine)
            col = mon = None
            if ts_on:
                col = Collector(cadence_s=ts_cadence)
                sigs, dets = standard_replica_sensors(
                    s.metrics.instance, stall_timeout_s=60.0, tag="bench")
                for sg in sigs:
                    col.add_signal(sg)
                for dt in dets:
                    col.add_detector(dt)
                mon = HealthMonitor(store=col.store)
                mon.watch(s.metrics.instance, detectors=dets)
                col.attach_health(mon)
                s.metrics.attach_health(
                    lambda m=mon, k=s.metrics.instance: m.score_json(k))
                col.start()
            t0 = time.time()
            reqs = [s.submit(p, n) for p, n in ts_jobs]
            s.run_until_idle()
            wall = time.time() - t0
            if col is not None:
                col.stop()
            return s, reqs, wall, col, mon

        s_off, reqs_off, wall_ts_off, _, _ = run_ts_workload(False)
        s_ts, reqs_ts, wall_ts_on, ts_col, ts_mon = run_ts_workload(True)
        ts_parity = all(
            bool(np.array_equal(a.output, b.output))
            for a, b in zip(reqs_ts, reqs_off))
        assert engine.compile_counts_detailed() == ts_counts, "recompiled!"
        m_ts = s_ts.metrics.report()
        record["telemetry_serving"] = {
            "cadence_s": ts_cadence,
            "wall_s_on": round(wall_ts_on, 3),
            "wall_s_off": round(wall_ts_off, 3),
            "overhead_frac": round(
                wall_ts_on / max(wall_ts_off, 1e-9) - 1.0, 4),
            "tokens_per_sec_on": s_ts.metrics.report()["tokens_per_sec"],
            "tokens_per_sec_off": s_off.metrics.report()["tokens_per_sec"],
            "parity_on_vs_off": ts_parity,
            "recompiles_after_warmup": 0,
            "n_series": len(ts_col.store.names()),
            "ticks": ts_col.ticks,
            "health": m_ts.get("health"),
            "worst_state": ts_mon.report()["worst"],
        }
        ts_rec = record["telemetry_serving"]
        log(f"telemetry serving: overhead={ts_rec['overhead_frac']} "
            f"({ts_rec['ticks']} ticks over {ts_rec['n_series']} series), "
            f"health={ts_rec['worst_state']}, parity={ts_parity}")

        if "prefix_serving" in skip_sections:
            log("prefix_serving: skipped via CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # ---- prefix-heavy workload: shared system prompt, mixed tails - #
            # The admission fast path's acceptance numbers (ISSUE 5): the SAME
            # workload runs twice through bucketed batched-prefill engines —
            # prefix cache ON vs OFF — so the TTFT delta isolates KV reuse.
            # Every request shares a system-prompt prefix; tails are ragged.
            buckets = tuple(
                int(x) for x in e(
                    "CHAINERMN_TPU_SERVE_BUCKETS",
                    f"{max(1, prefill_len // 4)},{prefill_len}").split(","))
            batch_k = int(e("CHAINERMN_TPU_SERVE_PREFILL_BATCH", "4"))
            shared_len = min(int(e("CHAINERMN_TPU_SERVE_SHARED_PREFIX",
                                   str(3 * prefill_len // 4))), prefill_len - 1)
            block = int(e("CHAINERMN_TPU_SERVE_PREFIX_BLOCK",
                          str(max(1, prefill_len // 8))))
            n_blocks = int(e("CHAINERMN_TPU_SERVE_PREFIX_BLOCKS", "64"))
            min_insert = int(e("CHAINERMN_TPU_SERVE_MIN_INSERT", "2"))
            shared = rng.randint(1, vocab, shared_len).astype(np.int32)
            tail_max = prefill_len - shared_len
            jobs = [
                (np.concatenate([shared, rng.randint(
                    1, vocab, 1 + i % tail_max).astype(np.int32)]),
                 int(rng.randint(1, max_new + 1)))
                for i in range(n_requests)
            ]

            def run_prefix_workload(prefix_on):
                eng = ServingEngine(
                    model, params, n_slots=n_slots, prefill_buckets=buckets,
                    prefill_batch=batch_k,
                    prefix_cache_blocks=n_blocks if prefix_on else 0,
                    prefix_block_size=block,
                    prefix_min_insert_blocks=min_insert)
                eng.warmup()                      # every program, off the clock
                counts = eng.compile_counts_detailed()
                seeder = FCFSScheduler(eng)       # seed the trie off the clock
                seeder.submit(
                    np.concatenate([shared, np.array([1], np.int32)]), 1)
                seeder.run_until_idle()
                s = FCFSScheduler(eng)
                t0 = time.time()
                reqs = [s.submit(p, n) for p, n in jobs]
                s.run_until_idle()
                wall = time.time() - t0
                assert eng.compile_counts_detailed() == counts, "recompiled!"
                return eng, s.metrics.report(), reqs, wall

            eng_on, m_on, reqs_on, wall_on = run_prefix_workload(True)
            eng_off, m_off, _, wall_off = run_prefix_workload(False)
            # token-for-token parity vs solo generate() (greedy), through
            # prefix fetch + batched suffix prefill
            parity = True
            for i in (0, 1):
                prompt, n = jobs[i]
                ref = np.asarray(generate(model, params,
                                          jnp.asarray(prompt)[None], n)[0])
                parity = parity and bool(np.array_equal(reqs_on[i].output, ref))
            pstats = eng_on.prefix_stats()
            record["prefix_serving"] = {
                "buckets": list(buckets),
                "prefill_batch": batch_k,
                "shared_prefix": shared_len,
                "prefix_blocks": n_blocks,
                "block_size": block,
                # per-ADMISSION hit rate (fraction of admitted requests whose
                # prompt was partly served from cache); the trie's own stats
                # (below) count every match probe incl. re-scanned candidates
                "hit_rate": m_on.get("prefix_hit_rate", 0.0),
                "trie": pstats,
                "evictions": pstats["evictions"],
                "cached_prefix_frac_mean": m_on.get("cached_prefix_frac_mean",
                                                    0.0),
                "prefill_batch_occupancy":
                    m_on.get("prefill_batch_size_mean", 0.0),
                "ttft_p50_ms": round(m_on["ttft_p50_s"] * 1e3, 3),
                "ttft_p99_ms": round(m_on["ttft_p99_s"] * 1e3, 3),
                "ttft_p50_ms_off": round(m_off["ttft_p50_s"] * 1e3, 3),
                "ttft_p99_ms_off": round(m_off["ttft_p99_s"] * 1e3, 3),
                "ttft_p50_speedup": round(
                    m_off["ttft_p50_s"] / max(m_on["ttft_p50_s"], 1e-9), 3),
                "tokens_per_sec": m_on["tokens_per_sec"],
                "tokens_per_sec_off": m_off["tokens_per_sec"],
                "wall_s": round(wall_on, 3),
                "wall_s_off": round(wall_off, 3),
                "recompiles_after_warmup":
                    sum(eng_on.recompiles.values())
                    + sum(eng_off.recompiles.values()),
                "parity_vs_solo_generate": parity,
                "compile_counts": eng_on.compile_counts_detailed(),
            }
            log(f"prefix serving: "
                f"hit_rate={record['prefix_serving']['hit_rate']} "
                f"ttft_p50 {record['prefix_serving']['ttft_p50_ms']}ms (on) vs "
                f"{record['prefix_serving']['ttft_p50_ms_off']}ms (off), "
                f"parity={parity}")

        if "paged_serving" in skip_sections:
            log("paged_serving: skipped via CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # ---- paged KV decode: ON vs OFF at the SAME device KV budget - #
            # The PR-7 acceptance: a dense engine reserves cache_len rows per
            # slot regardless of what requests actually use, so concurrency =
            # n_slots. The paged engine spends the SAME row budget as a block
            # pool and admits by blocks actually needed — short requests pack
            # 4x+ more concurrent decodes into identical memory (worst-case
            # block-budget admission, so zero preemptions in the clean run).
            pg_prefill = int(e("CHAINERMN_TPU_SERVE_PAGED_PREFILL", "16"))
            pg_cache = int(e("CHAINERMN_TPU_SERVE_PAGED_CACHE", "64"))
            pg_bs = int(e("CHAINERMN_TPU_SERVE_KV_BLOCK", "8"))
            pg_batch = int(e("CHAINERMN_TPU_SERVE_PAGED_BATCH", "4"))
            pg_max_new = int(e("CHAINERMN_TPU_SERVE_PAGED_MAX_NEW", "6"))
            pg_quant = e("CHAINERMN_TPU_SERVE_KV_QUANT", "none")
            dense_slots = int(e("CHAINERMN_TPU_SERVE_DENSE_SLOTS", "2"))
            paged_slots = int(e("CHAINERMN_TPU_SERVE_PAGED_SLOTS", "12"))
            budget_rows = dense_slots * pg_cache       # dense-resident KV rows
            pg_blocks = budget_rows // pg_bs + 1       # same rows (+ scratch)
            pg_jobs = [
                (rng.randint(1, vocab,
                             2 + i % (pg_prefill // 2 - 1)).astype(np.int32),
                 pg_max_new)
                for i in range(int(e("CHAINERMN_TPU_SERVE_PAGED_REQUESTS",
                                     "16")))
            ]

            def run_paged_workload(paged_on):
                kw = (dict(paged=True, kv_blocks=pg_blocks, kv_block_size=pg_bs,
                           kv_quant=pg_quant, n_slots=paged_slots)
                      if paged_on else dict(n_slots=dense_slots))
                eng = ServingEngine(model, params, prefill_buckets=(pg_prefill,),
                                    prefill_batch=pg_batch, cache_len=pg_cache,
                                    **kw)
                eng.warmup()
                counts = eng.compile_counts_detailed()
                s = FCFSScheduler(eng)
                t0 = time.time()
                reqs = [s.submit(p, n) for p, n in pg_jobs]
                s.run_until_idle()
                wall = time.time() - t0
                assert eng.compile_counts_detailed() == counts, "recompiled!"
                return eng, s.metrics.report(), reqs, wall

            eng_pg, m_pg, reqs_pg, wall_pg = run_paged_workload(True)
            eng_dn, m_dn, reqs_dn, wall_dn = run_paged_workload(False)
            pg_parity = True
            for i in (0, 1):
                prompt, n = pg_jobs[i]
                ref = np.asarray(generate(model, params,
                                          jnp.asarray(prompt)[None], n)[0])
                pg_parity = (pg_parity
                             and bool(np.array_equal(reqs_pg[i].output, ref))
                             and bool(np.array_equal(reqs_dn[i].output, ref)))
            record["paged_serving"] = {
                "kv_blocks": pg_blocks,
                "kv_block_size": pg_bs,
                "kv_quant": pg_quant,
                "kv_budget_rows": budget_rows,
                "dense_slots": dense_slots,
                "paged_slots": paged_slots,
                "max_concurrent_paged": eng_pg.peak_active,
                "max_concurrent_dense": eng_dn.peak_active,
                "concurrency_gain": round(
                    eng_pg.peak_active / max(eng_dn.peak_active, 1), 3),
                "tokens_per_sec": m_pg["tokens_per_sec"],
                "tokens_per_sec_dense": m_dn["tokens_per_sec"],
                "wall_s": round(wall_pg, 3),
                "wall_s_dense": round(wall_dn, 3),
                "preemptions": m_pg.get("kv_preemptions", 0),
                "kv_blocks_per_request_mean":
                    m_pg.get("kv_blocks_per_request_mean", 0.0),
                "kv_stats": eng_pg.kv_stats(),
                "parity_vs_solo_generate": pg_parity,
                "recompiles_after_warmup":
                    sum(eng_pg.recompiles.values())
                    + sum(eng_dn.recompiles.values()),
            }
            p = record["paged_serving"]
            log(f"paged serving: {p['max_concurrent_paged']} vs "
                f"{p['max_concurrent_dense']} concurrent "
                f"({p['concurrency_gain']}x) at {budget_rows} KV rows, "
                f"preemptions={p['preemptions']}, parity={pg_parity}")

        if "paged_kernel_serving" in skip_sections:
            log("paged_kernel_serving: skipped via "
                "CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # ---- fused paged-decode kernel: ON vs OFF ---------------------- #
            # ISSUE 14: two paged engines differing ONLY in paged_kernel= run
            # the identical workload. Off TPU the kernel executes in Pallas
            # interpret mode, so the tokens/s pair is parity/recompile
            # EVIDENCE there, not a performance claim — the speedup number is
            # only meaningful on real hardware (the smoke test gates on
            # device_kind the same way). The bytes-read model rides along:
            # it is the analytical XLA-dense-view vs streamed-blocks cost,
            # computed from the workload's final lengths, chip-free.
            from chainermn_tpu.parallel.paged_kernel import (
                bytes_read_model,
                kernel_supported,
            )

            def run_kernel_workload():
                eng = ServingEngine(model, params, prefill_buckets=(pg_prefill,),
                                    prefill_batch=pg_batch, cache_len=pg_cache,
                                    paged=True, kv_blocks=pg_blocks,
                                    kv_block_size=pg_bs, kv_quant=pg_quant,
                                    n_slots=paged_slots, paged_kernel=True)
                eng.warmup()
                counts = eng.compile_counts_detailed()
                s = FCFSScheduler(eng)
                t0 = time.time()
                reqs = [s.submit(p_, n_) for p_, n_ in pg_jobs]
                s.run_until_idle()
                wall = time.time() - t0
                assert eng.compile_counts_detailed() == counts, "recompiled!"
                return eng, s.metrics.report(), reqs, wall

            eng_kn, m_kn, reqs_kn, wall_kn = run_kernel_workload()
            # the OFF side IS the paged section's engine — identical config
            # down to paged_kernel=False, same jobs — so its run is reused
            # rather than rebuilt (the tier-1 bench smoke rides this)
            eng_kf, m_kf, reqs_kf, wall_kf = eng_pg, m_pg, reqs_pg, wall_pg
            kn_parity = all(
                bool(np.array_equal(a.output, b.output))
                for a, b in zip(reqs_kn, reqs_kf))
            for i in (0, 1):
                prompt, n = pg_jobs[i]
                ref = np.asarray(generate(model, params,
                                          jnp.asarray(prompt)[None], n)[0])
                kn_parity = (kn_parity
                             and bool(np.array_equal(reqs_kn[i].output, ref)))
            final_lengths = [len(p_) + n_ for p_, n_ in pg_jobs]
            supported, why = kernel_supported()
            record["paged_kernel_serving"] = {
                "kernel_used": bool(eng_kn.paged_kernel),
                "kernel_supported": supported,
                "fallback_reason": why,
                "interpret_mode": jax.default_backend() != "tpu",
                "device_kind": jax.devices()[0].device_kind,
                "kv_quant": pg_quant,
                "kv_block_size": pg_bs,
                "tokens_per_sec": m_kn["tokens_per_sec"],
                "tokens_per_sec_off": m_kf["tokens_per_sec"],
                "wall_s": round(wall_kn, 3),
                "wall_s_off": round(wall_kf, 3),
                "parity_vs_xla_and_solo": kn_parity,
                "recompiles_after_warmup":
                    sum(eng_kn.recompiles.values())
                    + sum(eng_kf.recompiles.values()),
                "bytes_read_model": bytes_read_model(
                    final_lengths, block_size=pg_bs,
                    max_blocks=-(-pg_cache // pg_bs),
                    n_heads=model.n_heads,
                    head_dim=model.d_model // model.n_heads,
                    n_layers=model.n_layers, kv_quant=pg_quant),
            }
            kn = record["paged_kernel_serving"]
            log(f"paged kernel: used={kn['kernel_used']} "
                f"(interpret={kn['interpret_mode']}), parity={kn_parity}, "
                f"read_amp={kn['bytes_read_model']['read_amplification']}x "
                f"modelled")

        if "speculative_serving" in skip_sections:
            log("speculative_serving: skipped via "
                "CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # ---- speculative decode: prompt-lookup drafting ON vs OFF ----- #
            # ISSUE 12: a shared-system-prompt workload with LONG greedy
            # generations (the regime speculation targets) through two paged
            # engines differing ONLY in ``speculative=``; the n-gram drafter
            # costs no second model, so the tokens/s ratio isolates
            # multi-token commit per dispatch. Outputs are asserted
            # token-identical ON vs OFF. A randomly-initialized transformer's
            # greedy trajectory is aperiodic noise (nothing for prompt-lookup
            # to mine — accept rate ~0, a pure slowdown), so this section
            # measures the CONTROLLED-accept-rate regime instead: the random
            # params are surgically rewritten into a "copy-cycle" model —
            # every block's output projections zeroed (residual blocks become
            # identity, attention still computed at full cost), one-hot
            # embeddings, and an lm_head permutation so greedy decode walks a
            # period-``sp_period`` token cycle with huge argmax margins. The
            # accept rate this induces travels in the record; the speedup
            # number is the dispatch-amortization mechanism, not a claim
            # about random-weight trajectories.
            from chainermn_tpu.serving import SpeculativeConfig
            sp_k = int(e("CHAINERMN_TPU_SERVE_SPEC_K", "6"))
            sp_max_new = int(e("CHAINERMN_TPU_SERVE_SPEC_MAX_NEW", "64"))
            sp_requests = int(e("CHAINERMN_TPU_SERVE_SPEC_REQUESTS", "8"))
            sp_slots = int(e("CHAINERMN_TPU_SERVE_SPEC_SLOTS", "4"))
            sp_period = int(e("CHAINERMN_TPU_SERVE_SPEC_PERIOD", "4"))
            # a deliberately tiny model: the section measures dispatch
            # amortization, which is LARGEST when per-step compute is small,
            # and two engines (ON + OFF) get compiled from it
            sp_d = int(e("CHAINERMN_TPU_SERVE_SPEC_DMODEL", "32"))
            sp_layers = int(e("CHAINERMN_TPU_SERVE_SPEC_LAYERS", "1"))
            sp_heads = int(e("CHAINERMN_TPU_SERVE_SPEC_HEADS", "2"))
            sp_vocab = min(vocab, sp_d)          # one-hot rows need d >= vocab
            sp_model = TransformerLM(
                vocab_size=sp_vocab, d_model=sp_d, n_heads=sp_heads,
                n_layers=sp_layers, max_len=prefill_len + sp_max_new)
            sp_params = jax.device_get(sp_model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, prefill_len), jnp.int32)))
            sp_p = sp_params["params"]
            sp_p["embed"]["embedding"] = (
                4.0 * np.eye(sp_vocab, sp_d)).astype(np.float32)
            sp_p["pos_embed"]["embedding"] = np.zeros_like(
                sp_p["pos_embed"]["embedding"])
            for li in range(sp_layers):
                blk = sp_p[f"block_{li}"]
                for nm in ("proj", "Dense_1"):
                    blk[nm]["kernel"] = np.zeros_like(blk[nm]["kernel"])
                    blk[nm]["bias"] = np.zeros_like(blk[nm]["bias"])
            sp_head = np.zeros_like(sp_p["lm_head"]["kernel"])
            for t in range(sp_vocab):     # successor permutation, short cycles
                sp_head[t, (t // sp_period) * sp_period
                        + ((t % sp_period) + 1) % sp_period] = 1.0
            sp_p["lm_head"]["kernel"] = sp_head
            sp_p["lm_head"]["bias"] = np.zeros_like(sp_p["lm_head"]["bias"])
            sp_shared = rng.randint(1, sp_vocab, shared_len).astype(np.int32)
            sp_cache = prefill_len + sp_max_new
            sp_blocks = sp_slots * (sp_cache // pg_bs + 2) + 1
            sp_jobs = [
                (np.concatenate([sp_shared, rng.randint(
                    1, sp_vocab, 1 + i % max(1, tail_max)).astype(np.int32)]),
                 sp_max_new)
                for i in range(sp_requests)
            ]

            def run_spec_workload(spec_on):
                eng = ServingEngine(
                    sp_model, sp_params, n_slots=sp_slots,
                    prefill_buckets=(prefill_len,), prefill_batch=pg_batch,
                    cache_len=sp_cache, paged=True, kv_blocks=sp_blocks,
                    kv_block_size=pg_bs,
                    speculative=(SpeculativeConfig(k=sp_k) if spec_on
                                 else None))
                eng.warmup()
                counts = eng.compile_counts_detailed()
                s = FCFSScheduler(eng)
                t0 = time.time()
                reqs = [s.submit(p, n) for p, n in sp_jobs]
                s.run_until_idle()
                wall = time.time() - t0
                assert eng.compile_counts_detailed() == counts, "recompiled!"
                return eng, s.metrics.report(), reqs, wall

            eng_sp, m_sp, reqs_sp, wall_sp = run_spec_workload(True)
            eng_ns, m_ns, reqs_ns, wall_ns = run_spec_workload(False)
            sp_parity = all(
                bool(np.array_equal(a.output, b.output))
                for a, b in zip(reqs_sp, reqs_ns))
            sp_stats = eng_sp.spec_stats()
            record["speculative_serving"] = {
                "drafter": "ngram",
                "spec_k": sp_k,
                "n_requests": sp_requests,
                "max_new": sp_max_new,
                "shared_prefix": shared_len,
                "cycle_period": sp_period,
                "model": {"vocab": sp_vocab, "d_model": sp_d,
                          "n_layers": sp_layers, "n_heads": sp_heads,
                          "family": "copy-cycle"},
                "accept_rate": sp_stats["accept_rate"],
                "spec_tokens_proposed": sp_stats["spec_tokens_proposed"],
                "spec_tokens_accepted": sp_stats["spec_tokens_accepted"],
                "tokens_per_sec": m_sp["tokens_per_sec"],
                "tokens_per_sec_off": m_ns["tokens_per_sec"],
                "decode_speedup": round(
                    m_sp["tokens_per_sec"]
                    / max(m_ns["tokens_per_sec"], 1e-9), 3),
                "ttft_p50_ms": round(m_sp["ttft_p50_s"] * 1e3, 3),
                "ttft_p50_ms_off": round(m_ns["ttft_p50_s"] * 1e3, 3),
                "tpot_p50_ms": round(m_sp["tpot_p50_s"] * 1e3, 3),
                "tpot_p50_ms_off": round(m_ns["tpot_p50_s"] * 1e3, 3),
                "wall_s": round(wall_sp, 3),
                "wall_s_off": round(wall_ns, 3),
                "parity_on_vs_off": sp_parity,
                "recompiles_after_warmup":
                    sum(eng_sp.recompiles.values())
                    + sum(eng_ns.recompiles.values()),
                "compile_counts": eng_sp.compile_counts_detailed(),
            }
            sp = record["speculative_serving"]
            log(f"speculative serving: accept_rate={sp['accept_rate']} "
                f"{sp['tokens_per_sec']} vs {sp['tokens_per_sec_off']} tok/s "
                f"({sp['decode_speedup']}x), parity={sp_parity}")

        if "hot_swap" in skip_sections:
            log("hot_swap: skipped via CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # -- hot swap: online weight publish through the version fence - #
            # ISSUE 10 serving-continuity probe: n_swaps publishes land in the
            # base engine while it decodes. Each cycle fills the pool, fences
            # a swap mid-stream (publish_async: this thread drives step(), so
            # a blocking publish would deadlock against its own fence), keeps
            # stepping until the swap lands, then submits post-swap work. The
            # record carries swap latency p50/max, the tokens/s dip inside the
            # swap windows vs steady state, the version ledger, and the
            # zero-recompile invariant across every swap.
            from chainermn_tpu.deploy import WeightPublisher

            n_swaps = int(e("CHAINERMN_TPU_SERVE_SWAPS", "3"))
            hs_sched = FCFSScheduler(engine)
            hs_pub = WeightPublisher(engine, hs_sched)
            hs_counts = engine.compile_counts_detailed()
            new_params = jax.tree_util.tree_map(lambda l: l * 1.001, params)
            base_version = engine.weight_version
            swap_total, swap_fence, swap_commit = [], [], []
            window_tokens = window_wall = 0.0
            versions_ok = True
            hs_done = 0
            hs_total = 0
            t0 = time.time()
            for k in range(n_swaps):
                pre = [hs_sched.submit(
                    rng.randint(1, vocab, rng.randint(
                        1, prefill_len + 1)).astype(np.int32), max_new)
                    for _ in range(n_slots)]
                hs_sched.step()            # admit the pool on the OLD weights
                handle = hs_pub.publish_async(new_params)
                t_sw = time.time()
                while not handle.done:     # fence drains, swap lands mid-loop
                    window_tokens += hs_sched.step()
                window_wall += time.time() - t_sw
                post = [hs_sched.submit(
                    rng.randint(1, vocab, rng.randint(
                        1, prefill_len + 1)).astype(np.int32), max_new)
                    for _ in range(2)]
                hs_sched.run_until_idle()
                swap_total.append(handle.total_s)
                swap_fence.append(handle.fence_s)
                swap_commit.append(handle.commit_s)
                want_pre = base_version + k
                versions_ok = versions_ok and all(
                    r.weight_version == want_pre for r in pre) and all(
                    r.weight_version == want_pre + 1 for r in post)
                hs_total += len(pre) + len(post)
                hs_done += sum(r.state.value == "done" for r in pre + post)
            wall_hs = time.time() - t0
            hs_m = hs_sched.metrics.report()
            steady_tps = hs_m["tokens_per_sec"]
            window_tps = window_tokens / max(window_wall, 1e-9)
            assert engine.compile_counts_detailed() == hs_counts, "recompiled!"
            record["hot_swap"] = {
                "swaps": n_swaps,
                "swap_total_s_p50": round(
                    float(np.percentile(swap_total, 50)), 6),
                "swap_total_s_max": round(float(max(swap_total)), 6),
                "swap_fence_s_p50": round(
                    float(np.percentile(swap_fence, 50)), 6),
                "swap_commit_s_p50": round(
                    float(np.percentile(swap_commit, 50)), 6),
                "tokens_per_sec_steady": steady_tps,
                "tokens_per_sec_during_swap": round(window_tps, 2),
                "throughput_dip_frac": round(
                    1.0 - window_tps / max(steady_tps, 1e-9), 4),
                "requests": hs_total,
                "requests_done": hs_done,
                "weight_version": engine.weight_version,
                "versions_correct": versions_ok,
                "wall_s": round(wall_hs, 3),
                "recompiles_after_warmup": sum(engine.recompiles.values()),
            }
            hsr = record["hot_swap"]
            log(f"hot swap: {n_swaps} swaps, total_p50="
                f"{hsr['swap_total_s_p50'] * 1e3:.1f}ms (fence "
                f"{hsr['swap_fence_s_p50'] * 1e3:.1f}ms), dip="
                f"{hsr['throughput_dip_frac']}, versions_ok={versions_ok}, "
                f"recompiles={hsr['recompiles_after_warmup']}")

        if "fleet_serving" in skip_sections:
            log("fleet_serving: skipped via CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # -- fleet: N replicas vs 1 at equal total KV budget (ISSUE 8) - #
            # The SAME prefix-heavy workload through a FleetRouter over
            # fl_n replicas of n_slots/fl_n slots each (total KV budget ==
            # the solo prefix engine above, whose numbers are the baseline),
            # plus the kill-one-replica continuity probe: replica 0 is
            # hard-killed once it owns live work — its queued/in-flight
            # requests must re-route (replayed, stream-dedup'd) or end
            # cleanly ERRORED per deadline policy; none may be lost.
            from chainermn_tpu.fleet import FleetRouter
            from chainermn_tpu.serving.scheduler import DeadlineExceededError

            fl_n = int(e("CHAINERMN_TPU_SERVE_FLEET_REPLICAS", "2"))
            fl_slots = max(1, n_slots // fl_n)
            fl_engines = [ServingEngine(
                model, params, n_slots=fl_slots, prefill_buckets=buckets,
                prefill_batch=batch_k, prefix_cache_blocks=n_blocks,
                prefix_block_size=block, prefix_min_insert_blocks=min_insert)
                for _ in range(fl_n)]
            router = FleetRouter(fl_engines, affinity=True)
            fl_col = None
            try:
                assert router.wait_ready(600), "fleet warmup timed out"
                # continuous telemetry rides the fleet run too (ISSUE 15):
                # per-replica sensors + health scoring + routing penalty,
                # sampled by a background collector for the whole probe
                from chainermn_tpu.monitor.health import fleet_health

                fl_col = fleet_health(router, cadence_s=ts_cadence,
                                      stall_timeout_s=60.0)
                fl_col.start()
                t0 = time.time()
                frs = [router.submit(prompt, n) for prompt, n in jobs]
                kill_deadline = time.time() + 60
                while time.time() < kill_deadline:
                    snap0 = router.replicas[0].snapshot()
                    if snap0.queue_depth + snap0.active_slots > 0:
                        break
                    if all(fr.finished for fr in frs):
                        break
                    time.sleep(0.001)
                router.kill_replica(0)
                finished = [fr.wait(timeout=600) for fr in frs]
                wall_fl = time.time() - t0
                # the health verdict is scored on the collector cadence: give
                # it a bounded window to observe the quarantine before the
                # report is captured (deterministic, not sleep-and-hope)
                h_deadline = time.time() + 30
                while time.time() < h_deadline:
                    h = router.fleet_report().get("health") or {}
                    if h.get("replicas", {}).get("0", {}).get(
                            "state") == "critical":
                        break
                    time.sleep(ts_cadence)
                rep = router.fleet_report()
                fl_parity = True
                for i in (0, 1):
                    prompt, n = jobs[i]
                    if frs[i].state.value != "done":
                        continue
                    ref = np.asarray(generate(model, params,
                                              jnp.asarray(prompt)[None], n)[0])
                    fl_parity = fl_parity and bool(
                        np.array_equal(frs[i].output, ref))
                lost = [fr.id for fr in frs
                        if not fr.finished
                        or (fr.state.value != "done"
                            and not isinstance(fr.error, DeadlineExceededError))]
                survivors = [r for r in router.replicas
                             if r.state.value != "quarantined"]
                pooled = rep["pooled"]
                pooled_ttft = pooled["histograms"].get(
                    "serving_ttft_seconds", {})
                fl_tokens = pooled["counters"].get("serving_tokens_total", 0)
                record["fleet_serving"] = {
                    "replicas": fl_n,
                    "slots_per_replica": fl_slots,
                    "solo_slots": n_slots,
                    "requests": len(jobs),
                    "done": sum(fr.state.value == "done" for fr in frs),
                    "all_terminal": all(finished),
                    "no_request_lost": not lost,
                    "killed_replica_quarantined":
                        router.replicas[0].state.value == "quarantined",
                    "capacity_after_kill": rep["capacity"],
                    "reroutes": rep["reroutes_total"],
                    "shed": rep["shed_total"],
                    "route_fallbacks": rep["route_fallbacks_total"],
                    "affinity_hit_rate": rep["affinity"]["hit_rate"],
                    "tokens_per_sec": round(fl_tokens / max(wall_fl, 1e-9), 2),
                    "tokens_per_sec_solo": m_on["tokens_per_sec"],
                    "ttft_p50_ms": round(
                        pooled_ttft.get("p50_s", 0.0) * 1e3, 3),
                    "ttft_p99_ms": round(
                        pooled_ttft.get("p99_s", 0.0) * 1e3, 3),
                    "ttft_p50_ms_solo": round(m_on["ttft_p50_s"] * 1e3, 3),
                    "wall_s": round(wall_fl, 3),
                    "parity_vs_solo_generate": fl_parity,
                    "recompiles_after_warmup_survivors": sum(
                        sum(r.engine.recompiles.values()) for r in survivors),
                    "replica_states": {k: v["state"]
                                       for k, v in rep["replicas"].items()},
                    # the health monitor's verdicts at probe end: the killed
                    # replica must have gone critical, survivors healthy
                    "health": rep.get("health"),
                    "ts_series": len(fl_col.store.names()),
                    "ts_ticks": fl_col.ticks,
                }
                # rolling publish through the surviving replicas: the
                # quarantined kill-probe victim is skipped, everyone still
                # accepting takes the new version with zero recompiles
                pub_out = router.publish(new_params, timeout=120.0)
                rep2 = router.fleet_report()
                record["fleet_serving"]["publish"] = {
                    "ok": pub_out["ok"],
                    "outcomes": pub_out["replicas"],
                    "weight_versions": {
                        k: v["weight_version"]
                        for k, v in rep2["replicas"].items()},
                    "recompiles_after_publish_survivors": sum(
                        sum(r.engine.recompiles.values()) for r in survivors),
                }
            finally:
                if fl_col is not None:
                    fl_col.stop()
                router.close()
            fl = record["fleet_serving"]
            log(f"fleet serving: {fl['replicas']}x{fl['slots_per_replica']} "
                f"slots, done {fl['done']}/{fl['requests']} through a "
                f"mid-run replica kill (reroutes={fl['reroutes']}, "
                f"lost={not fl['no_request_lost']}), affinity "
                f"hit_rate={fl['affinity_hit_rate']}, parity={fl_parity}")

        if "fleet_autoscale" in skip_sections:
            log("fleet_autoscale: skipped via CHAINERMN_TPU_SERVE_SKIP_SECTIONS")
        else:
            # ---- fleet autoscale: diurnal arrivals (ISSUE 16) ------------- #
            # A compressed diurnal cycle: sinusoidal arrival rate over one
            # window (trough -> peak -> trough) against a fleet that starts
            # at min_replicas with the closed-loop controller LIVE. Replica
            # count must track load — scale up under the peak, retire back
            # to the floor in the trough — with zero requests lost.
            import math

            from chainermn_tpu.fleet import AutoscalePolicy, FleetController

            as_window = float(e("CHAINERMN_TPU_SERVE_AS_WINDOW", "6.0"))
            # arrival rates are expressed as MULTIPLES of one replica's
            # measured service rate, so the peak is a genuine overload on
            # any machine (a fixed req/s would be a no-op on a fast box)
            as_base_x = float(e("CHAINERMN_TPU_SERVE_AS_BASE_X", "0.3"))
            as_peak_x = float(e("CHAINERMN_TPU_SERVE_AS_PEAK_X", "3.0"))
            as_cap = int(e("CHAINERMN_TPU_SERVE_AS_MAX_REQUESTS", "400"))
            as_min = int(e("CHAINERMN_TPU_SERVE_AS_MIN", "1"))
            as_max = int(e("CHAINERMN_TPU_SERVE_AS_MAX", "3"))
            as_prefill, as_new = 16, 12

            def as_engine():
                # deliberately small: ONE slot per replica, so the diurnal
                # peak genuinely exceeds a single replica's service rate
                return ServingEngine(model, params, n_slots=1,
                                     prefill_len=as_prefill,
                                     cache_len=as_prefill + as_new + 4)

            router2 = FleetRouter([as_engine() for _ in range(as_min)])
            ctrl = as_col = None
            try:
                assert router2.wait_ready(600), "autoscale warmup timed out"
                rng2 = np.random.RandomState(7)
                # calibrate: sequential service time of this request shape on
                # the floor fleet — the sinusoid's amplitude is set off it
                t_cal = time.time()
                for _ in range(3):
                    p2 = rng2.randint(1, vocab, size=8).astype(np.int32)
                    router2.submit(p2, as_new).wait(timeout=600)
                svc_s = max((time.time() - t_cal) / 3.0, 1e-3)
                as_base = as_base_x / svc_s
                as_peak = as_peak_x / svc_s
                as_col = fleet_health(router2, cadence_s=ts_cadence,
                                      stall_timeout_s=60.0)
                as_col.start()
                ctrl = FleetController(
                    router2, as_col, engine_factory=as_engine,
                    autoscale=AutoscalePolicy(
                        min_replicas=as_min, max_replicas=as_max,
                        queue_high=1.0, idle_low=0.25, up_after_s=0.2,
                        down_after_s=0.8, cooldown_s=0.3),
                    cadence_s=0.05, sensor_kw=dict(stall_timeout_s=60.0))
                ctrl.start()
                t0 = time.time()
                as_frs, caps = [], []
                while ((el := time.time() - t0) < as_window
                       and len(as_frs) < as_cap):
                    rate = as_base + (as_peak - as_base) * 0.5 * (
                        1.0 - math.cos(2.0 * math.pi * el / as_window))
                    # ~50ms arrival chunks: sleep() granularity stays sane
                    # even when the calibrated peak is hundreds of req/s
                    burst = max(1, int(rate * 0.05))
                    for _ in range(burst):
                        p2 = rng2.randint(
                            1, vocab, size=rng2.randint(4, 9)).astype(np.int32)
                        as_frs.append(router2.submit(p2, as_new))
                    caps.append(router2.capacity)
                    time.sleep(burst / max(rate, 0.5))
                as_done = [fr.wait(timeout=600) for fr in as_frs]
                # the trough: give the controller a bounded window to see
                # sustained idleness and retire back down to the floor
                down_deadline = time.time() + 60
                while (time.time() < down_deadline
                       and router2.capacity > as_min):
                    time.sleep(0.05)
                caps.append(router2.capacity)
                wall_as = round(time.time() - t0, 3)
                crep = ctrl.report()
                as_lost = [fr.id for fr in as_frs
                           if not fr.finished or fr.state.value != "done"]
                record["fleet_autoscale"] = {
                    "window_s": as_window,
                    "service_s_calibrated": round(svc_s, 4),
                    "arrival_base_hz": round(as_base, 2),
                    "arrival_peak_hz": round(as_peak, 2),
                    "requests": len(as_frs),
                    "done": sum(fr.state.value == "done" for fr in as_frs),
                    "all_terminal": all(as_done),
                    "no_request_lost": not as_lost,
                    "min_replicas": as_min,
                    "max_replicas": as_max,
                    "peak_capacity": max(caps),
                    "final_capacity": router2.capacity,
                    "scale_ups": crep["autoscale"]["scale_ups"],
                    "scale_downs": crep["autoscale"]["scale_downs"],
                    "replica_count_tracks_load": bool(
                        max(caps) > as_min and router2.capacity == as_min),
                    "recompiles_after_warmup": sum(
                        sum(r.engine.recompiles.values())
                        for r in router2.replicas if r.accepting),
                    "decisions": crep["decisions"],
                    "wall_s": wall_as,
                }
            finally:
                if ctrl is not None:
                    ctrl.stop()
                if as_col is not None:
                    as_col.stop()
                router2.close()
            fa = record["fleet_autoscale"]
            log(f"fleet autoscale: {fa['requests']} diurnal arrivals over "
                f"{fa['window_s']}s, capacity {fa['min_replicas']}->"
                f"{fa['peak_capacity']}->{fa['final_capacity']} "
                f"(ups={fa['scale_ups']}, downs={fa['scale_downs']}), "
                f"lost={not fa['no_request_lost']}")

        # ---- cost accounting: tenant ledger ON vs OFF, warm engine ---- #
        # ISSUE 17 acceptance: the per-request resource ledger must (a)
        # conserve — attributed device-seconds match the measured wall
        # time of every dispatch within ±10%; (b) cost <2% of serving
        # throughput; (c) let a deterministic threshold detector name the
        # bursty tenant. Two tenants share the warm base engine: "quiet"
        # submits a quarter of the jobs with short decodes, "bulk" the
        # rest with long ones. The SAME job list runs twice through fresh
        # schedulers — accounting OFF, then ON — so the wall-clock delta
        # isolates the ledger's host-side dict arithmetic.
        from chainermn_tpu.monitor._state import get_event_log
        from chainermn_tpu.monitor.costs import standard_tenant_sensors
        from chainermn_tpu.monitor.timeseries import Collector

        ca_jobs = [
            (rng.randint(1, vocab,
                         rng.randint(1, prefill_len + 1)).astype(np.int32),
             int(rng.randint(max(1, max_new // 2), max_new + 1)) if i % 4
             else int(rng.randint(1, max(2, max_new // 4))),
             "bulk" if i % 4 else "quiet")
            for i in range(n_requests)
        ]
        ca_counts = engine.compile_counts_detailed()

        def run_ca_workload(ca_on):
            s = FCFSScheduler(engine, cost_accounting=ca_on)
            col = None
            if ca_on:
                col = Collector(cadence_s=999.0)   # manual ticks only
                sigs, dets = standard_tenant_sensors(
                    "bulk", s.metrics.instance,
                    tenants=("bulk", "quiet"),
                    share_threshold=0.6, tag="bench")
                for sg in sigs:
                    col.add_signal(sg)
                for dt in dets:
                    col.add_detector(dt)
                # prime: one tiny request per tenant mints the per-tenant
                # counters, so the pre-burst tick anchors their rate
                # baselines (a counter's first sample derives no rate)
                for t in ("bulk", "quiet"):
                    s.submit(rng.randint(1, vocab, 2).astype(np.int32),
                             1, tenant=t)
                s.run_until_idle()
                col.tick()
            t0 = time.time()
            reqs = [s.submit(p, n, tenant=t) for p, n, t in ca_jobs]
            s.run_until_idle()
            wall = time.time() - t0
            summary = col.tick() if col is not None else None
            return s, reqs, wall, summary

        s_ca_off, reqs_ca_off, wall_ca_off, _ = run_ca_workload(False)
        assert s_ca_off.costs is None   # OFF really strips the ledger
        s_ca, reqs_ca, wall_ca_on, ca_tick = run_ca_workload(True)
        ca_parity = all(
            bool(np.array_equal(a.output, b.output))
            for a, b in zip(reqs_ca, reqs_ca_off))
        assert engine.compile_counts_detailed() == ca_counts, "recompiled!"
        cost_rep = s_ca.metrics.costs.report()
        ca_dt = cost_rep["device_time"]
        assert ca_dt["conservation_error"] <= 0.10, ca_dt
        assert ca_dt["max_dispatch_error"] <= 0.10, ca_dt
        nn = ca_tick["detectors"]["noisy_neighbor:bench"]
        nn_events = [ev for ev in get_event_log().tail(256)
                     if ev.get("kind") == "noisy_neighbor"]
        record["cost_accounting"] = {
            "wall_s_on": round(wall_ca_on, 3),
            "wall_s_off": round(wall_ca_off, 3),
            "accounting_overhead_frac": round(
                wall_ca_on / max(wall_ca_off, 1e-9) - 1.0, 4),
            "parity_on_vs_off": ca_parity,
            "recompiles_after_warmup": 0,
            "dispatches": ca_dt["dispatches"],
            "conservation_error": ca_dt["conservation_error"],
            "max_dispatch_error": ca_dt["max_dispatch_error"],
            "goodput": cost_rep["goodput"],
            "tenant_device_s": {
                t: row["device_total_s"]
                for t, row in cost_rep["tenants"].items()},
            "queue_wait_s": {
                t: row["queue_wait_s"]
                for t, row in cost_rep["tenants"].items()},
            "bulk_share": nn.get("value"),
            "noisy_neighbor_fired": bool(nn.get("firing")),
            "noisy_neighbor_tenant": (
                nn_events[-1].get("tenant") if nn_events else None),
        }
        ca = record["cost_accounting"]
        log(f"cost accounting: overhead={ca['accounting_overhead_frac']} "
            f"conservation={ca['conservation_error']} "
            f"(max_dispatch={ca['max_dispatch_error']} over "
            f"{ca['dispatches']} dispatches), goodput_useful="
            f"{ca['goodput']['useful']}, noisy_neighbor="
            f"{ca['noisy_neighbor_tenant']} "
            f"(share={ca['bulk_share']}), parity={ca_parity}")

        # ---- overload fairness: classes + weighted DRR vs FIFO -------- #
        # ISSUE 18 acceptance: drive the warm engine ~3x past its service
        # rate (a bursty tenant's interactive stream plus a batch tier
        # queued behind it). Plain FIFO makes the quiet tenant's
        # interactive TTFT collapse behind the backlog; fair admission
        # (strict interactive-before-batch + weighted DRR) holds it near
        # the unloaded baseline. The scheduler-owned brownout ladder
        # steps up under the sustained interactive backlog and fully
        # unwinds as it drains. Both overload runs see the SAME arrival
        # order, so token parity ON-vs-OFF proves admission order never
        # changes a stream; the warm engine never recompiles.
        from chainermn_tpu.serving.fairness import (
            BrownoutPolicy,
            FairAdmission,
        )
        from chainermn_tpu.serving.scheduler import RequestState

        of_nq = max(2, n_requests // 6)     # quiet interactive jobs
        of_rng = np.random.RandomState(18)

        def of_prompt():
            return of_rng.randint(
                1, vocab, of_rng.randint(max(1, prefill_len // 2),
                                         prefill_len + 1)).astype(np.int32)

        quiet_jobs = [(of_prompt(), max_new, "quiet", "interactive")
                      for _ in range(of_nq)]
        burst_jobs = [(of_prompt(), max_new, "burst", "interactive")
                      for _ in range(3 * of_nq)]
        batch_jobs = [(of_prompt(), max_new, "burst", "batch")
                      for _ in range(2 * of_nq)]
        # the arrival order both overload runs share: the batch backlog
        # is already queued, then the burst interleaves 3:1 with quiet
        mixed = list(batch_jobs)
        qi = iter(quiet_jobs)
        for i, job in enumerate(burst_jobs):
            mixed.append(job)
            if i % 3 == 2:
                nxt = next(qi, None)
                if nxt is not None:
                    mixed.append(nxt)
        mixed.extend(qi)

        def of_run(sched, jobs, track=None):
            t_first = {}
            reqs = []
            for prompt, n, tenant, priority in jobs:
                key = len(reqs)

                def cb(tok, _k=key):
                    t_first.setdefault(_k, time.perf_counter())
                reqs.append(sched.submit(prompt, n, tenant=tenant,
                                         priority=priority, stream_cb=cb))
            max_level = 0
            while sched.has_work:
                sched.step()
                if track is not None:
                    max_level = max(max_level, track.level)
            ttft = [t_first[i] - r.t_submit for i, r in enumerate(reqs)]
            return reqs, ttft, max_level

        def of_quiet_p99(jobs, ttft):
            vals = [t for j, t in zip(jobs, ttft)
                    if j[2] == "quiet" and j[3] == "interactive"]
            return float(np.percentile(np.asarray(vals), 99))

        of_counts = engine.compile_counts_detailed()
        # unloaded baseline: the quiet tenant alone on the warm engine
        s_of_base = FCFSScheduler(engine)
        _, base_ttft, _ = of_run(s_of_base, quiet_jobs)
        of_base_p99 = of_quiet_p99(quiet_jobs, base_ttft)
        # FIFO under overload: the pre-PR-18 scheduler, byte-identical
        s_of_fifo = FCFSScheduler(engine)
        fifo_reqs, fifo_ttft, _ = of_run(s_of_fifo, mixed)
        of_fifo_p99 = of_quiet_p99(mixed, fifo_ttft)
        # fair admission + brownout under the SAME arrivals. max_level=2
        # keeps L3's token cap and L4's shed out of play, so accepted
        # requests are EXACTLY the FIFO run's (parity + nothing lost);
        # quantum below typical request cost makes the 4:1 weights gate.
        of_bo = BrownoutPolicy(
            max_level=2, queue_high=float(max(2, n_slots // 2)),
            up_after_s=0.01, down_after_s=0.05, cooldown_s=0.03)
        of_fair = FairAdmission(
            tenant_weights={"quiet": 4.0, "burst": 1.0},
            quantum_tokens=2.0)
        s_of_fair = FCFSScheduler(engine, fair=of_fair, brownout=of_bo)
        fair_reqs, fair_ttft, of_max_level = of_run(s_of_fair, mixed,
                                                    track=of_bo)
        of_fair_p99 = of_quiet_p99(mixed, fair_ttft)
        # idle + calm: sustained zero interactive depth unwinds the
        # ladder one hysteresis window at a time
        of_deadline = time.time() + 30.0
        while of_bo.level > 0 and time.time() < of_deadline:
            s_of_fair.step()
            time.sleep(0.005)
        of_parity = all(
            bool(np.array_equal(a.output, b.output))
            for a, b in zip(fair_reqs, fifo_reqs))
        of_lost = not all(r.state is RequestState.DONE
                          for r in fifo_reqs + fair_reqs)
        assert engine.compile_counts_detailed() == of_counts, "recompiled!"
        of_cp = s_of_fair.metrics._c_class_preempt
        record["overload_fairness"] = {
            "slots": n_slots,
            "jobs": {"quiet_interactive": of_nq,
                     "burst_interactive": 3 * of_nq,
                     "batch": 2 * of_nq},
            "overload_factor": round(6 * of_nq / max(of_nq, 1), 2),
            "quiet_p99_unloaded": round(of_base_p99, 4),
            "quiet_p99_fifo": round(of_fifo_p99, 4),
            "quiet_p99_fair": round(of_fair_p99, 4),
            "fifo_collapse_factor": round(
                of_fifo_p99 / max(of_base_p99, 1e-9), 2),
            "quiet_slowdown_factor": round(
                of_fair_p99 / max(of_base_p99, 1e-9), 2),
            "quiet_goodput_tokens": int(of_nq * max_new),
            "brownout": {
                "max_level": int(of_max_level),
                "final_level": int(of_bo.level),
                "steps": of_bo.to_json()["steps"],
            },
            "preempted_interactive": int(of_cp["interactive"].value),
            "preempted_batch": int(of_cp["batch"].value),
            "token_parity_on_vs_off": of_parity,
            "no_request_lost": not of_lost,
            "recompiles_after_warmup": 0,
            "conservation_error": round(
                s_of_fair.costs.conservation_error, 9),
        }
        of = record["overload_fairness"]
        log(f"overload fairness: quiet TTFT p99 unloaded="
            f"{of['quiet_p99_unloaded']}s fifo={of['quiet_p99_fifo']}s "
            f"(x{of['fifo_collapse_factor']}) fair="
            f"{of['quiet_p99_fair']}s (x{of['quiet_slowdown_factor']}), "
            f"brownout {of['brownout']['max_level']}->"
            f"{of['brownout']['final_level']}, parity={of_parity}, "
            f"lost={of_lost}")

        # ---- chunked prefill: decode stall ON vs OFF ------------------ #
        # ISSUE 19 acceptance: with monolithic prefill, every long-prompt
        # admission stalls every decoding slot for the full top-bucket
        # prefill; chunked prefill bounds the stall to one chunk's bucket.
        # The SAME victim+aggressor arrival runs twice on one warm paged
        # engine — decode-gap p99 across the victims' streams must be
        # >= 2x better with chunking ON, token streams identical, zero
        # recompiles (chunks ride the warmup buckets).
        cp_chunk = int(e("CHAINERMN_TPU_SERVE_CHUNK_TOKENS", "16"))
        cp_nv = int(e("CHAINERMN_TPU_SERVE_CP_VICTIMS", "3"))
        cp_na = int(e("CHAINERMN_TPU_SERVE_CP_LONG", "2"))
        # the aggressor prompts get 8x the serving model's window: on CPU
        # a dispatch costs ~same as a small prefill, so the monolithic
        # top-bucket prefill has to DWARF one decode step (not just beat
        # it) for the stall to be the signal, not the call overhead
        cp_prefill = 8 * prefill_len
        cp_new = max(8, max_new)
        cp_model = TransformerLM(
            vocab_size=vocab, d_model=d_model, n_heads=n_heads,
            n_layers=n_layers, max_len=cp_prefill + cp_new)
        cp_params = cp_model.init(
            jax.random.PRNGKey(3), jnp.zeros((1, cp_prefill), jnp.int32))
        cp_rng = np.random.RandomState(19)
        cp_eng = ServingEngine(
            cp_model, cp_params, n_slots=cp_nv + 1,
            prefill_buckets=(cp_chunk, cp_prefill), prefill_batch=1,
            paged=True, kv_block_size=cp_chunk,
            kv_blocks=2 * (cp_nv + 1) * (-(-(cp_prefill + cp_new)
                                           // cp_chunk)),
            cache_len=cp_prefill + cp_new)
        cp_eng.warmup()
        cp_counts = cp_eng.compile_counts_detailed()
        victims = [(cp_rng.randint(1, vocab, cp_chunk - 2)
                    .astype(np.int32), cp_new) for _ in range(cp_nv)]
        aggressors = [(cp_rng.randint(1, vocab, cp_prefill - 1)
                       .astype(np.int32), 2) for _ in range(cp_na)]

        def cp_run(chunk):
            s = FCFSScheduler(cp_eng, chunk_tokens_per_step=chunk)
            stamps = [[] for _ in victims]
            vreqs = [
                s.submit(p, n, rng=jax.random.PRNGKey(100 + i),
                         stream_cb=lambda tok, _i=i: stamps[_i].append(
                             time.perf_counter()))
                for i, (p, n) in enumerate(victims)]
            while not all(stamps):          # victims all decoding first
                s.step()
            areqs = [s.submit(p, n, rng=jax.random.PRNGKey(200 + i))
                     for i, (p, n) in enumerate(aggressors)]
            while s.has_work:
                s.step()
            gaps = [b - a for ts in stamps
                    for a, b in zip(ts, ts[1:])]
            return ([r.tokens for r in vreqs + areqs],
                    float(np.percentile(np.asarray(gaps), 99)))

        cp_toks_off, cp_p99_off = cp_run(None)
        cp_toks_on, cp_p99_on = cp_run(cp_chunk)
        cp_parity = cp_toks_on == cp_toks_off
        assert cp_eng.compile_counts_detailed() == cp_counts, "recompiled!"
        record["chunked_prefill_serving"] = {
            "chunk_tokens": cp_chunk,
            "victims": cp_nv,
            "long_prompts": cp_na,
            "long_prompt_len": cp_prefill - 1,
            "decode_gap_p99_ms_off": round(cp_p99_off * 1e3, 3),
            "decode_gap_p99_ms_on": round(cp_p99_on * 1e3, 3),
            "stall_improvement": round(cp_p99_off / max(cp_p99_on, 1e-9),
                                       2),
            "token_parity_on_vs_off": cp_parity,
            "recompiles_after_warmup": 0,
        }
        cp = record["chunked_prefill_serving"]
        log(f"chunked prefill: victim decode-gap p99 "
            f"off={cp['decode_gap_p99_ms_off']}ms "
            f"on={cp['decode_gap_p99_ms_on']}ms "
            f"(x{cp['stall_improvement']}), parity={cp_parity}")

        # ---- disaggregated prefill/decode tiers ----------------------- #
        # 1P+1D with KV migration vs the same fleet symmetric: every
        # request prefills on the P tier, its blocks host-bounce to the D
        # tier, and the stream finishes there — same tokens either way,
        # nothing lost, no recompiles. The record carries both configs'
        # latency splits and the migration counters.
        from chainermn_tpu.fleet import FleetRouter
        from chainermn_tpu.monitor._state import get_registry

        dg_n = int(e("CHAINERMN_TPU_SERVE_DG_REQUESTS", "6"))
        dg_rng = np.random.RandomState(20)
        dg_jobs = [(dg_rng.randint(1, vocab, prefill_len - 1)
                    .astype(np.int32), max_new) for _ in range(dg_n)]

        def dg_engine():
            return ServingEngine(
                model, params, n_slots=2,
                prefill_buckets=(cp_chunk, prefill_len), prefill_batch=1,
                paged=True, kv_block_size=cp_chunk,
                kv_blocks=6 * (-(-(prefill_len + max_new) // cp_chunk)),
                cache_len=prefill_len + max_new)

        def dg_run(**tiers):
            router = FleetRouter([dg_engine(), dg_engine()], **tiers)
            try:
                assert router.wait_ready(600)
                t0 = time.perf_counter()
                frs = [router.submit(p, n,
                                     rng=jax.random.PRNGKey(300 + i))
                       for i, (p, n) in enumerate(dg_jobs)]
                done = all(fr.wait(300) for fr in frs)
                wall = time.perf_counter() - t0
                rep = router.fleet_report()
                for r in router.replicas:
                    assert r.engine.recompiles == {}, "recompiled!"
                return ([list(fr.tokens) for fr in frs], done, wall,
                        rep["tiers"])
            finally:
                router.close()

        dg_mig0 = sum(
            v for k, v in get_registry().snapshot()["counters"].items()
            if k.startswith("kv_migrations_total"))
        dg_toks, dg_done, dg_wall, dg_tiers = dg_run(
            prefill_replicas=1, decode_replicas=1,
            chunk_tokens_per_step=cp_chunk)
        dg_migrations = sum(
            v for k, v in get_registry().snapshot()["counters"].items()
            if k.startswith("kv_migrations_total")) - dg_mig0
        sym_toks, sym_done, sym_wall, _ = dg_run()
        record["disagg_serving"] = {
            "requests": dg_n,
            "tiers": dg_tiers,
            "migrations": int(dg_migrations),
            "wall_s_disagg": round(dg_wall, 3),
            "wall_s_symmetric": round(sym_wall, 3),
            "token_parity_vs_symmetric": dg_toks == sym_toks,
            "no_request_lost": bool(dg_done and sym_done),
            "recompiles_after_warmup": 0,
        }
        dg = record["disagg_serving"]
        log(f"disagg serving: {dg_n} reqs 1P+1D wall="
            f"{dg['wall_s_disagg']}s (symmetric="
            f"{dg['wall_s_symmetric']}s), migrations="
            f"{dg['migrations']}, parity={dg['token_parity_vs_symmetric']}"
            f", lost={not dg['no_request_lost']}")

        # ---- fleet-wide KV reuse: cross-replica prefix sharing -------- #
        # 3 paged replicas, every request carrying one shared system
        # prompt, and a zero-tolerance imbalance policy so the holder's
        # own load pushes traffic to its peers — the affinity-miss-heavy
        # arrival sharing exists for. ON: the holder exports the prefix
        # blocks ONCE through the fused gather, the host payload LRU
        # serves every later adopter, and peers prefill only their ragged
        # tails. OFF: every miss re-prefills the whole prompt. Same
        # tokens either way; the record carries TTFT p50 both ways plus
        # the fleet prefill tokens/FLOPs the shares avoided.
        from chainermn_tpu.fleet.routing import RoutingPolicy
        from chainermn_tpu.monitor._state import get_event_log

        ps_n = int(e("CHAINERMN_TPU_SERVE_PS_REQUESTS", "9"))
        ps_rng = np.random.RandomState(22)
        ps_shared = ps_rng.randint(1, vocab, prefill_len - 4) \
            .astype(np.int32)
        ps_jobs = [np.concatenate([ps_shared,
                                   ps_rng.randint(1, vocab, 1 + (i % 4))
                                   .astype(np.int32)])
                   for i in range(ps_n)]
        ps_params = int(sum(x.size
                            for x in jax.tree_util.tree_leaves(params)))

        # small blocks so the shared prefix spans MANY trie blocks: the
        # share trigger needs the fleet trie to know >=
        # prefix_share_min_blocks of it, and the fused transfer gets a
        # real multi-block payload. Overridable so CI can pick a bigger
        # block (fewer warmup-bucketed migration programs to compile).
        ps_block = int(e("CHAINERMN_TPU_SERVE_PS_BLOCK", "4"))

        def ps_engine():
            return ServingEngine(
                model, params, n_slots=2,
                prefill_buckets=(4, prefill_len), prefill_batch=1,
                paged=True, kv_block_size=ps_block,
                kv_blocks=6 * (-(-(prefill_len + max_new) // ps_block)),
                cache_len=prefill_len + max_new)

        def ps_fleet(share):
            return FleetRouter(
                [ps_engine() for _ in range(3)],
                policy=RoutingPolicy(max_imbalance=0.0),
                share_prefixes=share, prefix_share_min_blocks=2)

        def ps_run(router):
            assert router.wait_ready(600)
            evs0 = get_event_log().tail(1)
            seq0 = evs0[-1]["i"] if evs0 else -1
            t_submit, t_first, frs = {}, {}, []
            for i, p in enumerate(ps_jobs):
                def cb(tok, _i=i):
                    t_first.setdefault(_i, time.perf_counter())
                t_submit[i] = time.perf_counter()
                frs.append(router.submit(
                    p, max_new, rng=jax.random.PRNGKey(400 + i),
                    stream_cb=cb))
                if i == 0:
                    # the holder serves the system prompt once
                    # BEFORE the burst: sharing targets the steady
                    # state where the prefix is already resident
                    # somewhere, so the burst's misses find a
                    # populated trie to adopt from
                    assert frs[0].wait(300)
            done = all(fr.wait(300) for fr in frs)
            ttfts = [t_first[i] - t_submit[i] for i in range(ps_n)]
            cached = sum(ev.get("cached", 0)
                         for ev in get_event_log().tail()
                         if ev["i"] > seq0
                         and ev["kind"] == "slot_admit")
            rep = router.fleet_report()["kv_reuse"]
            for r in router.replicas:
                assert r.engine.recompiles == {}, "recompiled!"
            return ([list(fr.tokens) for fr in frs], done,
                    float(np.percentile(np.asarray(ttfts), 50)),
                    int(cached), rep)

        ps_router = ps_fleet(False)
        try:
            ps_toks_off, ps_done_off, ps_p50_off, ps_cached_off, _ = \
                ps_run(ps_router)
        finally:
            ps_router.close()
        ps_router = ps_fleet(True)
        try:
            ps_toks_on, ps_done_on, ps_p50_on, ps_cached_on, ps_rep = \
                ps_run(ps_router)
            ps_saved = max(0, ps_cached_on - ps_cached_off)

            # rebalance probe, riding the already-warm ON fleet: a
            # throttled stream keeps one request mid-decode while the
            # router drains it to a peer through the fused path — the
            # stream finishes token-exactly on its new home, nothing
            # lost. (ps_rep was snapshotted above, so the probe's own
            # counters don't leak into the share numbers.)
            rb_prompt = ps_jobs[0]
            rb_ref = ps_router.generate(rb_prompt, max_new,
                                        rng=jax.random.PRNGKey(500),
                                        timeout=300)
            rb_ref_tail = [int(t) for t in rb_ref[len(rb_prompt):]]
            rb_fr = ps_router.submit(
                rb_prompt, max_new, rng=jax.random.PRNGKey(500),
                stream_cb=lambda tok: time.sleep(0.01))
            while not (rb_fr.tokens or rb_fr.finished):
                time.sleep(0.002)
            rb_src = rb_fr.replica_id
            rb_dest_pick = (rb_src + 1) % len(ps_router.replicas)
            rb_ticket = ps_router.rebalance_decode(rb_src, rb_dest_pick)
            rb_moved = (bool(rb_ticket.wait(30))
                        if rb_ticket is not None else False)
            rb_done = rb_fr.wait(300)
            rb_parity = [int(t) for t in rb_fr.tokens] == rb_ref_tail
            rb_dest = rb_fr.replica_id
        finally:
            ps_router.close()

        record["fleet_prefix_share"] = {
            "replicas": 3,
            "requests": ps_n,
            "shared_prefix_tokens": int(len(ps_shared)),
            "ttft_p50_ms_on": round(ps_p50_on * 1e3, 3),
            "ttft_p50_ms_off": round(ps_p50_off * 1e3, 3),
            "ttft_p50_speedup": round(ps_p50_off / max(ps_p50_on, 1e-9),
                                      2),
            "shares": int(ps_rep["shares"]),
            "payload_cache": ps_rep["payload_cache"],
            "prefill_tokens_saved": int(ps_saved),
            "prefill_flops_saved": float(2 * ps_params * ps_saved),
            "token_parity_on_vs_off": ps_toks_on == ps_toks_off,
            "no_request_lost": bool(ps_done_on and ps_done_off),
            "recompiles_after_warmup": 0,
            "rebalance_probe": {
                "moved": bool(rb_moved),
                "src_replica": rb_src,
                "dest_replica": rb_dest,
                "token_parity": bool(rb_parity),
                "no_request_lost": bool(rb_done),
            },
        }
        psr = record["fleet_prefix_share"]
        log(f"fleet prefix share: {ps_n} reqs x3 replicas ttft_p50 "
            f"{psr['ttft_p50_ms_on']}ms (on) vs "
            f"{psr['ttft_p50_ms_off']}ms (off), shares={psr['shares']}, "
            f"tokens_saved={psr['prefill_tokens_saved']}, "
            f"parity={psr['token_parity_on_vs_off']}; rebalance "
            f"moved={psr['rebalance_probe']['moved']} "
            f"parity={psr['rebalance_probe']['token_parity']}")

        from chainermn_tpu.monitor import snapshot as monitor_snapshot

        record["monitor"] = monitor_snapshot()
    except Exception as exc:  # one parseable line, never a bare traceback
        log(f"serving bench failed: {type(exc).__name__}: {exc}")
        record = {
            "metric": "serving_decode_throughput",
            "value": None,
            "unit": "tokens/sec",
            "mode": "serving",
            "error": type(exc).__name__,
            "detail": str(exc)[-500:],
        }
        print(json.dumps(record))
        raise SystemExit(1)
    print(json.dumps(record))


def monitor_main() -> None:
    """``bench.py --mode monitor``: telemetry-subsystem smoke cell.

    Proves, in one JSON record, the two monitor acceptance criteria that
    need a live workload: (1) **overhead** — the same compiled LM train
    step timed bare vs through ``monitor.instrument`` (events + metrics +
    recompile tracking), reported as ``overhead_frac`` (<2% is the
    production target; the CI assertion uses a generous bound because
    millisecond CPU steps are noisy); (2) **flight recorder** — a serving
    burst runs with monitoring on, then a simulated hang inside a
    watchdog-armed window must dump the last events (slot admits/retires
    included) + per-device memory stats. The record also embeds the full
    registry ``snapshot`` like every other mode.

    Knobs: ``CHAINERMN_TPU_MONITOR_STEPS`` (timed steps per side, default
    30) and the ``CHAINERMN_TPU_SERVE_*`` sizes shared with serving mode.
    The ``slow``-marked soak variant in tests/test_bench_smoke.py raises
    the step/request counts through these.
    """
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    import io

    import numpy as np

    import jax

    devs = _start_backend()

    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu import monitor
    from chainermn_tpu.extensions import Watchdog
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.serving import FCFSScheduler, ServingEngine
    from chainermn_tpu.training import jit_lm_train_step

    e = os.environ.get
    n_steps = int(e("CHAINERMN_TPU_MONITOR_STEPS", "30"))
    n_slots = int(e("CHAINERMN_TPU_SERVE_SLOTS", "4"))
    n_requests = int(e("CHAINERMN_TPU_SERVE_REQUESTS", "12"))
    prefill_len = int(e("CHAINERMN_TPU_SERVE_PREFILL_LEN", "8"))
    max_new = int(e("CHAINERMN_TPU_SERVE_MAX_NEW", "8"))
    vocab = int(e("CHAINERMN_TPU_SERVE_VOCAB", "64"))
    d_model = int(e("CHAINERMN_TPU_SERVE_DMODEL", "64"))
    n_layers = int(e("CHAINERMN_TPU_SERVE_LAYERS", "2"))
    n_heads = int(e("CHAINERMN_TPU_SERVE_HEADS", "4"))

    log(f"monitor smoke: devices={len(devs)} kind={devs[0].device_kind!r} "
        f"steps={n_steps} requests={n_requests}")
    try:
        # ---- overhead: bare jitted step vs instrumented wrapper -------- #
        lm = TransformerLM(vocab_size=vocab, d_model=d_model,
                           n_heads=n_heads, n_layers=n_layers,
                           max_len=prefill_len + max_new)
        comm = chainermn_tpu.create_communicator("tpu")
        tokens = jnp.zeros((8 * max(len(devs), 1), 16), jnp.int32)
        targets = jnp.zeros_like(tokens)
        params = comm.bcast_data(
            lm.init(jax.random.PRNGKey(0), tokens[:1]))
        # the multi-node wrapper owns the gradient mean: a plain optax
        # transform would leave params per-rank, which check_vma rejects
        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
        opt_state = jax.device_put(opt.init(params), comm.named_sharding())
        bare = jit_lm_train_step(lm, opt, comm, donate=False,
                                 monitored=False)
        mon = monitor.instrument(bare, "lm_train_step")  # same jit cache

        def timed(step, k):
            best = None
            for _ in range(2):  # best-of-2 damps scheduler noise
                t0 = time.perf_counter()
                for _ in range(k):
                    p, s, loss, _ = step(params, opt_state, tokens, targets)
                float(loss)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            return best

        timed(bare, 3)  # compile + warm both paths (same executable)
        timed(mon, 3)
        t_bare = timed(bare, n_steps)
        t_mon = timed(mon, n_steps)
        overhead = (t_mon - t_bare) / t_bare
        log(f"monitored step overhead: {overhead:+.2%} "
            f"({t_mon / n_steps * 1e3:.3f} vs {t_bare / n_steps * 1e3:.3f} "
            "ms/step)")

        # ---- serving burst + simulated hang -> flight recorder --------- #
        sink = io.StringIO()
        # engine watchdog: genuinely armed around every device call, but
        # sized not to fire on warmup compiles (this cell proves wiring,
        # not hangs); the short-fuse dog below simulates the actual hang
        engine_dog = Watchdog(timeout=120.0, on_timeout="warn", _sink=sink)
        dog = Watchdog(timeout=0.25, on_timeout="warn", _sink=sink)
        eng_params = lm.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, prefill_len), jnp.int32))
        engine = ServingEngine(lm, eng_params, n_slots=n_slots,
                               prefill_len=prefill_len, watchdog=engine_dog)
        sched = FCFSScheduler(engine)
        rng = np.random.RandomState(0)
        for _ in range(n_requests):
            prompt = rng.randint(1, vocab, rng.randint(1, prefill_len + 1))
            sched.submit(prompt.astype(np.int32),
                         int(rng.randint(1, max_new + 1)))
        sched.run_until_idle()
        with dog.step("simulated hang (monitor smoke)"):
            time.sleep(0.6)   # > timeout: watchdog fires and dumps
        flight = sink.getvalue()
        flight_events = sum(
            1 for line in flight.splitlines() if line.startswith("{"))

        # ---- tracing + SLO + HTTP scrape surface ----------------------- #
        # The burst above ran through the default tracer (the scheduler
        # opens a trace per request), so the ring already holds serving
        # span trees; declare a generous TTFT SLO over the live registry,
        # stand the stdlib endpoint up on an ephemeral port, and scrape
        # all four routes the way a Prometheus/Perfetto consumer would.
        from urllib.request import urlopen

        from chainermn_tpu.monitor import http as monitor_http
        from chainermn_tpu.monitor.slo import LatencyObjective, SLOEngine
        from chainermn_tpu.monitor.trace import get_tracer

        tracer = get_tracer()
        serving_traces = tracer.finished(kind="serving")
        slo = SLOEngine()
        slo.add(LatencyObjective("ttft_p99", "serving_ttft_seconds",
                                 threshold_s=30.0, windows=(60.0, 300.0)))
        slo_report = slo.evaluate()
        with monitor_http.serve(port=0, slo=slo) as srv:
            http_block = {"port": srv.port}
            metrics_txt = urlopen(srv.url + "/metrics",
                                  timeout=10).read().decode()
            http_block["metrics_ok"] = "serving_ttft_seconds" in metrics_txt
            tr = json.loads(urlopen(srv.url + "/traces", timeout=10).read())
            trace_events = tr.get("traceEvents", [])
            http_block["trace_events"] = len(trace_events)
            http_block["traces_ok"] = bool(trace_events) and all(
                ev.get("ph") in ("X", "M") and "pid" in ev and "tid" in ev
                for ev in trace_events)
            slo_http = json.loads(urlopen(srv.url + "/slo",
                                          timeout=10).read())
            http_block["slo_ok"] = "ttft_p99" in slo_http
            evs = json.loads(urlopen(srv.url + "/events", timeout=10).read())
            http_block["events_ok"] = bool(evs.get("events"))
        snap = monitor.snapshot()
        steps_counted = sum(
            v for k, v in snap["counters"].items()
            if k.startswith("steps_total"))
        record = {
            "metric": "monitor_smoke",
            "value": steps_counted,
            "unit": "monitored_steps",
            "mode": "monitor",
            "n_chips": len(devs),
            "device_kind": devs[0].device_kind,
            "overhead_frac": round(overhead, 4),
            "step_time_ms": round(t_bare / n_steps * 1e3, 3),
            "watchdog_fired": dog.fired,
            "flight_events_in_dump": flight_events,
            "flight_has_slot_admit": '"kind": "slot_admit"' in flight,
            "flight_has_slot_retire": '"kind": "slot_retire"' in flight,
            "flight_has_memory": "device memory" in flight,
            "serving": sched.metrics.report(),
            "recompiles": engine.compile_counts(),
            "trace": {
                "serving_traces": len(serving_traces),
                "spans_example": ([s.name for s in serving_traces[0].spans]
                                  if serving_traces else []),
            },
            "http": http_block,
            "slo": {k: {"max_burn_rate": v["max_burn_rate"],
                        "compliant": v["compliant"]}
                    for k, v in slo_report.items()},
            "monitor": snap,
        }
    except Exception as exc:  # one parseable line, never a bare traceback
        log(f"monitor smoke failed: {type(exc).__name__}: {exc}")
        record = {
            "metric": "monitor_smoke",
            "value": None,
            "unit": "monitored_steps",
            "mode": "monitor",
            "error": type(exc).__name__,
            "detail": str(exc)[-500:],
        }
        print(json.dumps(record))
        raise SystemExit(1)
    print(json.dumps(record))


def resilience_main() -> None:
    """``bench.py --mode resilience``: fault-injection / recovery cell.

    One JSON record proving the resilience loop live, with the numbers the
    ISSUE names: **checkpoint save/restore latency** (the recovery path's
    I/O cost), **MTTR** — wall-clock from an injected crash at a chosen
    training step to the first completed post-resume step — plus a
    **bit-exactness** verdict (the faulted run's final loss must equal an
    uninterrupted reference run's, RNG/iterator state round-tripping
    through the snapshot), and the serving degradation counts
    (rejected / shed / errored / restarts) from a burst driven into a
    bounded queue with an injected engine raise. Embeds the registry
    ``snapshot`` like every other mode.

    Knobs: ``CHAINERMN_TPU_RESIL_STEPS`` (default 16),
    ``CHAINERMN_TPU_RESIL_FAULT_STEP`` (default 9),
    ``CHAINERMN_TPU_RESIL_SAVE_EVERY`` (default 4) and the
    ``CHAINERMN_TPU_SERVE_*`` sizes shared with serving mode.
    """
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    import tempfile

    import numpy as np

    import jax

    devs = _start_backend()

    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu import monitor
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.resilience import FaultInjector, resilient_fit
    from chainermn_tpu.serving import (
        QueueFullError,
        RequestState,
        ServingEngine,
    )
    from chainermn_tpu.training import jit_lm_train_step

    e = os.environ.get
    n_steps = int(e("CHAINERMN_TPU_RESIL_STEPS", "16"))
    fault_step = int(e("CHAINERMN_TPU_RESIL_FAULT_STEP", "9"))
    save_every = int(e("CHAINERMN_TPU_RESIL_SAVE_EVERY", "4"))
    n_slots = int(e("CHAINERMN_TPU_SERVE_SLOTS", "2"))
    prefill_len = int(e("CHAINERMN_TPU_SERVE_PREFILL_LEN", "8"))
    max_new = int(e("CHAINERMN_TPU_SERVE_MAX_NEW", "8"))
    vocab = int(e("CHAINERMN_TPU_SERVE_VOCAB", "64"))
    d_model = int(e("CHAINERMN_TPU_SERVE_DMODEL", "32"))
    n_layers = int(e("CHAINERMN_TPU_SERVE_LAYERS", "1"))
    n_heads = int(e("CHAINERMN_TPU_SERVE_HEADS", "4"))
    seq_len = 16

    log(f"resilience smoke: devices={len(devs)} "
        f"kind={devs[0].device_kind!r} steps={n_steps} "
        f"fault_step={fault_step}")
    try:
        # ---- auto-resume training: crash at fault_step, recover -------- #
        lm = TransformerLM(vocab_size=vocab, d_model=d_model,
                           n_heads=n_heads, n_layers=n_layers,
                           max_len=seq_len)
        comm = chainermn_tpu.create_communicator("tpu")
        rng = np.random.RandomState(0)
        toks = rng.randint(1, vocab, (64, seq_len)).astype(np.int32)
        tgts = np.roll(toks, -1, axis=1)
        batch = 2 * max(len(devs), 1)
        params0 = comm.bcast_data(
            lm.init(jax.random.PRNGKey(0), jnp.asarray(toks[:1])))
        # multi-node wrapper: grads allreduced before the update, so every
        # device's replica stays bitwise identical — the property that
        # makes a replica-0 snapshot restore bit-exact
        opt = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(0.1), comm)
        jitted = jit_lm_train_step(lm, opt, comm, donate=False)

        def step_fn(state, batch_idx):
            sel = np.asarray(batch_idx)
            p, s, loss, _ = jitted(state["params"], state["opt"],
                                   jnp.asarray(toks[sel]),
                                   jnp.asarray(tgts[sel]))
            return {"params": p, "opt": s, "loss": float(loss)}

        def init_state():
            return {"params": params0,
                    "opt": jax.device_put(opt.init(params0),
                                          comm.named_sharding()),
                    "loss": None}

        def restore_hook(state):
            # snapshots hold host arrays; put them back on the mesh with
            # the original (replicated) shardings so the resumed step
            # reuses the same executable -> bit-exact trajectory
            return {"params": jax.device_put(state["params"],
                                             comm.named_sharding()),
                    "opt": jax.device_put(state["opt"],
                                          comm.named_sharding()),
                    "loss": state["loss"]}

        def run(path, injector=None):
            ckpt = chainermn_tpu.create_multi_node_checkpointer(
                "bench", comm, path=path)
            it = chainermn_tpu.SerialIterator(
                list(range(len(toks))), batch_size=batch, shuffle=True,
                seed=7)
            if injector is None:
                return resilient_fit(step_fn, init_state(), it, n_steps,
                                     ckpt, save_every=save_every,
                                     restore_hook=restore_hook)
            with injector:
                return resilient_fit(step_fn, init_state(), it, n_steps,
                                     ckpt, save_every=save_every,
                                     restore_hook=restore_hook,
                                     dump_on_failure=False)

        with tempfile.TemporaryDirectory() as ref_dir:
            ref_state, ref_report = run(ref_dir)
        inj = FaultInjector(seed=0)
        inj.arm("trainer.step", kind="raise", after=fault_step, times=1)
        with tempfile.TemporaryDirectory() as crash_dir:
            state, report = run(crash_dir, injector=inj)
        bit_exact = bool(state["loss"] == ref_state["loss"])
        mttr_s = report["mttr_s"][0] if report["mttr_s"] else None
        ck = report["checkpoint_stats"]
        log(f"crash at step {fault_step}: restores={report['restores']} "
            f"mttr={mttr_s:.3f}s save={ck['save'] * 1e3:.1f}ms "
            f"load={ck['load'] * 1e3:.1f}ms bit_exact={bit_exact}")

        # ---- serving degradation burst (deterministic scenario) -------- #
        from chainermn_tpu.serving import FCFSScheduler

        eng_params = lm.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, prefill_len), jnp.int32))
        engine = ServingEngine(lm, eng_params, n_slots=n_slots,
                               prefill_len=prefill_len,
                               cache_len=prefill_len + max_new)
        sched = FCFSScheduler(engine, max_queue=4)

        def prompt():
            return rng.randint(
                1, vocab, rng.randint(1, prefill_len + 1)).astype(np.int32)

        reqs = []
        for _ in range(n_slots):           # occupy every slot
            reqs.append(sched.submit(prompt(), max_new))
        sched.step()
        for _ in range(3):                 # doomed: shed before admission
            reqs.append(sched.submit(prompt(), 2, deadline_s=0.01))
        rejected = 0
        for _ in range(3):                 # overflow the bounded queue
            try:
                reqs.append(sched.submit(prompt(), 2))
            except QueueFullError:
                rejected += 1
        time.sleep(0.05)                   # the doomed deadlines expire
        sinj = FaultInjector(seed=0)
        sinj.arm("serving.decode", kind="raise", times=1)
        with sinj:                         # in-flight fail -> warm restart
            sched.run_until_idle()
        terminal = all(
            r.state in (RequestState.DONE, RequestState.ERRORED,
                        RequestState.CANCELLED) for r in reqs)
        sm = sched.metrics.report()

        snap = monitor.snapshot()
        record = {
            "metric": "resilience_mttr",
            "value": round(mttr_s * 1e3, 3) if mttr_s is not None else None,
            "unit": "ms",
            "mode": "resilience",
            "n_chips": len(devs),
            "device_kind": devs[0].device_kind,
            "bit_exact_resume": bit_exact,
            "checkpoint_save_ms": round(ck["save"] * 1e3, 3),
            "checkpoint_load_ms": round(ck["load"] * 1e3, 3),
            "trainer": {
                "steps": report["steps"],
                "failures": report["failures"],
                "restores": report["restores"],
                "fault_step": fault_step,
                "save_every": save_every,
            },
            "serving": {
                "submitted": len(reqs),
                "rejected": rejected,
                "shed": sm["requests_shed"],
                "errored": sm["requests_errored"],
                "engine_restarts": sm["engine_restarts"],
                "all_terminal": terminal,
            },
            "faults_injected": len(inj.fired_log) + len(sinj.fired_log),
            "monitor": snap,
        }
    except Exception as exc:  # one parseable line, never a bare traceback
        log(f"resilience smoke failed: {type(exc).__name__}: {exc}")
        record = {
            "metric": "resilience_mttr",
            "value": None,
            "unit": "ms",
            "mode": "resilience",
            "error": type(exc).__name__,
            "detail": str(exc)[-500:],
        }
        print(json.dumps(record))
        raise SystemExit(1)
    print(json.dumps(record))


def pipeline_main() -> None:
    """``bench.py --mode pipeline``: async hot-loop overlap proof.

    One JSON record demonstrating the ``dataflow`` claim: with a loader
    that takes ``d`` ms per batch, the SYNCHRONOUS loop (draw batch ->
    device_put -> step -> ``float(loss)`` per step) pays ``step + d`` per
    iteration, while the pipelined loop (``DevicePrefetcher`` producer
    thread + ``training.fit`` dispatch-ahead with batched loss fetches)
    pays ~``max(step, d)`` — the loader delay and the H2D transfer hide
    under device compute, and the per-step host sync disappears
    (``loss_fetch_total`` counts one fetch per ``fetch_every`` steps).
    Both loops consume the identical batch stream from identical initial
    state with the SAME compiled executable, so their losses must match
    float-for-float and the executable count stays 1 (zero recompiles
    after warmup). Also measured: async checkpointing's critical-path
    cost (the ``save_async`` enqueue = one device_get) vs the full save
    duration that moved off-thread.

    Knobs: ``CHAINERMN_TPU_PIPE_STEPS`` (default 30),
    ``CHAINERMN_TPU_PIPE_DELAY_MS`` (default: auto, ~1.5x the measured
    bare step), ``CHAINERMN_TPU_PIPE_FETCH_EVERY`` (default 8),
    ``CHAINERMN_TPU_PIPE_DEPTH`` (default 2), plus the
    ``CHAINERMN_TPU_SERVE_*`` model sizes shared with the other modes.
    """
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    import tempfile

    import numpy as np

    import jax

    devs = _start_backend()

    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu import monitor
    from chainermn_tpu.dataflow import DevicePrefetcher
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.monitor import get_registry
    from chainermn_tpu.training import fit, jit_lm_train_step

    e = os.environ.get
    n_steps = int(e("CHAINERMN_TPU_PIPE_STEPS", "30"))
    fetch_every = int(e("CHAINERMN_TPU_PIPE_FETCH_EVERY", "8"))
    depth = int(e("CHAINERMN_TPU_PIPE_DEPTH", "2"))
    delay_env = e("CHAINERMN_TPU_PIPE_DELAY_MS", "")
    seq_len = int(e("CHAINERMN_TPU_PIPE_SEQ_LEN", "16"))
    vocab = int(e("CHAINERMN_TPU_SERVE_VOCAB", "64"))
    d_model = int(e("CHAINERMN_TPU_SERVE_DMODEL", "64"))
    n_layers = int(e("CHAINERMN_TPU_SERVE_LAYERS", "2"))
    n_heads = int(e("CHAINERMN_TPU_SERVE_HEADS", "4"))

    log(f"pipeline bench: devices={len(devs)} kind={devs[0].device_kind!r} "
        f"steps={n_steps} fetch_every={fetch_every} depth={depth}")
    try:
        lm = TransformerLM(vocab_size=vocab, d_model=d_model,
                           n_heads=n_heads, n_layers=n_layers,
                           max_len=seq_len)
        comm = chainermn_tpu.create_communicator("tpu")
        batch = 2 * max(len(devs), 1)
        pool = np.random.RandomState(0).randint(
            1, vocab, (8 * batch, seq_len)).astype(np.int32)
        params0 = comm.bcast_data(
            lm.init(jax.random.PRNGKey(0), jnp.asarray(pool[:1])))
        opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
        # donate=False: the same params/opt arrays seed both loops
        step = jit_lm_train_step(lm, opt, comm, donate=False,
                                 monitored=False)
        data_sharding = comm.named_sharding(*comm.data_spec)

        def fresh():
            return (jax.device_put(params0, comm.named_sharding()),
                    jax.device_put(opt.init(params0),
                                   comm.named_sharding()))

        def batches(delay_s):
            # the injected loader: d seconds of host-side work per batch,
            # deterministic batch sequence (same seed for both loops)
            r = np.random.RandomState(1)
            while True:
                if delay_s:
                    time.sleep(delay_s)
                sel = r.randint(0, len(pool), batch)
                yield pool[sel], np.roll(pool[sel], -1, axis=1)

        def put(b):
            return jax.device_put(
                (jnp.asarray(b[0]), jnp.asarray(b[1])), data_sharding)

        # ---- bare step time (no loader delay, dispatch-ahead) ---------- #
        params, opt_state = fresh()
        gen = batches(0.0)
        for _ in range(3):  # compile + warm
            x, y = put(next(gen))
            params, opt_state, loss, _ = step(params, opt_state, x, y)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            x, y = put(next(gen))
            params, opt_state, loss, _ = step(params, opt_state, x, y)
        float(loss)  # closing fetch
        bare_ms = (time.perf_counter() - t0) / n_steps * 1e3

        delay_ms = float(delay_env) if delay_env else max(1.5 * bare_ms,
                                                          20.0)
        d = delay_ms / 1e3

        # ---- synchronous loop: step + d per iteration ------------------ #
        params, opt_state = fresh()
        gen = batches(d)
        sync_losses = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            x, y = put(next(gen))
            params, opt_state, loss, _ = step(params, opt_state, x, y)
            sync_losses.append(float(loss))  # the per-step host sync
        sync_ms = (time.perf_counter() - t0) / n_steps * 1e3

        # ---- pipelined loop: ~max(step, d) per iteration --------------- #
        reg = get_registry()
        c_fetch = reg.counter("loss_fetch_total", {"loop": "pipeline"})
        fetches_before = c_fetch.value
        params, opt_state = fresh()
        pre = DevicePrefetcher(
            batches(d), depth=depth, sharding=data_sharding,
            transform=lambda b: (jnp.asarray(b[0]), jnp.asarray(b[1])),
            name="pipeline")
        # steady state: let the producer fill the queue before the clock
        # starts (the first-fill delay is a one-time cost, paid while the
        # sync loop's FIRST batch would also still be loading)
        fill_deadline = time.perf_counter() + depth * d + 2.0
        while (pre._q.qsize() < depth
               and time.perf_counter() < fill_deadline):
            pre._ensure_started()
            time.sleep(0.005)
        t0 = time.perf_counter()
        params, opt_state, pipe_losses = fit(
            step, params, opt_state, pre, n_steps,
            fetch_every=fetch_every, name="pipeline")
        pipe_ms = (time.perf_counter() - t0) / n_steps * 1e3
        pre.close()
        fetch_events = c_fetch.value - fetches_before

        # ---- async checkpoint: critical-path cost vs moved-off work ---- #
        with tempfile.TemporaryDirectory() as ckdir:
            ck = chainermn_tpu.create_multi_node_checkpointer(
                "pipe", comm, path=ckdir)
            state = {"params": params, "opt": opt_state}
            t0 = time.perf_counter()
            ck.save(state, 1)
            sync_save_ms = (time.perf_counter() - t0) * 1e3
            t0 = time.perf_counter()
            ck.save_async(state, 2)
            enqueue_ms = (time.perf_counter() - t0) * 1e3
            ck.wait_async()
            async_save_ms = ck.stats["save_async"][-1] * 1e3
            ck.finalize()

        max_ideal = max(bare_ms, delay_ms)
        snap = monitor.snapshot()
        h2d = next((v for k, v in snap["histograms"].items()
                    if k.startswith("prefetch_h2d_seconds")
                    and 'name="pipeline"' in k), {})
        record = {
            "metric": "pipeline_overlap_step_time",
            "value": round(pipe_ms, 3),
            "unit": "ms/step",
            "mode": "pipeline",
            "n_chips": len(devs),
            "device_kind": devs[0].device_kind,
            "n_steps": n_steps,
            "fetch_every": fetch_every,
            "prefetch_depth": depth,
            "bare_step_ms": round(bare_ms, 3),
            "loader_delay_ms": round(delay_ms, 3),
            "sync_step_ms": round(sync_ms, 3),
            "pipelined_step_ms": round(pipe_ms, 3),
            "max_step_delay_ms": round(max_ideal, 3),
            # sync/pipelined: how much wall the overlap bought
            "overlap_ratio": round(sync_ms / pipe_ms, 4),
            # max(step,d)/pipelined: 1.0 = perfect overlap (acceptance:
            # pipelined <= 1.15 x max(step, d) in steady state)
            "pipeline_efficiency": round(max_ideal / pipe_ms, 4),
            "within_1p15_of_ideal": bool(pipe_ms <= 1.15 * max_ideal),
            "losses_bit_identical": bool(sync_losses == pipe_losses),
            "loss_fetch_events": int(fetch_events),
            "h2d_ms_p50": round(h2d.get("p50_s", 0.0) * 1e3, 3),
            "async_save_enqueue_ms": round(enqueue_ms, 3),
            "async_save_ms": round(async_save_ms, 3),
            "sync_save_ms": round(sync_save_ms, 3),
            # the jit cache must hold exactly the warmup executable
            "executables": int(step._cache_size()),
            "monitor": snap,
        }
    except Exception as exc:  # one parseable line, never a bare traceback
        log(f"pipeline bench failed: {type(exc).__name__}: {exc}")
        record = {
            "metric": "pipeline_overlap_step_time",
            "value": None,
            "unit": "ms/step",
            "mode": "pipeline",
            "error": type(exc).__name__,
            "detail": str(exc)[-500:],
        }
        print(json.dumps(record))
        raise SystemExit(1)
    print(json.dumps(record))


def _cli_mode(argv) -> str:
    """``--mode serving`` / ``--mode monitor`` / ``--mode resilience`` /
    ``--mode pipeline`` / ``--mode=...`` (default: the ResNet training
    benchmark)."""
    for i, a in enumerate(argv):
        if a == "--mode" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--mode="):
            return a.split("=", 1)[1]
    return "train"


def main() -> None:
    mode = _cli_mode(sys.argv[1:])
    if mode == "serving":
        serving_main()
    elif mode == "monitor":
        monitor_main()
    elif mode == "resilience":
        resilience_main()
    elif mode == "pipeline":
        pipeline_main()
    elif mode != "train":
        raise SystemExit(
            f"unknown --mode {mode!r} "
            "(train|serving|monitor|resilience|pipeline)")
    else:
        # stdout carries ONLY JSON records; everything else is stderr
        train_main()


if __name__ == "__main__":
    main()
