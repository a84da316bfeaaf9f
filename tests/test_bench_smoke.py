"""bench.py harness smoke test: the headline + strategy/db sweep must
produce one parseable JSON record (tiny model, CPU, 8 devices).

The real benchmark runs on the driver's TPU; this pins the harness logic —
JSON shape, sweep table, bandwidth fields — so a bench-side regression is
caught in CI instead of burning a round's real-chip run."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow  # ~70s train-mode soak; serving smoke is the tier-1 bench anchor — keep tier-1 inside its timeout
def test_bench_smoke_tiny_cpu():
    env = dict(
        os.environ,
        CHAINERMN_TPU_BENCH_PLATFORM="cpu",
        CHAINERMN_TPU_BENCH_TINY="1",
        CHAINERMN_TPU_BENCH_BATCH="16",
        CHAINERMN_TPU_BENCH_STEPS="2",
        CHAINERMN_TPU_BENCH_SWEEP_STEPS="2",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "resnet50_imagenet_train_throughput"
    assert rec["tiny"] is True
    assert rec["value"] and rec["value"] > 0
    assert rec["n_chips"] == 8
    assert "allreduce_gbps" in rec
    # sweep table: 5 strategies x {off, on} = 10 rows, none errored
    sweep = rec["sweep"]
    assert len(sweep) == 10, [s.get("config") for s in sweep]
    errs = [s for s in sweep if "error" in s]
    assert not errs, errs
    configs = {s["config"] for s in sweep}
    assert configs == {
        "tpu_f32", "tpu_f32+db", "tpu_bf16", "tpu_bf16+db",
        "flat", "flat+db", "hierarchical", "hierarchical+db",
        "two_dimensional", "two_dimensional+db",
    }
    # on 8 real (virtual) devices every strategy must move bytes
    for s in sweep:
        if "skipped" not in s:
            assert s["collective_bytes_per_step"] > 0, s
    assert "double_buffering_speedup" in rec


def _run_serving_mode(extra_env):
    env = dict(
        os.environ,
        CHAINERMN_TPU_BENCH_PLATFORM="cpu",
        CHAINERMN_TPU_SERVE_SLOTS="4",
        CHAINERMN_TPU_SERVE_REQUESTS="12",
        CHAINERMN_TPU_SERVE_PREFILL_LEN="128",
        CHAINERMN_TPU_SERVE_MAX_NEW="6",
        CHAINERMN_TPU_SERVE_VOCAB="128",
        # a single thin layer: every section's compile+run shrinks while
        # all the asserted gates (parity, conservation, decode-gap and
        # fairness ratios, shares, migrations) stay comfortably clear —
        # keep tier-1 inside its timeout
        CHAINERMN_TPU_SERVE_DMODEL="32",
        CHAINERMN_TPU_SERVE_LAYERS="1",
        CHAINERMN_TPU_SERVE_HEADS="4",
        CHAINERMN_TPU_SERVE_BUCKETS="16,128",
        CHAINERMN_TPU_SERVE_SHARED_PREFIX="112",
        CHAINERMN_TPU_SERVE_PREFIX_BLOCK="16",
        # keep the autoscale section inside the tier-1 budget: a shorter
        # diurnal window and a 2-replica ceiling still exercise scale-up,
        # peak>min, and drain-back-to-min (asserted below)
        CHAINERMN_TPU_SERVE_AS_WINDOW="3.0",
        CHAINERMN_TPU_SERVE_AS_MAX="2",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        **extra_env,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode", "serving"],
        env=env, capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_bench_serving_mode_smoke():
    """``bench.py --mode serving`` (acceptance criterion): one parseable
    JSON record with tokens/s, TTFT p50/p99, and slot occupancy on the
    emulated CPU mesh — the serving perf baseline's harness, pinned so a
    bench-side regression is caught in CI, not on a chip window. This
    tier-1 run asserts the base record plus the newest perf sections
    (cost accounting, overload fairness, chunked prefill, disagg tiers,
    fleet KV reuse + rebalance) and the continuous-telemetry block.

    The remaining sections (prefix/paged/kernel/speculative and the
    legacy fleet trio — together most of the bench wall on a
    single-core runner) are skipped via
    ``CHAINERMN_TPU_SERVE_SKIP_SECTIONS`` and asserted by the ``@slow``
    full-record twin below, keeping tier-1 inside its timeout."""
    rec = _run_serving_mode({
        # paged_serving expands to the kernel + speculative sections,
        # which reuse its workload/engine parameters
        "CHAINERMN_TPU_SERVE_SKIP_SECTIONS":
            "prefix_serving,paged_serving,hot_swap,"
            "fleet_serving,fleet_autoscale",
    })
    # the skip really skipped (nothing ran silently under the old keys)
    for skipped in ("prefix_serving", "paged_serving",
                    "paged_kernel_serving", "speculative_serving",
                    "hot_swap", "fleet_serving", "fleet_autoscale"):
        assert skipped not in rec, skipped
    assert rec["metric"] == "serving_decode_throughput"
    assert rec["unit"] == "tokens/sec"
    assert rec["value"] and rec["value"] > 0
    assert rec["n_chips"] == 8
    assert rec["n_slots"] == 4 and rec["n_requests"] == 12
    assert rec["ttft_p50_ms"] > 0 and rec["ttft_p99_ms"] >= rec["ttft_p50_ms"]
    assert rec["tpot_p50_ms"] > 0
    assert 0 < rec["slot_occupancy"] <= 1
    assert rec["tokens_generated"] > 0
    # the zero-recompile invariant travels with the perf record
    assert rec["recompiles"] == {"prefill": 1, "decode": 1}
    # ---- the ISSUE-15 continuous telemetry (acceptance criterion) ---- #
    ts = rec["telemetry_serving"]
    # the collector + detector graph ran against the warm engine for the
    # whole ON workload and cost (<2% production target; generous CI
    # bound). On a single-core runner the collector's background thread
    # timeshares with the decode loop itself, so the ON-vs-OFF wall ratio
    # measures the OS scheduler, not the collector (0.03 standalone vs
    # 0.6+ under full-suite load) — the bound only means something with a
    # second core to absorb the thread; parity/recompiles stay asserted.
    if os.cpu_count() and os.cpu_count() > 1:
        assert ts["overhead_frac"] < 0.40, ts
    assert ts["parity_on_vs_off"] is True
    assert ts["recompiles_after_warmup"] == 0
    assert ts["ticks"] > 0 and ts["n_series"] > 0
    assert ts["tokens_per_sec_on"] > 0 and ts["tokens_per_sec_off"] > 0
    # the health verdict travels with the record: scored, named state
    assert ts["worst_state"] in ("healthy", "degraded", "critical")
    assert ts["health"]["state"] == ts["worst_state"]
    assert isinstance(ts["health"]["contributing"], list)
    # ---- the ISSUE-17 cost accounting (acceptance criterion) --------- #
    ca = rec["cost_accounting"]
    # conservation: attributed device-seconds match the measured time of
    # every dispatch within ±10% (by construction it sits at float eps)
    assert ca["conservation_error"] <= 0.10, ca
    assert ca["max_dispatch_error"] <= 0.10, ca
    assert ca["dispatches"] > 0
    # the ledger's dict arithmetic is cheap (<2% production target; CI
    # bound generous — millisecond CPU decodes on a single-core shared
    # runner put suite scheduler noise into this wall-clock ratio)
    assert ca["accounting_overhead_frac"] < 0.40, ca
    assert ca["parity_on_vs_off"] is True
    assert ca["recompiles_after_warmup"] == 0
    # goodput fractions partition the measured time (padding/idle/etc.)
    gp = ca["goodput"]
    assert set(gp) == {"useful", "padding", "idle", "wasted", "replay",
                       "migrate"}
    assert gp["useful"] > 0
    assert abs(sum(gp.values()) - 1.0) < 0.02, gp
    # the bursty tenant out-billed the quiet one, and the threshold
    # detector fired deterministically NAMING it
    assert ca["tenant_device_s"]["bulk"] > ca["tenant_device_s"]["quiet"]
    assert ca["bulk_share"] is not None and ca["bulk_share"] > 0.6, ca
    assert ca["noisy_neighbor_fired"] is True
    assert ca["noisy_neighbor_tenant"] == "bulk"
    # ---- the ISSUE-18 overload fairness (acceptance criterion) ------- #
    of = rec["overload_fairness"]
    # 3x+ overload: bursty interactive + batch tier vs the quiet tenant
    assert of["overload_factor"] >= 3.0, of
    # FIFO collapses the quiet tenant's interactive TTFT behind the
    # backlog; fair admission holds it near the unloaded baseline
    # (locally x8 vs x1.1). The absolute bound carries slack for
    # single-core suite-load timer noise (1.6x observed under a full
    # tier-1 run); the relative check is the discriminating signal —
    # fair admission must beat FIFO by 2x on the same arrival order.
    assert of["fifo_collapse_factor"] >= 3.0, of
    assert of["quiet_slowdown_factor"] <= 2.5, of
    assert of["quiet_slowdown_factor"] * 2 <= of["fifo_collapse_factor"], of
    # the brownout ladder stepped up under pressure and fully unwound
    assert of["brownout"]["max_level"] >= 1, of
    assert of["brownout"]["final_level"] == 0, of
    assert of["brownout"]["steps"] >= 2, of
    # batch is always the preemption victim before any interactive
    assert of["preempted_interactive"] == 0, of
    # admission order never changes a stream, nothing is dropped, the
    # warm engine never retraces, and attribution stays conservative
    assert of["token_parity_on_vs_off"] is True
    assert of["no_request_lost"] is True
    assert of["recompiles_after_warmup"] == 0
    assert of["conservation_error"] < 1e-6, of
    # ---- the ISSUE-19 chunked prefill (acceptance criterion) --------- #
    cp = rec["chunked_prefill_serving"]
    # chunking bounds the decode stall a long admission inflicts on
    # resident streams: victim decode-gap p99 at least 2x better ON
    assert cp["stall_improvement"] >= 2.0, cp
    assert cp["decode_gap_p99_ms_on"] < cp["decode_gap_p99_ms_off"], cp
    assert cp["token_parity_on_vs_off"] is True
    assert cp["recompiles_after_warmup"] == 0
    # ---- the ISSUE-19 disaggregated tiers (acceptance criterion) ----- #
    dg = rec["disagg_serving"]
    assert dg["tiers"] == {"prefill": [0], "decode": [1]}, dg
    # every request prefilled on the P tier and migrated out to decode
    assert dg["migrations"] >= dg["requests"], dg
    assert dg["token_parity_vs_symmetric"] is True
    assert dg["no_request_lost"] is True
    assert dg["recompiles_after_warmup"] == 0
    # ---- the ISSUE-20 fleet KV reuse (acceptance criterion) ---------- #
    ps = rec["fleet_prefix_share"]
    # affinity misses turned into cross-replica prefix hits: the holder
    # exported at least once and peers adopted from the payload cache
    assert ps["shares"] >= 1, ps
    assert ps["payload_cache"]["imports"] >= 1, ps
    assert ps["prefill_tokens_saved"] > 0, ps
    assert ps["prefill_flops_saved"] > 0, ps
    assert ps["token_parity_on_vs_off"] is True
    assert ps["no_request_lost"] is True
    assert ps["recompiles_after_warmup"] == 0
    # mid-stream decode rebalancing: the throttled victim moved and
    # finished token-exactly on the peer
    rb = ps["rebalance_probe"]
    assert rb["moved"] is True, rb
    assert rb["dest_replica"] != rb["src_replica"], rb
    assert rb["token_parity"] is True, rb
    assert rb["no_request_lost"] is True, rb


def _check_full_record_sections(rec):
    # ---- the PR-5 admission fast path (ISSUE 5 acceptance) ---------- #
    p = rec["prefix_serving"]
    assert p["hit_rate"] > 0.5, p
    assert p["parity_vs_solo_generate"] is True
    assert p["recompiles_after_warmup"] == 0
    # every program compiled exactly once at warmup (both buckets + the
    # decode step + the prefix insert)
    assert set(p["compile_counts"].values()) == {1}, p["compile_counts"]
    # TTFT p50 strictly better than the prefix-cache-off run of the same
    # workload (the CPU-mesh margin is ~3x — ample against timer noise)
    assert p["ttft_p50_ms"] < p["ttft_p50_ms_off"], p
    assert p["prefill_batch_occupancy"] > 1.0  # batching really batched
    # ---- the PR-7 paged KV store (acceptance criterion) ------------- #
    pg = rec["paged_serving"]
    # >= 4x the dense engine's concurrency under the SAME device KV
    # memory budget (identical resident-row count), token parity intact,
    # nothing recompiled, and the clean run needed no preemption (block-
    # budget admission reserved worst-case growth up front)
    assert pg["concurrency_gain"] >= 4.0, pg
    assert pg["max_concurrent_dense"] == pg["dense_slots"]
    assert pg["parity_vs_solo_generate"] is True
    assert pg["recompiles_after_warmup"] == 0
    assert pg["preemptions"] == 0
    assert pg["kv_blocks_per_request_mean"] >= 1.0
    # ---- the PR-14 fused paged-decode kernel (acceptance criterion) -- #
    kn = rec["paged_kernel_serving"]
    # on the CPU mesh the kernel runs in Pallas interpret mode, so the
    # record is parity/recompile EVIDENCE; the tokens/s pair is only a
    # performance claim on real hardware (asserted by the driver there)
    assert kn["kernel_used"] is True
    assert kn["kernel_supported"] is True
    assert kn["interpret_mode"] is True        # this suite runs on CPU
    assert kn["parity_vs_xla_and_solo"] is True
    assert kn["recompiles_after_warmup"] == 0
    assert kn["tokens_per_sec"] > 0 and kn["tokens_per_sec_off"] > 0
    brm = kn["bytes_read_model"]
    # the analytical read model must show the kernel streaming strictly
    # fewer bytes than the XLA dense-view gather on this ragged workload
    assert brm["kernel_bytes"] < brm["xla_bytes"]
    assert brm["read_amplification"] > 1.0
    # ---- the PR-12 speculative decode (acceptance criterion) --------- #
    sp = rec["speculative_serving"]
    assert sp["drafter"] == "ngram"
    # the prompt-lookup drafter on the long-generation workload commits
    # multiple tokens per dispatch: faster decode tokens/s vs the SAME
    # engine with speculation off (measured 2x+ on the CPU mesh; the
    # floor is generous — single-core shared runners squeeze the ratio
    # toward 1, so accept_rate/parity below carry the real evidence)
    assert sp["decode_speedup"] >= 1.1, sp
    assert sp["parity_on_vs_off"] is True
    assert sp["accept_rate"] > 0.3, sp
    assert sp["spec_tokens_accepted"] > 0
    assert sp["recompiles_after_warmup"] == 0
    # ONE verify program, compiled at warmup, across every accept length
    assert sp["compile_counts"]["spec_verify"] == 1
    # ---- the ISSUE-10 hot swap (acceptance criterion) ---------------- #
    hs = rec["hot_swap"]
    # three publishes landed mid-stream through the version fence: every
    # request (pre- and post-swap alike) completed, stamped with the
    # version it was admitted under, and the jit cache never grew
    assert hs["swaps"] == 3
    assert hs["requests_done"] == hs["requests"] > 0
    assert hs["versions_correct"] is True
    assert hs["weight_version"] == 3
    assert hs["recompiles_after_warmup"] == 0
    # the swap cost decomposition travels with the record (commit is the
    # device_put outside the fence; fence is drain-only)
    assert hs["swap_total_s_p50"] > 0
    assert hs["swap_fence_s_p50"] > 0 and hs["swap_commit_s_p50"] > 0
    assert "throughput_dip_frac" in hs    # CPU timers are too noisy to sign
    # ---- the ISSUE-8 serving fleet (acceptance criterion) ------------ #
    fl = rec["fleet_serving"]
    # N=2 replicas at HALF the solo engine's slots each: equal total KV
    assert fl["replicas"] == 2
    assert fl["slots_per_replica"] * fl["replicas"] == fl["solo_slots"]
    # the continuity probe: replica 0 was hard-killed mid-run; every
    # accepted request still reached a terminal state and none was lost
    # (re-routed + replayed, or cleanly ERRORED per deadline policy —
    # with no deadlines set, that means every single one finished DONE)
    assert fl["all_terminal"] is True
    assert fl["no_request_lost"] is True
    assert fl["done"] == fl["requests"]
    assert fl["killed_replica_quarantined"] is True
    assert fl["capacity_after_kill"] == 1
    # token-for-token parity vs solo generate() through the router, and
    # zero recompiles on every SURVIVING replica (warm restarts/reroutes
    # never grew an executable cache)
    assert fl["parity_vs_solo_generate"] is True
    assert fl["recompiles_after_warmup_survivors"] == 0
    # shared-system-prompt traffic really routed by affinity
    assert fl["affinity_hit_rate"] > 0.3, fl
    assert fl["ttft_p50_ms"] > 0 and fl["ttft_p99_ms"] >= fl["ttft_p50_ms"]
    # rolling publish after the kill probe (ISSUE 10): the quarantined
    # replica is skipped-and-reported, every surviving replica takes the
    # new version, and no survivor recompiled
    # the fleet ran under fleet_health the whole time (ISSUE 15): pooled
    # per-replica series collected on the background cadence, and the
    # router's health report embedded in the record. The kill probe
    # quarantined replica 0, so its verdict is critical by lifecycle.
    assert fl["ts_series"] > 0 and fl["ts_ticks"] > 0
    assert fl["health"]["n_watched"] == 2
    assert fl["health"]["worst"] == "critical"
    assert fl["health"]["replicas"]["0"]["state"] == "critical"
    assert "replica_state" in fl["health"]["replicas"]["0"]["contributing"]
    pub = fl["publish"]
    assert pub["ok"] is True
    assert "skipped" in pub["outcomes"]["0"]         # the kill-probe victim
    assert pub["outcomes"]["1"]["ok"] is True
    assert pub["outcomes"]["1"]["version"] == 1
    assert pub["weight_versions"]["1"] == 1
    assert pub["recompiles_after_publish_survivors"] == 0
    # ---- the ISSUE-16 closed-loop autoscaler (acceptance criterion) -- #
    fa = rec["fleet_autoscale"]
    # diurnal sinusoidal arrivals: the fleet scaled up under the peak
    # and retired back to the floor in the trough, losing nothing
    assert fa["all_terminal"] is True
    assert fa["no_request_lost"] is True
    assert fa["done"] == fa["requests"] > 0
    assert fa["scale_ups"] >= 1
    assert fa["peak_capacity"] > fa["min_replicas"]
    assert fa["final_capacity"] == fa["min_replicas"]
    assert fa["replica_count_tracks_load"] is True
    assert fa["recompiles_after_warmup"] == 0
    # every decision in the ring names its triggering signals
    assert all(d.get("signals") for d in fa["decisions"]
               if d["action"] in ("scale_up", "scale_down"))


@pytest.mark.slow  # ~130s; the tier-1 serving smoke asserts the other sections — keep tier-1 inside its timeout
def test_bench_serving_mode_full_record_sections():
    """Full-record twin of the serving smoke: ``--mode serving`` with
    NO section skips, asserting the sections the tier-1 smoke skips
    for CI budget (ISSUE-5 prefix cache, ISSUE-7 paged KV, ISSUE-14
    fused kernel, ISSUE-12 speculative decode, ISSUE-10 hot swap,
    ISSUE-8 fleet continuity + rolling publish, ISSUE-16 autoscaler).
    The pair together covers the full serving record."""
    rec = _run_serving_mode({})
    _check_full_record_sections(rec)


def _run_monitor_mode(extra_env):
    env = dict(
        os.environ,
        CHAINERMN_TPU_BENCH_PLATFORM="cpu",
        CHAINERMN_TPU_SERVE_DMODEL="32",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        **extra_env,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--mode", "monitor"],
        env=env, capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _check_monitor_record(rec):
    assert rec["metric"] == "monitor_smoke"
    # well-formed registry snapshot with nonzero step counters (acceptance)
    snap = rec["monitor"]
    assert set(snap) >= {"counters", "gauges", "histograms"}
    steps = {k: v for k, v in snap["counters"].items()
             if k.startswith("steps_total")}
    assert steps and all(v > 0 for v in steps.values()), snap["counters"]
    assert rec["value"] == sum(steps.values())
    st = [v for k, v in snap["histograms"].items()
          if k.startswith("step_time_seconds")]
    assert st and st[0]["count"] > 0 and st[0]["p99_s"] >= st[0]["p50_s"]
    # monitoring-enabled overhead (acceptance: <2% production target, CI
    # bound generous — millisecond CPU steps under a shared runner)
    assert rec["overhead_frac"] < 0.15, rec["overhead_frac"]
    # simulated hang produced a flight-recorder dump with the serving
    # lifecycle visible
    assert rec["watchdog_fired"] is True
    assert rec["flight_events_in_dump"] >= 20
    assert rec["flight_has_slot_admit"] and rec["flight_has_slot_retire"]
    assert rec["flight_has_memory"]
    # serving side ran monitored with zero steady-state recompiles
    assert rec["serving"]["requests_completed"] > 0
    assert rec["recompiles"] == {"prefill": 1, "decode": 1}


@pytest.mark.slow  # ~17s; monitor spine also asserted via telemetry_serving in the serving smoke — keep tier-1 inside its timeout
def test_bench_monitor_mode_smoke():
    """``bench.py --mode monitor`` (acceptance criterion): one parseable
    JSON record proving the telemetry spine live — nonzero monitored step
    counters in the embedded registry snapshot, <2%-target instrumentation
    overhead (generous CI bound), and a flight-recorder dump (slot
    admits/retires + device memory) from a simulated hang."""
    rec = _run_monitor_mode({
        "CHAINERMN_TPU_MONITOR_STEPS": "10",
        "CHAINERMN_TPU_SERVE_REQUESTS": "6",
    })
    _check_monitor_record(rec)


@pytest.mark.slow
def test_bench_monitor_mode_soak():
    """Soak variant: enough steps/requests that reservoir truncation and
    watchdog re-arm paths are exercised; same record invariants."""
    rec = _run_monitor_mode({
        "CHAINERMN_TPU_MONITOR_STEPS": "60",
        "CHAINERMN_TPU_SERVE_REQUESTS": "32",
        "CHAINERMN_TPU_SERVE_SLOTS": "4",
    })
    _check_monitor_record(rec)
    assert rec["serving"]["requests_completed"] == 32


@pytest.mark.slow  # ~10s; chaos paths covered tier-1 by resilience_tests + the serving fleet record — keep tier-1 inside its timeout
def test_bench_resilience_mode_smoke():
    """``bench.py --mode resilience`` (acceptance criterion): one parseable
    JSON record proving the recovery loop live — an injected crash at a
    chosen training step restored bit-exactly from the snapshot (MTTR +
    checkpoint save/load latency measured), and the deterministic serving
    degradation scenario (bounded queue, deadline sheds, engine raise +
    warm restart) with every request terminal."""
    env = dict(
        os.environ,
        CHAINERMN_TPU_BENCH_PLATFORM="cpu",
        CHAINERMN_TPU_SERVE_DMODEL="32",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "resilience"],
        env=env, capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "resilience_mttr" and rec["unit"] == "ms"
    # MTTR: injected crash -> first completed post-resume step
    assert rec["value"] and rec["value"] > 0
    assert rec["checkpoint_save_ms"] > 0 and rec["checkpoint_load_ms"] > 0
    # crash-resume bit-exactness (acceptance): faulted run's final loss
    # equals the uninterrupted reference's, float-for-float
    assert rec["bit_exact_resume"] is True
    assert rec["trainer"]["failures"] == 1
    assert rec["trainer"]["restores"] == 1
    # the serving scenario is deterministic: counts are pinned, not >= 0
    s = rec["serving"]
    assert s["all_terminal"] is True
    assert s["rejected"] == 2 and s["shed"] == 3
    assert s["errored"] == 2 and s["engine_restarts"] == 1
    # every injected fault is observable in the embedded registry snapshot
    fired = {k: v for k, v in rec["monitor"]["counters"].items()
             if k.startswith("faults_injected_total")}
    assert sum(fired.values()) == rec["faults_injected"] >= 2


@pytest.mark.slow  # ~9s; async overlap covered by ops_tests/test_pipeline tier-1 — keep tier-1 inside its timeout
def test_bench_pipeline_mode_smoke():
    """``bench.py --mode pipeline`` (acceptance criterion): one parseable
    JSON record proving the async hot loop overlaps — with an injected
    loader delay ``d`` comparable to the step, the pipelined loop's
    wall/step tracks max(step, d) while the synchronous loop pays
    step + d; losses bit-identical, zero recompiles after warmup, and
    the per-step host sync replaced by one batched fetch per window."""
    env = dict(
        os.environ,
        CHAINERMN_TPU_BENCH_PLATFORM="cpu",
        CHAINERMN_TPU_SERVE_DMODEL="32",
        CHAINERMN_TPU_PIPE_STEPS="20",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "pipeline"],
        env=env, capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "pipeline_overlap_step_time"
    assert rec["unit"] == "ms/step"
    assert rec["value"] and rec["value"] > 0
    assert rec["n_chips"] == 8
    # the overlap proof: the synchronous loop pays step + d, the
    # pipelined loop does not (generous CI bound; the record carries the
    # exact 1.15x verdict for the driver)
    assert rec["sync_step_ms"] > rec["pipelined_step_ms"]
    assert rec["overlap_ratio"] > 1.15, rec
    assert rec["within_1p15_of_ideal"] is True, rec
    # same executable, same batches -> same math, no per-step host syncs
    assert rec["losses_bit_identical"] is True
    assert rec["executables"] == 1                      # zero recompiles
    assert rec["loss_fetch_events"] == 3                # ceil(20/8), not 20
    # h2d measured off the critical path; async save's critical-path cost
    # is the enqueue (device_get), the write itself happened off-thread
    assert rec["h2d_ms_p50"] > 0
    assert rec["async_save_ms"] > 0
    assert rec["async_save_enqueue_ms"] >= 0
    snap = rec["monitor"]
    assert any(k.startswith("prefetch_batches_total")
               for k in snap["counters"])
