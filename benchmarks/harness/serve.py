"""The serving driver: one engine behind ``ServingClient``, load from
``traffic.py`` in an open or a closed loop as the traffic file says
(``arrivals.loop``), every token stamped in ``stream_cb`` by the harness's own
clock. It names no cell: sizes come from the configuration's file, the engine
and the load from the traffic file.

A run: weights from the seed, engine, warm-up, generator started, ramp (set-up
the traffic needs: slots filled, ages mixed), window of ``--seconds``, close.
After that memory is read, the
engine is freed and the plain reference is run over a sample of what was
served (``correct.py``). Where the file gives ``check.limits`` a
``slots_held_share``, a window whose slots fell empty is not correct.
"""

from __future__ import annotations

import collections
import gc
import threading
import time

from harness import common, correct, families, trace, traffic as traffic_mod
from harness import weights
from harness.common import log


class RequestRecord:
    """What the harness knows of one request: when it was due and sent, and
    the harness's own stamp of every token. ``on_last(record)`` is called on
    the engine thread once the answer's final token is stamped."""

    __slots__ = ("due", "sent", "prompt", "max_new", "stamps", "req",
                 "on_last")

    def __init__(self, due, prompt, max_new: int, on_last=None) -> None:
        self.due = due            # absolute, perf_counter clock; None until
        self.sent = None          # a closed loop's client comes free
        self.prompt = prompt
        self.max_new = max_new
        self.stamps = []
        self.req = None           # the program's Request, for its tokens
        self.on_last = on_last

    def stamp(self, _tok) -> None:
        self.stamps.append(time.perf_counter())
        if self.on_last is not None and len(self.stamps) == self.max_new:
            self.on_last(self)


class Generator(threading.Thread):
    """Sends the run's arrivals in their order, from one thread of its own.
    Open loop: each when it is due. Closed loop: an arrival that has no due
    time waits for a client to come free, which is when the final token of
    that client's last answer is stamped (or its request failed); that stamp
    becomes the record's ``due``. What is in flight is read from the
    harness's own stamps, never from the program's counters. The engine
    thread pays one ``Semaphore.release()`` a request; this thread sleeps on
    it and wakes on its own no more often than every 50 ms."""

    def __init__(self, client, arrivals, t_start: float, key) -> None:
        super().__init__(name="bench-generator", daemon=True)
        self.client, self.key = client, key
        self.closed = any(a.due is None for a in arrivals)
        self.free = threading.Semaphore(0)
        self.freed = collections.deque()      # records, one per release
        self.in_flight = set()                # closed loop only
        on_last = self._client_free if self.closed else None
        self.records = [RequestRecord(
            None if a.due is None else t_start + a.due, a.prompt, a.max_new,
            on_last) for a in arrivals]
        self.halt = threading.Event()
        self.error = None

    def _client_free(self, rec: RequestRecord) -> None:
        self.freed.append(rec)
        self.free.release()

    def _next_free(self):
        """The stamp at which a client came free, or ``None`` once halted. A
        request that failed never stamps its last token: it frees its client
        when this thread, idle, sees the error."""
        while not self.halt.is_set():
            if self.free.acquire(timeout=0.05):
                rec = self.freed.popleft()
                self.in_flight.discard(rec)
                return rec.stamps[-1]
            for rec in self.in_flight:
                if rec.req.error is not None:
                    self.in_flight.remove(rec)
                    return time.perf_counter()
        return None

    def run(self) -> None:
        try:
            for rec in self.records:
                if rec.due is None:
                    rec.due = self._next_free()
                    if rec.due is None:
                        return
                while True:
                    wait = rec.due - time.perf_counter()
                    if wait <= 0 or self.halt.is_set():
                        break
                    self.halt.wait(min(wait, 0.05))
                if self.halt.is_set():
                    return
                rec.req = self.client.submit(
                    rec.prompt, rec.max_new, rng=self.key,
                    stream_cb=rec.stamp)
                rec.sent = time.perf_counter()
                if self.closed:
                    self.in_flight.add(rec)
            if self.closed and self._next_free() is not None:
                raise RuntimeError(
                    f"the closed loop ran out of work: all "
                    f"{len(self.records)} conversations of the list were "
                    f"sent and a client came free again, so the engine "
                    f"finishes more than the traffic file's "
                    f"arrivals.ceiling_per_s requests a second")
        except BaseException as e:  # noqa: BLE001 - read by the main thread
            self.error = e


def slots_held_share(sent: list, t0: float, t1: float, n_slots: int) -> float:
    """The share of slots holding a request between its first and last token
    (or the close, where that cut it short), over the window: under 1 by the
    step between an answer's end and the next request's first token, and
    far under it where the load left slots empty."""
    held = sum(max(0.0, min(r.stamps[-1] if len(r.stamps) >= r.max_new else t1,
                            t1) - max(r.stamps[0], t0))
               for r in sent if r.stamps)
    return held / ((t1 - t0) * n_slots)


def log_steadiness(sent: list, t0: float, t1: float, n_slots: int) -> None:
    """For whoever looks for the cause of a run that reads far off: tokens in
    each fifth of the window, the longest silence (no token of any request),
    and the share of slots holding a request (a stall shows in the second,
    slots left empty in the third)."""
    stamps = sorted(s for r in sent for s in r.stamps if t0 <= s < t1)
    if not stamps:
        return
    fifth = (t1 - t0) / 5
    per = [sum(1 for s in stamps if t0 + k * fifth <= s < t0 + (k + 1) * fifth)
           for k in range(5)]
    silence, since = max((b - a, a) for a, b in zip([t0] + stamps,
                                                    stamps + [t1]))
    log(f"steadiness: tokens by fifth of the window {per}, longest silence "
        f"{silence * 1e3:.0f} ms (from {since - t0:.1f} s), slots holding a "
        f"request "
        f"{100 * slots_held_share(sent, t0, t1, n_slots):.1f}%")


def build_engine(config: dict, tr: dict, seed: int):
    import jax

    from chainermn_tpu.serving import ServingEngine

    model = families.build_model(config)
    params = weights.make_tree(families.init_shapes(config, model), seed,
                               families.param_dtype(config))
    jax.block_until_ready(params)
    engine = ServingEngine(model, params, **{
        k: (tuple(v) if isinstance(v, list) else v)
        for k, v in tr["engine"].items()})
    return model, params, engine


def run(cell: dict, config: dict, tr: dict, args, device: dict,
        t_process: float, tamper=None):
    """One run of a serving cell. ``tamper(engine, client)`` is for the
    fault tests: it breaks the timed path underneath the harness."""
    import jax

    from chainermn_tpu.serving import ServingClient

    compiles = common.CompileCounter()
    model, params, engine = build_engine(config, tr, args.seed)
    log(f"weights and engine built: {tr['engine']}")
    if tr["engine"].get("paged_kernel") and not engine.paged_kernel:
        raise RuntimeError("the engine fell back from the paged kernel")
    engine.warmup()
    log(f"engine warm: {len(engine.compile_counts_detailed())} programs, "
        f"{compiles.count} compilations so far")
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(key)

    ramp_s = float(tr["ramp"]["seconds"])
    horizon = ramp_s + args.seconds
    arrivals = traffic_mod.schedule(
        tr, weights.numpy_rng(args.seed, stream=2), horizon,
        config["vocab_size"])
    client = ServingClient(engine)
    if tamper is not None:
        tamper(engine, client)
    tracing = None
    try:
        gen = Generator(client, arrivals, time.perf_counter() + 0.05, key)
        t_gen = gen.records[0].due
        gen.start()
        # the ramp is set-up that this traffic needs
        time.sleep(max(0.0, t_gen + ramp_s - time.perf_counter()))
        if tr["ramp"].get("require_full_slots"):
            # a slot is empty for a moment between an answer's last token
            # and the next admission: look for up to a second
            for _ in range(1000):
                if engine.active_slots == engine.n_slots:
                    break
                time.sleep(0.001)
            else:
                if gen.error is not None:     # the cause, where it has one
                    raise gen.error
                raise RuntimeError(
                    f"the window would open on {engine.active_slots} of "
                    f"{engine.n_slots} slots in use")
        if args.trace:
            tracing = trace.Session()
            tracing.start()
        compiled_before = compiles.count
        gc.collect()
        gc.freeze()
        gc.disable()
        t0 = time.perf_counter()
        if tracing is not None:
            tracing.mark()
            stop_at = t0 + min(args.seconds, float(tr["trace_seconds"]))
            time.sleep(max(0.0, stop_at - time.perf_counter()))
            tracing.stop()
        time.sleep(max(0.0, t0 + args.seconds - time.perf_counter()))
        t1 = time.perf_counter()
        gc.enable()
        gen.halt.set()
        compiled_in_window = compiles.count - compiled_before
        gen.join(timeout=10)
        if gen.error is not None:
            raise gen.error
        sent = [r for r in gen.records if r.sent is not None]
        program_says = dict(client.scheduler.metrics.report(),
                            **engine.kv_stats())
    finally:
        gc.enable()
        client.close()
    log("the program's own counters: " + ", ".join(
        f"{k} {program_says[k]}" for k in (
            "requests_submitted", "requests_completed", "requests_errored",
            "tokens_generated", "kv_preemptions", "prefix_hit_rate",
            "prefill_batch_size_mean", "kv_blocks", "blocks_in_use",
            "blocks_reserved") if k in program_says))
    log(f"window closed: {len(sent)} sent, {compiled_in_window} compilations "
        f"inside the window")
    log_steadiness(sent, t0, t1, engine.n_slots)

    peak = common.memory_peak_bytes(jax.devices()[:cell["chips"]])
    log(f"memory: {[d.memory_stats() for d in jax.devices()[:1]]}")
    run_rec = {
        "cell": cell, "config": config, "traffic": tr, "device": device,
        "setup_s": t0 - t_process, "t0": t0, "t1": t1,
        "seconds": t1 - t0, "requests": sent,
        "n_slots": engine.n_slots,
        # what the block store can hold (block 0 is the engine's scratch)
        "kv_pool_tokens": (engine.kv_blocks - 1) * engine.kv_block_size,
        "compiled_in_window": compiled_in_window,
        "trace": None,
    }
    # requests the close cancelled are not failures; errored ones are
    attempted = len(sent)
    failed = sum(1 for r in sent if r.req.state.name == "ERRORED")

    # free the program's state before the reference takes the chip
    del client, gen
    engine = None
    gc.collect()
    checks = correct.check_served(config, tr, params, run_rec, args.seed)
    checks["compiled_in_window"] = {
        "value": compiled_in_window, "limit": 0,
        "ok": compiled_in_window == 0}
    floor = tr["check"]["limits"].get("slots_held_share")
    if floor is not None:
        # a lower limit: a window whose slots fell empty measured the load
        # that was offered, not the replica run full
        held = slots_held_share(sent, t0, t1, run_rec["n_slots"])
        checks["slots_held_share"] = {"value": held, "limit": floor,
                                      "must_be": ">=", "ok": held >= floor}
    if tracing is not None:
        run_rec["trace"] = tracing.reduce()
        log(f"trace reduced: {run_rec['trace'].window_s:.3f} s traced, "
            f"{run_rec['trace'].busy_s:.3f} s busy")
    result = {"attempted": attempted, "failed": failed,
              "memory_peak_bytes": peak}
    return run_rec, result, checks
