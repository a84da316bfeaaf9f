"""The training driver: ``create_communicator`` -> ``bcast_data`` ->
``create_multi_node_optimizer`` -> the program's jitted step, compiled once.
It names no cell: sizes come from the configuration's file, the batch, the
optimizer and the wire type from the job file, and what differs between the
program's trainers from ``families/<family>.py`` and
``optimizers/<name>.py``.

Set-up builds one object, the compiled step with its state, drives it from the
seed through its first three steps (whose losses, first gradient and parameter
change the comparison reads) and hands that same object to the window. The
window's feed is a small pool of batches made on the device from the seed,
every row different.
"""

from __future__ import annotations

import collections
import gc
import time

from harness import common, correct, families, trace, weights
from harness.common import log

CHECKED_STEPS = 3


def run(cell: dict, config: dict, job: dict, args, device: dict,
        t_process: float, tamper=None):
    """One run of a training cell. ``tamper(step) -> step`` is for the fault
    tests: it breaks the timed path underneath the harness."""
    import jax
    import numpy as np

    import chainermn_tpu

    compiles = common.CompileCounter()
    devices = jax.devices()[:device["count"]]
    comm = chainermn_tpu.create_communicator(
        "tpu", devices=devices,
        allreduce_grad_dtype=job.get("allreduce_grad_dtype"))
    task = families.task(config, job)
    model = families.build_model(config, **job.get("model_kwargs", {}))
    shapes = families.init_shapes(config, model)
    optimizer = correct.optimizer_file(job["optimizer"])
    replicated = comm.named_sharding()
    on_mesh = comm.named_sharding(*comm.data_spec)
    dtype = families.param_dtype(config)
    variables = comm.bcast_data(weights.make_tree(shapes, args.seed, dtype))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optimizer.for_program(job["optimizer"]), comm)
    opt_state = jax.jit(opt.init, out_shardings=replicated)(
        task.optimizer_target(variables))
    n_rows = int(job["batch_per_chip"]) * comm.size
    n_pool = max(int(job["pool"]), CHECKED_STEPS)
    pool = jax.jit(
        lambda key: [task.batch(k, n_rows)
                     for k in jax.random.split(key, n_pool)],
        out_shardings=on_mesh)(weights.key_from_seed(args.seed, stream=4))
    log(f"state and a pool of {n_pool} batches of {n_rows} rows on "
        f"{comm.size} chip(s)")
    step = task.build_step(model, opt, comm).lower(
        variables, opt_state, *pool[0]).compile()
    log(f"step compiled; {compiles.count} compilations so far")
    if tamper is not None:
        step = tamper(step)

    # the first steps of the object the window drives, read for the check
    moment_norms = jax.jit(lambda st: correct.leaf_norms(
        optimizer.first_moment(st, job["optimizer"])[0]))
    losses, first_grad, first_grad_tree = [], None, None
    for i in range(CHECKED_STEPS):
        out = step(variables, opt_state, *pool[i])
        variables, opt_state = out[0], out[1]
        losses.append(out[2])
        if i == 0:
            moment, factor = optimizer.first_moment(opt_state,
                                                    job["optimizer"])
            first_grad = moment_norms(opt_state) * factor
            # the whole first gradient goes to the host: the next step
            # overwrites it, and the chip has no room for a second copy
            first_grad_tree = (jax.device_get(moment), factor)
    # the parameters' change: the start is made again from the seed inside
    # the same program, leaf by leaf, so that no second copy is held
    rebuild = weights.tree_builder(shapes, dtype)
    change = jax.jit(lambda now, key: correct.leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x - y, now, task.optimizer_target(rebuild(key)))))(
        task.optimizer_target(variables), weights.weights_key(args.seed))
    program = {
        "loss": [float(x) for x in losses],
        "first_grad": np.asarray(first_grad, np.float64),
        "first_grad_tree": first_grad_tree,
        "change": np.asarray(change, np.float64),
    }
    jax.block_until_ready((variables, opt_state))
    log(f"first {CHECKED_STEPS} steps: losses {program['loss']}")

    tracing = None
    if args.trace:
        tracing = trace.Session()
        tracing.start()
    compiled_before = compiles.count
    gc.collect()
    gc.freeze()
    gc.disable()
    pending = collections.deque()
    steps, i = 0, CHECKED_STEPS
    traced = None
    t0 = time.perf_counter()
    if tracing is not None:
        tracing.mark()
        stop_at = t0 + min(args.seconds, float(job["trace_seconds"]))
    try:
        while True:
            out = step(variables, opt_state, *pool[i % n_pool])
            variables, opt_state = out[0], out[1]
            pending.append(out[2])
            i += 1
            steps += 1
            if len(pending) > 2:                # two steps dispatched ahead
                pending.popleft().block_until_ready()
            now = time.perf_counter()
            if tracing is not None and traced is None and now >= stop_at:
                jax.block_until_ready((variables, opt_state))
                traced = (steps, time.perf_counter() - t0)
                tracing.stop()
            if now - t0 >= args.seconds:
                break
        jax.block_until_ready((variables, opt_state, pending[-1]))
        t1 = time.perf_counter()
    finally:
        gc.enable()
    compiled_in_window = compiles.count - compiled_before
    last_loss = float(pending[-1])
    log(f"window closed: {steps} steps in {t1 - t0:.3f} s, last loss "
        f"{last_loss:.4f}, {compiled_in_window} compilations inside it")

    peak = common.memory_peak_bytes(devices)
    log(f"memory: {devices[0].memory_stats()}")
    run_rec = {
        "cell": cell, "config": config, "traffic": job, "device": device,
        "setup_s": t0 - t_process, "t0": t0, "t1": t1,
        "seconds": t1 - t0, "steps": steps,
        "flops_per_step": task.flops_per_step(n_rows),
        "attention_work_per_step": task.attention_work_per_step(n_rows),
        "traced_steps": None, "trace": None,
        "compiled_in_window": compiled_in_window,
    }
    if traced is not None:
        # the stall of stopping the profiler is not the program's: the traced
        # run's host-clock readings stop where the trace does
        run_rec["steps"], run_rec["seconds"] = traced
        run_rec["traced_steps"] = traced[0]

    # free the program's state before the reference takes the chip
    batches = [jax.device_get(pool[k]) for k in range(CHECKED_STEPS)]
    del variables, opt_state, step, pool, out, pending
    gc.collect()
    checks = correct.check_trained(cell, config, job, task, shapes,
                                   program, batches, args.seed)
    checks["compiled_in_window"] = {
        "value": compiled_in_window, "limit": 0,
        "ok": compiled_in_window == 0}
    if tracing is not None:
        run_rec["trace"] = tracing.reduce()
        log(f"trace reduced: {run_rec['trace'].window_s:.3f} s traced, "
            f"{run_rec['trace'].busy_s:.3f} s busy")
    result = {"attempted": steps, "failed": 0 if np.isfinite(last_loss) else 1,
              "memory_peak_bytes": peak}
    return run_rec, result, checks
