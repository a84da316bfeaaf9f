"""The benchmark's own tests, at sizes a CPU holds (see conftest.py).

1. The lower-precision control comes out as not correct (step 3 of "How
   correct is decided"): the reference computed in float8 operands, put in the
   program's place, fails a limit, in a serving cell and in the trainer.
2. A run of the harness with the timed path broken underneath comes out as
   not correct, once for each fault a cell can have: a token altered where it
   is produced; a step that returns its state unchanged; half of the batch
   left out and the mean taken over the rest; an update of the right size
   applied with the wrong sign. (No cell runs across chips yet, so none can
   leave the exchange between them out.)
3. A sound run at the same sizes comes out correct, so the limits are not
   simply too tight.
4. The traffic a seed offers is the same multiset for every seed, in an
   open loop and in a closed one; a closed loop keeps its clients in flight,
   fails a run whose slots it left empty, and fails when its list runs out.
5. BENCHMARK.json and the files it names belong together.
6. The trace reduction agrees with the hand-counted values of the recorded
   trace.

The small configurations' limits (``data/*.json``) were set as the cells' own
were: above what sound runs read on eight seeds, below what the control and
the faults read, at these sizes on the CPU. ``slots_held_share`` of the small
closed loop (answers of 8 to 18 tokens, so the step between two requests in
a slot is a tenth of them) read 0.884 to 0.899 on eight seeds and 0.670 to
0.674 with half of the clients lost; its lower limit there is 0.8.
"""

import json
import os
import time
import types

import pytest

import run as benchrun
from harness import common, correct, families, peaks, trace, traffic, weights

DATA = os.path.join(os.path.dirname(__file__), "data")
KINDS = {
    "serve": ("cgpt13b-serve-decode", "tiny-gpt.json", "tiny-chat.json", 1),
    "serve_closed": ("cgpt13b-serve-decode", "tiny-gpt.json",
                     "tiny-chat-closed.json", 1),
    "lm": ("cgpt13b-l8-train", "tiny-gpt-train.json", "tiny-packed.json", 1),
}


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def drive(kind, seed, tamper=None, seconds=1.5, job=None):
    """The rest of a run once the look for a chip is skipped. ``job`` takes
    the place of the kind's traffic or job file."""
    name, cfg, job_file, chips = KINDS[kind]
    peaks.PEAKS.setdefault("cpu", {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    cell = {"name": name, "config": "tiny", "traffic": "tiny", "chips": chips}
    device = common.require_chips(chips, allow_cpu=True)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    out, checks = benchrun.measure(cell, load(cfg), job or load(job_file),
                                   args, device, tamper=tamper)
    return out, checks


# -- 3. sound runs ---------------------------------------------------------- #

@pytest.mark.parametrize("kind", ["serve", "serve_closed", "lm"])
def test_sound_run_is_correct(kind):
    out, checks = drive(kind, seed=2**31 + 5)       # a seed past 32 signed bits
    assert out["correct"], checks
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["metrics"]["setup_s"]["value"] > 0


# -- 2. faults underneath the harness --------------------------------------- #

def alter_a_token(engine, client):
    """Every token comes out one higher than the model chose. (In one slot
    alone the fault would show only when the sample, drawn from the seed,
    holds a request that slot served.)"""
    sound = engine.decode_round
    vocab = engine.model.vocab_size

    def broken(ctx=None):
        return {slot: [(t + 1) % vocab for t in toks]
                for slot, toks in sound(ctx=ctx).items()}

    engine.decode_round = broken


def _copy(tree):
    import jax

    return jax.tree_util.tree_map(lambda a: a + 0, tree)


def state_unchanged(step):
    def broken(variables, opt_state, x, y):
        out = step(_copy(variables), _copy(opt_state), x, y)
        return (variables, opt_state) + tuple(out[2:])
    return broken


def _rows_from(x, y, n_keep):
    """The batch with its first ``n_keep`` rows repeated over the rest: the
    mean over it is the mean over those rows alone."""
    import jax
    import jax.numpy as jnp

    reps = len(x) // n_keep
    put = lambda a: jax.device_put(
        jnp.concatenate([a[:n_keep]] * reps), a.sharding)
    return put(x), put(y)


def half_batch(step):
    return lambda v, s, x, y: step(v, s, *_rows_from(x, y, len(x) // 2))


def wrong_sign(step):
    """Every update of the right size, up the slope instead of down."""
    import jax

    def broken(variables, opt_state, x, y):
        out = step(_copy(variables), opt_state, x, y)
        turned = jax.tree_util.tree_map(lambda old, new: 2 * old - new,
                                        variables, out[0])
        return (turned,) + tuple(out[1:])
    return broken


def half_the_clients_never_resend(engine, client):
    """The load generator held back inside the window: a little after it has
    opened on full slots, every second client of a closed loop is lost (its
    next conversation is cancelled as it is sent, so no last token ever
    frees that client again)."""
    submit = client.submit
    job = load(KINDS["serve_closed"][2])
    n_lost, lost, began = job["arrivals"]["clients"] // 2, [], []

    def lossy(prompt, max_new, **kw):
        req = submit(prompt, max_new, **kw)
        began.append(time.perf_counter())
        in_window = began[-1] > began[0] + job["ramp"]["seconds"] + 0.3
        if in_window and len(lost) < n_lost:
            lost.append(req)
            client.cancel(req)
        return req

    client.submit = lossy


FAILS = {alter_a_token: "served_logit_gap",
         half_the_clients_never_resend: "slots_held_share"}


@pytest.mark.parametrize("kind,fault", [
    ("serve", alter_a_token), ("serve_closed", alter_a_token),
    ("serve_closed", half_the_clients_never_resend),
    ("lm", state_unchanged), ("lm", half_batch), ("lm", wrong_sign),
])
def test_fault_is_not_correct(kind, fault):
    out, checks = drive(kind, seed=11, tamper=fault)
    assert not out["correct"], (fault.__name__, checks)
    if fault in FAILS:      # by the number that is there to catch it, alone
        assert [n for n, c in checks.items() if not c["ok"]] == [FAILS[fault]]


# -- 1. the lower-precision control ----------------------------------------- #

@pytest.mark.parametrize("seed", [2, 4, 8])
def test_control_fails_serving(seed):
    from harness import serve

    name, cfg_f, tr_f, chips = KINDS["serve"]
    cfg, tr = load(cfg_f), load(tr_f)
    cell = {"name": name, "config": "tiny", "traffic": "tiny", "chips": 1}
    args = types.SimpleNamespace(seed=seed, seconds=1.5, trace=0)
    run_rec, _, checks = serve.run(
        cell, cfg, tr, args, common.require_chips(1, allow_cpu=True),
        time.perf_counter())
    assert all(c["ok"] for c in checks.values()), checks
    params = weights.make_tree(
        families.init_shapes(cfg, families.build_model(cfg)), seed,
        families.param_dtype(cfg))
    control = correct.check_served(cfg, tr, params, run_rec, seed, lowp=True)
    assert not control["served_logit_gap"]["ok"], control


@pytest.mark.parametrize("seed", [1, 2, 7])
def test_control_fails_training(seed):
    import jax

    name, cfg_f, job_f, chips = KINDS["lm"]
    cfg, job = load(cfg_f), load(job_f)
    cell = {"name": name, "config": "tiny", "traffic": "tiny", "chips": chips}
    task = families.task(cfg, job)
    model = families.build_model(cfg, **job["model_kwargs"])
    shapes = families.init_shapes(cfg, model)
    variables = lambda: weights.make_tree(shapes, seed,
                                          families.param_dtype(cfg))
    n_rows = job["batch_per_chip"] * chips
    batches = [jax.device_get(task.batch(k, n_rows)) for k in jax.random.split(
        weights.key_from_seed(seed, stream=4), 3)]
    ref = common.load_reference(correct.reference_name(cell, cfg))
    control = correct.reference_steps(ref, cfg, job, variables, batches,
                                      lowp=True, keep_grad=True)
    sound = correct.reference_steps(
        ref, cfg, job, variables, batches,
        against=(control.pop("first_grad_tree"), 1.0))
    verdict = correct.verdict(correct.training_gaps(control, sound),
                              job["check"]["limits"])
    assert not all(c["ok"] for c in verdict.values()), verdict


# -- 4. traffic -------------------------------------------------------------- #

def _open(tr):
    """The same lengths offered in an open loop, for the generator's open
    path: what a cell below capacity would name in its file."""
    return dict(tr, arrivals={"loop": "open", "rate_per_s": 4.5},
                ramp=dict(tr["ramp"], backlog=0))


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_every_seed_offers_the_same_work(loop):
    tr = common.load_json("traffic", "chat-long-answers.json")
    assert tr["arrivals"]["loop"] == "closed"
    m = len(tr["lengths"]["prompt"])
    start = tr["engine"]["n_slots"] if loop == "closed" else 0
    assert start % m == 0           # the requests under way end on a block
    if loop == "open":
        tr = _open(tr)
    # the list is passes of m*m pairs from its first entry on; the steady
    # start takes the place of the first pass's first blocks
    first = -(-start // (m * m)) * m * m
    seen = []
    for seed in (1, 2**31 + 7):
        arrivals = traffic.schedule(tr, weights.numpy_rng(seed, 2), 1e9 / 1e6,
                                    50257)
        whole_pass = arrivals[first:first + m * m]
        pairs = sorted((len(a.prompt), a.max_new) for a in whole_pass)
        if loop == "open":
            dues = [arrivals[first - 1].due if first else 0.0] + [
                a.due for a in whole_pass]
            seen.append((pairs, sorted(
                round(b - a, 9) for a, b in zip(dues, dues[1:]))))
        else:       # no schedule: a client sends when its answer is whole
            assert all(a.due is None
                       for a in arrivals[tr["arrivals"]["clients"]:])
            seen.append((pairs, None))
        for lo in range(start, first + m * m, m):   # every block is balanced
            block = arrivals[lo:lo + m]
            assert sorted(len(a.prompt) for a in block) == sorted(
                tr["lengths"]["prompt"])
            assert sorted(a.max_new for a in block) == sorted(
                tr["lengths"]["answer"])
    assert seen[0] == seen[1]
    assert seen[0][0] == sorted(
        (p, a) for p in tr["lengths"]["prompt"] for a in tr["lengths"]["answer"])
    order = [[(len(a.prompt), a.max_new) for a in traffic.schedule(
        tr, weights.numpy_rng(s, 2), 30.0, 50257)] for s in (1, 2)]
    assert order[0] != order[1]


def test_closed_loop_starts_from_its_clients_in_a_steady_state():
    tr = common.load_json("traffic", "chat-long-answers.json")
    n, slots = tr["arrivals"]["clients"], tr["engine"]["n_slots"]
    assert n == 1.25 * slots
    arrivals = traffic.schedule(tr, weights.numpy_rng(3, 2), 55.0, 50257)
    assert [a.due for a in arrivals[:n]] == [0.0] * n
    assert len(arrivals) == n + tr["arrivals"]["ceiling_per_s"] * 55
    # in the slots, ages spread from none to all: what is left of the answers
    # is half of the length-weighted mean answer, the prompts hold the rest
    answers, prompts = tr["lengths"]["answer"], tr["lengths"]["prompt"]
    weighted = sum(a * a for a in answers) / sum(answers)
    left = sum(a.max_new for a in arrivals[:slots]) / slots
    assert abs(left - weighted / 2) < 0.05 * weighted
    assert max(len(a.prompt) for a in arrivals) == max(prompts)
    # the clients that wait for a slot have not begun: whole pairs of the grid
    for lo in range(slots, n, len(prompts)):
        block = arrivals[lo:lo + len(prompts)]
        assert sorted(len(a.prompt) for a in block) == sorted(prompts)
        assert sorted(a.max_new for a in block) == sorted(answers)


def test_closed_loop_keeps_its_clients_in_flight():
    """Never more than ``clients`` in flight, and back at ``clients`` within a
    scheduler step of each completion. Read from the harness's own records,
    which the watched ``submit`` finds behind each ``stream_cb``."""
    records, began = [], []

    def watch(engine, client):
        submit = client.submit

        def watched(prompt, max_new, **kw):
            records.append(kw["stream_cb"].__self__)
            began.append(time.perf_counter())
            return submit(prompt, max_new, **kw)

        client.submit = watched

    out, checks = drive("serve_closed", seed=5, tamper=watch)
    assert out["correct"], checks
    n = load(KINDS["serve_closed"][2])["arrivals"]["clients"]
    whole = [r for r in records if len(r.stamps) == r.max_new]
    assert len(whole) > 10 * n
    events = sorted([(t, 1) for t in began]
                    + [(r.stamps[-1], -1) for r in whole])
    in_flight = peak = 0
    for _, step in events:
        in_flight += step
        peak = max(peak, in_flight)
    assert peak == n
    # every answer's last stamp is the due time of one later conversation,
    # in their order, up to the close (the engine runs on a little after it)
    resent = records[n:]
    assert len(resent) > 9 * n
    assert [r.due for r in resent] == sorted(
        r.stamps[-1] for r in whole)[:len(resent)]
    late = sorted(r.sent - r.due for r in resent)
    gaps = sorted(b - a for r in whole for a, b in zip(r.stamps, r.stamps[1:]))
    # woken by the completion, not by its own 50 ms clock; on the CPU the
    # engine thread keeps the interpreter for up to its 5 ms switch interval
    assert late[-1] < 0.05, late[-5:]
    assert late[len(late) * 9 // 10] < gaps[len(gaps) // 2]
    assert out["attempted"] == len(records) and out["failed"] == 0


def test_closed_loop_that_runs_out_of_work_raises():
    job = load(KINDS["serve_closed"][2])
    job["arrivals"]["ceiling_per_s"] = 2    # 5 conversations past the clients'
    with pytest.raises(RuntimeError, match="ran out of work"):
        drive("serve_closed", seed=3, job=job)


# -- 5. the files belong together ------------------------------------------- #

def test_benchmark_json_names_files_that_exist():
    bj = common.benchmark_json()
    for c in bj["configs"]:
        assert os.path.exists(os.path.join(common.ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "configs", c["name"] + ".py")), c["name"]
    cells = {w["name"] for w in bj["workloads"]}
    e2e = {m["name"] for m in bj["end_to_end"]}
    for w in bj["workloads"]:
        tr = common.load_json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "harness", tr["driver"] + ".py")), tr["driver"]
        names = [m["name"] for m in common.metric_entries(w["name"], "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert common.metric_entries(w["name"], "per_layer")
    for m in bj["end_to_end"] + bj["per_layer"]:
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "metrics", m["name"] + ".py")), m["name"]
        assert set(m.get("workloads", [])) <= cells
        if "moves" in m:
            assert m["moves"] in e2e
            for w in m["workloads"]:
                assert m["moves"] in [e["name"] for e in common.metric_entries(
                    w, "end_to_end")]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("TPU v99")
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["bytes_per_s"] == 819e9


# -- 6. trace reduction ------------------------------------------------------ #

def test_trace_reduction_selfcheck():
    assert trace.selfcheck() == 0
