"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference beside the configuration's file.

Serving: a sample, drawn from the seed, of the requests the window finished,
the longest among them. The reference runs once over each prompt with its
served tokens, and the number compared is the widest gap by which a served
(greedy) token's logit lies below the reference's best at that position.

Training: the first three steps of the very object the window then drives.
Each step's loss, the first gradient's norm leaf by leaf (read from the
optimizer's state after one step) and the parameters' change after three,
each against the reference's, by the worst leaf; and the norm of the whole
first gradient's difference from the reference's, which is what tells a lower
precision from the stated one (rounding averages out inside a norm).

Limits live in the traffic or job file (``check.limits``), one per number,
set from chip readings that PERF.md records.
"""

from __future__ import annotations

import functools

import numpy as np

from harness import common, weights
from harness.common import log


def verdict(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit", "ok"}}``; a number must not pass its limit.
    A number with no limit in the file is not compared (PERF.md says why)."""
    out = {}
    for name, value in values.items():
        limit = limits.get(name)
        if not np.isfinite(value):
            value, ok = 1e30, False      # JSON has no infinity
        else:
            ok = True if limit is None else bool(value <= limit)
        out[name] = {"value": float(value), "limit": limit, "ok": ok}
    return out


# --------------------------------------------------------------------------- #
# serving                                                                      #
# --------------------------------------------------------------------------- #

@functools.lru_cache(maxsize=4)
def _gap_fn(ref_name: str, cfg_json: str, length: int, lowp: bool):
    """``(params, tokens[1, length]) -> gap[length - 1]``: at each position
    the reference's best logit minus its logit of the token in question. The
    token is the one that follows in ``tokens`` (what was served), or, for the
    control, the one that the lower precision puts first."""
    import jax
    import jax.numpy as jnp

    import json

    ref = common.load_reference(ref_name)
    cfg = json.loads(cfg_json)

    def fn(params, tokens):
        lg = ref.logits(params, tokens, cfg)[0, :-1]          # [L-1, V]
        if lowp:
            token = jnp.argmax(ref.logits(params, tokens, cfg,
                                          lowp=True)[0, :-1], axis=-1)
        else:
            token = tokens[0, 1:]
        picked = jnp.take_along_axis(lg, token[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - picked

    return jax.jit(fn)


def served_whole(r) -> bool:
    """The program says the request is done (not cancelled at the close, not
    errored) and the harness stamped a token."""
    return (r.req is not None and r.req.state.name == "DONE"
            and r.req.error is None and bool(r.stamps))


def sample_finished(run_rec: dict, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the seed,
    among those whose last token came inside the window."""
    done = [r for r in run_rec["requests"]
            if served_whole(r) and run_rec["t0"] <= r.stamps[-1]]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.req.tokens), r.due))
    longest, rest = done[-1], done[:-1]
    rng = weights.numpy_rng(seed, stream=3)
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def reference_name(cell: dict, config: dict) -> str:
    """The reference beside the cell's configuration, unless the file names
    another's (a test's small configuration borrows a published one's)."""
    return config.get("reference", cell["config"])


def served_gaps(ref_name: str, config: dict, params, sample: list,
                length: int, lowp: bool = False) -> list:
    """Per sampled request, the gaps at its served positions."""
    import json

    import jax.numpy as jnp

    fn = _gap_fn(ref_name, json.dumps(config, sort_keys=True), length, lowp)
    out = []
    for r in sample:
        served = np.asarray(r.req.tokens, np.int32)
        seq = np.zeros((1, length), np.int32)
        p, n = len(r.prompt), len(served)
        seq[0, :p] = r.prompt
        seq[0, p:p + n] = served
        gap = np.asarray(fn(params, jnp.asarray(seq)))
        out.append(gap[p - 1:p - 1 + n])       # position p-1 predicts s_1
    return out


def check_served(config: dict, tr: dict, params, run_rec: dict,
                 seed: int, lowp: bool = False) -> dict:
    """``lowp`` puts the lower-precision control in the program's place: the
    gap read is then that of the token the control puts first."""
    cell = run_rec["cell"]
    sample = sample_finished(run_rec, seed, int(tr["check"]["requests"]))
    finished = [r for r in run_rec["requests"] if served_whole(r)]
    # every finished answer used its whole budget (no EOS is set), and the
    # harness stamped exactly the tokens the request holds
    short = {id(r) for r in finished if len(r.req.tokens) != r.max_new}
    restamped = {id(r) for r in finished
                 if len(r.stamps) != len(r.req.tokens)}
    log(f"{len(finished)} requests finished: {len(short)} with another "
        f"length than asked for, {len(restamped)} stamped another number of "
        f"times than they hold tokens")
    values = {"served_length_mismatch": len(short | restamped)}
    if sample:
        gaps = served_gaps(reference_name(cell, config), config, params,
                           sample, int(tr["engine"]["cache_len"]), lowp=lowp)
        values["served_logit_gap"] = max(float(g.max()) for g in gaps)
        n_tok = sum(len(g) for g in gaps)
        log(f"reference ran over {len(sample)} requests, {n_tok} served "
            f"tokens; widest gap {values['served_logit_gap']:.5f}, "
            f"positions off the reference's first choice "
            f"{sum(int((g > 0).sum()) for g in gaps)}")
    else:
        values["served_logit_gap"] = float("inf")   # nothing finished
    limits = dict(tr["check"]["limits"], served_length_mismatch=0)
    return verdict(values, limits)


# --------------------------------------------------------------------------- #
# training                                                                     #
# --------------------------------------------------------------------------- #

def _parts(x):
    """A leaf, or its few parts where its first axis stacks them (the fused
    q, k, v bias is ``[3, heads, head size]``): one part can be free of
    gradient (a key's bias under softmax) while the others are not, and a
    norm over all three would hide which."""
    if x.ndim >= 2 and x.shape[0] <= 4:
        return [x[i] for i in range(x.shape[0])]
    return [x]


def leaf_norms(tree):
    """The L2 norm of every leaf (of every part, see ``_parts``), in the
    order ``norm_paths`` names them."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)
                      for p in _parts(x)])


def norm_paths(tree) -> list:
    import jax

    out = []
    for path, x in zip(weights.leaf_paths(tree),
                       jax.tree_util.tree_leaves(tree)):
        stacked = len(x.shape) >= 2 and x.shape[0] <= 4
        out += [f"{path}[{i}]" for i in range(x.shape[0])] if stacked else [
            path]
    return out


def optimizer_file(spec: dict):
    """``harness/optimizers/<name>.py`` for the job file's ``optimizer``."""
    return common.load_module("harness", "optimizers", spec["name"] + ".py")


def _diff_squares(mean_grads, other, factor: float) -> tuple:
    """Sum of squares of ``mean_grads - factor * other`` and of ``mean_grads``
    over the whole tree, leaf by leaf: ``other`` is a tree on the host, and
    one leaf of it at a time is put beside the gradients on the device."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(lambda g, o: (
        jnp.sum(jnp.square(g - factor * o.astype(jnp.float32))),
        jnp.sum(jnp.square(g))))
    pairs = [one(g, jnp.asarray(o)) for g, o in zip(
        jax.tree_util.tree_leaves(mean_grads),
        jax.tree_util.tree_leaves(other))]
    return (float(sum(float(d) for d, _ in pairs)),
            float(sum(float(r) for _, r in pairs)))


def reference_steps(ref, config: dict, job: dict, make_variables,
                    batches: list, lowp: bool = False, fault: str = "",
                    against=None, keep_grad: bool = False) -> dict:
    """The reference's first steps on the same weights and batches: losses,
    the first gradient's norm and the parameters' change, leaf by leaf.
    ``against = (tree on the host, factor)`` is the first gradient as the
    program's optimizer got it: ``grad_diff_rel`` is then the norm of its
    difference from the reference's over the reference's norm, whole tree.
    ``keep_grad`` hands the reference's own first gradient back on the host
    (the control and the planted faults stand in the program's place).
    ``make_variables()`` makes the weights from the seed; it is called again
    at the end for the start that the change is measured from, so that one
    copy is held, and every update is made in place (donated buffers): at
    16 bytes a parameter the chip holds no second set.

    Gradients are the mean over blocks of ``check.row_block`` rows (one chip's
    share where batch statistics are per chip; one row where only memory
    matters), which is what the mean over the whole batch is. ``fault`` plants
    one in the reference put in the program's place: ``half_batch`` leaves the
    second half of the rows out and takes the mean over the rest;
    ``wrong_sign`` applies every update with its sign turned (the step goes
    up the slope by as much as it should go down)."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    variables = make_variables()
    rest = {k: v for k, v in variables.items() if k != "params"}
    params = variables["params"]
    del variables

    def loss_and_sum(p, acc, x, y):
        l, g = jax.value_and_grad(
            lambda q: ref.loss({"params": q, **rest}, x, y, config,
                               lowp=lowp))(p)
        return l, tm(jnp.add, acc, g)

    grad_into = jax.jit(loss_and_sum, donate_argnums=1)
    spec = job["optimizer"]
    opt = optimizer_file(spec)

    def mean_and_update(p, acc, state, k, t):
        grads = tm(lambda v: v / k, acc)
        new_p, new_state = opt.plain_update(spec, p, grads, state, t)
        if fault == "wrong_sign":
            new_p = tm(lambda old, new: 2 * old - new, p, new_p)
        return new_p, new_state, leaf_norms(grads)

    update = jax.jit(mean_and_update, donate_argnums=(0, 1, 2))
    state = opt.plain_init(spec, params)
    block = int(job["check"]["row_block"])
    out = {"loss": [], "first_grad": None, "change": None}
    for i, (x, y) in enumerate(batches):
        n = len(x)
        if fault == "half_batch":
            n = n // 2
        acc, losses = tm(jnp.zeros_like, params), []
        for lo in range(0, n, block):
            l, acc = grad_into(params, acc, jnp.asarray(x[lo:lo + block]),
                               jnp.asarray(y[lo:lo + block]))
            losses.append(l)
        k = len(losses)
        out["loss"].append(float(sum(float(l) for l in losses) / k))
        if i == 0 and (against is not None or keep_grad):
            mean = jax.jit(lambda a: tm(lambda v: v / k, a))(acc)
            if against is not None:
                d, r = _diff_squares(mean, *against)
                out["grad_diff_rel"] = float(np.sqrt(d / r))
            if keep_grad:
                out["first_grad_tree"] = jax.device_get(mean)
            del mean
        params, state, norms = update(params, acc, state, jnp.float32(k),
                                      jnp.float32(i + 1))
        del acc
        if i == 0:
            out["first_grad"] = np.asarray(norms, np.float64)
    del state
    out["change"] = np.asarray(jax.jit(lambda a, b: leaf_norms(
        tm(jnp.subtract, a, b)))(params, make_variables()["params"]),
        np.float64)
    return out


def training_gaps(program: dict, ref: dict, paths=None) -> dict:
    """The numbers compared. Norms go by the worst leaf: the gap between the
    program's norm and the reference's, against the reference's norm of that
    leaf or of the median leaf, whichever is larger. Leaves whose reference
    gradient is nought to rounding (under a thousandth of the median leaf's)
    move under Adam by round-off alone and are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], ref["loss"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    g_p, g_r = program["first_grad"], ref["first_grad"]
    med = float(np.median(g_r))
    rel_g = np.abs(g_p - g_r) / np.maximum(g_r, med)
    out["grad_norm_gap"] = float(np.max(rel_g))
    keep = g_r >= 1e-3 * med
    c_p, c_r = program["change"], ref["change"]
    med_c = float(np.median(c_r[keep]))
    rel_c = np.where(keep, np.abs(c_p - c_r) / np.maximum(c_r, med_c), 0.0)
    out["update_norm_gap"] = float(np.max(rel_c))
    if paths is not None:
        i, j = int(np.argmax(rel_g)), int(np.argmax(rel_c))
        log(f"worst leaves: gradient {paths[i]} ({g_p[i]:.4g} against "
            f"{g_r[i]:.4g}, median leaf {med:.4g}); change {paths[j]} "
            f"({c_p[j]:.4g} against {c_r[j]:.4g}); {int((~keep).sum())} "
            f"leaves without gradient left out of the change")
    if "grad_diff_rel" in ref:
        out["grad_diff_rel"] = ref["grad_diff_rel"]
    return out


def check_trained(cell: dict, config: dict, job: dict, task, shapes,
                  program: dict, batches: list, seed: int) -> dict:
    from harness import families

    ref = common.load_reference(reference_name(cell, config))
    make = lambda: weights.make_tree(shapes, seed,
                                     families.param_dtype(config))
    numbers = reference_steps(ref, config, job, make, batches,
                              against=program.pop("first_grad_tree"))
    values = training_gaps(program, numbers,
                           norm_paths(task.optimizer_target(shapes)))
    log(f"reference ran {len(batches)} steps: losses {numbers['loss']}; "
        f"gaps {values}")
    return verdict(values, job["check"]["limits"])
