"""AdamW, found by the job file's ``optimizer.name``: the optax transformation
the program is given, where its state keeps the first gradient, and the same
arithmetic written out for the plain reference (none of optax)."""

from __future__ import annotations


def for_program(spec: dict):
    import optax

    return optax.adamw(spec["learning_rate"], b1=spec["b1"], b2=spec["b2"],
                       eps=spec["eps"], weight_decay=spec["weight_decay"])


def first_moment(opt_state, spec: dict):
    """The tree the state keeps the (averaged) gradient in after its first
    step, and the factor that turns it back into that gradient."""
    import jax

    holds = lambda s: hasattr(s, "mu")
    for s in jax.tree_util.tree_leaves(opt_state, is_leaf=holds):
        if holds(s):
            return s.mu, 1.0 / (1.0 - spec["b1"])
    raise ValueError("the optimizer's state keeps no first moment")


def plain_init(spec: dict, params):
    import jax
    import jax.numpy as jnp

    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros(), "v": zeros()}


def plain_update(spec: dict, params, grads, state, t):
    """Step number ``t`` (1 for the first), a traced scalar."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    lr, b1, b2 = spec["learning_rate"], spec["b1"], spec["b2"]
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    new = tm(lambda p, m, v: p - lr * (
        (m / c1) / (jnp.sqrt(v / c2) + spec["eps"])
        + spec["weight_decay"] * p), params, m, v)
    return new, {"m": m, "v": v}
