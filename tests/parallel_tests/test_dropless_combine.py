"""The dropless expert layer's combine (ISSUE 36): each of a token's top-k
expert rows comes back as one ``[t, d]`` gather, is cast, masked and weighed
in float32 and added into one ``[t, d]`` accumulator. Held against a plain
loop over tokens and their experts, against products that leave NaN in the
rows they did not write, and against the traced program's own structure: no
array with the top-k in its second-minor dimension."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.parallel import moe as moe_mod
from chainermn_tpu.parallel.moe import DroplessMoE

N, D, F = 16, 16, 8
HELD = (4, 8)           # experts 4..11 of 16: assignments fall on both sides


def _layer(k, held):
    return DroplessMoE(n_experts=N, d_model=D, d_ff=F, top_k=k,
                       compute_dtype=jnp.float32, activation="silu",
                       weight_scale=2.5, held=held)


def _inputs(k, held, t, seed=0):
    layer = _layer(k, held)
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((t, D)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(seed), x)
    return layer, params, x


def _reference(params, x, k, held, scale=2.5):
    """Token by token, expert by expert, in float32: the top-k of the
    router's logits, the softmax over those k, and the weighted sum of the
    experts held (all of them with ``held=None``). Also how many of the
    ``t * k`` assignments fell under, inside and over the share."""
    p = {n: np.asarray(v, np.float32) for n, v in params["params"].items()}
    x = np.asarray(x, np.float32)
    first, count = held or (0, N)
    out = np.zeros_like(x)
    sides = [0, 0, 0]
    logits = x @ p["router"]
    for i, row in enumerate(x):
        chosen = np.argsort(-logits[i], kind="stable")[:k + 1]
        # a near-tie at the cut would let summation order pick the experts
        assert logits[i, chosen[k - 1]] - logits[i, chosen[k]] > 1e-5
        chosen = chosen[:k]
        w = np.exp(logits[i, chosen] - logits[i, chosen].max())
        w = scale * w / w.sum()
        for e, w_e in zip(chosen, w):
            sides[int(e >= first) + int(e >= first + count)] += 1
            if not first <= e < first + count:
                continue
            g = row @ p["w_gate"][e - first]
            u = row @ p["w_up"][e - first]
            out[i] += w_e * (((g / (1.0 + np.exp(-g))) * u)
                             @ p["w_down"][e - first])
    return out, sides


@pytest.mark.parametrize("held", [None, HELD], ids=["all_held", "a_share"])
@pytest.mark.parametrize("k", [6, 10])
def test_combine_is_the_loop_over_tokens_and_their_experts(k, held):
    """37 tokens (no multiple of 8), top-6 and top-10 of 16: the layer is
    the plain loop's weighted sum; with a share held, assignments under,
    inside and over it all occur and only those inside count."""
    layer, params, x = _inputs(k, held, 37)
    want, sides = _reference(params, x, k, held)
    if held is not None:
        assert min(sides) > 20 and sum(sides) == 37 * k
    np.testing.assert_allclose(layer.apply(params, x), want, atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("held", [None, HELD], ids=["all_held", "a_share"])
def test_two_passes_of_the_token_map_are_the_loop_too(monkeypatch, held):
    """A prefill longer than ``_TOKEN_PASS`` goes through ``lax.map`` in
    passes that share the combine: two passes of 27 tokens here."""
    monkeypatch.setattr(moe_mod, "_TOKEN_PASS", 27)
    layer, params, x = _inputs(10, held, 54, seed=1)
    want, _ = _reference(params, x, 10, held)
    got = jax.jit(layer.apply)(params, x)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and they are the one-pass layer's rows, pass by pass
    monkeypatch.setattr(moe_mod, "_TOKEN_PASS", 8192)
    halves = [layer.apply(params, x[:27]), layer.apply(params, x[27:])]
    np.testing.assert_allclose(got, jnp.concatenate(halves), atol=1e-6,
                               rtol=1e-6)


@pytest.mark.parametrize("k,held", [(6, HELD), (10, HELD), (10, None)],
                         ids=["top6_share", "top10_share", "top10_all"])
def test_rows_no_product_wrote_never_reach_the_sum(monkeypatch, k, held):
    """The grouped product leaves the rows past the groups' total undefined.
    With NaN there after every product, the output is finite and is, bit
    for bit, what it is with zeros there: the mask is on the row, a zero
    weight would not do (0 * NaN)."""
    layer, params, x = _inputs(k, held, 37, seed=2)
    clean = layer.apply(params, x)
    real = moe_mod.grouped_matmul
    poisoned = []

    def leaves_nan(lhs, rhs, group_sizes, out_dtype):
        out = real(lhs, rhs, group_sizes, out_dtype)
        written = jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes)
        poisoned.append(out.shape[0] - jnp.sum(group_sizes))
        return jnp.where(written, out, jnp.nan)

    monkeypatch.setattr(moe_mod, "grouped_matmul", leaves_nan)
    got = layer.apply(params, x)
    assert len(poisoned) == 3
    assert (int(poisoned[0]) > 100) == (held is not None)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_array_equal(got, clean)
    want, _ = _reference(params, x, k, held)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# -- the traced program's structure ----------------------------------------- #

class _Block(nn.Module):
    """The layer under the name the models give it."""
    fields: tuple

    @nn.compact
    def __call__(self, x):
        return DroplessMoE(name="moe", **dict(self.fields))(x)


def _equations(jaxpr, under=""):
    """``(name stack, equation)`` of every equation of a jaxpr and of the
    jaxprs its equations call; a called jaxpr's stacks continue its
    caller's."""
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (under, str(eqn.source_info.name_stack))
                         if s)
        yield stack, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, stack)


@pytest.mark.parametrize("k,held,t", [(6, None, 40), (10, (0, 8), 37)],
                         ids=["top6_all", "top10_share"])
def test_no_array_holds_the_top_k_in_a_sublane_dimension(k, held, t):
    """In the traced layer (bfloat16, as served) nothing under ``combine``
    has the shape ``(t, k, d)``, and nothing there is a float32 ``(t * k,
    d)``: either is the array the chip pads from k to 8 or 16 sublanes and
    relays in float32. The four scopes the benchmark's readers go by still
    name operations."""
    block = _Block((("n_experts", N), ("d_model", D), ("d_ff", F),
                    ("top_k", k), ("held", held), ("shared_d_ff", F),
                    ("compute_dtype", jnp.bfloat16)))
    x = jnp.zeros((t, D), jnp.bfloat16)
    params = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), x))
    traced = jax.make_jaxpr(block.apply)(params, x)
    scopes = set()
    gathers = 0
    for stack, eqn in _equations(traced.jaxpr):
        for scope in ("route", "experts", "combine", "shared"):
            if f"moe/{scope}" in stack:
                scopes.add(scope)
        if "moe/combine" not in stack:
            continue
        for v in eqn.outvars:
            shape, dtype = v.aval.shape, v.aval.dtype
            assert shape != (t, k, D), (stack, eqn.primitive, shape)
            assert not (shape == (t * k, D) and dtype == jnp.float32), (
                stack, eqn.primitive)
            gathers += (eqn.primitive.name == "gather"
                        and (shape, dtype) == ((t, D), jnp.bfloat16))
    assert scopes == {"route", "experts", "combine", "shared"}
    assert gathers == k         # each expert row read once, in bfloat16
