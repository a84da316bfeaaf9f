"""``LagunaLM`` against the plain reference that sits beside the benchmark's
configuration (``benchmarks/configs/laguna-s-2.1-l5-ep2.py``: ``jax.numpy``,
float32, nothing of the program): a layer of each kind, the whole model's
logits, prefill and then decode through both block stores past the window
with 6 and 9 query heads a KV head, the expert layer as one chip's share
(the shares add up, a token none of whose experts is held, the counters),
YaRN's table worked by hand, the per-head gate, and that
``SmallThinkerBlock``'s program is the one it was. Small sizes, seeded
weights, CPU.
"""

import hashlib
import importlib.util
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import LagunaLM
from chainermn_tpu.models.laguna import LagunaBlock, yarn_inv_freq
from chainermn_tpu.models.smallthinker import SmallThinkerBlock, rope
from chainermn_tpu.parallel.moe import DroplessMoE, GatedMLP
from chainermn_tpu.serving import FCFSScheduler, ServingEngine

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _reference():
    path = ROOT / "benchmarks" / "configs" / "laguna-s-2.1-l5-ep2.py"
    spec = importlib.util.spec_from_file_location("laguna_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": 1}

# the published key names and layer pattern (full + dense, three sliding,
# full) at a size the CPU holds: groups of 6 and 9 query heads a KV head, a
# window of 32, 4 of 8 routed experts held (the second half), top-3
CFG = {
    "vocab_size": 97, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 5, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "moe_routed_scaling_factor": 2.5, "sliding_window": 32,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12],
    "rope_parameters": {"full_attention": YARN, "sliding_attention": PLAIN},
    "held_experts": {"first": 4, "count": 4, "published": 8},
}


def positions(rp):
    head = (rp["rope_type"], float(rp["rope_theta"]),
            float(rp["partial_rotary_factor"]))
    if rp["rope_type"] != "yarn":
        return head
    return head + (float(rp["factor"]),
                   rp["original_max_position_embeddings"],
                   float(rp["beta_fast"]), float(rp["beta_slow"]),
                   float(rp["attention_factor"]))


def build(cfg, **kw):
    n, held = cfg["num_hidden_layers"], cfg["held_experts"]
    return LagunaLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=n,
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"][:n]),
        window=cfg["sliding_window"],
        window_layers=tuple(t == "sliding_attention"
                            for t in cfg["layer_types"][:n]),
        dense_layers=tuple(t == "dense" for t in cfg["mlp_layer_types"][:n]),
        dense_d_ff=cfg["intermediate_size"],
        d_ff=cfg["moe_intermediate_size"], n_experts=held["published"],
        top_k=cfg["num_experts_per_tok"],
        held_experts=(held["first"], held["count"]),
        routed_scale=cfg["moe_routed_scaling_factor"],
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        rope_full=positions(cfg["rope_parameters"]["full_attention"]),
        rope_window=positions(cfg["rope_parameters"]["sliding_attention"]),
        max_len=128, compute_dtype=jnp.float32, **kw)


def seeded(model, seed=0):
    """Weights from a seed, the norm scales moved off 1 so that a path which
    dropped them would show."""
    params = {"params": model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        if str(getattr(path[-1], "key", "")) == "scale":
            leaf = 1.0 + 0.1 * jax.random.normal(key, leaf.shape)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def lm():
    model = build(CFG)
    return model, seeded(model)


def tokens_of(seed, b, t):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (b, t)), jnp.int32)


# -- the model against the reference ---------------------------------------- #

@pytest.mark.parametrize("kind,heads,mlp", [
    ("full_attention", 12, "dense"), ("full_attention", 12, "sparse"),
    ("sliding_attention", 18, "sparse")])
def test_one_layer_of_each_kind_matches_reference(kind, heads, mlp):
    """Float32 on both sides: what is left is the order of summation (flash
    blocks, the sorted grouped products), some 1e-6 of logits of size 1."""
    cfg = dict(CFG, num_hidden_layers=1, layer_types=[kind],
               mlp_layer_types=[mlp], num_attention_heads_per_layer=[heads])
    model = build(cfg)
    params = seeded(model, seed=3)
    toks = tokens_of(1, 2, 48)                   # past the window of 32
    np.testing.assert_allclose(model.apply(params, toks),
                               REF.logits(params, toks, cfg),
                               atol=2e-5, rtol=2e-5)


def test_whole_model_logits_match_reference(lm):
    model, params = lm
    toks = tokens_of(2, 2, 64)
    # five layers of the same rounding: 5e-5
    np.testing.assert_allclose(model.apply(params, toks),
                               REF.logits(params, toks, CFG),
                               atol=5e-5, rtol=5e-5)


def test_reference_control_is_another_model(lm):
    _, params = lm
    toks = tokens_of(2, 1, 64)
    exact = REF.logits(params, toks, CFG)
    low = REF.logits(params, toks, CFG, lowp=True)
    assert float(jnp.max(jnp.abs(exact - low))) > 1e-2


# -- prefill, then decode, through both stores ------------------------------ #

def served_gap(params, prompt, served):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position (the benchmark's number): a comparison
    of logits, since with random weights the first place changes hands on
    rounding."""
    seq = jnp.asarray(np.concatenate([prompt, served])[None], jnp.int32)
    lg = REF.logits(params, seq, CFG)[0]
    p = len(prompt)
    rows = lg[p - 1:p - 1 + len(served)]
    picked = rows[jnp.arange(len(served)), jnp.asarray(served)]
    return float(jnp.max(jnp.max(rows, axis=-1) - picked))


def engine_for(model, params, **kw):
    args = dict(n_slots=3, prefill_buckets=(8, 32, 64), prefill_batch=2,
                paged=True, kv_block_size=8, cache_len=104)
    args.update(kw)
    return ServingEngine(model, params, **args)


WORK = [(5, 20), (40, 60), (20, 30), (33, 9), (64, 36)]


def serve(engine, seed=5):
    rng = np.random.default_rng(seed)
    sched = FCFSScheduler(engine)
    reqs = [sched.submit(rng.integers(0, CFG["vocab_size"], p), a)
            for p, a in WORK]
    sched.run_until_idle()
    return sched, reqs


@pytest.mark.parametrize("kv_quant,kernel,limit", [
    ("none", True, 1e-4), ("int8", False, 0.15), ("int8", True, 0.15)])
def test_prefill_then_decode_past_the_window_matches_reference(
        lm, kv_quant, kernel, limit):
    """Prompts shorter and longer than the window of 32, contexts to 100,
    through layers of 12 and of 18 query heads on 2 KV heads: what the
    engine serves, greedy, is what the reference's full forward pass puts
    first, by logits. A float32 store leaves summation order (1e-4); an
    int8 store rounds K and V rows to 1 part in 254 of their largest
    entry, which moves logits of size 1 by some hundredths (0.15, the
    limit the SmallThinker model's test has for the same store)."""
    model, params = lm
    engine = engine_for(model, params, kv_quant=kv_quant,
                        paged_kernel=kernel)
    engine.warmup()
    compiled = sum(engine.compile_counts_detailed().values())
    _, reqs = serve(engine)
    assert sum(engine.compile_counts_detailed().values()) == compiled
    assert engine.recompiles == {}
    for r, (p, a) in zip(reqs, WORK):
        assert r.finished and len(r.tokens) == a
        gap = served_gap(params, np.asarray(r.prompt), np.asarray(r.tokens))
        assert gap <= limit, (p, a, gap)
    assert all(k["blocks_live"] == 0 and k["blocks_reserved"] == 0
               for k in engine.kv_stats()["kinds"].values())


def test_held_share_is_counted_on_the_device_and_reported(lm):
    """``moe_local_share``: every processed token (a prompt's, and each
    decoded token but an answer's last, which no program reads) makes
    ``top_k`` assignments in each of the four sparse layers, none for
    padding or an empty slot; the share held is what routing the same
    sequences in one pass gives."""
    model, params = lm
    engine = engine_for(model, params, kv_quant="none", paged_kernel=False)
    sched, reqs = serve(engine)
    report = sched.metrics.report()
    tokens = sum(p + a - 1 for p, a in WORK)
    assert sched.metrics._c_moe_total.value == tokens * 4 * 3
    local = 0
    for r in reqs:
        seq = np.concatenate([r.prompt, r.tokens[:-1]])[None]
        _, sown = model.apply(params, jnp.asarray(seq, jnp.int32),
                              mutable=["serving_stats"])
        local += sum(int(jnp.sum(leaf)) for path, leaf in
                     jax.tree_util.tree_flatten_with_path(sown)[0]
                     if "moe_local" in jax.tree_util.keystr(path))
    # the same routing up to a near-tie broken differently by the cache's
    # summation order: a handful of assignments in eleven thousand
    assert abs(report["moe_local_share"] - local / (tokens * 12)) < 5e-3
    assert 0.2 < report["moe_local_share"] < 0.8
    assert engine.pop_moe_counts() is None            # drained


# -- the expert layer as a share -------------------------------------------- #

def _moe(held, shared=8, n=8, k=3):
    return DroplessMoE(n_experts=n, d_model=16, d_ff=8, top_k=k,
                       compute_dtype=jnp.float32, activation="silu",
                       weight_scale=2.5, held=held, shared_d_ff=shared)


def _share_of(params, first, count):
    p = dict(params["params"])
    for name in ("w_gate", "w_up", "w_down"):
        p[name] = p[name][first:first + count]
    return {"params": p}


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips hold experts 0-3 and 4-7 of 8 and the shared expert each:
    their routed parts and the shared expert counted once are the uncut
    layer's output, and each part is the reference's with that share.
    Float32; sums of three or six products in another order: 1e-5."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 16)),
                    jnp.float32)
    whole = _moe(None)
    params = {"params": whole.init(jax.random.PRNGKey(0), x)["params"]}
    shared = GatedMLP(d_model=16, d_ff=8, compute_dtype=jnp.float32).apply(
        {"params": params["params"]["shared"]}, x)
    parts = []
    for first in (0, 4):
        part = _moe((first, 4)).apply(_share_of(params, first, 4), x)
        parts.append(part - shared)
        cfg = {"held_experts": {"first": first}, "num_experts_per_tok": 3,
               "moe_routed_scaling_factor": 2.5}
        want = REF._experts(_share_of(params, first, 4)["params"], x, cfg,
                            False)
        np.testing.assert_allclose(part - shared, want, atol=1e-5,
                                   rtol=1e-5)
        assert float(jnp.max(jnp.abs(want))) > 1e-2     # both chips work
    np.testing.assert_allclose(parts[0] + parts[1] + shared,
                               whole.apply(params, x), atol=1e-5, rtol=1e-5)


def test_token_with_no_expert_held_gets_the_shared_expert_alone():
    """A router pushed to experts 4-7 for every token, and experts 0-3 held:
    no product runs, the rows they would have written are whatever memory
    held (NaN here), and the output is the shared expert's, bit for bit."""
    x = jnp.asarray(np.random.default_rng(4).standard_normal((24, 16)),
                    jnp.float32)
    layer = _moe((0, 4), k=2)
    params = {"params": layer.init(jax.random.PRNGKey(1), x)["params"]}
    p = params["params"]
    p["router"] = p["router"].at[:, 4:].set(0.0).at[0, 4:].set(100.0)
    x = x.at[:, 0].set(3.0)                 # logits 300 for experts 4-7
    p["w_down"] = jnp.full_like(p["w_down"], jnp.nan)
    out, sown = layer.apply(params, x, mutable=["serving_stats"])
    shared = GatedMLP(d_model=16, d_ff=8, compute_dtype=jnp.float32).apply(
        {"params": p["shared"]}, x)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_array_equal(out, shared)
    assert int(jnp.sum(sown["serving_stats"]["moe_local"][0])) == 0
    assert int(jnp.sum(sown["serving_stats"]["moe_total"][0])) == 24 * 2


@pytest.mark.parametrize("held", [(5, 4), (0, 0), (-1, 2)])
def test_a_share_outside_the_experts_is_refused(held):
    with pytest.raises(ValueError, match="held"):
        _moe(held).init(jax.random.PRNGKey(0), jnp.zeros((4, 16)))


# -- positions and the gate -------------------------------------------------- #

def test_yarn_table_against_values_worked_by_hand():
    """Rotary width 64, base 500000, factor 128, original length 8192,
    beta 32 and 1: corr(32) = 64 ln(8192 / 64 pi) / (2 ln 500000) = 9.04 and
    corr(1) = 64 ln(8192 / 2 pi) / (2 ln 500000) = 17.49, so low = 9, high
    = 18: pairs 0-9 keep base^(-2j/64), pairs 18-31 are divided by 128, and
    pair 12 lies a third of the way (ramp 3/9)."""
    ln_base = math.log(500000.0)
    assert math.floor(64 * math.log(8192 / (64 * math.pi))
                      / (2 * ln_base)) == 9
    assert math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * ln_base)) == 18
    plain = lambda j: math.exp(-2 * j / 64 * ln_base)
    want = {0: 1.0, 9: plain(9), 12: plain(12) * (2 / 3 + 1 / (3 * 128)),
            18: plain(18) / 128, 31: plain(31) / 128}
    for table in (yarn_inv_freq(500000.0, 64, 128.0, 8192),
                  np.asarray(REF.yarn_inv_freq(YARN, 64))):
        assert table.shape == (32,)
        for j, value in want.items():
            assert table[j] == pytest.approx(value, rel=1e-5), j
    # and the attention factor is YaRN's 0.1 ln(factor) + 1
    assert YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(128) + 1, rel=1e-12)


def test_partial_rotary_turns_the_first_half_and_scales_by_the_factor():
    """Half of a head of 16 turns: at position 0 the rotary entries come
    back times the factor, the others as they were; at position 3 entry j
    turns with entry j + 4 by 3 inv_freq[j]."""
    inv_freq = jnp.asarray([1.0, 0.5, 0.25, 0.125])
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 2, 3, 16)),
                    jnp.float32)
    pos = jnp.asarray([[0, 3]])
    out, _ = rope(x, x, pos, inv_freq, 1.5)
    np.testing.assert_allclose(out[0, 0, :, :8], 1.5 * x[0, 0, :, :8],
                               rtol=1e-6)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    ang = 3 * np.asarray(inv_freq)
    a, b = np.asarray(x[0, 1, :, :4]), np.asarray(x[0, 1, :, 4:8])
    np.testing.assert_allclose(
        out[0, 1, :, :8], 1.5 * np.concatenate(
            [a * np.cos(ang) - b * np.sin(ang),
             b * np.cos(ang) + a * np.sin(ang)], -1), rtol=1e-5, atol=1e-6)


def test_a_gate_at_zero_silences_its_head():
    """Head 5's gate reads -1e4 times an input coordinate that is positive
    for every token, so it is sigmoid(very negative) = 0 exactly: the block
    gives what it gives with head 5's rows of the output projection zeroed
    and the gate left alone."""
    block = LagunaBlock(
        d_model=32, n_heads=12, n_kv_heads=2, head_dim=16, window=None,
        positions=positions(YARN), dense_d_ff=48, d_ff=16, n_experts=8,
        top_k=3, held_experts=None, routed_scale=2.5, shared_d_ff=16,
        rms_norm_eps=1e-6, compute_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(7).standard_normal((2, 24, 32)),
                    jnp.float32).at[..., 0].set(4.0)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    params = block.init(jax.random.PRNGKey(0), x, pos)
    p = params["params"]
    gated = dict(p, g_proj={"kernel": p["g_proj"]["kernel"]
                            .at[:, 5].set(0.0).at[0, 5].set(-1e4)})
    cut = dict(p, o_proj={"kernel": p["o_proj"]["kernel"]
                          .at[5 * 16:6 * 16].set(0.0)})
    silenced, _ = block.apply({"params": gated}, x, pos)
    without, _ = block.apply({"params": cut}, x, pos)
    normal, _ = block.apply(params, x, pos)
    np.testing.assert_allclose(silenced, without, atol=1e-6, rtol=1e-6)
    assert float(jnp.max(jnp.abs(normal - without))) > 1e-3


# -- the other model's program ---------------------------------------------- #

def test_smallthinker_block_traces_to_the_program_it_was():
    """The expert layer's new fields at their defaults, and the attention
    branch and the rotary turn shared with ``LagunaBlock``, leave
    ``SmallThinkerBlock`` its program: the jaxpr of a small block, to the
    letter, is the one commit fade432 (before ``held``, the weight scale,
    the activation and the shared expert existed) traces, but for the
    expert layer's combine, which PR 36 rewrote for every family (the first
    2,152 of that jaxpr's 2,185 lines stand; from there on the three rows
    a token come back one ``[t, d]`` gather each). The digest was taken
    with this very code; a new JAX prints jaxprs its own way, so the
    comparison holds for the version it was taken under."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digest was taken under jax 0.9.0")
    block = SmallThinkerBlock(
        d_model=32, n_heads=14, n_kv_heads=2, head_dim=8, d_ff=16,
        n_experts=8, top_k=3, window=32, use_rope=True, rope_theta=10000.0,
        rms_norm_eps=1e-6, compute_dtype=jnp.float32)
    x = jnp.zeros((2, 48, 32))
    pos = jnp.broadcast_to(jnp.arange(48), (2, 48))
    params = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), x,
                                               pos))
    with jax.default_matmul_precision("highest"):  # as tests/conftest.py
        text = str(jax.make_jaxpr(lambda p, x, pos: block.apply(p, x, pos))(
            params, x, pos))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert "logistic" not in text            # no SiLU, no gate
    assert len(text) == 86890
    assert hashlib.sha256(text.encode()).hexdigest().startswith(
        "5464e98836016459")
