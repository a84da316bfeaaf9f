"""A model with window and full attention layers, grouped KV heads and a
dropless mixture of experts, against the plain reference that sits beside
the benchmark's configuration
(``benchmarks/configs/smallthinker-21b-a3b-l8.py``: ``jax.numpy``, float32,
nothing of the program): a layer of each kind, the whole model's logits,
prefill and then decode through both block stores past the window, the paged
kernel and the flash kernel with groups and a window, routing that drops
nothing, admission against two pools, and
what an engine refuses for such a model. Small sizes, seeded weights, CPU.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import SmallThinkerLM
from chainermn_tpu.ops import flash_attention
from chainermn_tpu.parallel.moe import DroplessMoE
from chainermn_tpu.parallel.sequence import (
    full_attention,
    paged_scale_shape,
    paged_update_cache_and_attend,
    paged_write_kv,
)
from chainermn_tpu.serving import FCFSScheduler, ServingEngine
from chainermn_tpu.serving.speculative import SpeculativeConfig

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _reference():
    path = ROOT / "benchmarks" / "configs" / "smallthinker-21b-a3b-l8.py"
    spec = importlib.util.spec_from_file_location("st_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

# the published key names, at a size the CPU holds: groups of 7 query heads
# a KV head, a window of 32, the published period of one full layer in four
CFG = {
    "vocab_size": 97, "hidden_size": 32, "num_attention_heads": 14,
    "num_key_value_heads": 2, "head_dim": 8, "num_hidden_layers": 4,
    "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "sliding_window_size": 32,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
}


def build(cfg, **kw):
    n = cfg["num_hidden_layers"]
    return SmallThinkerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=n, d_ff=cfg["moe_ffn_hidden_size"],
        n_experts=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        window=cfg["sliding_window_size"],
        window_layers=tuple(cfg["sliding_window_layout"][:n]),
        rope_layers=tuple(cfg["rope_layout"][:n]),
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
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

@pytest.mark.parametrize("kind", ["full", "window"])
def test_one_layer_of_each_kind_matches_reference(kind):
    flag = int(kind == "window")
    cfg = dict(CFG, num_hidden_layers=1, sliding_window_layout=[flag],
               rope_layout=[flag])
    model = build(cfg)
    params = seeded(model, seed=3)
    toks = tokens_of(1, 2, 48)                   # past the window of 32
    got = model.apply(params, toks)
    want = REF.logits(params, toks, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_whole_model_logits_match_reference(lm):
    model, params = lm
    toks = tokens_of(2, 2, 64)
    np.testing.assert_allclose(model.apply(params, toks),
                               REF.logits(params, toks, CFG),
                               atol=5e-5, rtol=5e-5)


def test_reference_control_is_another_model(lm):
    """The float8 control moves the reference's logits by far more than the
    program differs from it."""
    _, params = lm
    toks = tokens_of(2, 1, 64)
    exact = REF.logits(params, toks, CFG)
    low = REF.logits(params, toks, CFG, lowp=True)
    assert float(jnp.max(jnp.abs(exact - low))) > 1e-2


# -- prefill, then decode, through both stores ------------------------------ #

def served_gap(params, prompt, served):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position (the benchmark's number)."""
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


@pytest.mark.parametrize("kv_quant,kernel,limit", [
    ("none", False, 1e-4), ("none", True, 1e-4),
    ("int8", False, 0.15), ("int8", True, 0.15)])
def test_prefill_then_decode_past_the_window_matches_reference(
        lm, kv_quant, kernel, limit):
    """Prompts shorter and longer than the window of 32, contexts to 100:
    what the engine serves, greedy, is what the reference's full forward
    puts first (to the store's precision), and a window layer's table never
    holds more than its ring."""
    model, params = lm
    engine = engine_for(model, params, kv_quant=kv_quant,
                        paged_kernel=kernel)
    engine.warmup()
    compiled = sum(engine.compile_counts_detailed().values())
    rng = np.random.default_rng(5)
    work = [(5, 20), (40, 60), (20, 30), (33, 9), (64, 36)]
    sched = FCFSScheduler(engine)
    reqs = [sched.submit(rng.integers(0, CFG["vocab_size"], p), a)
            for p, a in work]
    ring_cap = -(-(CFG["sliding_window_size"] + 8) // 8)
    most = 0
    while sched.has_work:
        sched.step()
        kinds = engine.kv_stats()["kinds"]
        assert set(kinds) == {"full", "window"}
        for kv in engine._kv:
            if kv.window is not None:
                held = max(len(ids) for ids in kv.slot_blocks)
                assert held <= ring_cap
                most = max(most, held)
        assert (kinds["window"]["blocks_live"]
                <= engine.n_slots * ring_cap)
    assert most == ring_cap            # a long request did fill its ring
    assert sum(engine.compile_counts_detailed().values()) == compiled
    assert engine.recompiles == {}
    for r, (p, a) in zip(reqs, work):
        assert r.finished and len(r.tokens) == a
        gap = served_gap(params, np.asarray(r.prompt), np.asarray(r.tokens))
        assert gap <= limit, (p, a, gap)
    after = engine.kv_stats()["kinds"]
    assert all(k["blocks_live"] == 0 and k["blocks_reserved"] == 0
               and k["blocks_in_use"] == 0 for k in after.values())


def test_decode_span_counts_the_tokens_held(lm):
    model, params = lm
    engine = engine_for(model, params)
    sched = FCFSScheduler(engine)
    sched.submit(np.arange(40) % 90, 4)
    sched.step()
    assert engine._decode_stats() == {"active": 1, "live_tokens": 41}


# -- admission against two pools -------------------------------------------- #

@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_waits_for_either_pool_and_releases_both(lm, short):
    """Two requests that each fit, and together do not fit the pool that is
    short: the second stays queued until the first has released, whichever
    pool binds, and both pools come back whole."""
    model, params = lm
    # a request of 40 + 24 tokens reserves 8 full blocks and a ring of 5
    size = {"full": dict(kv_blocks=13, kv_window_blocks=31),
            "window": dict(kv_blocks=31, kv_window_blocks=8)}[short]
    engine = engine_for(model, params, **size)
    need = engine.blocks_needed(40, 24)
    assert list(need) == [8, 5]
    sched = FCFSScheduler(engine)
    a = sched.submit(np.arange(40), 24)
    b = sched.submit(np.arange(40) + 1, 24)
    sched.step()
    assert a.slot >= 0 and b.slot < 0               # b was deferred
    assert (engine.kv_blocks_admittable() < need).any()
    sched.run_until_idle()
    assert a.finished and b.finished and len(b.tokens) == 24
    for kv in engine._kv:
        assert kv.pool.used_blocks == 0 and int(kv.reserved.sum()) == 0
        assert (kv.tables == 0).all()


def test_request_larger_than_a_pool_is_refused(lm):
    model, params = lm
    engine = engine_for(model, params, kv_window_blocks=4)
    with pytest.raises(ValueError, match="window pool"):
        engine.validate_request(40, 24)


# -- what an engine refuses for window layers ------------------------------- #

@pytest.mark.parametrize("option,match", [
    (dict(paged=False, prefix_cache_blocks=8), "prefix reuse"),
    (dict(speculative=SpeculativeConfig(k=2)), "speculative"),
    (dict(decode_window=2), "decode_window"),
    (dict(paged=False), "paged=False"),
])
def test_engine_refuses_at_construction(lm, option, match):
    model, params = lm
    with pytest.raises(ValueError, match="window layers.*" + match):
        engine_for(model, params, **option)


def test_engine_refuses_tensor_axis_and_migration(lm):
    model, params = lm
    with pytest.raises(ValueError, match="window layers.*tensor_axis"):
        engine_for(build(CFG, tensor_axis="mp"), params, comm=object())
    engine = engine_for(model, params)
    assert not engine.migration_supported and not engine.prefix_enabled
    with pytest.raises(ValueError, match="window layers.*migration"):
        engine.export_slot_kv(0)
    with pytest.raises(ValueError, match="window layers.*migration"):
        engine.import_slot_kv({})
    # and a pool for window layers is not an option of a model without them
    from chainermn_tpu.models import TransformerLM

    plain = TransformerLM(vocab_size=17, d_model=16, n_heads=4, n_layers=1,
                          max_len=32, compute_dtype=jnp.float32)
    with pytest.raises(ValueError, match="kv_window_blocks"):
        ServingEngine(plain, None, n_slots=1, prefill_len=8, paged=True,
                      kv_window_blocks=9)


def test_prompts_repeat_without_prefix_reuse(lm):
    """The trie indexes one pool: an engine with window layers inserts and
    matches nothing, and a repeated prompt is served like a new one."""
    model, params = lm
    engine = engine_for(model, params)
    sched = FCFSScheduler(engine)
    first = sched.submit(np.arange(24), 5)
    sched.run_until_idle()
    again = sched.submit(np.arange(24), 5)
    sched.run_until_idle()
    assert list(first.tokens) == list(again.tokens)
    assert engine.prefix_stats() == {}


# -- the kernels ------------------------------------------------------------ #

def _quantized(x):
    sc = np.maximum(np.abs(x).max(-1) / 127.0, 1e-8)[..., None]
    return np.clip(np.round(x / sc), -127, 127) * sc


@pytest.mark.parametrize("store", ["int8", "bf16"])
@pytest.mark.parametrize("windowed", [False, True])
def test_paged_kernel_with_groups_of_seven_and_a_first_position(store,
                                                               windowed):
    """7 query heads a KV head, rows that start at their first visible
    position and go round their table row: the kernel against the XLA read path, and
    both against attention over the visible positions written out."""
    b, h, hk, d, bs, w = 3, 14, 2, 16, 4, 12
    rng = np.random.default_rng(0)
    span = w + bs if windowed else 64
    width = -(-span // bs)
    totals = [40, 9, 23]
    ring = np.array([-(-min(t, span) // bs) for t in totals], np.int32)
    table = np.zeros((b, width), np.int32)
    table[np.arange(width)[None, :] < ring[:, None]] = 1 + np.arange(
        int(ring.sum()))
    n_blocks = int(ring.sum()) + 1
    dt = jnp.bfloat16 if store == "bf16" else jnp.float32
    cache = {"k": jnp.zeros((n_blocks, bs, hk, d),
                            jnp.int8 if store == "int8" else dt)}
    cache["v"] = cache["k"]
    if store == "int8":
        cache["k_scale"] = jnp.zeros(
            paged_scale_shape(n_blocks, bs, hk), jnp.float32)
        cache["v_scale"] = cache["k_scale"]
    ks, vs = (rng.standard_normal((b, 40, hk, d)).astype(np.float32)
              for _ in range(2))
    qs = rng.standard_normal((b, 40, h, d)).astype(np.float32)
    extra = {"window": w} if windowed else {}
    # the prompts in one call, padded to 16, then a token at a time
    prompt = np.array([13, 5, 16])
    cache = paged_write_kv(
        dict(cache, table=jnp.asarray(table), valid=jnp.asarray(prompt),
             **extra),
        jnp.asarray(ks[:, :16], dt), jnp.asarray(vs[:, :16], dt),
        jnp.zeros((b,), jnp.int32))
    pos = prompt.copy()
    tol = 2e-2 if store == "bf16" else 2e-6
    for _ in range(24):
        live = pos < np.array(totals)
        at = np.minimum(pos, 39)
        row = lambda x: jnp.asarray(x[np.arange(b), at][:, None], dt)
        tab = jnp.asarray(np.where(live[:, None], table, 0))
        outs = []
        for use_kernel in (False, True):
            c = dict(cache, table=tab, **extra)
            if use_kernel:
                c["use_kernel"] = True
            o, new = paged_update_cache_and_attend(
                c, row(qs), row(ks), row(vs), jnp.asarray(pos, jnp.int32))
            outs.append(np.asarray(o, np.float32))
        cache = new
        for i in np.flatnonzero(live):
            t = pos[i]
            lo = max(0, t - w + 1) if windowed else 0
            kd, vd = ks[i, lo:t + 1], vs[i, lo:t + 1]
            if store == "int8":
                kd, vd = _quantized(kd), _quantized(vd)
            want = np.zeros((h, d), np.float32)
            for head in range(h):
                g = head // (h // hk)
                sc = kd[:, g] @ qs[i, t, head] / np.sqrt(d)
                p = np.exp(sc - sc.max())
                want[head] = (p / p.sum()) @ vd[:, g]
            for o in outs:
                np.testing.assert_allclose(o[i, 0], want, atol=tol * 5,
                                           rtol=tol)
        pos = pos + live


@pytest.mark.parametrize("window", [None, 8, 20, 64])
@pytest.mark.parametrize("block", [8, 32])
def test_flash_with_groups_and_a_window_matches_plain_attention(window,
                                                                block):
    b, t, h, hk, d = 2, 64, 6, 2, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, t, hk, d)), jnp.float32)
            for _ in range(2))
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=block, block_k=block)
    rep = lambda x: jnp.repeat(x, h // hk, axis=2)
    if window is None:
        want = full_attention(q, rep(k), rep(v), causal=True)
    else:
        i = np.arange(t)
        seen = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :]
                                             < window)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        want = jnp.einsum("bhqk,bkhd->bqhd", p, rep(v))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


def test_flash_ungrouped_unwindowed_call_is_the_kernel_it_was():
    """The training cell's call: same jaxpr with and without the new
    arguments left at their defaults (the kernel's own parameters hold
    neither a group nor a window)."""
    q = jnp.zeros((1, 16, 2, 8), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda q: flash_attention(q, q, q, causal=True))(q))
    assert "window" not in text and "custom_vjp" in text
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=4)


# -- the expert layer ------------------------------------------------------- #

def _moe(n_experts, top_k, d=16, f=8):
    return DroplessMoE(n_experts=n_experts, d_model=d, d_ff=f, top_k=top_k,
                       compute_dtype=jnp.float32)


def _reference_moe(params, x, r_in, top_k):
    cfg = {"moe_num_active_primary_experts": top_k}
    p = params["params"]
    r = REF._dot(r_in, p["router"], False)
    return REF._experts(p, x, r, cfg, False)


@pytest.mark.parametrize("bias", [0.0, 50.0])
def test_dropless_routing_drops_nothing(bias):
    """Under a router biased to one expert every token's first choice is
    that expert (256 of 256 assignments in one group): the layer still
    gives what the reference's sum over the chosen experts gives."""
    layer = _moe(8, 2)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((256, 16)),
                    jnp.float32)
    params = {"params": layer.init(jax.random.PRNGKey(0), x)["params"]}
    router = params["params"]["router"]
    # every token's logit for expert 3 is far above the rest
    params["params"]["router"] = router.at[:, 3].add(
        bias * jnp.sign(jnp.sum(x, 0)) / 16)
    x = x + bias * jnp.sign(jnp.sum(x, 0))[None] / 16 if bias else x
    if bias:
        first = jnp.argmax(REF._dot(x, params["params"]["router"], False), -1)
        assert int(jnp.sum(first == 3)) == 256
    np.testing.assert_allclose(layer.apply(params, x),
                               _reference_moe(params, x, x, 2),
                               atol=1e-4, rtol=1e-4)


def test_top_six_of_sixty_four_with_a_router_input_of_its_own():
    """The published routing shape: the router reads another tensor than
    the experts, and some of the 64 groups are empty at 40 tokens."""
    layer = _moe(64, 6)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    r_in = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    params = {"params": layer.init(jax.random.PRNGKey(1), x)["params"]}
    assert params["params"]["w_down"].shape == (64, 8, 16)
    np.testing.assert_allclose(layer.apply(params, x, r_in),
                               _reference_moe(params, x, r_in, 6),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("top_k", [0, 9])
def test_top_k_outside_the_experts_is_refused(top_k):
    with pytest.raises(ValueError, match="top_k"):
        _moe(8, top_k).init(jax.random.PRNGKey(0), jnp.zeros((4, 16)))
