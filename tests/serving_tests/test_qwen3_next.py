"""A model of Gated DeltaNet (linear-attention) layers and gated
full-attention layers with a share of the experts held, against the plain
reference that sits beside the benchmark's configuration
(``benchmarks/configs/qwen3-next-80b-a3b-l4-ep2.py``: ``jax.numpy``,
float32, the recurrence a token at a time, nothing of the program): the
whole model's logits, the chunked form (the Pallas kernel and the XLA form)
against the recurrence at fast and at slow decay, prefill and then decode through the block store and the state
store, slots reused, a preempted request replayed, the two shares of the
experts, what an engine refuses for such a model, its instruments, the
kernels at heads of 256, and the scopes its device operations are found by.
Small sizes that keep every ratio of the published model (2 value heads a
key head, 8 query heads a KV head, rotary on a quarter of the head, 8
experts top-2 with 4 held), seeded weights, CPU.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import KVCacheKind, Qwen3NextLM, SlotStateKind
from chainermn_tpu.models import qwen3_next
from chainermn_tpu.models.qwen3_next import (
    CHUNK,
    GatedDeltaNet,
    chunk_gated_delta_rule,
    recurrent_gated_delta_rule,
    recurrent_gated_delta_step,
)
from chainermn_tpu.ops import flash_attention
from chainermn_tpu.ops.gated_delta import (
    chunk_gated_delta,
    decode_kernel_takes,
    kernel_takes,
    recurrent_gated_delta,
)
from chainermn_tpu.parallel.moe import DroplessMoE
from chainermn_tpu.parallel.sequence import (
    paged_scale_shape,
    paged_store_shape,
    paged_update_cache_and_attend,
    paged_write_kv,
)
from chainermn_tpu.resilience.faults import FaultInjector
from chainermn_tpu.serving import FCFSScheduler, ServingEngine
from chainermn_tpu.serving.speculative import SpeculativeConfig

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _reference():
    path = ROOT / "benchmarks" / "configs" / "qwen3-next-80b-a3b-l4-ep2.py"
    spec = importlib.util.spec_from_file_location("qwen3_next_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

# the published key names, at a size the CPU holds
CFG = {
    "vocab_size": 97, "hidden_size": 32, "num_hidden_layers": 4,
    "full_attention_interval": 4,
    "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16,
    "held_experts": {"first": 4, "count": 4, "published": 8},
    "rms_norm_eps": 1e-6,
}


def build(cfg, **kw):
    held = cfg["held_experts"]
    return Qwen3NextLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        linear_k_heads=cfg["linear_num_key_heads"],
        linear_v_heads=cfg["linear_num_value_heads"],
        linear_k_dim=cfg["linear_key_head_dim"],
        linear_v_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        full_attention_interval=cfg["full_attention_interval"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_theta"], d_ff=cfg["moe_intermediate_size"],
        n_experts=held["published"], top_k=cfg["num_experts_per_tok"],
        held_experts=(held["first"], held["count"]),
        shared_d_ff=cfg["shared_expert_intermediate_size"],
        rms_norm_eps=cfg["rms_norm_eps"], max_len=128,
        compute_dtype=jnp.float32, **kw)


def seeded(model, seed=0):
    """Weights from a seed, the norm scales moved off 1 so that a path which
    dropped them would show, and heads that forget at a few tenths a token
    as the benchmark's draws do (the published start, A up to 16, forgets
    everything at once)."""
    params = {"params": model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        name = str(getattr(path[-1], "key", ""))
        if name == "scale":
            leaf = 1.0 + 0.1 * jax.random.normal(key, leaf.shape)
        elif name == "A_log":
            leaf = 0.5 * jax.random.normal(key, leaf.shape)
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

def test_whole_model_logits_match_reference(lm):
    """Float32 on both sides, 150 tokens (two chunks and a ragged third).
    What is left is the order of summation, which the gated norm after the
    recurrence amplifies where a head's output is small (it divides by the
    output's own size): 2e-3 of logits of size 4, where a dropped gate, a
    wrong pairing of the rotary entries or a state carried wrongly moves
    them by tenths."""
    model, params = lm
    toks = tokens_of(0, 2, 150)
    got, want = model.apply(params, toks), REF.logits(params, toks, CFG)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_reference_control_is_another_model(lm):
    _, params = lm
    toks = tokens_of(2, 1, 64)
    exact = REF.logits(params, toks, CFG)
    low = REF.logits(params, toks, CFG, lowp=True)
    assert float(jnp.max(jnp.abs(exact - low))) > 1e-2


def test_the_spec_names_both_kinds_of_state(lm):
    model, _ = lm
    full, linear = model.kv_cache_spec()
    assert isinstance(full, KVCacheKind) and full.layers == (3,)
    assert (full.kv_heads, full.head_dim, full.window) == (1, 16, None)
    assert isinstance(linear, SlotStateKind) and linear.layers == (0, 1, 2)
    assert linear.chunk == CHUNK
    assert linear.arrays == (("S", (4, 8, 8), "float32"),
                             ("conv", (3, 2 * 16 + 32), "float32"))


# -- the two forms of the gated delta rule ---------------------------------- #

def _rule_inputs(seed, b, t, hk, hv, dk, dv, keep):
    """q and k on ``hk`` key heads (not normed: :func:`_normed`), v, beta
    and g on ``hv`` value heads, g such that a head keeps ``keep`` of its
    state a token (a pair: the range)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, t, hk, dk))
    k = rng.standard_normal((b, t, hk, dk))
    v = rng.standard_normal((b, t, hv, dv))
    beta = 1 / (1 + np.exp(-rng.standard_normal((b, t, hv))))
    g = np.log(rng.uniform(*keep, (b, t, hv)))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _normed(q, k, hv):
    """q and k as the rule takes them: normed a head (``x * rsqrt(sum x^2
    + 1e-6)``), q scaled by ``dk^-1/2``, repeated to the value heads that
    read them."""
    norm = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    repeat = lambda x: jnp.repeat(x, hv // x.shape[2], axis=2)
    return repeat(norm(q) * q.shape[-1] ** -0.5), repeat(norm(k))


def _chunked_form(form, q, k, v, g, beta, valid):
    """``(o, final state)`` of a chunked form over rows of ``valid`` real
    tokens: the kernel takes the convolution's output as it is, q and k
    unnormed, and ``valid``; the XLA form what the layer gives it, normed
    q and k and ``g = beta = 0`` past a row's length."""
    b, t, hk, dk = q.shape
    hv = v.shape[2]
    if form == "kernel":
        qkv = jnp.concatenate([x.reshape(b, t, -1) for x in (q, k, v)], -1)
        return chunk_gated_delta(qkv, g, beta, valid, k_heads=hk, dk=dk)
    real = (jnp.arange(t)[None, :] < valid[:, None])[..., None]
    return chunk_gated_delta_rule(*_normed(q, k, hv), v,
                                  jnp.where(real, g, 0.0),
                                  jnp.where(real, beta, 0.0))


# (key heads, value heads, dk, dv): heads of whole lanes as served, and
# narrow ones as the small model's
WIDTHS = {"lanes": (2, 4, 128, 128), "narrow": (2, 4, 16, 8)}


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("t", [1, CHUNK - 1, CHUNK, CHUNK + 1, 150, 300])
@pytest.mark.parametrize("keep", [(0.25, 0.75), (0.99, 0.999)])
def test_chunked_form_is_the_recurrence(form, width, t, keep):
    """Rows of a bucket of 320 as a prefill program holds them: one of
    length ``t`` (no multiple of the chunk, or one), one 20 shorter and one
    that holds no request (``valid == 0``); heads that forget in a few
    tokens and heads that keep 0.99-0.999 a token (a state 300 tokens
    deep). Each row's outputs and final state are the recurrence's over
    its real tokens, to float32's rounding; the kernel gives zeros past a
    row's length and a zero state for the empty row."""
    hk, hv, dk, dv = WIDTHS[width]
    args = _rule_inputs(t, 3, 320, hk, hv, dk, dv, keep)
    valid = jnp.asarray([t, max(t - 20, 1), 0], jnp.int32)
    o, state = _chunked_form(form, *args, valid)
    for row, length in enumerate(np.asarray(valid)):
        if length == 0:
            assert float(jnp.max(jnp.abs(state[row]))) == 0.0
            continue
        q, k, v, g, beta = (x[row:row + 1, :length] for x in args)
        o_rec, s_rec = recurrent_gated_delta_rule(*_normed(q, k, hv), v, g,
                                                  beta)
        np.testing.assert_allclose(o[row:row + 1, :length], o_rec,
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(state[row:row + 1], s_rec, atol=2e-5,
                                   rtol=2e-5)
    if form == "kernel":
        past = jnp.arange(320)[None, :] >= valid[:, None]
        assert float(jnp.max(jnp.abs(jnp.where(
            past[..., None, None], o, 0.0)))) == 0.0


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_padding_of_a_bucket_row_leaves_the_state_alone(form):
    """``g = 0`` and ``beta = 0`` past a row's length, the row given as
    whole: the final state is the state after its last real token,
    whatever the padding holds."""
    q, k, v, g, beta = _rule_inputs(5, 2, 100, 2, 4, 16, 8, (0.9, 0.99))
    real = jnp.arange(100)[None, :, None] < jnp.asarray([37, 100])[:, None,
                                                                   None]
    _, state = _chunked_form(form, q, k, v, jnp.where(real, g, 0.0),
                        jnp.where(real, beta, 0.0), jnp.asarray([100, 100]))
    _, short = recurrent_gated_delta_rule(
        *_normed(q[:1, :37], k[:1, :37], 4),
        *(x[:1, :37] for x in (v, g, beta)))
    np.testing.assert_allclose(state[0], short[0], atol=2e-5, rtol=2e-5)


def test_kernel_takes_pairs_of_value_heads_on_whole_lanes():
    """The one place the layer's whole-prompt form is chosen: the kernel
    at two value heads of one key head a program, heads of whole tiles of
    lanes (the published 16 key heads on 32 value heads of 128); the XLA
    form elsewhere. The kernel itself refuses what it cannot pair."""
    assert kernel_takes(16, 32, 128, 128) and kernel_takes(2, 4, 128, 128)
    assert not kernel_takes(2, 4, 8, 8)           # the small model's
    assert not kernel_takes(3, 3, 128, 128)       # one value head a key head
    with pytest.raises(ValueError, match="two value heads of one key head"):
        chunk_gated_delta(jnp.zeros((1, 64, 2 * 3 * 16 + 3 * 8)),
                          jnp.zeros((1, 64, 3)), jnp.zeros((1, 64, 3)),
                          k_heads=3, dk=16)


@pytest.mark.parametrize("head", [8, 128])
@pytest.mark.parametrize("keep", ["fast", "slow"])
def test_layer_prefill_then_recurrence_is_the_whole_sequence(keep, head):
    """The layer itself: a prompt through the chunked form into a slot's
    row (rows of unlike lengths, one of them padding only), then a token at
    a time through the recurrence on the store, against the whole sequence
    at once. Heads of 128 run the kernel (``kernel_takes``), which leaves
    zeros past a row's length: nothing the prefill hands on reads them
    (the state after the last real token, the conv's last real inputs, the
    outputs at real positions); heads of 8 run the XLA form."""
    assert kernel_takes(2, 4, head, head) == (head == 128)
    layer = GatedDeltaNet(d_model=32, n_k_heads=2, n_v_heads=4, d_k=head,
                          d_v=head, conv_kernel=4, rms_norm_eps=1e-6,
                          compute_dtype=jnp.float32)
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 90, 32))
    params = layer.init(jax.random.PRNGKey(1), a)
    if keep == "slow":      # exp(g) of 0.99-0.999: A = exp(-5.5)
        params = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.full_like(x, -5.5)
            if "A_log" in jax.tree_util.keystr(p) else x, params)
    whole, _ = layer.apply(params, a)
    lengths = jnp.asarray([70, 5, 0])
    store = {"S": jnp.full((5, 4, head, head), 7.0),  # a former tenant's
             "conv": jnp.full((5, 3, 8 * head), 7.0)}
    out, store = layer.apply(params, a[:, :80], dict(
        store, valid=lengths, slots=jnp.asarray([2, 0, 4])))
    np.testing.assert_allclose(out[0, :70], whole[0, :70], atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(out[1, :5], whole[1, :5], atol=1e-4,
                               rtol=1e-4)
    assert float(jnp.max(jnp.abs(store["S"][1] - 7.0))) == 0.0   # untouched
    # decode: the batch rows are the store's rows 0..3 in their order
    feed = jnp.zeros((4, 1, 32)).at[2].set(a[0, 70:71]).at[0].set(a[1, 5:6])
    valid = jnp.asarray([1, 0, 1, 0])
    for step in range(10):
        feed = jnp.zeros((4, 1, 32)).at[2, 0].set(a[0, 70 + step]).at[
            0, 0].set(a[1, 5 + step])
        out, store = layer.apply(params, feed, dict(store, valid=valid))
        np.testing.assert_allclose(out[2, 0], whole[0, 70 + step],
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(out[0, 0], whole[1, 5 + step],
                                   atol=1e-4, rtol=1e-4)
    assert float(jnp.max(jnp.abs(store["S"][1] - 7.0))) == 0.0


# -- the decode step as one kernel ------------------------------------------ #

def _step_inputs(seed, rows, hk, hv, scale=1.0):
    """A token's convolution output for each of ``rows`` batch rows (q and
    k unnormed, on ``hk`` key heads of 128, then v on ``hv`` value heads),
    g and beta, and a store of ``rows + 2`` rows of ``scale``-sized
    states: the rows past the batch stand for the scratch row."""
    rng = np.random.default_rng(seed)
    qkv = rng.standard_normal((rows, (2 * hk + hv) * 128))
    g = np.log(rng.uniform(0.25, 0.999, (rows, hv)))
    beta = 1 / (1 + np.exp(-rng.standard_normal((rows, hv))))
    store = scale * rng.standard_normal((rows + 2, hv, 128, 128))
    return [jnp.asarray(x, jnp.float32) for x in (qkv, g, beta, store)]


def _step_reference(qkv, g, beta, state, hk):
    """:func:`recurrent_gated_delta_step` on the batch rows, q and k split
    out, normed and repeated as the XLA form of the layer gives them."""
    rows, hv = g.shape
    q, k = (qkv[:, i * hk * 128:(i + 1) * hk * 128].reshape(rows, 1, hk, 128)
            for i in (0, 1))
    q, k = (x[:, 0] for x in _normed(q, k, hv))
    v = qkv[:, 2 * hk * 128:].reshape(rows, hv, 128)
    return recurrent_gated_delta_step(state[:rows], q, k, v, g, beta)


# (key heads, value heads) at heads of 128: the engine test's one key head,
# the published ratio of two value heads a key head, the published 16 on 32
DECODE_HEADS = [(1, 2), (2, 4), (16, 32)]


@pytest.mark.parametrize("heads", DECODE_HEADS, ids=str)
@pytest.mark.parametrize("rows", [1, 3, 8])
@pytest.mark.parametrize("scale", [1.0, 1e-30], ids=["random", "near-zero"])
def test_decode_kernel_is_the_step(heads, rows, scale):
    """One token a row through the kernel against the XLA step: outputs
    and new states to float32's rounding, and the store's rows past the
    batch as they were, bit for bit (the kernel neither reads nor writes
    them)."""
    hk, hv = heads
    qkv, g, beta, store = _step_inputs(rows, rows, hk, hv, scale)
    o, new = recurrent_gated_delta(qkv, g, beta, store, k_heads=hk, dk=128)
    o_ref, s_ref = _step_reference(qkv, g, beta, store, hk)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(o_ref))))
    np.testing.assert_allclose(new[:rows], s_ref, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(s_ref))))
    np.testing.assert_array_equal(new[rows:], store[rows:])


@pytest.mark.parametrize("heads", DECODE_HEADS, ids=str)
def test_decode_kernel_keeps_a_row_that_holds_nothing(heads):
    """Rows with ``g = 0`` and ``beta = 0`` (a slot that holds no request)
    give back their state bit for bit; the rows beside them advance."""
    hk, hv = heads
    qkv, g, beta, store = _step_inputs(7, 4, hk, hv)
    idle = jnp.asarray([True, False, True, False])[:, None]
    g, beta = (jnp.where(idle, 0.0, x) for x in (g, beta))
    _, new = recurrent_gated_delta(qkv, g, beta, store, k_heads=hk, dk=128)
    np.testing.assert_array_equal(new[0::2], store[0::2])
    assert float(jnp.min(jnp.max(jnp.abs(new[1:4:2] - store[1:4:2]),
                                 axis=(1, 2, 3)))) > 1e-3


@pytest.mark.parametrize("heads", DECODE_HEADS[:2], ids=str)
def test_decode_kernel_twenty_steps_are_the_recurrence(heads):
    """Twenty tokens a token at a time on a store, from zero, against the
    recurrence over the same tokens at once."""
    hk, hv = heads
    rows, steps = 3, 20
    q, k, v, g, beta = _rule_inputs(11, rows, steps, hk, hv, 128, 128,
                                    (0.9, 0.999))
    store = jnp.zeros((rows + 1, hv, 128, 128), jnp.float32)
    outs = []
    for t in range(steps):
        qkv = jnp.concatenate(
            [x[:, t].reshape(rows, -1) for x in (q, k, v)], -1)
        o, store = recurrent_gated_delta(qkv, g[:, t], beta[:, t], store,
                                         k_heads=hk, dk=128)
        outs.append(o)
    o_rec, s_rec = recurrent_gated_delta_rule(*_normed(q, k, hv), v, g,
                                              beta)
    np.testing.assert_allclose(jnp.stack(outs, 1), o_rec, atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(store[:rows], s_rec, atol=2e-5, rtol=2e-5)
    assert float(jnp.max(jnp.abs(store[rows]))) == 0.0


def test_decode_kernel_takes_heads_of_whole_lanes():
    """The one place the layer's decode form is chosen: the kernel at heads
    of whole tiles of lanes and a whole number of value heads a key head
    (the published 16 on 32 of 128), XLA at the small model's 8."""
    assert decode_kernel_takes(16, 32, 128, 128)
    assert decode_kernel_takes(1, 2, 128, 128)
    assert decode_kernel_takes(3, 3, 128, 128)
    assert not decode_kernel_takes(2, 4, 8, 8)
    assert not decode_kernel_takes(2, 4, 128, 64)
    assert not decode_kernel_takes(2, 3, 128, 128)
    with pytest.raises(ValueError, match="whole number of value heads"):
        recurrent_gated_delta(jnp.zeros((1, 5 * 128)), jnp.zeros((1, 3)),
                              jnp.zeros((1, 3)),
                              jnp.zeros((2, 3, 128, 128)), k_heads=2,
                              dk=128)


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
    args = dict(n_slots=3, prefill_buckets=(8, 32, 64, 72), prefill_batch=2,
                paged=True, kv_block_size=8, cache_len=104)
    args.update(kw)
    return ServingEngine(model, params, **args)


# more requests than slots, two of them in one prefill program (5 and 7 of a
# bucket of 8, rows of unlike lengths), prompts of one chunk and of two
WORK = [(5, 20), (40, 60), (20, 30), (7, 12), (33, 9), (64, 36), (70, 30)]


def serve(engine, seed=5, work=WORK):
    rng = np.random.default_rng(seed)
    sched = FCFSScheduler(engine)
    reqs = [sched.submit(rng.integers(0, CFG["vocab_size"], p), a)
            for p, a in work]
    sched.run_until_idle()
    return sched, reqs


@pytest.mark.parametrize("kv_quant,kernel,limit", [
    ("none", True, 2e-3), ("int8", False, 0.15), ("int8", True, 0.15)])
def test_prefill_then_decode_matches_the_full_forward(lm, kv_quant, kernel,
                                                      limit):
    """Seven requests on three slots (every slot freed and taken again),
    contexts to 100: what the engine serves through the block store of the
    full layer and the state store of the linear layers is, position by
    position, within ``limit`` of the reference's best logit. Float32
    without a quantised store: the order of summation alone, as in the
    whole-model test; an int8 store adds its rounding of K and V."""
    model, params = lm
    engine = engine_for(model, params, kv_quant=kv_quant,
                        paged_kernel=kernel)
    sched, reqs = serve(engine)
    assert all(len(r.tokens) == a for r, (_, a) in zip(reqs, WORK))
    for r in reqs:
        assert served_gap(params, r.prompt, list(r.tokens)) <= limit
    assert engine.compile_counts() == {"prefill": 4, "decode": 1}
    assert engine.recompiles == {}
    assert sched.metrics.report()["prefill_batch_size_max"] == 2


def test_a_request_is_served_the_same_alone_and_in_company(lm):
    """Rows of unlike lengths in one program, other slots' states beside it
    in the store, a slot a longer request has just left: a request's tokens
    are what it gets on an engine of its own."""
    model, params = lm
    _, together = serve(engine_for(model, params))
    for i in (0, 3, 5):
        _, alone = serve(engine_for(model, params), work=WORK[i:i + 1],
                         seed=100 + i)
        again = FCFSScheduler(engine_for(model, params))
        req = again.submit(together[i].prompt, WORK[i][1])
        again.run_until_idle()
        assert list(req.tokens) == list(together[i].tokens)
        assert len(alone[0].tokens) == WORK[i][1]


def test_a_preempted_request_replays_to_the_same_tokens(lm):
    """A replay is a prefill from position 0: the state it left behind is
    overwritten, not continued."""
    model, params = lm
    _, want = serve(engine_for(model, params), work=WORK[:2])
    engine = engine_for(model, params)
    engine.warmup()
    sched = FCFSScheduler(engine)
    rng = np.random.default_rng(5)
    reqs = [sched.submit(rng.integers(0, CFG["vocab_size"], p), a)
            for p, a in WORK[:2]]
    inj = FaultInjector(seed=0)
    inj.arm("serving.kv_append", kind="raise", times=1)
    with inj:
        sched.run_until_idle()
    assert inj.fired_log == [("serving.kv_append", "raise")]
    assert sched.metrics.report()["kv_preemptions"] == 1
    assert sched.engine_restarts == 0
    for got, ref in zip(reqs, want):
        assert list(got.tokens) == list(ref.tokens)


# -- the share of the experts ----------------------------------------------- #

def test_two_shares_and_the_gated_shared_expert_once_add_up():
    """Experts 0-3 on one chip and 4-7 on the other, each with the whole
    shared expert and its gate: the two parts less one shared term are the
    uncut layer."""
    kw = dict(n_experts=8, d_model=32, d_ff=16, top_k=2,
              compute_dtype=jnp.float32, activation="silu", shared_d_ff=16,
              shared_gate=True)
    whole = DroplessMoE(**kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 32))
    params = whole.init(jax.random.PRNGKey(1), x)
    inner = params["params"]
    gate = jax.nn.sigmoid(x @ inner["shared_gate"])
    sh = inner["shared"]
    shared = gate * ((jax.nn.silu(x @ sh["gate_proj"]["kernel"])
                      * (x @ sh["up_proj"]["kernel"]))
                     @ sh["down_proj"]["kernel"])
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    parts = []
    for first in (0, 4):
        cut = {"params": dict(inner, **{
            name: inner[name][first:first + 4]
            for name in ("w_gate", "w_up", "w_down")})}
        parts.append(DroplessMoE(held=(first, 4), **kw).apply(cut, x))
    np.testing.assert_allclose(parts[0] + parts[1] - shared,
                               whole.apply(params, x), atol=1e-5, rtol=1e-5)


# -- what an engine refuses for a recurrent state --------------------------- #

def _constructed(option):
    return lambda model, params: engine_for(model, params, **option)


def _tensor_axis(model, params):
    return engine_for(build(CFG, tensor_axis="mp"), params, comm=object())


def _migration(model, params):
    return engine_for(model, params).export_slot_kv(0)


def _chunked(model, params):
    engine = engine_for(model, params)
    plan = engine.plan_admission(np.arange(40), max_new=4)
    assert engine.plan_chunks(plan, 16) is None
    return engine.begin_chunked(plan, [(0, 16, 32), (16, 24, 32)])


@pytest.mark.parametrize("attempt,match", [
    (_constructed(dict(paged=False, prefix_cache_blocks=8)), "prefix reuse"),
    (_constructed(dict(speculative=SpeculativeConfig(k=2))), "speculative"),
    (_constructed(dict(decode_window=2)), "decode_window"),
    (_chunked, "chunked prefill"),
    (_migration, "migration"),
    (_tensor_axis, "tensor_axis"),
    (_constructed(dict(paged=False)), "paged=False"),
], ids=["prefix-reuse", "speculation", "decode-window", "chunked-prefill",
        "kv-migration", "tensor-axis", "dense"])
def test_engine_refuses_what_continues_a_prompt_at_an_offset(lm, attempt,
                                                             match):
    """A hit, a verify window, a chunk and a migrated request all need the
    state at an offset, which is not kept (snapshots are not built)."""
    model, params = lm
    with pytest.raises(ValueError, match="recurrent state.*" + match):
        attempt(model, params)


def test_prompts_repeat_without_prefix_reuse(lm):
    model, params = lm
    engine = engine_for(model, params)
    assert not engine.prefix_enabled and not engine.migration_supported
    sched = FCFSScheduler(engine)
    first = sched.submit(np.arange(24), 5)
    sched.run_until_idle()
    again = sched.submit(np.arange(24), 5)
    sched.run_until_idle()
    assert list(first.tokens) == list(again.tokens)
    assert engine.prefix_stats() == {}


# -- the state kind's account and instruments ------------------------------- #

def test_kv_stats_admission_and_the_three_instruments(lm):
    from chainermn_tpu.monitor import catalog
    from chainermn_tpu.monitor._state import get_registry

    model, params = lm
    engine = engine_for(model, params)
    kinds = engine.kv_stats()["kinds"]
    assert set(kinds) == {"full", "linear"}
    # three slots and the scratch row, three layers: S in float32 and the
    # convolution's three last inputs
    per_row = 4 * 8 * 8 * 4 + 3 * 64 * 4
    assert kinds["linear"] == {"slots": 3, "layers": 3, "slots_live": 0,
                               "bytes": 3 * 4 * per_row}
    assert [x.shape for x in engine._store[0].values()] == [
        (4, 4, 8, 8), (4, 3, 64)]
    # admission counts the kind as one unit a slot
    assert list(engine.blocks_needed(40, 60)) == [13, 1]
    assert engine.kv_blocks_admittable()[1] == 3
    sched = FCFSScheduler(engine)
    steps = engine._c_decode_steps.value       # the process's, shared
    reqs = [sched.submit(np.arange(p), a) for p, a in ((5, 6), (20, 3))]
    sched.step()
    sched.step()
    assert engine.kv_stats()["kinds"]["linear"]["slots_live"] == 2
    assert engine.kv_blocks_admittable()[1] == 1
    sched.run_until_idle()
    steps = int(engine._c_decode_steps.value - steps)
    report = sched.metrics.report()
    assert report["state_bytes"] == kinds["linear"]["bytes"]
    assert report["state_slots_live"] == 0
    # tokens x linear layers whose state a program advanced: both prompts,
    # and every token decoded after a request's first
    assert report["linear_state_tokens"] == 3 * (25 + (6 - 1) + (3 - 1))
    # heads of 8 decode in XLA: every decode step's slots x linear layers
    assert steps > 0
    assert (report["linear_decode_rows_kernel"],
            report["linear_decode_rows_xla"]) == (0, steps * 3 * 3)
    assert all(len(r.tokens) == n for r, n in zip(reqs, (6, 3)))
    names = {"serving_state_slots_live", "serving_state_bytes",
             "linear_state_tokens_total", "linear_prefill_chunks_total",
             "linear_decode_rows_total"}
    assert names <= set(catalog.METRIC_NAMES)
    snap = get_registry().snapshot()
    assert names <= {key.split("{")[0] for kind in ("counters", "gauges")
                     for key in snap[kind]}


def test_prefill_chunks_walked_and_skipped(lm):
    """``linear_prefill_chunks_total``: a program walks ``cdiv(valid,
    CHUNK)`` chunks of each row a linear layer (the rows' prompts), and
    walked and skipped make the bucket's chunks times the rows it ran
    (``prefill_rows_run_total``): prompts of 5 and 7 share a program of
    bucket 8, 40 and 70 run in buckets 64 and 72 (two chunks a row), and
    the last prompt of 6 runs one of bucket 8's two rows."""
    model, params = lm
    engine = engine_for(model, params)
    work = [(5, 4), (7, 4), (40, 3), (70, 2), (6, 2)]
    sched, reqs = serve(engine, work=work)
    assert all(len(r.tokens) == a for r, (_, a) in zip(reqs, work))
    report = sched.metrics.report()
    assert report["prefill_batch_size_max"] == 2
    layers = len(model.linear_layers())
    live = layers * sum(-(-p // CHUNK) for p, _ in work)
    assert report["linear_prefill_chunks_live"] == live == 18
    rows_run = {bucket: int(run.value)
                for bucket, (_, run) in sched.metrics._c_rows.items()}
    assert live + report["linear_prefill_chunks_padding"] == layers * sum(
        rows * -(-bucket // CHUNK) for bucket, rows in rows_run.items())
    assert report["linear_prefill_chunks_padding"] > 0


# a model of one linear layer and one full layer whose linear heads are 128
# wide (one key head, two value heads), so that its decode step takes the
# kernel (``decode_kernel_takes``)
WIDE = dict(CFG, num_hidden_layers=2, full_attention_interval=2,
            linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128)


def _calls(jaxpr, name):
    """The equations of primitive ``name`` in ``jaxpr`` and the jaxprs
    inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _calls(inner, name)


def test_decode_kernel_serves_what_the_xla_step_serves(monkeypatch):
    """An engine over a model whose linear heads are 128 wide decodes
    through the kernel: the decode program hands the kernel the state
    store as the buffer it writes (aliased, no copy), the requests get the
    greedy tokens the same model gets forced onto the XLA step, and
    ``linear_decode_rows_total`` counts every decode step's slots x linear
    layers under ``kernel`` (under ``xla`` when forced)."""
    model = build(WIDE)
    params = seeded(model)
    # four requests on three slots (one waits for a freed slot), two
    # prompts in one program of bucket 8
    work = [(5, 12), (40, 16), (7, 9), (33, 6)]

    def run():
        """The engine, its scheduler's ``linear_decode_rows_total`` by
        path, the slots x decode steps it ran (its step counter is the
        process's, shared by every engine) and the tokens served."""
        engine = engine_for(model, params, prefill_buckets=(8, 64))
        steps = engine._c_decode_steps.value
        sched, reqs = serve(engine, work=work)
        assert all(len(r.tokens) == a for r, (_, a) in zip(reqs, work))
        report = sched.metrics.report()
        rows = int(engine._c_decode_steps.value - steps) * engine.n_slots
        assert rows > 0
        return engine, (report["linear_decode_rows_kernel"],
                        report["linear_decode_rows_xla"]), rows, [
                            list(r.tokens) for r in reqs]

    engine, counted, rows, tokens = run()
    assert counted == (rows, 0)
    store = engine._store[0]["S"]
    steps = [eqn for eqn in _calls(jax.make_jaxpr(engine._decode_fn)(
        *engine._decode_args()).jaxpr, "pallas_call")
        if eqn.invars[3].aval.shape == store.shape]
    assert len(steps) == len(model.linear_layers()) == 1
    assert steps[0].params["input_output_aliases"] == ((3, 1),)
    monkeypatch.setattr(qwen3_next, "decode_kernel_takes",
                        lambda *heads: False)
    _, counted, rows, forced = run()
    assert counted == (0, rows)
    assert forced == tokens


# -- the kernels at heads of 256 -------------------------------------------- #

def _quantized(x):
    sc = np.maximum(np.abs(x).max(-1) / 127.0, 1e-8)[..., None]
    return np.clip(np.round(x / sc), -127, 127) * sc


@pytest.mark.parametrize("store", ["int8", "bf16"])
def test_paged_kernel_at_heads_of_256_on_two_kv_heads(store):
    """16 query heads on 2 KV heads of 256 (8 a group, a head spans two
    tiles of lanes), blocks of 16; the int8 store of 2 heads is held folded
    (``paged_store_shape``). The kernel against the XLA read path, and both
    against attention written out."""
    b, h, hk, d, bs = 2, 16, 2, 256, 16
    rng = np.random.default_rng(0)
    totals = [70, 37]
    width = 5
    table = np.zeros((b, width), np.int32)
    table[0, :5] = 1 + np.arange(5)
    table[1, :3] = 6 + np.arange(3)
    n_blocks = 9
    dt = jnp.bfloat16 if store == "bf16" else jnp.float32
    shape = paged_store_shape(n_blocks, bs, hk, d,
                              "int8" if store == "int8" else "none")
    assert shape == ((n_blocks, bs * hk, d) if store == "int8"
                     else (n_blocks, bs, hk, d))
    cache = {"k": jnp.zeros(shape, jnp.int8 if store == "int8" else dt)}
    cache["v"] = cache["k"]
    if store == "int8":
        cache["k_scale"] = jnp.zeros(
            paged_scale_shape(n_blocks, bs, hk), jnp.float32)
        cache["v_scale"] = cache["k_scale"]
    ks, vs = (rng.standard_normal((b, 70, hk, d)).astype(np.float32)
              for _ in range(2))
    qs = rng.standard_normal((b, 70, h, d)).astype(np.float32)
    prompt = np.array([45, 20])
    cache = paged_write_kv(
        dict(cache, table=jnp.asarray(table), valid=jnp.asarray(prompt)),
        jnp.asarray(ks[:, :48], dt), jnp.asarray(vs[:, :48], dt),
        jnp.zeros((b,), jnp.int32))
    pos = prompt.copy()
    tol = 2e-2 if store == "bf16" else 2e-6
    for _ in range(26):
        live = pos < np.array(totals)
        at = np.minimum(pos, 69)
        row = lambda x: jnp.asarray(x[np.arange(b), at][:, None], dt)
        tab = jnp.asarray(np.where(live[:, None], table, 0))
        outs = []
        for use_kernel in (False, True):
            c = dict(cache, table=tab)
            if use_kernel:
                c["use_kernel"] = True
            o, new = paged_update_cache_and_attend(
                c, row(qs), row(ks), row(vs), jnp.asarray(pos, jnp.int32))
            outs.append(np.asarray(o, np.float32))
        cache = new
        for i in np.flatnonzero(live):
            t = pos[i]
            kd, vd = ks[i, :t + 1], vs[i, :t + 1]
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


def test_a_store_of_four_heads_or_more_keeps_its_shape():
    """Only an int8 store of fewer than 4 heads is held folded: the served
    cells' stores (4, 8 and 16 KV heads) are the arrays they were."""
    for heads in (4, 8, 16):
        assert paged_store_shape(9, 16, heads, 128, "int8") == (
            9, 16, heads, 128)
    assert paged_store_shape(9, 16, 2, 256, "none") == (9, 16, 2, 256)
    assert paged_store_shape(9, 16, 2, 256, "int8") == (9, 32, 256)


@pytest.mark.parametrize("block", [32, 64])
def test_flash_forward_at_heads_of_256_matches_plain_attention(block):
    b, t, h, hk, d = 1, 128, 16, 2, 256
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b, t, hk, d)), jnp.float32)
            for _ in range(2))
    got = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    rep = lambda x: jnp.repeat(x, h // hk, axis=2)
    i = np.arange(t)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where((i[:, None] >= i[None, :])[None, None], s,
                                 -jnp.inf), -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, rep(v))
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)


# -- the scopes the benchmark's readers find operations by ------------------ #

@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_scopes_are_in_the_lowered_programs(lm, program):
    model, params = lm
    engine = engine_for(model, params)
    if program == "decode":
        text = engine._decode_fn.lower(*engine._decode_args()).as_text(
            debug_info=True)
    else:
        k = engine.prefill_rows(32)
        zeros = jnp.zeros((k,), jnp.int32)
        text = engine._prefill_fns[32].lower(
            engine.params, engine._store, engine._table_args(rows=k),
            jnp.zeros((k, 32), jnp.int32), zeros, zeros,
            jnp.zeros((k,), bool), jnp.zeros((k, 2), jnp.uint32)).as_text(
                debug_info=True)
    for scope in ("in_proj", "conv", "recurrence", "norm_gate", "out_proj"):
        for block in (0, 1, 2):
            assert f"block_{block}/gdn/{scope}" in text, scope
    assert "block_3/gdn" not in text
    assert "block_3/attn/q_proj" in text and "block_3/attn/q_norm" in text
    for block in range(4):
        for scope in ("moe/shared/gate_proj", "moe/shared/gate",
                      "moe/route", "moe/experts", "moe/combine"):
            assert f"block_{block}/{scope}" in text, scope
