"""Flash attention kernel vs the XLA reference: values and gradients.

Runs in Pallas interpret mode on CPU (the TPU-compiled path is the same
kernel code; interpret mode checks the math, SURVEY.md S4's 'multi-node
without a cluster' testing stance applied to kernels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import flash_attention
from chainermn_tpu.parallel.sequence import full_attention


def _qkv(key, b=2, t=64, h=2, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return (jax.random.normal(kq, shape, dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), t=32, d=8)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = full_attention(q, k, v, causal=causal)
        return jnp.sum(o * o)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_cross_attention_rectangular():
    """T_q != T_k (cross attention shape)."""
    q, _, _ = _qkv(jax.random.PRNGKey(2), t=24)
    _, k, v = _qkv(jax.random.PRNGKey(3), t=48)
    got = flash_attention(q, k, v, block_q=8, block_k=16)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_offsets_reproduce_sharded_causal_slice():
    """flash on a q slice with q_offset equals the slice of full causal
    attention — the sequence-sharding contract."""
    q, k, v = _qkv(jax.random.PRNGKey(4), t=32)
    want = full_attention(q, k, v, causal=True)
    t_half = 16
    got_hi = flash_attention(
        q[:, t_half:], k, v, causal=True,
        q_offset=t_half, k_offset=0, block_q=8, block_k=8,
    )
    np.testing.assert_allclose(np.asarray(got_hi), np.asarray(want[:, t_half:]),
                               rtol=1e-5, atol=1e-5)


def test_traced_offsets():
    """Offsets may be traced values (axis_index-style callers)."""
    q, k, v = _qkv(jax.random.PRNGKey(5), t=16)

    @jax.jit
    def f(off):
        return flash_attention(q[:, 8:], k, v, causal=True,
                               q_offset=off, block_q=8, block_k=8)

    got = f(jnp.int32(8))
    want = full_attention(q, k, v, causal=True)[:, 8:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fully_masked_rows_zero_grads():
    """A q slice entirely BEFORE all keys (causal): output 0, grads 0 — the
    -inf lse sentinel must not produce NaN/garbage in backward."""
    q, k, v = _qkv(jax.random.PRNGKey(6), t=16)

    def loss(k, v):
        o = flash_attention(q, k, v, causal=True,
                            q_offset=0, k_offset=100,  # all keys in future
                            block_q=8, block_k=8)
        return jnp.sum(o * o), o

    (l, o), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(k, v)
    assert float(l) == 0.0
    np.testing.assert_array_equal(np.asarray(o), 0.0)
    for gi in g:
        np.testing.assert_array_equal(np.asarray(gi), 0.0)


def test_bfloat16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(7), dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = full_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_partially_masked_block_rows_zero():
    """Causal with k_offset not a multiple of block_q: rows 0..3 are fully
    masked INSIDE a visited k-block. They must output exactly 0 (not
    mean-of-V garbage from exp(sentinel - sentinel) == 1)."""
    q, k, v = _qkv(jax.random.PRNGKey(9), t=8, h=1, d=4)
    got = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=4,
                          block_q=8, block_k=8)
    # reference with explicit global-position mask
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (4 ** -0.5)
    mask = (jnp.arange(8)[:, None] >= (4 + jnp.arange(8))[None, :])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jnp.where(mask[None, None], jax.nn.softmax(s, axis=-1), 0.0)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_array_equal(np.asarray(got[:, :4]), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_awkward_length_falls_back_to_xla():
    """T prime and above the block size has no usable divisor (block would
    degenerate to 1): the XLA fallback must engage (same numerics), and the
    offset-causal case must raise clearly."""
    q, k, v = _qkv(jax.random.PRNGKey(8), t=251)
    got = flash_attention(q, k, v, causal=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="pad the sequence"):
        flash_attention(q, k, v, causal=True, q_offset=13)


def test_flash_kind_rejects_sharded_axis():
    from chainermn_tpu.parallel.sequence import sequence_parallel_attention
    with pytest.raises(ValueError, match="ring"):
        sequence_parallel_attention("flash", "ranks")


def test_pick_block_contract():
    """Sublane-granular block picker (round 5): candidates are multiples of
    8 for t > 8 (Mosaic's tiling rule — the old picker could emit e.g. one
    251-row block that only lowers in interpret mode), sub-8 requests on
    t > 8 round up to the hardware-minimum 8, no-divisor lengths return 1
    (the callers' fallback/raise sentinel), and t <= 8 keeps the plain
    largest-divisor-<=-preferred search."""
    from chainermn_tpu.ops.flash_attention import _pick_block

    assert _pick_block(1024, 512) == 512       # default path
    assert _pick_block(2048, 512) == 512
    assert _pick_block(64, 512) == 64          # whole (multiple-of-8) block
    assert _pick_block(24, 512) == 24
    assert _pick_block(16, 512) == 16
    assert _pick_block(251, 512) == 1          # prime: fallback sentinel
    assert _pick_block(12, 512) == 1           # no multiple-of-8 divisor
    assert _pick_block(64, 4) == 8             # sub-8 request rounds up
    assert _pick_block(8, 4) == 4              # t <= 8: plain divisor search
    assert _pick_block(6, 512) == 6
    assert _pick_block(4, 512) == 4


def test_default_block():
    """The data-driven default (round-5 on-chip sweep + the block-1024
    T=131072 AOT ceiling proof): 1024 at every length. This widening was
    the deliberate test change the previous revision's comment promised,
    backed by the landed ceiling run (aot_flash_ceiling.jsonl)."""
    from chainermn_tpu.ops.flash_attention import _default_block

    assert _default_block(2048) == 1024
    assert _default_block(8192) == 1024
    assert _default_block(16384) == 1024
    assert _default_block(131072) == 1024


@pytest.mark.parametrize("entry", ["flash_attention", "flash_fwd_with_lse",
                                   "flash_block_grads"])
def test_an_explicit_zero_block_is_refused_not_taken_for_unset(entry):
    """``block_q or default`` read an explicit 0 as unset and ran the
    default block; only ``None`` asks for the default."""
    import importlib

    fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
    q, k, v = _qkv(jax.random.PRNGKey(9), t=16)
    b, t, h, _ = q.shape
    stat = jnp.zeros((b, h, t), jnp.float32)      # an lse or a delta
    args = {"flash_attention": (q, k, v),
            "flash_fwd_with_lse": (q, k, v),
            "flash_block_grads": (q, k, v, q, stat, stat)}[entry]
    with pytest.raises(ValueError, match="block_q must be positive"):
        getattr(fa, entry)(*args, block_q=0)
    with pytest.raises(ValueError, match="block_k must be positive"):
        getattr(fa, entry)(*args, block_k=0)
