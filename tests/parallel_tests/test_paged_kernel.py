"""Fused Pallas paged-decode kernel (PR 14): parity against the XLA
paged path across the shapes the serving engine compiles.

The kernel replaces only the READ side of ``paged_update_cache_and_
attend`` — table-indexed block gather, in-register int8 dequant and
online-softmax attention in one pass, streaming only each row's
``ceil(len/bs)`` active blocks. The load-bearing properties pinned here,
in dependency order: raw ``paged_attend`` matching a dense
``cached_attention`` reference on the gathered span (f32 tight, int8
against the SAME quantized store — the quantization error itself is
pinned by ``test_paged_int8_quant_tolerance``); ragged per-row lengths
including block-boundary edges; the decode-shape family (S=1, the
decode-window body, the speculative verify window with its ``valid``
write redirect); the static ``max_blocks`` tightening changing nothing;
the TP head-sharded store under ``shard_map``; and the availability
probe's env-var kill switch. On CPU everything runs the kernel in
Pallas interpret mode — the same code path tier-1 always exercises."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.parallel import paged_kernel
from chainermn_tpu.parallel.paged_kernel import (
    chunk_blocks,
    kernel_supported,
    paged_attend,
)
from chainermn_tpu.parallel.sequence import (
    _dequant_cached_attention,
    cached_attention,
    fold_block_scales,
    paged_update_cache_and_attend,
    update_cache_and_attend,
)


def fold(sc):
    """``[n, bs, H]`` scales (a bf16 store has none) as the store holds
    them: a block a row."""
    return None if sc is None else fold_block_scales(sc)


def _stores(b, h, d, bs, n_max, *, quant=False, seed=0):
    """A filled block store with identity tables (row i's blocks are a
    contiguous span; block 0 is scratch) and its dense per-row view."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    t = n_max * bs
    kbuf = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    vbuf = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    pad = jnp.zeros((1, bs, h, d), jnp.float32)
    store_k = jnp.concatenate([pad, kbuf.reshape(b * n_max, bs, h, d)])
    store_v = jnp.concatenate([pad, vbuf.reshape(b * n_max, bs, h, d)])
    table = (1 + jnp.arange(b * n_max, dtype=jnp.int32)).reshape(b, n_max)
    if not quant:
        return kbuf, vbuf, store_k, store_v, None, None, table

    def q8(x):
        sc = jnp.maximum(jnp.max(jnp.abs(x), axis=-1) / 127.0, 1e-8)
        return (jnp.clip(jnp.round(x / sc[..., None]), -127, 127)
                .astype(jnp.int8), sc)

    k8, ksc = q8(store_k)
    v8, vsc = q8(store_v)
    return kbuf, vbuf, k8, v8, ksc, vsc, table


def _dense_ref(q, kbuf, vbuf, lengths):
    """Per-row dense reference: ``cached_attention`` over each row's
    gathered span with the row's own position (= length - S)."""
    s = q.shape[1]
    return cached_attention(q, kbuf, vbuf, jnp.asarray(lengths) - s)


# --------------------------------------------------------------------- #
# raw kernel vs dense reference                                          #
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("s", [1, 3])
def test_kernel_matches_dense_reference_f32(s):
    """S=1 is the per-token decode shape (and the decode-window body:
    the fori_loop calls it per iteration); S=3 is a verify-window shape.
    Lengths are ragged on purpose: exactly S (youngest possible row), a
    mid-block tail, and an exact block boundary."""
    b, h, d, bs, n_max = 3, 4, 8, 4, 5
    kbuf, vbuf, sk, sv, _, _, table = _stores(b, h, d, bs, n_max)
    lengths = jnp.asarray([s, 7, 12], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(7), (b, s, h, d), jnp.float32)
    got = paged_attend(q, sk, sv, table, lengths)
    want = _dense_ref(q, kbuf, vbuf, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=5e-6)


def test_kernel_int8_matches_xla_dequant_path():
    """Same quantized store through the kernel and through the XLA
    folded-dequant read: identical masked set, same scales — the two
    reads must agree to fp tolerance (the quant error itself is pinned
    elsewhere)."""
    b, h, d, bs, n_max = 3, 4, 8, 4, 5
    _, _, k8, v8, ksc, vsc, table = _stores(b, h, d, bs, n_max, quant=True)
    lengths = jnp.asarray([2, 9, 20], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(8), (b, 2, h, d), jnp.float32)
    got = paged_attend(q, k8, v8, table, lengths,
                       k_scale=fold(ksc), v_scale=fold(vsc))
    # dense dequant reference over the full span (mask hides the tail)
    kd = (k8.astype(jnp.float32) * ksc[..., None])[table.reshape(-1)]
    vd = (v8.astype(jnp.float32) * vsc[..., None])[table.reshape(-1)]
    kd = kd.reshape(b, -1, h, d)
    vd = vd.reshape(b, -1, h, d)
    want = _dense_ref(q, kd, vd, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=5e-6)


def test_static_tightening_changes_nothing():
    """max_blocks clamped to the batch-max active count must be
    invisible: the dropped tail slots are provably past every row's
    length."""
    b, h, d, bs, n_max = 3, 4, 8, 4, 6
    _, _, sk, sv, _, _, table = _stores(b, h, d, bs, n_max)
    lengths = jnp.asarray([1, 8, 11], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(9), (b, 1, h, d), jnp.float32)
    full = paged_attend(q, sk, sv, table, lengths)
    tight = paged_attend(q, sk, sv, table, lengths,
                         max_blocks=int(-(-11 // bs)))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(tight))


# --------------------------------------------------------------------- #
# through paged_update_cache_and_attend (write + read, all shapes)       #
# --------------------------------------------------------------------- #


def _empty_paged(b, h, d, bs, n_max, quant):
    n_blocks = b * n_max + 1
    if quant:
        z = jnp.zeros((n_blocks, bs, h, d), jnp.int8)
        sc = fold(jnp.zeros((n_blocks, bs, h), jnp.float32))
        cache = {"k": z, "v": z, "k_scale": sc, "v_scale": sc}
    else:
        z = jnp.zeros((n_blocks, bs, h, d), jnp.float32)
        cache = {"k": z, "v": z}
    cache["table"] = (1 + jnp.arange(b * n_max, dtype=jnp.int32)
                      ).reshape(b, n_max)
    return cache


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("s,with_valid", [(1, False), (2, False),
                                          (3, True)])
def test_use_kernel_matches_xla_paged_path(quant, s, with_valid):
    """The routed form the engine traces: identical history written
    through both paths (stores bit-identical), then the kernel read vs
    the XLA read on the updated store — including the verify window's
    ``valid`` write redirect, which must affect both paths identically
    (it gates WRITES; the kernel only changes the read)."""
    b, h, d, bs, n_max = 3, 4, 8, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    pos = jnp.asarray([0, 5, 9], jnp.int32)
    hist_k = jax.random.normal(ks[0], (b, 10, h, d), jnp.float32)
    hist_v = jax.random.normal(ks[1], (b, 10, h, d), jnp.float32)
    base = _empty_paged(b, h, d, bs, n_max, quant)
    _, hist = paged_update_cache_and_attend(
        base, jnp.zeros_like(hist_k), hist_k, hist_v,
        jnp.zeros((b,), jnp.int32))
    cache = dict(hist, table=base["table"])
    if with_valid:
        cache["valid"] = jnp.asarray([3, 2, 1], jnp.int32)
    q = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[4], (b, s, h, d), jnp.float32)
    out_x, new_x = paged_update_cache_and_attend(cache, q, k, v, pos)
    out_k, new_k = paged_update_cache_and_attend(
        dict(cache, use_kernel=True), q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=5e-6, rtol=5e-6)
    for key in new_x:       # the write side is the SAME scatter
        np.testing.assert_array_equal(np.asarray(new_k[key]),
                                      np.asarray(new_x[key]))


def test_use_kernel_under_jit_with_static_flag():
    """The engine closes over ``use_kernel`` as a static Python bool
    inside its traced bodies — the routed call must trace and run under
    jit that way (the flag selects a trace, it is never an operand)."""
    b, h, d, bs, n_max = 2, 4, 8, 4, 3
    cache = _empty_paged(b, h, d, bs, n_max, False)
    pos = jnp.asarray([0, 3], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(kk, (b, 1, h, d), jnp.float32)
               for kk in ks)

    f = jax.jit(lambda c, q, k, v, p: paged_update_cache_and_attend(
        dict(c, use_kernel=True), q, k, v, p))
    out_j, _ = f(cache, q, k, v, pos)
    out_e, _ = paged_update_cache_and_attend(
        dict(cache, use_kernel=True), q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_e),
                               atol=5e-6, rtol=5e-6)


def test_update_cache_and_attend_routes_use_kernel():
    """The shared dispatcher honors the flag on a table-carrying cache
    and still strips host-managed keys from the returned cache."""
    b, h, d, bs, n_max = 2, 4, 8, 4, 3
    cache = _empty_paged(b, h, d, bs, n_max, False)
    pos = jnp.asarray([2, 0], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (jax.random.normal(kk, (b, 1, h, d), jnp.float32)
               for kk in ks)
    out, new = update_cache_and_attend(dict(cache, use_kernel=True),
                                       q, k, v, pos)
    assert out.shape == q.shape
    assert set(new) == {"k", "v"}


# --------------------------------------------------------------------- #
# TP: head-sharded store                                                 #
# --------------------------------------------------------------------- #


def test_kernel_on_head_sharded_store_matches_unsharded():
    """The TP layout: store and q sharded over heads (the engine's
    ``P(None, None, axis)`` resting spec), table/lengths replicated —
    per-shard kernels over local heads must reassemble to the unsharded
    result."""
    comm = chainermn_tpu.create_communicator("tpu")
    b, h, d, bs, n_max = 2, 8, 8, 4, 3
    kbuf, vbuf, sk, sv, _, _, table = _stores(b, h, d, bs, n_max)
    lengths = jnp.asarray([3, 10], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(21), (b, 2, h, d),
                          jnp.float32)
    want = paged_attend(q, sk, sv, table, lengths)
    hspec = P(None, None, comm.axis_name)
    f = jax.jit(comm.shard_map(
        lambda q, sk, sv, tb, ln: paged_attend(q, sk, sv, tb, ln),
        in_specs=(hspec, hspec, hspec, P(), P()),
        out_specs=hspec))
    got = f(q, sk, sv, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=5e-6)


# --------------------------------------------------------------------- #
# the sweep: chunks of C blocks, a trip count from ``lengths``            #
# --------------------------------------------------------------------- #


def _xla_int8_ref(q, k8, v8, ksc, vsc, table, lengths):
    """The XLA read path on the same quantized store: every row's whole
    table span gathered, the scales folded into the contractions."""
    b = q.shape[0]
    flat = table.reshape(-1)
    rows = lambda x: x[flat].reshape((b, -1) + x.shape[2:])
    return _dequant_cached_attention(
        q, rows(k8), rows(ksc), rows(v8), rows(vsc),
        jnp.asarray(lengths) - q.shape[1])


@pytest.mark.parametrize("quant", [False, True])
def test_lengths_on_every_edge_of_a_chunk(quant):
    """One ragged batch with a slot on each edge the sweep has: nothing
    live, one row, a whole block and one row more, one row short of a
    chunk, a whole chunk and one row more, the table's whole width. The
    f32 store is held against ``cached_attention``, the int8 store
    against the XLA read path on the same store."""
    h, d, bs, n_max = 4, 8, 4, 10
    c = chunk_blocks(bs, h, 128, jnp.int8 if quant else jnp.float32,
                     quant, n_max)
    assert 1 < c < n_max          # a chunk, and a ragged last one
    edges = [0, 1, bs, bs + 1, c * bs - 1, c * bs, c * bs + 1, n_max * bs]
    b = len(edges)
    kbuf, vbuf, sk, sv, ksc, vsc, table = _stores(b, h, d, bs, n_max,
                                                  quant=quant)
    lengths = jnp.asarray(edges, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(31), (b, 1, h, d), jnp.float32)
    got = np.asarray(paged_attend(q, sk, sv, table, lengths,
                                  k_scale=fold(ksc), v_scale=fold(vsc)))
    if quant:
        want = _xla_int8_ref(q, sk, sv, ksc, vsc, table, lengths)
    else:
        want = _dense_ref(q, kbuf, vbuf, lengths)
    np.testing.assert_array_equal(got[0], 0.0)    # nothing live: zeros
    np.testing.assert_allclose(got[1:], np.asarray(want)[1:],
                               atol=5e-6, rtol=5e-6)


def test_served_shape_int8_bf16_query():
    """``cgpt13b-serve-decode``'s own shape at a small batch: 16 heads of
    128, blocks of 16 tokens, an int8 store, a table 64 wide, the
    model's bf16 query. The sweep there takes chunks of 8 blocks."""
    b, h, d, bs, n_max = 2, 16, 128, 16, 64
    assert chunk_blocks(bs, h, d, jnp.int8, True, n_max) == 8
    _, _, k8, v8, ksc, vsc, table = _stores(b, h, d, bs, n_max, quant=True)
    lengths = jnp.asarray([8 * bs + 5, 23 * bs], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(32), (b, 1, h, d),
                          jnp.bfloat16)
    got = paged_attend(q, k8, v8, table, lengths,
                       k_scale=fold(ksc), v_scale=fold(vsc))
    assert got.dtype == jnp.bfloat16
    want = np.asarray(_xla_int8_ref(q, k8, v8, ksc, vsc, table, lengths),
                      np.float32)
    err = np.max(np.abs(np.asarray(got, np.float32) - want))
    assert err <= 2 ** -8 * np.max(np.abs(want))   # one bf16 rounding


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("h,d", [(4, 64), (8, 32)])
def test_heads_narrower_than_a_lane_row_share_one(h, d, quant):
    """Heads of 64 (``chip_smoke``'s model) and of 32: two and four heads
    a 128-lane row of the store, no padded copy of it; S = 2 so that
    rows of both tokens pick their own head's lanes and scales."""
    b, bs, n_max = 3, 4, 5
    kbuf, vbuf, sk, sv, ksc, vsc, table = _stores(b, h, d, bs, n_max,
                                                  quant=quant)
    lengths = jnp.asarray([2, bs + 3, n_max * bs], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(37), (b, 2, h, d), jnp.float32)
    got = paged_attend(q, sk, sv, table, lengths,
                       k_scale=fold(ksc), v_scale=fold(vsc))
    if quant:
        want = _xla_int8_ref(q, sk, sv, ksc, vsc, table, lengths)
    else:
        want = _dense_ref(q, kbuf, vbuf, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=5e-6)


@pytest.mark.parametrize("max_blocks", [3, 5])
def test_max_blocks_inside_one_chunk(max_blocks):
    """A static cap smaller than one chunk, and not a multiple of it,
    bounds the trip count and nothing else."""
    b, h, d, bs, n_max = 3, 4, 8, 4, 10
    assert chunk_blocks(bs, h, 128, jnp.float32, False, n_max) == 8
    kbuf, vbuf, sk, sv, _, _, table = _stores(b, h, d, bs, n_max)
    lengths = jnp.asarray([1, max_blocks * bs - 1, max_blocks * bs],
                          jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(33), (b, 1, h, d), jnp.float32)
    tight = paged_attend(q, sk, sv, table, lengths, max_blocks=max_blocks)
    np.testing.assert_array_equal(
        np.asarray(tight), np.asarray(paged_attend(q, sk, sv, table, lengths)))
    np.testing.assert_allclose(
        np.asarray(tight), np.asarray(_dense_ref(q, kbuf, vbuf, lengths)),
        atol=5e-6, rtol=5e-6)


@pytest.mark.parametrize("quant", [False, True])
def test_verify_window_across_a_chunk_edge(quant):
    """S = 3 with ``valid``: windows that end before, on and after the
    first chunk's last row, written and read through both paths."""
    b, h, d, bs, n_max = 3, 4, 8, 4, 10
    c = chunk_blocks(bs, h, 128, jnp.int8 if quant else jnp.float32,
                     quant, n_max)
    ks = jax.random.split(jax.random.PRNGKey(34), 5)
    t = c * bs + 2
    hist_k = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    hist_v = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
    base = _empty_paged(b, h, d, bs, n_max, quant)
    _, hist = paged_update_cache_and_attend(
        base, jnp.zeros_like(hist_k), hist_k, hist_v,
        jnp.zeros((b,), jnp.int32))
    cache = dict(hist, table=base["table"],
                 valid=jnp.asarray([3, 2, 1], jnp.int32))
    pos = jnp.asarray([c * bs - 4, c * bs - 2, c * bs - 1], jnp.int32)
    q, k, v = (jax.random.normal(kk, (b, 3, h, d), jnp.float32)
               for kk in ks[2:])
    out_x, new_x = paged_update_cache_and_attend(cache, q, k, v, pos)
    out_k, new_k = paged_update_cache_and_attend(
        dict(cache, use_kernel=True), q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=5e-6, rtol=5e-6)
    for key in new_x:
        np.testing.assert_array_equal(np.asarray(new_k[key]),
                                      np.asarray(new_x[key]))


@pytest.mark.parametrize("quant", [False, True])
def test_head_sharded_store_four_local_heads(quant):
    """Tensor-parallel serving's shard: 4 local heads a device, more
    than one chunk live, the int8 store's scales sharded with it."""
    comm = chainermn_tpu.create_communicator("tpu")
    b, h, d, bs, n_max = 2, 4 * comm.size, 8, 4, 10
    _, _, sk, sv, ksc, vsc, table = _stores(b, h, d, bs, n_max, quant=quant)
    lengths = jnp.asarray([bs + 1, n_max * bs - 3], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(35), (b, 1, h, d), jnp.float32)
    scales = (fold(ksc), fold(vsc)) if quant else ()
    # a device's scale array folds ITS heads: the whole array is the
    # devices' side by side along the columns
    by_rank = tuple(
        jnp.concatenate([fold(sc[:, :, r * 4:(r + 1) * 4])
                         for r in range(comm.size)], axis=2)
        for sc in ((ksc, vsc) if quant else ()))

    def attend(q, sk, sv, tb, ln, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return paged_attend(q, sk, sv, tb, ln, **kw)

    hspec = P(None, None, comm.axis_name)
    f = jax.jit(comm.shard_map(
        attend, in_specs=(hspec, hspec, hspec, P(), P())
        + (hspec,) * len(scales), out_specs=hspec))
    np.testing.assert_allclose(
        np.asarray(f(q, sk, sv, table, lengths, *by_rank)),
        np.asarray(attend(q, sk, sv, table, lengths, *scales)),
        atol=5e-6, rtol=5e-6)


@pytest.mark.parametrize("quant", [False, True])
def test_poisoned_dead_blocks_are_never_read(quant):
    """Every block the live span does not reach, and block 0, holds NaN
    rows (f32 store) or infinite scales (int8 store); half of the dead
    table entries point at block 0. A probability of 0 times such a
    value is NaN, so the output says whether one was looked at: it must
    be finite and bit-equal to the clean store's."""
    b, h, d, bs, n_max = 4, 4, 8, 4, 10
    _, _, sk, sv, ksc, vsc, table = _stores(b, h, d, bs, n_max, quant=quant)
    lengths = np.asarray([0, 3, 8 * bs, 8 * bs + 1])
    live = np.arange(n_max)[None, :] < -(-lengths // bs)[:, None]
    table = np.asarray(table)
    dead = np.ones(sk.shape[0], bool)
    dead[table[live]] = False                      # block 0 stays dead
    table = jnp.asarray(np.where(
        live | (np.arange(n_max) % 2 == 0)[None, :], table, 0))
    q = jax.random.normal(jax.random.PRNGKey(36), (b, 1, h, d), jnp.float32)
    mark = jnp.asarray(dead)
    if quant:
        bad = lambda sc: jnp.where(mark[:, None, None], jnp.inf, sc)
        clean = paged_attend(q, sk, sv, table, lengths,
                             k_scale=fold(ksc), v_scale=fold(vsc))
        got = paged_attend(q, sk, sv, table, lengths,
                           k_scale=fold(bad(ksc)), v_scale=fold(bad(vsc)))
    else:
        bad = lambda x: jnp.where(mark[:, None, None, None], jnp.nan, x)
        clean = paged_attend(q, sk, sv, table, lengths)
        got = paged_attend(q, bad(sk), bad(sv), table, lengths)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("dtype,want", [(jnp.float32, 4), (jnp.bfloat16, 8),
                                        (jnp.int8, 8)])
def test_chunk_fits_the_vmem_budget_at_served_widths(dtype, want):
    """C is a pure function of the shapes: at 16 heads of 128 and blocks
    of 16 tokens the two buffers of K, V and scales stay inside the
    budget for every element size, and twice the chunk would not."""
    bs, h, d = 16, 16, 128
    quant = dtype == jnp.int8
    c = chunk_blocks(bs, h, d, dtype, quant, 64)
    size = jnp.dtype(dtype).itemsize
    block = 2 * bs * h * d * size + (2 * 8 * bs * h * 4 if quant else 0)
    assert c == want
    assert 2 * c * block <= paged_kernel._VMEM_BUDGET < 2 * 2 * c * block


def test_chunk_is_at_least_one_and_at_most_the_table():
    assert chunk_blocks(16, 16, 128, jnp.int8, True, 5) == 4
    assert chunk_blocks(16, 16, 128, jnp.int8, True, 1) == 1
    assert chunk_blocks(64, 64, 256, jnp.float32, False, 64) == 1


# --------------------------------------------------------------------- #
# availability probe + bytes-read model                                  #
# --------------------------------------------------------------------- #


def test_kernel_supported_env_kill_switch(monkeypatch):
    ok, why = kernel_supported()
    assert ok and why == ""
    monkeypatch.setenv("CHAINERMN_TPU_NO_PAGED_KERNEL", "1")
    ok, why = kernel_supported()
    assert not ok and "CHAINERMN_TPU_NO_PAGED_KERNEL" in why
    assert "CHAINERMN_TPU_NO_PAGED_KERNEL" not in os.environ or True


@pytest.mark.parametrize("quant", [False, True])
def test_layers_share_one_trace_and_nothing_wraps_the_kernel(quant):
    """A model calls the kernel once a layer with one set of shapes: the
    caller's trace holds one ``pallas_call`` per layer, directly (a call
    in between would take the kernel out from under its layer's name),
    and all of them are one traced kernel, so a program lowers it once."""
    b, h, d, bs, n_max = 2, 4, 8, 4, 10
    _, _, sk, sv, ksc, vsc, table = _stores(b, h, d, bs, n_max, quant=quant)
    lengths = jnp.asarray([3, n_max * bs], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(38), (b, 1, h, d), jnp.float32)

    def layers(q):
        for _ in range(3):
            q = paged_attend(q, sk, sv, table, lengths,
                             k_scale=fold(ksc), v_scale=fold(vsc))
        return q

    eqns = jax.make_jaxpr(layers)(q).jaxpr.eqns
    kernels = [e.params["jaxpr"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3
    assert all(k is kernels[0] for k in kernels)
