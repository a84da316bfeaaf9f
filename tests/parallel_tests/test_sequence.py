"""Ring/Ulysses sequence parallelism: exactness vs full attention, gradients
(TPU-first extension — SURVEY.md S2.16/S5 marks this absent upstream)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.parallel.sequence import (
    full_attention,
    paged_scale_shape,
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
    unfold_block_scales,
    zigzag_flash_attention,
    zigzag_permutation,
    zigzag_positions,
    zigzag_ring_attention,
)


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("tpu")


def _qkv(b=2, t=32, h=8, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def _sharded(comm, fn, *, causal):
    spec = P(None, comm.axis_name)  # shard the sequence axis

    def body(q, k, v):
        return fn(q, k, v, comm.axis_name, causal=causal)

    return jax.jit(comm.shard_map(body, in_specs=(spec, spec, spec),
                                  out_specs=spec))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_matches_full_attention(comm, causal, impl):
    q, k, v = _qkv()
    want = full_attention(q, k, v, causal=causal)
    got = _sharded(comm, impl, causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", [
    # ~3s; ring gradients stay tier-1 via test_ring_flash_gradients_match_full_attention
    pytest.param(ring_attention, marks=pytest.mark.slow),
    ulysses_attention,
])
def test_gradients_match_full_attention(comm, impl):
    q, k, v = _qkv(t=16, h=8, d=8)

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    sharded = _sharded(comm, impl, causal=True)

    def loss_sharded(q, k, v):
        return (sharded(q, k, v) ** 2).sum()

    g_want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_bf16_inputs(comm):
    q, k, v = _qkv(t=16)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = _sharded(comm, ring_attention, causal=True)(q, k, v)
    assert out.dtype == jnp.bfloat16
    want = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=4e-2, rtol=4e-2)


def test_ulysses_rejects_indivisible_heads(comm):
    q, k, v = _qkv(h=6)
    with pytest.raises(ValueError):
        _sharded(comm, ulysses_attention, causal=False)(q, k, v)


@pytest.mark.parametrize("causal", [
    # ~7s; non-causal chunking covered by the parity sweep above — keep tier-1 inside its timeout
    pytest.param(False, marks=pytest.mark.slow),
    True,
])
def test_ulysses_head_chunks_match_full(comm, causal):
    """head_chunks pipelining is exact for any chunking (heads are
    independent); bad chunkings are rejected loudly."""
    import functools

    q, k, v = _qkv(h=16)
    want = full_attention(q, k, v, causal=causal)
    got = _sharded(
        comm, functools.partial(ulysses_attention, head_chunks=2),
        causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="head_chunks"):
        # 16 heads / 8 chunks = 2 per group, not divisible by axis size 8
        _sharded(comm, functools.partial(ulysses_attention, head_chunks=8),
                 causal=False)(q, k, v)

    # gradients through the chunked pipeline (slice -> exchange -> attend
    # -> exchange -> concat) must also match the dense oracle
    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    sharded = _sharded(
        comm, functools.partial(ulysses_attention, head_chunks=2),
        causal=True)

    def loss_sharded(q, k, v):
        return (sharded(q, k, v) ** 2).sum()

    g_want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


# --------------------------------------------------------------------------- #
# Ring with Pallas flash blocks (ring-level custom VJP)                       #
# --------------------------------------------------------------------------- #

def _rf_sharded(comm, *, causal):
    spec = P(None, comm.axis_name)
    # interpret-mode Pallas needs check_vma off (same as plain 'flash')
    return jax.jit(comm.shard_map(
        lambda q, k, v: ring_flash_attention(
            q, k, v, comm.axis_name, causal=causal),
        in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    ))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_full_attention(comm, causal):
    q, k, v = _qkv(t=64)
    want = full_attention(q, k, v, causal=causal)
    got = _rf_sharded(comm, causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_ring_flash_gradients_match_full_attention(comm):
    """The ring-level custom VJP (second rotation pass with the flash
    backward kernels; dk/dv accumulators riding the ring) against AD
    through full attention."""
    q, k, v = _qkv(t=64, h=4, d=8)
    f = _rf_sharded(comm, causal=True)

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    def loss_rf(q, k, v):
        return (f(q, k, v) ** 2).sum()

    g_want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_rf, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [
    # ~4s; non-causal covered by the non-flash parity sweep — keep tier-1 inside its timeout
    pytest.param(False, marks=pytest.mark.slow),
    True,
])
def test_ulysses_flash_matches_full_attention(comm, causal):
    """Ulysses with the Pallas kernel as the local attention: same
    collectives, O(T)-memory scores instead of the materialized
    [B, H/n, T, T] tile."""
    from chainermn_tpu.parallel.sequence import ulysses_flash_attention

    q, k, v = _qkv(t=64)
    want = full_attention(q, k, v, causal=causal)
    spec = P(None, comm.axis_name)
    f = jax.jit(comm.shard_map(
        lambda q, k, v: ulysses_flash_attention(
            q, k, v, comm.axis_name, causal=causal),
        in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    ))
    got = f(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    g_got = jax.grad(lambda q, k, v: (f(q, k, v) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(
        lambda q, k, v: (full_attention(q, k, v, causal=causal) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_flash_bf16(comm):
    """bf16 q/k/v feed the kernels; partials merge in f32 (out_dtype)."""
    q, k, v = _qkv(t=64)
    got = _rf_sharded(comm, causal=True)(
        *(x.astype(jnp.bfloat16) for x in (q, k, v)))
    assert got.dtype == jnp.bfloat16
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=4e-2, rtol=4e-2)


# --------------------------------------------------------------------------- #
# Zigzag (load-balanced causal) ring                                          #
# --------------------------------------------------------------------------- #

def test_zigzag_permutation_layout(comm):
    """Shard i of the permuted sequence is exactly chunks (i, 2n-1-i), and
    zigzag_positions reproduces each shard's global positions."""
    n = comm.size
    t = 4 * n  # chunk size 2
    perm = np.asarray(zigzag_permutation(t, n))
    assert sorted(perm.tolist()) == list(range(t))
    t_local, c = t // n, t // (2 * n)
    for i in range(n):
        shard = perm[i * t_local:(i + 1) * t_local]
        want = np.concatenate([
            np.arange(i * c, (i + 1) * c),
            np.arange((2 * n - 1 - i) * c, (2 * n - i) * c),
        ])
        np.testing.assert_array_equal(shard, want)
        np.testing.assert_array_equal(
            np.asarray(zigzag_positions(i, n, t_local)), want
        )


def _zigzag_sharded(comm, q, k, v):
    """Run zigzag ring attention on a contiguous global (q, k, v): permute,
    shard, attend, un-permute — the exact recipe callers use."""
    t = q.shape[1]
    perm = zigzag_permutation(t, comm.size)
    inv = jnp.argsort(perm)
    spec = P(None, comm.axis_name)
    f = jax.jit(comm.shard_map(
        lambda q, k, v: zigzag_ring_attention(q, k, v, comm.axis_name),
        in_specs=(spec,) * 3, out_specs=spec,
    ))
    return f(q[:, perm], k[:, perm], v[:, perm])[:, inv]


def test_zigzag_matches_full_attention(comm):
    q, k, v = _qkv()
    want = full_attention(q, k, v, causal=True)
    got = _zigzag_sharded(comm, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_gradients_match_full_attention(comm):
    q, k, v = _qkv(t=16, h=8, d=8)

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    def loss_zig(q, k, v):
        return (_zigzag_sharded(comm, q, k, v) ** 2).sum()

    g_want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_zigzag_bf16(comm):
    q, k, v = _qkv(t=16)
    got = _zigzag_sharded(comm, *(x.astype(jnp.bfloat16) for x in (q, k, v)))
    assert got.dtype == jnp.bfloat16
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=4e-2, rtol=4e-2)


def _zzf_run(comm, q, k, v):
    t = q.shape[1]
    perm = zigzag_permutation(t, comm.size)
    inv = jnp.argsort(perm)
    spec = P(None, comm.axis_name)
    f = jax.jit(comm.shard_map(
        lambda q, k, v: zigzag_flash_attention(q, k, v, comm.axis_name),
        in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    ))
    return f(q[:, perm], k[:, perm], v[:, perm])[:, inv]


def test_zigzag_flash_matches_full_attention(comm):
    """The flagship composition: balanced zigzag layout with Pallas kernel
    blocks (diag = 2 causal + 1 full chunk call; off-diag = one unmasked
    call per step, equal FLOPs in both cond branches)."""
    q, k, v = _qkv(t=64)
    want = full_attention(q, k, v, causal=True)
    got = _zzf_run(comm, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # ~8s; zigzag-flash forward parity + bf16 stay tier-1, plain-zigzag gradients stay tier-1 — keep tier-1 inside its timeout
def test_zigzag_flash_gradients_match_full_attention(comm):
    q, k, v = _qkv(t=64, h=4, d=8)

    def loss_full(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    def loss_z(q, k, v):
        return (_zzf_run(comm, q, k, v) ** 2).sum()

    g_want = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(loss_z, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_zigzag_flash_bf16(comm):
    q, k, v = _qkv(t=64)
    got = _zzf_run(comm, *(x.astype(jnp.bfloat16) for x in (q, k, v)))
    assert got.dtype == jnp.bfloat16
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=4e-2, rtol=4e-2)


@pytest.mark.slow  # ~6s; the 2x-work perf property rides the slow tier, zigzag parity stays tier-1 — keep tier-1 inside its timeout
def test_zigzag_halves_causal_work(comm):
    """The point of zigzag + block skipping: executed causal work is ~half
    of the round-3 compute-every-masked-block ring. HLO cost analysis can't
    see it (it counts fori_loop bodies once and BOTH lax.cond branches), so
    measure executed work as wall-clock on this serialized CPU mesh, where
    total time ~ total executed FLOPs. Per-rank balance holds by
    construction: both zigzag cond branches compute the same-size
    [t, t/2]-score update, so every rank does identical work each step
    (the contiguous ring's skip branch is empty — rank n-1 stays the
    lockstep straggler there)."""
    import time

    b, t, h, d = 1, 2048, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.float32) for kk in ks)
    spec = P(None, comm.axis_name)

    def timed(fn, *args):
        f = jax.jit(comm.shard_map(fn, in_specs=(spec,) * 3, out_specs=spec))
        f(*args).block_until_ready()  # compile
        t0, n = time.time(), 0
        while time.time() - t0 < 2.0:
            f(*args).block_until_ready()
            n += 1
        return (time.time() - t0) / n

    noskip = timed(
        lambda q, k, v: ring_attention(q, k, v, comm.axis_name, causal=True,
                                       skip_masked_blocks=False), q, k, v)
    perm = zigzag_permutation(t, comm.size)
    zig = timed(
        lambda q, k, v: zigzag_ring_attention(q, k, v, comm.axis_name),
        q[:, perm], k[:, perm], v[:, perm])
    # theory: 0.5 + O(1/n); generous bound for timer noise
    assert zig < 0.8 * noskip, (zig, noskip)


# --------------------------------------------------------------------- #
# paged KV decode path (PR 7)                                            #
# --------------------------------------------------------------------- #


def _paged_setup(b=3, s=2, h=4, d=8, bs=4, n_max=4, quant="none", seed=3):
    """Random q/k/v rows plus a dense cache and its paged twin holding
    identical pre-existing KV, with identity block tables (row i's blocks
    are a contiguous span of the store) and per-row positions."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    t = n_max * bs
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    kbuf = jax.random.normal(ks[3], (b, t, h, d), jnp.float32)
    vbuf = jax.random.normal(ks[4], (b, t, h, d), jnp.float32)
    pos = jnp.asarray([0, 5, 9][:b], jnp.int32)  # ragged per-row depths
    dense = {"k": kbuf, "v": vbuf}
    n_blocks = b * n_max + 1                     # + scratch block 0
    store_k = kbuf.reshape(b * n_max, bs, h, d)
    store_v = vbuf.reshape(b * n_max, bs, h, d)
    pad = jnp.zeros((1, bs, h, d), jnp.float32)
    paged = {
        "k": jnp.concatenate([pad, store_k]),
        "v": jnp.concatenate([pad, store_v]),
        "table": (1 + jnp.arange(b * n_max, dtype=jnp.int32)
                  ).reshape(b, n_max),
    }
    if quant == "int8":
        # start from an EMPTY int8 store (pre-existing rows would need
        # quantizing too; the engine only ever writes through the quant
        # path, so an empty store + fresh writes is the honest setup)
        z = jnp.zeros((n_blocks, bs, h, d), jnp.int8)
        sc = jnp.zeros(paged_scale_shape(n_blocks, bs, h), jnp.float32)
        paged = {"k": z, "v": z, "k_scale": sc, "v_scale": sc,
                 "table": paged["table"]}
    return q, k, v, pos, dense, paged


def test_paged_update_matches_dense_update():
    """paged_update_cache_and_attend == the dense [B] path bit-for-bit
    when the store holds the same KV: same writes (round-tripped through
    the block layout), same attention output."""
    from chainermn_tpu.parallel.sequence import update_cache_and_attend

    q, k, v, pos, dense, paged = _paged_setup()
    out_d, new_d = update_cache_and_attend(dense, q, k, v, pos)
    out_p, new_p = update_cache_and_attend(paged, q, k, v, pos)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))
    b, _, h, d = q.shape
    n_max = paged["table"].shape[1]
    bs = paged["k"].shape[1]
    for kk in ("k", "v"):
        round_trip = np.asarray(new_p[kk])[1:].reshape(b, n_max * bs, h, d)
        np.testing.assert_array_equal(round_trip, np.asarray(new_d[kk]))
    assert "table" not in new_p       # host-managed state, not returned


def test_paged_update_scatters_through_ragged_tables():
    """A permuted (non-identity) table must read/write the same logical
    rows: permuting each row's blocks AND its table entries together
    changes nothing observable."""
    from chainermn_tpu.parallel.sequence import update_cache_and_attend

    q, k, v, pos, _, paged = _paged_setup(b=2, n_max=3)
    out_ref, _ = update_cache_and_attend(paged, q, k, v, pos)
    perm = np.array([0, 5, 3, 1, 6, 2, 4])       # fixed block shuffle
    inv = np.argsort(perm)
    shuffled = {
        "k": jnp.asarray(np.asarray(paged["k"])[inv]),
        "v": jnp.asarray(np.asarray(paged["v"])[inv]),
        "table": jnp.asarray(perm[np.asarray(paged["table"])], jnp.int32),
    }
    out_sh, _ = update_cache_and_attend(shuffled, q, k, v, pos)
    np.testing.assert_array_equal(np.asarray(out_sh), np.asarray(out_ref))


def test_paged_int8_quant_tolerance():
    """int8 resident blocks: per-row-per-head scales bound the dequant
    error at ~0.8% of each row's max |x|, and the attention output stays
    within a small absolute tolerance of the fp path built from the SAME
    (quantize-on-write) history."""
    from chainermn_tpu.parallel.sequence import update_cache_and_attend

    q, k, v, pos, _, paged_q = _paged_setup(quant="int8")
    _, _, _, _, _, paged_f = _paged_setup()
    # write the same rows through both stores starting EMPTY (zero the fp
    # store's pre-existing rows so both paths attend identical history)
    paged_f = {"k": jnp.zeros_like(paged_f["k"]),
               "v": jnp.zeros_like(paged_f["v"]),
               "table": paged_f["table"]}
    out_f, new_f = update_cache_and_attend(paged_f, q, k, v, pos)
    out_q, new_q = update_cache_and_attend(paged_q, q, k, v, pos)
    # round-trip error bound: |x - x_q*scale| <= scale/2 = max|x|/254
    deq = (np.asarray(new_q["k"], np.float32)
           * np.asarray(unfold_block_scales(
               new_q["k_scale"], *new_q["k"].shape[1:3]))[..., None])
    ref = np.asarray(new_f["k"])
    written = np.abs(ref) > 0
    err = np.abs(deq - ref)[written]
    step = (np.abs(ref).max(axis=-1, keepdims=True) / 127.0
            + 1e-8) * np.ones_like(ref)
    assert (err <= 0.51 * step[written] + 1e-6).all()
    # end-to-end attention perturbation stays small
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                               atol=0.08)
