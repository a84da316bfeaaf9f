"""The int8 block store's scale arrays (ISSUE 32): a block's scales in ONE
row, ``[blocks, 1, bs*H in whole 128s]``, column ``t*H + h``.

What is held here: every read path over what ``paged_write_kv`` stored
agrees with a dense reference that quantises the same way; the writes that
must NOT land (``valid``, the scratch block, a ring longer than its table
row) leave every live row's scale as it was; and the store holds, element
for element, what the old ``[n_blocks, bs, H]`` arrays held after the same
writes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.parallel.sequence import (
    paged_scale_shape,
    paged_update_cache_and_attend,
    paged_write_kv,
    unfold_block_scales,
)


def _q8(x):
    """The store's own quantisation: one scale a row and head."""
    sc = jnp.maximum(jnp.max(jnp.abs(x), axis=-1) / 127.0, 1e-8)
    return jnp.clip(jnp.round(x / sc[..., None]), -127, 127), sc


def _empty(n_blocks, bs, hk, d):
    z = jnp.zeros((n_blocks, bs, hk, d), jnp.int8)
    sc = jnp.zeros(paged_scale_shape(n_blocks, bs, hk), jnp.float32)
    return {"k": z, "v": z, "k_scale": sc, "v_scale": sc}


def _dense_attention(q, k, v, q_pos, window):
    """``q [B, S, H, D]`` at positions ``q_pos [B, S]`` over dequantised
    ``k``/``v [B, T, Hkv, D]``, grouped heads, an optional window."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k, v = (jnp.repeat(x, g, axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision="highest") * d ** -0.5
    k_pos = jnp.arange(k.shape[1])[None, None, :]
    mask = k_pos <= q_pos[:, :, None]
    if window is not None:
        mask = mask & (k_pos > q_pos[:, :, None] - window)
    sc = jnp.where(mask[:, None], sc, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                      precision="highest")


# path -> (query heads, KV heads, window, use_kernel)
PATHS = {
    "kernel": (4, 4, None, True),
    "plain_gather": (4, 4, None, False),
    "gather_grouped": (6, 2, None, False),
    "gather_window": (4, 4, 10, False),
    "kernel_grouped": (6, 2, None, True),
    "kernel_grouped_window": (6, 2, 10, True),
}
# the kernel takes ONE first visible position a slot (``first``), so under
# a window it serves one query row a call, as the engine asks of it
CALLS = [(path, s) for path in PATHS for s in (1, 7)
         if (path, s) != ("kernel_grouped_window", 7)]


@pytest.mark.parametrize("path,s_new", CALLS,
                         ids=[f"{p}-{s}_rows" for p, s in CALLS])
def test_write_then_read_matches_dense_quantised(path, s_new):
    """A history written by a prefill that ends off a block boundary, then
    ``s_new`` rows more (from there on: the first shares the prefill's last
    block, and at 7 they span three) written and attended in one call."""
    h, hk, window, kernel = PATHS[path]
    b, d, bs = 3, 8, 4
    start = np.array([3, 6, 9])                   # none on a boundary
    total = int(start.max()) + s_new
    rng = np.random.default_rng(5)
    ks, vs = (jnp.asarray(rng.standard_normal((b, total, hk, d)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((b, s_new, h, d)), jnp.float32)
    if window is None:
        width = -(-total // bs)
    else:            # a ring: what the call's oldest query sees + a block
        width = -(-(window + s_new - 1) // bs) + 1
    table = jnp.asarray(1 + rng.permutation(b * width).reshape(b, width),
                        jnp.int32)
    extra = {} if window is None else {"window": window}
    cache = dict(_empty(b * width + 1, bs, hk, d), table=table, **extra)
    s_fill = int(start.max())
    stored = paged_write_kv(
        dict(cache, valid=jnp.asarray(start, jnp.int32)),
        ks[:, :s_fill], vs[:, :s_fill], jnp.zeros((b,), jnp.int32))
    new = lambda x: jnp.stack([x[i, p:p + s_new]
                               for i, p in enumerate(start)])
    out, _ = paged_update_cache_and_attend(
        dict(stored, table=table, use_kernel=kernel, **extra),
        q, new(ks), new(vs), jnp.asarray(start, jnp.int32))
    # the reference sees the same rows, each quantised as the store does
    deq = lambda x: (lambda q8, sc: q8 * sc[..., None])(*_q8(x))
    q_pos = jnp.asarray(start)[:, None] + jnp.arange(s_new)[None, :]
    want = _dense_attention(q, deq(ks), deq(vs), q_pos, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _old_layout_write(store, table, k, v, pos, valid, ring):
    """``paged_write_kv`` as it was before ISSUE 32, over
    ``[n_blocks, bs, H]`` scale arrays: the reference the new arrays are
    held to."""
    bs = store["k"].shape[1]
    b, s = k.shape[:2]
    p = pos[:, None] + jnp.arange(s)[None, :]
    entry = p // bs if ring is None else (p // bs) % ring
    blk = jnp.take_along_axis(table, entry, axis=1).reshape(-1)
    off = (p % bs).reshape(-1)
    if valid is not None:
        rv = jnp.arange(s)[None, :] < valid[:, None]
        if ring is not None:
            newest = (pos + valid - 1) // bs
            rv = rv & (p // bs > newest[:, None] - ring)
        blk = jnp.where(rv.reshape(-1), blk, 0)
        off = jnp.where(rv.reshape(-1), off, 0)
    out = {}
    for name, rows in (("k", k), ("v", v)):
        q8, sc = _q8(rows.reshape((b * s,) + rows.shape[2:])
                     .astype(jnp.float32))
        out[name] = store[name].at[blk, off].set(q8.astype(jnp.int8))
        out[name + "_scale"] = store[name + "_scale"].at[blk, off].set(sc)
    return out


# case -> (rows a call, positions, valid, ring entries)
WRITES = {
    "decode_step": (1, [0, 5, 11, 16], None, None),
    "prefill_off_boundary": (11, [3, 0, 6, 1], None, None),
    "valid_caps_rows": (7, [2, 9, 4, 13], [3, 0, 7, 1], None),
    "all_rows_to_scratch": (5, [0, 4, 8, 12], [0, 0, 0, 0], None),
    "ring_shorter_than_call": (19, [0, 2, 5, 1], [19, 9, 14, 0], 3),
    "ring_decode_step": (1, [13, 4, 22, 9], [1, 1, 0, 1], 3),
}


@pytest.mark.parametrize("case", list(WRITES))
def test_store_holds_what_the_old_layout_held(case):
    """Two rounds of writes (the second over a store that already holds
    rows, so an untouched live row is one that must survive) through the
    new arrays and through a ``[n_blocks, bs, H]`` reference: int8 rows and
    scales equal element for element in every block but scratch, whose
    content is whichever duplicate the scatter kept."""
    s, pos, valid, ring = WRITES[case]
    b, hk, d, bs = 4, 2, 8, 4
    width = ring if ring is not None else 8
    n_blocks = b * width + 1
    rng = np.random.default_rng(11)
    table = jnp.asarray(1 + rng.permutation(b * width).reshape(b, width),
                        jnp.int32)
    new = _empty(n_blocks, bs, hk, d)
    old = dict(new, k_scale=jnp.zeros((n_blocks, bs, hk), jnp.float32),
               v_scale=jnp.zeros((n_blocks, bs, hk), jnp.float32))
    extra = {} if ring is None else {"window": 2 * bs}
    pos = jnp.asarray(pos, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid, jnp.int32)
    for round_, shift in enumerate((0, 2)):
        k, v = (jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
                for _ in range(2))
        cache = dict(new, table=table, **extra)
        if valid is not None:
            cache["valid"] = valid
        new = paged_write_kv(cache, k, v, pos + shift)
        old = _old_layout_write(old, table, k, v, pos + shift, valid, ring)
    assert new["k_scale"].shape == paged_scale_shape(n_blocks, bs, hk)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[name])[1:],
                                      np.asarray(old[name])[1:])
        got = unfold_block_scales(new[name + "_scale"], bs, hk)
        np.testing.assert_array_equal(np.asarray(got)[1:],
                                      np.asarray(old[name + "_scale"])[1:])
        # and the pad beside the scales stays what it was made as
        np.testing.assert_array_equal(
            np.asarray(new[name + "_scale"])[:, :, bs * hk:], 0.0)


@pytest.mark.parametrize("case", ["valid", "scratch_duplicates", "ring"])
def test_writes_that_must_not_land_leave_live_scales_alone(case):
    """A store whose every live row holds a known scale; then a call whose
    rows must all be dropped: past ``valid``, from slots whose table is all
    scratch, or blocks a ring has no entry left for. Not one live scale
    moves (nor an int8 row)."""
    b, hk, d, bs, width = 3, 2, 8, 4, 3
    n_blocks = b * width + 1
    rng = np.random.default_rng(2)
    table = jnp.asarray(1 + np.arange(b * width).reshape(b, width), jnp.int32)
    base = _empty(n_blocks, bs, hk, d)
    k, v = (jnp.asarray(rng.standard_normal((b, width * bs, hk, d)),
                        jnp.float32) for _ in range(2))
    full = paged_write_kv(dict(base, table=table), k, v,
                          jnp.zeros((b,), jnp.int32))
    k2, v2 = (100.0 * x for x in (k, v))          # scales that would show
    if case == "valid":
        cache = dict(full, table=table, valid=jnp.zeros((b,), jnp.int32))
        after = paged_write_kv(cache, k2[:, :6], v2[:, :6],
                               jnp.asarray([1, 5, 6], jnp.int32))
    elif case == "scratch_duplicates":
        cache = dict(full, table=jnp.zeros_like(table))
        after = paged_write_kv(cache, k2[:, :1], v2[:, :1],
                               jnp.asarray([0, 3, 7], jnp.int32))
    else:
        # 20 rows over a ring of 3 blocks of 4: of each sequence's valid
        # rows only the last ring's worth may land, and none are valid
        cache = dict(full, table=table, window=2 * bs,
                     valid=jnp.zeros((b,), jnp.int32))
        rows = jnp.concatenate([k2, k2], axis=1)[:, :20]
        after = paged_write_kv(cache, rows, rows,
                               jnp.asarray([0, 2, 7], jnp.int32))
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(after[name])[1:],
                                      np.asarray(full[name])[1:])


@pytest.mark.parametrize("n_rows", [5, 300], ids=["one_program", "two_padded"])
def test_write_scale_rows_patches_column_runs_in_place(n_rows):
    """The kernel alone: each listed row takes the fresh values in its run
    of columns and keeps the rest, every other row is untouched; 300 rows
    of 128 columns are two programs, the second padded with scratch."""
    from chainermn_tpu.parallel.paged_kernel import write_scale_rows

    n, w = 400, 128
    rng = np.random.default_rng(3)
    ks, vs = (jnp.asarray(rng.standard_normal((n, 1, w)), jnp.float32)
              for _ in range(2))
    blocks = 1 + rng.permutation(n - 1)[:n_rows]
    lo = rng.integers(0, w, n_rows)
    hi = np.minimum(lo + rng.integers(0, w, n_rows), w)   # some runs empty
    fk, fv = (jnp.asarray(rng.standard_normal((n_rows, 1, w)), jnp.float32)
              for _ in range(2))
    got_k, got_v = write_scale_rows(ks, vs, jnp.asarray(blocks),
                                    jnp.asarray(lo), jnp.asarray(hi), fk, fv)
    col = np.arange(w)[None, None, :]
    keep = (col >= lo[:, None, None]) & (col < hi[:, None, None])
    for got, old, fresh in ((got_k, ks, fk), (got_v, vs, fv)):
        want = np.asarray(old).copy()
        want[blocks] = np.where(keep, np.asarray(fresh), want[blocks])
        np.testing.assert_array_equal(np.asarray(got), want)
