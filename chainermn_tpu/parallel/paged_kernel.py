"""Fused Pallas paged-attention decode kernel (ROADMAP Speed 1).

The XLA paged decode path (:func:`chainermn_tpu.parallel.sequence.
paged_update_cache_and_attend`) reads the shared block store through a
``jnp.take`` gather that materializes each row's FULL table span as a
dense ``[B, max_blocks*bs, H, D]`` view, and streams rows past each
sequence's length just to mask them. This kernel fuses the whole read
path, one program per batch row (slot):

- **the sweep**: the ``[B, max_blocks]`` table and the per-row ``lengths``
  ride as scalar-prefetch operands; the store and its scale arrays stay in
  HBM, and the program walks its slot's live blocks in chunks of C table
  entries, starting one copy per block (K rows, V rows and, for an int8
  store, the two scale rows) from ``store[table[b, j]]`` into one of two
  VMEM buffers and computing on one chunk while the next is on its way.
  The dense per-sequence view never exists. The scale arrays are held as
  ``[n_blocks, 1, W]``: a block's ``bs * H`` scales in one row of whole
  lanes, in the kernel's own column order, an array the chip tiles row by
  row. They reach the kernel untouched, a block's scales are one contiguous
  copy, and :func:`write_scale_rows` writes them in the same shape, so a
  decode program holds no operation on a whole scale array (as
  ``[n_blocks, bs, H]`` each was relaid three times a layer, 42% of a
  step's device time);
- **a trip count from ``lengths``**: the walk takes
  ``cdiv(cdiv(lengths[b], bs), C)`` steps, so a dead table tail costs
  nothing; in the last chunk the entries past the last live block copy
  that block again and the position mask drops them, so a dead entry is
  never looked through, whatever it points at;
- **C from the shapes** (:func:`chunk_blocks`): the largest power of two
  whose two buffers fit a fixed VMEM budget — 8 blocks, 128 tokens, at 16
  heads of 128 in int8;
- **all heads at once**: heads fold into the row dimension (free
  contiguous reshapes — ``q`` as ``[B, S*H, D]``, store blocks as
  ``[bs*H, D]`` tiles) and a chunk's scores are one ``[C, S·H, bs·H]``
  tile over all head pairs with a head-match mask. Mosaic's tiling rules
  force this shape (single-head ``(..., 1, D)`` blocks and strided
  middle-dim slices are both unloadable); a block's bytes move once per
  decode step, not once per head, and the MXU does ``H`` times the useful
  work for it. Heads narrower than the 128 lanes share a store row, so the
  kernel copies whole lanes at any head size that divides 128;
- **operands no wider than exact**: int8 rows are exact in the query's
  type and ride the MXU in it (bf16 x bf16 with f32 accumulation in a
  served model); the per-row-per-head scales fold into the contractions
  (``s *= k_scale[t]`` after the QK product; ``p *= v_scale[t]`` before
  the PV product), and P, the one f32 operand, goes in as two or three
  bf16 terms against the exact V (:func:`_pv`). A float32 store keeps
  float32 operands at ``HIGHEST``;
- **position-masked online softmax**: the flash (m, l, acc) recurrence in
  f32 across the walk, written out once at its end.

Shapes are the serving decode family: ``S = 1`` (per-token decode), the
``decode_window`` fori_loop body, and the speculative verify window
(``S = k+1``); ``lengths = pos + S`` per row. The ``valid`` scratch
redirect affects only WRITES (handled XLA-side before the kernel runs);
the attention itself is position-masked identically to
:func:`cached_attention`. One write is a kernel's own: the scales of the
rows a call stores (:func:`write_scale_rows`), because XLA writes a row of
such an array only after relaying all of it. Off TPU the kernels run in
Pallas interpret mode (the same code path CPU tier-1 tests pin);
``scripts/aot_paged_kernel.py`` lowers it for the chip without one, and
PERF.md §5 and §6 hold what it takes on the chip. A model's layers call
:func:`paged_attend` with one set of shapes, and the kernel is traced and
lowered once for all of them (:func:`_attend`): tracing it a layer at a
time cost a served model's warm-up more than compiling it did.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import (
    _LANE,
    _NEG_BIG,
    _out_vma,
    _prec,
    kernels_interpreted,
)


def kernel_supported() -> tuple[bool, str]:
    """Cheap host-side availability probe for the fused kernel path.

    ``(True, "")`` when the Pallas TPU frontend imports and the kernel is
    not explicitly disabled; ``(False, reason)`` otherwise. Engines built
    with ``paged_kernel=True`` call this once at construction and fall
    back to the XLA path (emitting the ``paged_kernel_fallback`` event)
    instead of failing warmup — the kernel is an optimization, never a
    capability. (It is the READ that falls back: an int8 store's scales are
    written by :func:`write_scale_rows` whatever this says, interpreted
    off the chip like every kernel here.)"""
    if os.environ.get("CHAINERMN_TPU_NO_PAGED_KERNEL"):
        return False, "disabled by CHAINERMN_TPU_NO_PAGED_KERNEL"
    try:  # pragma: no cover - import failure is environment-specific
        from jax.experimental.pallas import tpu as _  # noqa: F401
    except Exception as exc:  # pragma: no cover
        return False, f"pallas unavailable: {type(exc).__name__}: {exc}"
    return True, ""


# What the two chunk buffers of K, V and (int8) their scale rows may take
# of VMEM, as Mosaic lays them out. The chip's scoped default is 16 MiB,
# which the score tile, the query, the output and the compiler's own
# temporaries share; at the served widths (16 heads of 128, blocks of 16
# tokens, int8) this gives chunks of 8 blocks, 128 tokens.
_VMEM_BUDGET = 2 * 2 ** 20


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a ``[rows, cols]`` array in Mosaic's tiling: 128
    lanes by 8, 16 or 32 sublanes for 4-, 2- and 1-byte elements."""
    size = jnp.dtype(dtype).itemsize
    sub = 32 // size
    return -(-rows // sub) * sub * -(-cols // _LANE) * _LANE * size


def chunk_blocks(bs: int, n_heads: int, head_dim: int, dtype, quant: bool,
                 n_j: int) -> int:
    """Store blocks per chunk of the sweep: the largest power of two whose
    two buffers of K and V rows (and scale rows, for an int8 store) fit
    :data:`_VMEM_BUDGET`, at least 1 and at most the ``n_j`` entries of a
    slot's table row. Read from the operands' shapes, never from a user."""
    block = 2 * _tile_bytes(bs * n_heads, head_dim, dtype)
    if quant:
        block += 2 * _tile_bytes(1, bs * n_heads, jnp.float32)
    c = 1
    while 2 * c <= n_j and 2 * (2 * c) * block <= _VMEM_BUDGET:
        c *= 2
    return c


def _pv(p, vb, out_dtype):
    """``P·V`` over a chunk, ``[C, S·H, bs·H] x [C, bs·H, D]``, summed
    over the chunk's blocks. A float32 store keeps float32 operands at
    ``HIGHEST``. int8 and bfloat16 rows are exact in bfloat16, so there P,
    the one float32 operand, is split into bfloat16 terms of 8 mantissa
    bits, each riding the MXU's native mode: three for a float32 result
    (24 bits: float32 again, half the passes of a float32 product), two
    where the result is rounded to the model's bfloat16 anyway (16 bits
    against its 8)."""
    dims = (((2,), (1,)), ((0,), (0,)))
    if vb.dtype == jnp.float32:
        pv = jax.lax.dot_general(p, vb, dims,
                                 preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.HIGHEST)
        return jnp.sum(pv, axis=0)
    vb = vb.astype(jnp.bfloat16)
    pv = None
    for _ in range(3 if out_dtype == jnp.float32 else 2):
        hi = p.astype(jnp.bfloat16)
        term = jax.lax.dot_general(hi, vb, dims,
                                   preferred_element_type=jnp.float32)
        pv = term if pv is None else pv + term
        p = p - hi.astype(jnp.float32)
    return jnp.sum(pv, axis=0)


def _sweep_kernel(table_ref, len_ref, *rest,
                  scale: float, bs: int, n_j: int, n_heads: int,
                  pack: int, chunk: int, quant: bool, group: int = 1,
                  windowed: bool = False):
    """One program per slot ``b``: it walks the slot's live blocks in
    chunks of ``chunk`` table entries, copying each chunk's blocks from the
    store (left in HBM) into one of two VMEM buffers while it computes on
    the other, and keeps the online-softmax state (m, l, acc) in float32
    across the walk. The loop runs ``cdiv(cdiv(lengths[b], bs), chunk)``
    times: a dead table tail costs nothing, and a slot of length 0 writes
    zeros. In the last chunk the entries past the last live block copy
    that block again, so a buffer never holds anything but live blocks of
    its slot — whatever a dead table entry points at (NaN rows, infinite
    scales) is never read — and the position mask drops the repeats.

    The copies run one chunk ahead of the arithmetic ACROSS slots: while a
    slot's last chunk is computed on, the first chunk of the next slot
    that has one is already on its way (the programs run in order, and
    ``par`` carries the buffer's parity from one to the next), so only
    the call's very first chunk is waited for with nothing to do.

    Heads are not a grid axis and are not sliced: they fold into the row
    dimension (``q`` as ``[S*H, D]`` with row ``t*H + h``, a store block as
    ``[bs*H, D]``), since Mosaic loads neither single-head ``(..., 1, D)``
    blocks nor strided middle-dimension slices. A chunk's scores are one
    ``[C, S·H, bs·H]`` tile over all head pairs, of which the head mask
    keeps one in ``H``: the MXU does ``H`` times the useful work, the price
    of moving a block's bytes once for all heads. Where a head is narrower
    than the 128 lanes, ``pack`` of them share a store row (see
    :func:`paged_attend`) and a column stands for ``pack`` heads.

    ``group`` query heads read one KV head (``n_heads`` counts the query's;
    the store holds ``n_heads // group``): the rows of a group multiply the
    same K columns, and the head mask keeps one column head in
    ``n_heads // group``. ``windowed`` adds a scalar-prefetch operand,
    ``first`` (a slot's first visible position), and the table row becomes
    a ring: the walk starts at block ``first // bs``, block ``j`` sits in
    entry ``j % n_j``, and positions before ``first`` are masked like those
    after the query."""
    if windowed:
        first_ref, *rest = rest
    q_ref, k_hbm, v_hbm, *rest = rest
    if quant:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, par = rest
    else:
        o_ref, k_buf, v_buf, sem, par = rest
    sh, d = q_ref.shape[1], q_ref.shape[2]                 # S * H, D
    kvh = k_buf.shape[2]                                   # bs * H / pack
    s_len, hp = sh // n_heads, n_heads // group // pack
    b = pl.program_id(0)
    nxt = jnp.minimum(b + 1, pl.num_programs(0) - 1)
    length = len_ref[b]

    def first_block(slot_b):
        return first_ref[slot_b] // bs if windowed else 0

    def live_blocks(slot_b):
        return jnp.minimum(
            pl.cdiv(len_ref[slot_b], bs) - first_block(slot_b), n_j)

    n_chunks = pl.cdiv(live_blocks(b), chunk)
    # the slot after this one has a first chunk to send for
    has_next = (b + 1 < pl.num_programs(0)) & (live_blocks(nxt) > 0)

    pairs = [(k_hbm, k_buf), (v_hbm, v_buf)]
    if quant:
        pairs += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]

    def copies(slot_b, i, buf, act):
        """``act`` (start or wait) on every copy of chunk ``i`` of slot
        ``slot_b`` into buffer ``buf``: per block its K rows, its V rows
        and, for an int8 store, its two scale rows."""
        last = live_blocks(slot_b) - 1

        def block(c, carry):
            entry = jnp.minimum(i * chunk + c, last)
            if windowed:
                entry = jax.lax.rem(first_block(slot_b) + entry, n_j)
            blk = table_ref[slot_b, entry]
            for src, dst in pairs:
                act(pltpu.make_async_copy(src.at[blk], dst.at[buf, c],
                                          sem.at[buf]))
            return carry

        jax.lax.fori_loop(0, chunk, block, None)

    def start(slot_b, i, buf):
        copies(slot_b, i, buf, lambda cp: cp.start())

    @pl.when(b == 0)
    def _first():
        par[0] = 0
        pl.when(n_chunks > 0)(lambda: start(b, 0, 0))

    first = par[0]                  # the buffer this slot's chunk 0 is in

    def sweep():
        # row i of the query is (token i // H, head i % H), the token at
        # global position lengths-S + i//H; column c of block j in the
        # chunk is (store row c // hp, heads c % hp * pack and the
        # pack - 1 after it, hp = H / pack of them a token): keep causal
        # entries whose column holds the row's head
        ri = jax.lax.broadcasted_iota(jnp.int32, (1, sh, 1), 1)
        ci = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kvh), 2)
        ji = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
        same_head = ri % n_heads // (group * pack) == ci % hp
        k_tok = ji * bs + ci // hp                         # [C, 1, kvh]
        if windowed:
            k_tok = k_tok + first_block(b) * bs
        q_tok = (length - s_len) + ri // n_heads           # [1, S*H, 1]

        def scales(ref, buf):
            """The scale of each column for each row's own head: one row
            of the block's ``pack``, ``[C, 1 or S*H, kvh]``."""
            out = ref[buf, :, 0:1, :kvh]
            for u in range(1, pack):
                out = jnp.where(ri % pack == u, ref[buf, :, u:u + 1, :kvh],
                                out)
            return out

        q = q_ref[0]
        # int8 rows are exact in the query's type (the model's bf16, or
        # the tests' f32) and ride the MXU in it, as the XLA read path's
        # ``k8.astype(q.dtype)`` does; the scales fold in after the product
        ctype = jnp.promote_types(q.dtype, k_buf.dtype)
        qc = jnp.broadcast_to(q.astype(ctype)[None], (chunk, sh, d))

        def body(i, carry):
            m, l, acc = carry
            buf = jax.lax.rem(first + i, 2)
            more = i + 1 < n_chunks
            # send for what is computed on next: this slot's next
            # chunk, or after its last one the next slot's first
            @pl.when(more | has_next)
            def _ahead():
                start(jnp.where(more, b, nxt), jnp.where(more, i + 1, 0),
                      1 - buf)

            copies(b, i, buf, lambda cp: cp.wait())
            s = jax.lax.dot_general(
                qc, k_buf[buf].astype(ctype),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32, precision=_prec(qc),
            )                                              # [C, S*H, bs*H]
            # the softmax scale rides on the scale row where there is
            # one: a row, not the tile's S*H
            s = s * (scales(ks_buf, buf) * scale if quant else scale)
            k_pos = i * (chunk * bs) + k_tok
            keep = same_head & (k_pos <= q_tok)
            if windowed:
                keep = keep & (k_pos >= first_ref[b])
            s = jnp.where(keep, s, _NEG_BIG)
            m_new = jnp.maximum(
                m, jnp.max(jnp.max(s, axis=0), axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            # an explicit zero where masked: a row with nothing to see
            # yet has s == m_new == the sentinel, and exp(0) would count
            p = jnp.where(keep, jnp.exp(s - m_new[None]), 0.0)
            l_new = l * corr + jnp.sum(jnp.sum(p, axis=0), axis=-1,
                                       keepdims=True)
            if quant:
                p = p * scales(vs_buf, buf)
            return m_new, l_new, acc * corr + _pv(p, v_buf[buf], o_ref.dtype)

        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((sh, 1), _NEG_BIG, jnp.float32),
             jnp.zeros((sh, 1), jnp.float32),
             jnp.zeros((sh, d), jnp.float32)))
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        par[0] = jax.lax.rem(first + n_chunks, 2)

    @pl.when(n_chunks == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)
        pl.when(has_next)(lambda: start(nxt, 0, first))

    pl.when(n_chunks > 0)(sweep)


def paged_attend(q, store_k, store_v, table, lengths, *,
                 k_scale=None, v_scale=None, scale: Optional[float] = None,
                 max_blocks: Optional[int] = None, first=None,
                 kv_heads: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Paged-attention decode over the shared block store, fused.

    - ``q``: ``[B, S, H, D]`` queries for global positions
      ``lengths[b]-S .. lengths[b]-1`` of each row (``S`` is 1 for
      per-token decode, ``k+1`` for the speculative verify window);
    - ``store_k``/``store_v``: ``[n_blocks, bs, Hkv, D]`` — the shared
      store, already holding this step's writes (the scatter stays XLA:
      it moves ``S`` rows; the kernel owns the O(length) read side).
      ``Hkv`` divides ``H``: query head ``g`` reads KV head
      ``g // (H // Hkv)``. A store held folded, ``[n_blocks, bs * Hkv, D]``
      (:func:`~chainermn_tpu.parallel.sequence.paged_store_shape`: an int8
      store of fewer than 4 heads), is the kernel's own view of a block and
      goes in as it is; ``kv_heads`` then says ``Hkv``;
    - ``table``: ``[B, max_blocks]`` int32 block table;
    - ``lengths``: ``[B]`` int32 — valid KV rows per row AFTER the
      write (``pos + S``). Only the ``ceil(lengths[b]/bs)`` blocks they
      reach are copied or computed on; a table entry past them is never
      looked through, whatever it holds;
    - ``k_scale``/``v_scale``: ``[n_blocks, 1, W]`` f32
      (:func:`~chainermn_tpu.parallel.sequence.paged_scale_shape`), present
      iff the store is int8 (dequant folds into the contractions): a
      block's ``bs * Hkv`` scales in one row of whole lanes, row ``t`` of
      head ``h`` in column ``t * Hkv + h``, the order the kernel's columns
      have. The arrays go to the kernel as they are and it copies a block's
      row out of HBM: any other shape costs a pass over the whole array a
      call;
    - ``max_blocks``: optional static cap on the table entries a row can
      have live (callers with static positions pass the batch-max active
      count); it bounds the sweep's trip count and nothing else;
    - ``first``: ``[B]`` int32, a window layer's rows: the first position
      row ``b`` sees (positions before it are masked and their blocks not
      walked). The table row is then a ring: the block of positions
      ``[j*bs, (j+1)*bs)`` sits in entry ``j % max_blocks`` (the table's
      width), and the caller keeps ``lengths - first`` within the ring
      less a block.

    Returns ``[B, S, H, D]`` in ``q.dtype`` — position-masked exactly
    like :func:`~chainermn_tpu.parallel.sequence.cached_attention` over
    the gathered table span, to fp tolerance (same masked set, flash
    summation order). Off TPU runs in interpret mode by default."""
    n_j = table.shape[1]
    if max_blocks is not None:
        n_j = max(1, min(n_j, int(max_blocks)))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = kernels_interpreted()
    if first is not None:
        first = jnp.asarray(first, jnp.int32)
        if n_j != table.shape[1]:
            raise ValueError("a ring goes round the whole table row: "
                             "max_blocks cannot cut it")
    if store_k.ndim == 3 and not kv_heads:
        raise ValueError("a folded store needs kv_heads")
    return _attend(q, store_k, store_v, jnp.asarray(table, jnp.int32),
                   jnp.asarray(lengths, jnp.int32), k_scale, v_scale, first,
                   scale=float(scale), n_j=n_j, interpret=bool(interpret),
                   kv_heads=kv_heads if store_k.ndim == 3 else None)


@functools.partial(jax.jit, static_argnames=("scale", "n_j", "interpret",
                                             "kv_heads"), inline=True)
def _attend(q, store_k, store_v, table, lengths, k_scale, v_scale,
            first=None, *,
            scale: float, n_j: int, interpret: bool,
            kv_heads: Optional[int] = None):
    """:func:`paged_attend` with its defaults filled in. A model calls it
    once a layer with the same shapes: ``jit`` traces the kernel for the
    first and hands the others the same equations, which then also lower
    once a program; ``inline`` writes them into the caller's trace under
    the caller's own names, so no call stands between a block and its
    kernel."""
    b, s_len, h, d = q.shape
    if kv_heads is None:
        n_blocks, bs, hk = store_k.shape[:3]
    else:                       # held folded: a block is its rows already
        n_blocks, hk = store_k.shape[0], kv_heads
        bs = store_k.shape[1] // hk
    if h % hk:
        raise ValueError(f"{h} query heads do not divide over {hk} KV heads")
    group = h // hk
    quant = k_scale is not None
    windowed = first is not None

    def lanes(x):
        """``x`` zero-padded to whole tiles of 128 lanes: Mosaic copies
        nothing narrower out of HBM."""
        pad = -x.shape[-1] % _LANE
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x

    # heads fold into the ROW dimension (free contiguous reshapes) so
    # every copy and every operand is a full 2D tile; the scales are held
    # as row vectors of whole lanes, a block a row, for the same reason and
    # go in untouched. The store stays where it is and the kernel copies
    # the blocks it needs. A head narrower than the 128 lanes shares a row
    # with its neighbours (``pack`` heads a row, a free view again): the
    # query then sits in its own head's lanes of a row of zeros, the scales
    # go in as ``pack`` rows a block, and each output row is read from its
    # head's lanes. Only what no view brings to whole lanes is padded or
    # regrouped, at the price of a copy a call: nothing at the served
    # widths (heads of 128), the scales and rows of a toy head size.
    # (grouped heads keep a row each: a row shared by KV heads would mix
    # the lanes of query heads that read different ones)
    pack = (_LANE // d if group == 1 and _LANE % d == 0
            and h % (_LANE // d) == 0 else 1)
    sh, rows, dl = s_len * h, bs * hk // pack, pack * d
    qf = q.reshape(b, sh, 1, d)
    if pack > 1:
        own = (jnp.arange(sh) % pack)[:, None] == jnp.arange(pack)
        own = own[None, :, :, None].astype(q.dtype)       # [1, S*H, pack, 1]
        qf = qf * own
    operands = [lanes(qf.reshape(b, sh, dl)),
                lanes(store_k.reshape(n_blocks, rows, dl)),
                lanes(store_v.reshape(n_blocks, rows, dl))]
    dp = operands[0].shape[-1]
    # from the table's width, not from ``max_blocks``: the cap bounds the
    # trip count and leaves the chunking, so the summation order, alone
    chunk = chunk_blocks(bs, hk // pack, dp, store_k.dtype, quant,
                         table.shape[1])
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    qo_spec = pl.BlockSpec((1, sh, dp), lambda b_, *scalars: (b_, 0, 0))
    scalars = (table, lengths) + ((first,) if windowed else ())
    in_specs = [qo_spec, in_hbm, in_hbm]
    scratch = [pltpu.VMEM((2, chunk, rows, dp), store_k.dtype),
               pltpu.VMEM((2, chunk, rows, dp), store_v.dtype)]
    if quant:
        in_specs += [in_hbm, in_hbm]
        if pack > 1:
            # column t*H + h to row h % pack, column (t*H + h) // pack
            k_scale, v_scale = (
                lanes(sc[:, 0, :rows * pack].reshape(n_blocks, rows, pack)
                      .swapaxes(1, 2)) for sc in (k_scale, v_scale))
        operands += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, chunk) + operands[-1].shape[1:],
                               jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]
    vma = _out_vma(q, store_k, store_v, *scalars)
    out = pl.pallas_call(
        functools.partial(_sweep_kernel, scale=scale, bs=bs, n_j=n_j,
                          n_heads=h, pack=pack, chunk=chunk, quant=quant,
                          group=group, windowed=windowed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b,),
            in_specs=in_specs,
            out_specs=qo_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, sh, dp), q.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            # in order: a slot's last chunk sends for the next slot's
            # first (one TensorCore a chip on the v5e, so nothing is lost)
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, *operands)
    out = out[..., :dl]
    if pack > 1:
        out = jnp.sum(out.reshape(b, sh, pack, d) * own, axis=2)
    return out.reshape(b, s_len, h, d)


def _scale_rows_kernel(blk_ref, lo_ref, hi_ref, fresh_k, fresh_v, ks_in,
                       vs_in, ks_out, vs_out, k_buf, v_buf, sem):
    """One program per ``rows`` touched blocks: their scale rows come out
    of the two arrays (left in HBM; ``*_out`` are ``*_in``, aliased), the
    columns ``[lo, hi)`` of each take the fresh scales, and the rows go
    back. All of a program's copies of one direction are in flight
    together."""
    rows, _, w = fresh_k.shape
    base = pl.program_id(0) * rows
    arrays = ((ks_in, ks_out, k_buf, fresh_k), (vs_in, vs_out, v_buf, fresh_v))

    def copies(act, back: bool):
        def row(r, carry):
            blk = blk_ref[base + r]
            for src, dst, buf, _ in arrays:
                act(pltpu.make_async_copy(buf.at[r], dst.at[blk], sem.at[1])
                    if back else
                    pltpu.make_async_copy(src.at[blk], buf.at[r], sem.at[0]))
            return carry

        jax.lax.fori_loop(0, rows, row, None)

    copies(lambda cp: cp.start(), False)
    copies(lambda cp: cp.wait(), False)
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, 1, w), 2)

    def patch(r, carry):
        keep = (ci >= lo_ref[base + r]) & (ci < hi_ref[base + r])
        for _, _, buf, fresh in arrays:
            at = pl.ds(r, 1)
            buf[at] = jnp.where(keep, fresh[at], buf[at])
        return carry

    jax.lax.fori_loop(0, rows, patch, None)
    copies(lambda cp: cp.start(), True)
    copies(lambda cp: cp.wait(), True)


def write_scale_rows(k_scale, v_scale, blocks, lo, hi, fresh_k, fresh_v, *,
                     interpret: Optional[bool] = None):
    """The write side of an int8 store's scales, in place: rows
    ``blocks [R]`` of ``k_scale``/``v_scale`` (``[n_blocks, 1, W]``) take
    ``fresh_k``/``fresh_v`` (``[R, 1, W]``) in their columns
    ``[lo[r], hi[r])`` and keep what they held elsewhere. The arrays stay
    in HBM and are handed back aliased: a call moves ``2 * R`` rows each
    way and nothing else. XLA can do the same with a gather and a scatter,
    but not in this layout, which is the one the decode kernel copies a
    block's scales out of with one contiguous copy: it relays the whole
    array to write a row of it.

    A block listed twice gets one of its writers' rows (callers list only
    the scratch block more than once); ``blocks`` must lie inside the
    arrays, a copy has no bounds to drop it at."""
    if interpret is None:
        interpret = kernels_interpreted()
    return _write_rows(k_scale, v_scale,
                       *(jnp.asarray(x, jnp.int32) for x in (blocks, lo, hi)),
                       fresh_k, fresh_v, interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _write_rows(k_scale, v_scale, blocks, lo, hi, fresh_k, fresh_v, *,
                interpret: bool):
    """:func:`write_scale_rows` with its default filled in, traced once
    for all the layers of a model and lowered once a program, as
    :func:`_attend` is and for its reason."""
    r, _, w = fresh_k.shape
    # rows a program: its two buffers and two double-buffered operands,
    # each row a tile of 8 sublanes in VMEM, 6 of the scoped 16 MiB at
    # most (128 rows at the served 256 columns: a decode step's in one)
    rows = max(1, min(r, 2 ** 20 // _tile_bytes(1, w, jnp.float32)))
    pad = -r % rows
    if pad:
        # the scratch block again, no column of it
        blocks, lo, hi = (jnp.pad(x, (0, pad)) for x in (blocks, lo, hi))
        fresh_k, fresh_v = (jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
                            for x in (fresh_k, fresh_v))
    row = pl.BlockSpec((rows, 1, w), lambda i, *_: (i, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vma = _out_vma(k_scale, v_scale, blocks, lo, hi, fresh_k, fresh_v)
    return pl.pallas_call(
        _scale_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=((r + pad) // rows,),
            in_specs=[row, row, in_hbm, in_hbm],
            out_specs=[in_hbm, in_hbm],
            scratch_shapes=[pltpu.VMEM((rows, 1, w), jnp.float32)] * 2
            + [pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
                   for x in (k_scale, v_scale)],
        # operands count from the first scalar: 5 and 6 are the arrays
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blocks, lo, hi, fresh_k, fresh_v, k_scale, v_scale)


__all__ = ["kernel_supported", "paged_attend", "write_scale_rows"]
