"""Fused Pallas paged-attention decode kernel (ROADMAP item 5).

The XLA paged decode path (:func:`chainermn_tpu.parallel.sequence.
paged_update_cache_and_attend`) reads the shared block store through a
``jnp.take`` gather that materializes each row's FULL table span as a
dense ``[B, max_blocks*bs, H, D]`` view — in f32 when the store is int8,
so the ``kv_quant`` bandwidth win (PERF.md "KV memory model") is thrown
away at read time, and rows past each sequence's length are streamed
just to be masked. This kernel fuses the whole read path per batch row:

- **block-table gather in the index map**: the ``[B, max_blocks]`` table
  and the per-row ``lengths`` ride as scalar-prefetch operands
  (``PrefetchScalarGridSpec``), so the K/V streaming index maps resolve
  ``table[b, j]`` on the fly — blocks are DMA'd straight from the store,
  and the dense per-sequence view never exists;
- **clamp-skip past ``lengths``** (the paged analog of the flash
  kernels' causal DMA clamp, PERF.md "Causal DMA clamp + block-1024
  ceiling"): grid steps past ``ceil(lengths[b]/bs)`` alias the row's
  last active block in the index map — Mosaic's pipeline elides the
  repeat copy — and skip their compute via ``pl.when``, so a row streams
  only the blocks it actually occupies;
- **one DMA per live block, all heads**: heads fold into the row
  dimension (free contiguous reshapes — ``q`` as ``[B, S*H, D]``, store
  blocks as ``[bs*H, D]`` tiles) and each ``(b, j)`` grid cell computes
  one dense all-head-pairs score tile with a head-match mask. Mosaic's
  tiling rules force this shape anyway (single-head ``(..., 1, D)``
  blocks and strided middle-dim slices are both unloadable), and it is
  the right read schedule: a store block's bytes move once per decode
  step, not once per head;
- **in-register int8 dequant**: the per-row-per-head scales
  ``[bs, H]`` tiles fold into the score/output contractions
  (``s *= k_scale[t]`` after the QK dot; ``p *= v_scale[t]`` before the
  PV dot) — bytes moved stay int8 + the tiny f32 scale vectors;
- **position-masked online softmax**: the flash (m, l, acc) recurrence
  in f32 VMEM scratch across the block sweep, flushed once at the last
  grid step — exactly :func:`_fwd_kernel`'s structure with the k-chunk
  axis replaced by table-indexed store blocks.

Shapes are the serving decode family: ``S = 1`` (per-token decode), the
``decode_window`` fori_loop body, and the speculative verify window
(``S = k+1``); ``lengths = pos + S`` per row. The ``valid`` scratch
redirect affects only WRITES (handled XLA-side before the kernel runs);
the attention itself is position-masked identically to
:func:`cached_attention`. Off TPU the kernel runs in Pallas interpret
mode (the same code path CPU tier-1 tests pin); real-hardware evidence
lands per PERF.md's chip-free AOT discipline
(``scripts/aot_paged_kernel.py``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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
    capability."""
    if os.environ.get("CHAINERMN_TPU_NO_PAGED_KERNEL"):
        return False, "disabled by CHAINERMN_TPU_NO_PAGED_KERNEL"
    try:  # pragma: no cover - import failure is environment-specific
        from jax.experimental.pallas import tpu as _  # noqa: F401
    except Exception as exc:  # pragma: no cover
        return False, f"pallas unavailable: {type(exc).__name__}: {exc}"
    return True, ""


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                   scale: float, bs: int, n_j: int, n_heads: int,
                   quant: bool):
    """Grid ``(batch row b, table slot j)``, j INNERMOST: the
    online-softmax state (m, l, acc) lives in f32 VMEM scratch across the
    row's block sweep and the output block flushes once at the last slot.
    ``k_ref``/``v_ref`` blocks arrive via the table-indexed clamped maps
    (:func:`_store_map`), so slot j past the row's active block count
    re-delivers the last active block — its compute is skipped below, so
    values are unchanged and Mosaic elides the repeat DMA.

    Heads are NOT a grid axis, and they are not sliced in-kernel either:
    the caller flattens them into the row dimension (``q`` arrives as
    ``[1, S*H, D]`` blocks with row ``t*H + h``; K/V store blocks as
    ``[1, bs*H, D]``), so every operation here touches full 2D tiles —
    Mosaic's tiling rules reject both single-head ``(..., 1, D)`` blocks
    and strided middle-dim ref slices. One dense ``(S·H, bs·H)`` score
    tile per block covers all head pairs; the cross-head entries
    (``row % H != col % H``) are masked to the sentinel and zeroed in
    ``p`` exactly like dead positions, so they add exact +0.0 terms to
    the contractions. That spends H× the MXU work of a per-head sweep —
    expected to be free: decode attention reads each KV byte once for a
    few FLOPs (kernel time on the chip: not measured), and this shape is
    what buys one DMA per live block for ALL heads."""
    if quant:
        ks_ref, vs_ref, o_ref, m_acc, l_acc, o_acc = rest
    else:
        o_ref, m_acc, l_acc, o_acc = rest
    sh = q_ref.shape[1]                                    # S * H
    kvh = k_ref.shape[1]                                   # bs * H
    s_len = sh // n_heads
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _NEG_BIG)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    def compute():
        q = q_ref[0]                                       # [S*H, D]
        kb = k_ref[0]                                      # [bs*H, D]
        vb = v_ref[0]
        m = m_acc[:, 0]
        l = l_acc[:, 0]
        if quant:
            # int8 rows hit the MXU through an in-register cast; the
            # dequant SCALES fold into the contractions instead of
            # scaling the tiles (same math, fewer multiplies, and the
            # f32 dense view never exists anywhere). q rides along to
            # f32 (exact): Mosaic's matmul wants matching operand types
            # and XLA's mixed-dtype dot promotes to f32 anyway.
            kb = kb.astype(jnp.float32)
            q = q.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(q, kb),
        ) * scale
        if quant:
            s = s * ks_ref[0]                              # [1, bs*H]
        # row i is (token t = i // H, head i % H) at global position
        # lengths-S+t; col c is (store row c // H, head c % H) at
        # position j*bs + c//H — keep causal AND same-head entries
        ri = jax.lax.broadcasted_iota(jnp.int32, (sh, kvh), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (sh, kvh), 1)
        q_pos = (length - s_len) + ri // n_heads
        k_pos = j * bs + ci // n_heads
        keep = (k_pos <= q_pos) & (ri % n_heads == ci % n_heads)
        s = jnp.where(keep, s, _NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        # explicit zero for masked entries (see _fwd_kernel: a fully-
        # masked row within a visited block would otherwise accumulate
        # mean-of-V garbage through exp(sentinel - sentinel) == 1);
        # here the zeroing also erases the cross-head columns
        p = jnp.where(s <= _NEG_BIG / 2, 0.0, jnp.exp(s - m_new[:, None]))
        l_new = l * corr + jnp.sum(p, axis=-1)
        if quant:
            p = p * vs_ref[0]
        # the PV product runs f32·f32 with V upcast IN-REGISTER —
        # matching cached_attention's `p @ v.astype(f32)` numerics, NOT
        # the flash kernels' storage-dtype MXU trick: greedy decode
        # argmax-ties against the XLA paged path (the token-parity
        # acceptance bar) are far tighter than a bf16 probability
        # matrix's ~0.4% rounding. Streamed bytes are unaffected (the
        # cast happens after the DMA).
        pv = jax.lax.dot_general(
            p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(p),
        )
        m_acc[...] = jnp.broadcast_to(m_new[:, None], m_acc.shape)
        l_acc[...] = jnp.broadcast_to(l_new[:, None], l_acc.shape)
        o_acc[...] = o_acc[...] * corr[:, None] + pv

    # blocks wholly past the row's length never contribute — skip the
    # math (their DMA is already aliased away by the clamped map)
    pl.when(j * bs < length)(compute)

    @pl.when(j == n_j - 1)
    def _flush():
        l = l_acc[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (o_acc[...] / l_safe[:, None]).astype(o_ref.dtype)


def _store_map(bs: int):
    """Streaming-side index map for the K/V store (and its scale
    arrays): slot j of row b maps to store block ``table[b, j]``, and
    slots past the row's last active block alias that block — the paged
    analog of :func:`_kv_clamped_map`'s causal DMA clamp, driven by the
    scalar-prefetched per-row ``lengths`` instead of a static delta."""
    def kv_map(b, j, table_ref, len_ref):
        n_active = (len_ref[b] + bs - 1) // bs
        jc = jnp.minimum(j, jnp.maximum(n_active - 1, 0))
        return (table_ref[b, jc], 0, 0)

    return kv_map


def paged_attend(q, store_k, store_v, table, lengths, *,
                 k_scale=None, v_scale=None, scale: Optional[float] = None,
                 max_blocks: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Paged-attention decode over the shared block store, fused.

    - ``q``: ``[B, S, H, D]`` queries for global positions
      ``lengths[b]-S .. lengths[b]-1`` of each row (``S`` is 1 for
      per-token decode, ``k+1`` for the speculative verify window);
    - ``store_k``/``store_v``: ``[n_blocks, bs, H, D]`` — the shared
      store, already holding this step's writes (the scatter stays XLA:
      it moves ``S`` rows; the kernel owns the O(length) read side);
    - ``table``: ``[B, max_blocks]`` int32 block table;
    - ``lengths``: ``[B]`` int32 — valid KV rows per row AFTER the
      write (``pos + S``). Blocks past ``ceil(lengths[b]/bs)`` are
      clamp-skipped: neither streamed nor computed;
    - ``k_scale``/``v_scale``: ``[n_blocks, bs, H]`` f32, present iff
      the store is int8 (dequant folds into the contractions);
    - ``max_blocks``: optional static cap on table slots to sweep
      (callers with static positions pass the batch-max active count —
      the grid then never visits provably-dead table tail entries).

    Returns ``[B, S, H, D]`` in ``q.dtype`` — position-masked exactly
    like :func:`~chainermn_tpu.parallel.sequence.cached_attention` over
    the gathered table span, to fp tolerance (same masked set, flash
    summation order). Off TPU runs in interpret mode by default."""
    b, s_len, h, d = q.shape
    bs = store_k.shape[1]
    n_j = table.shape[1]
    if max_blocks is not None:
        n_j = max(1, min(n_j, int(max_blocks)))
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = kernels_interpreted()
    quant = k_scale is not None
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    kv_map = _store_map(bs)
    # heads fold into the ROW dimension (free contiguous reshapes) so
    # every block is a full 2D tile: Mosaic's tiling rules reject both
    # single-head (..., 1, D) blocks and strided middle-dim slices, and
    # the flat shape is the better schedule anyway — one DMA per live
    # block for ALL heads. Scales flatten to [n_blocks, 1, bs*H] row
    # vectors for the same reason.
    n_blocks = store_k.shape[0]
    qf = q.reshape(b, s_len * h, d)
    kf = store_k.reshape(n_blocks, bs * h, d)
    vf = store_v.reshape(n_blocks, bs * h, d)
    qo_map = lambda b_, j_, table_ref, len_ref: (b_, 0, 0)
    in_specs = [
        pl.BlockSpec((1, s_len * h, d), qo_map),
        pl.BlockSpec((1, bs * h, d), kv_map),
        pl.BlockSpec((1, bs * h, d), kv_map),
    ]
    operands = [qf, kf, vf]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, bs * h), kv_map),
                     pl.BlockSpec((1, 1, bs * h), kv_map)]
        operands += [k_scale.reshape(n_blocks, 1, bs * h),
                     v_scale.reshape(n_blocks, 1, bs * h)]
    vma = _out_vma(q, store_k, store_v, table, lengths)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=bs, n_j=n_j,
                          n_heads=h, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_j),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, s_len * h, d), qo_map),
            scratch_shapes=[
                pltpu.VMEM((s_len * h, _LANE), jnp.float32),  # running max m
                pltpu.VMEM((s_len * h, _LANE), jnp.float32),  # running l
                pltpu.VMEM((s_len * h, d), jnp.float32),      # unnorm. acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s_len * h, d), q.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, lengths, *operands)
    return out.reshape(b, s_len, h, d)


def bytes_read_model(lengths, *, block_size: int, max_blocks: int,
                     n_heads: int, head_dim: int, n_layers: int = 1,
                     kv_quant: str = "none") -> dict:
    """Per-decode-step KV bytes-READ model (PERF.md "Paged-decode
    kernel"): what one step's attention streams from the store, XLA
    gather path vs fused kernel, summed over rows and layers.

    The XLA path gathers every row's full ``max_blocks`` table span and
    — when int8 — materializes the dequantized f32 dense view (counted
    as its write + read back through the attention contractions). The
    kernel streams ``ceil(len/bs)`` blocks per row in storage dtype and
    never builds the view. Host-side arithmetic on host values: this is
    the cost MODEL the bench record carries next to measured tokens/s,
    not a measurement."""
    lengths = np.asarray(lengths, np.int64)
    row_elems = n_heads * head_dim
    esize = 1 if kv_quant == "int8" else 4
    kv_rows_xla = int(lengths.size) * max_blocks * block_size
    kv_rows_kern = int(
        np.sum(-(-np.maximum(lengths, 0) // block_size)) * block_size)
    per_row_scale = n_heads * 4 if kv_quant == "int8" else 0
    # k + v, per layer
    xla = 2 * kv_rows_xla * (row_elems * esize + per_row_scale)
    kern = 2 * kv_rows_kern * (row_elems * esize + per_row_scale)
    if kv_quant == "int8":
        # the f32 dense view: written once, read back by the einsums
        xla += 2 * 2 * kv_rows_xla * row_elems * 4
    return {
        "xla_bytes": int(xla * n_layers),
        "kernel_bytes": int(kern * n_layers),
        "read_amplification": round(xla / max(kern, 1), 3),
    }


__all__ = ["bytes_read_model", "kernel_supported", "paged_attend"]
