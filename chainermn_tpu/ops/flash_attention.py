"""Pallas TPU flash attention (forward + backward kernels).

The reference has no attention ops at all (SURVEY.md S2.16: it predates
them); this kernel is the TPU-native hot-op for the long-context extension
(:mod:`chainermn_tpu.parallel.sequence`). Design per the Pallas TPU guide:

- every kernel grids over ``(batch*heads, outer-seq-block,
  reduction-chunk)`` with the reduction chunk innermost; the running state
  (online-softmax (m, l, acc) forward; dq / (dk, dv) accumulators
  backward) lives in f32 VMEM scratch across the sweep and flushes to the
  output block once at the last chunk — attention scores are never
  materialized in HBM, so memory is O(T) instead of O(T^2);
- causal masking is computed from *global* positions: ``q_offset`` /
  ``k_offset`` arrive as SMEM scalars so sequence-sharded callers (ring
  attention shards, ``pos_offset`` in the LM) can pass traced offsets;
- fully-masked (future) chunks skip their COMPUTE via ``pl.when`` — the
  standard ~2x causal FLOP saving — and, when the offsets are static
  (the plain ``flash_attention`` LM path), their DMAs too: the
  streaming-side index maps clamp masked chunks to the previous chunk's
  block index, which Mosaic's pipeline elides (see ``_static_delta``).
  Ring shards pass traced offsets, where the ring layer's block-level
  masking decides which whole blocks to visit instead;
- backward is the standard two-kernel flash backward: ``dq`` gridded over
  q-blocks and ``(dk, dv)`` gridded over k-blocks, both recomputing scores
  from the saved row logsumexp (``lse``) instead of storing P;
- contractions accumulate in f32 (``preferred_element_type``) from bf16 or
  f32 inputs.

Numerical contract: identical to
:func:`chainermn_tpu.parallel.sequence.full_attention` (tested to fp
tolerance, values and grads). Off TPU the kernels run in Pallas interpret
mode, so the same code path is unit-testable on the CPU mesh.

All three kernels grid over BOTH sequence dims with the reduction dim
innermost and f32 VMEM scratch carrying the running state (online-softmax
m/l/acc forward; dq / dk+dv accumulators backward) — per-cell VMEM is
O(block_q + block_k) regardless of T. This structure is load-bearing:
the earlier form held full-length [T, d] K/V (or q/do) blocks per grid
cell, and XLA's scoped-VMEM accounting killed fwd+bwd compilation at
T >= 16384 on v5e; chunked, the same program AOT-compiles to T = 131072
(AOT-verified round 5, 8 heads, d=64 — HBM, not VMEM, is then the
binding limit, and beyond it the ring in
:mod:`chainermn_tpu.parallel.sequence` shards T across devices).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
# Row statistics (lse, delta) are stored lane-broadcast to this width so
# their blocks satisfy Mosaic's (8, 128) tiling rule — the same layout the
# reference jax.experimental.pallas TPU flash kernel uses for l/m.
_LANE = 128


def _smem_spec():
    """Spec for the (1, 1) int32 offset scalars (SMEM on TPU; the guide's
    'scalars must be 2D in SMEM' rule)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _pick_block(t: int, preferred: int = 1024) -> int:
    """Largest hardware-legal divisor of ``t`` near ``preferred`` (kernel
    blocks must tile the sequence exactly; callers fall back to XLA
    otherwise). "Near": sub-8 requests on t > 8 round UP to the 8-row
    hardware minimum, so the result can exceed ``preferred``.

    The 1024 default is measured, not guessed: the round-5 on-chip sweep
    (the record is scripts/flash_tune.jsonl; its script, flash_tune.py, is
    in git at 28e6de9; v5e, bf16 fwd+bwd, causal) is monotonic in block
    size at both T=4096 and T=8192 — 28.3 TFLOP/s at block 1024 vs 18.0
    (512) / 6.7 (128) at T=8192.
    Per-cell fixed work (mask iota, scratch flush, grid bookkeeping)
    amortizes over more MXU work, and VMEM per cell stays O(block) —
    ~3 MB at block 1024, d=64, far under the ~128 MB budget. The
    T = 131072 single-call ceiling is AOT-verified at every block in
    {128, 256, 512, 1024} with the post-round-5 kernels (clamped causal
    maps, storage-dtype MXU inputs): 3.25 GB peak at each
    (scripts/aot_flash_ceiling.jsonl).

    Blocks respect the 8-row sublane granularity (Mosaic's (8, 128)
    tiling rule): candidates step down in multiples of 8, and a length
    with no such divisor returns 1, which is below every caller's
    usable-block floor — flash_attention falls back to XLA, ring callers
    raise their pad-the-shard error. (The pre-round-5 picker accepted any
    divisor, so e.g. t=251 with a >=251 preferred would have produced one
    251-row block that only works in interpret mode.) A sub-8 ``preferred``
    on a t > 8 sequence rounds UP to the hardware-minimum 8-row block
    (a 4-row block cannot tile on the MXU regardless of the request);
    t <= 8 keeps the plain largest-divisor-<=-preferred search (tiny test
    shapes, where interpret mode has no tiling rule)."""
    step = 1 if t <= 8 else 8
    m = min(preferred, t)
    b = max(step, m - m % step)
    while b >= step and t % b:
        b -= step
    return b if b >= step else 1


_interpret_override: Optional[bool] = None


def kernels_interpreted() -> bool:
    """Whether the Pallas kernels run in the interpreter: they compile with
    Mosaic on a TPU backend and are interpreted anywhere else. The ONE place
    that decides it, for the flash kernels, the paged decode kernel and the
    train steps' ``check_vma`` choice alike. A script that lowers for an
    abstract TPU from a CPU host (``scripts/aot_*.py``) calls
    :func:`set_kernels_interpreted` first — lowering the interpreted program
    there describes a program the chip never runs."""
    if _interpret_override is not None:
        return _interpret_override
    return jax.default_backend() != "tpu"


def set_kernels_interpreted(value: Optional[bool]) -> None:
    """Override :func:`kernels_interpreted` for every kernel and train step
    (``False``: trace as the chip does); ``None`` returns to the backend's
    default."""
    global _interpret_override
    _interpret_override = value


def _default_block(t: int) -> int:
    """Default preferred block: 1024 at every length. On-chip sweep
    coverage (T <= 8192) shows 1024 is 1.6x faster than 512 and the gain
    GROWS with T (the mechanism — fewer K/V re-streams per q-block —
    scales with n_blocks); the T = 131072 fwd+bwd ceiling is AOT-verified
    at block 1024 with the clamped causal maps active (3.25 GB peak,
    scripts/aot_flash_ceiling.jsonl), so long-T compilability is proven,
    not assumed. Kept as a function: the tuning boundary lives in one
    place if on-chip long-T data ever disagrees."""
    return 1024


def _pick_blocks(tq: int, tk: int, block_q: Optional[int],
                 block_k: Optional[int]) -> tuple[int, int]:
    """The q and k blocks of a call: ``None`` asks for the default, any
    other request must be a positive block (0 is not "unset")."""

    def pick(name, t, want):
        if want is None:
            want = _default_block(t)
        elif want < 1:
            raise ValueError(f"{name} must be positive, got {want}")
        return _pick_block(t, want)

    return pick("block_q", tq, block_q), pick("block_k", tk, block_k)


def _out_vma(*xs) -> frozenset:
    """Varying-manner annotation for kernel outputs: the union of the
    inputs' vma sets. pallas_call does not infer vma, so under
    ``shard_map(check_vma=True)`` — the default on real TPU — out_shapes
    with ``vma=None`` fail at trace time. Caught by the round-5 AOT
    schedule analysis (scripts/aot_ring_overlap.py); the CPU suite never
    sees it because interpret-mode tests run with check_vma=False."""
    vma = frozenset()
    for x in xs:
        vma |= jax.typeof(x).vma
    return vma


def _prec(*xs):
    """HIGHEST precision for f32 MXU operands: Mosaic's default f32 dot
    (like XLA's) may round operands through bf16 passes; flash in f32 is a
    correctness surface (the CPU oracle path), not a perf path, so pay for
    exactness. bf16 operands are single-pass exact either way -> None keeps
    the fast path untouched."""
    return (jax.lax.Precision.HIGHEST
            if any(x.dtype == jnp.float32 for x in xs) else None)


def _fold_args(b, h, d, *xs):
    """Model layout ``[B, T, H, D]`` -> kernel layout ``[B*H, T, D]``."""
    return tuple(x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
                 for x in xs)


def _static_delta(causal, q_offset, k_offset):
    """``q_offset - k_offset`` when both offsets are static Python ints and
    the call is causal, else None. A static delta lets the kernels CLAMP
    their streaming-side index maps so fully-masked chunks alias the
    previous chunk's block index — Mosaic's pipeline emitter skips the
    copy when consecutive grid steps map to the same block, so the ~2x
    causal FLOP saving (pl.when compute skip) gains the matching ~2x DMA
    saving. This matters more than it sounds: the reduction-chunk grids
    re-stream K/V once per q-block (and q/do once per k-block in the dkv
    kernel), so a masked chunk that is still copied costs bytes for no
    FLOPs (the kernels' share of the LM step: not measured). Traced
    offsets (ring shards) return None — the ring layer
    already skips wholly-invisible blocks at the block level."""
    if (causal and isinstance(q_offset, (int, np.integer))
            and isinstance(k_offset, (int, np.integer))):
        return int(q_offset) - int(k_offset)
    return None


# --------------------------------------------------------------------------- #
# Forward                                                                     #
# --------------------------------------------------------------------------- #

def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_acc, l_acc, o_acc, *, scale: float, causal: bool,
                n_k: int, window: Optional[int] = None):
    """Grid ``(bh, q-block, k-chunk)``, k-chunk INNERMOST: the online-
    softmax state (m, l, acc) lives in f32 VMEM scratch across the k sweep
    and the o/lse output blocks flush once at the last chunk — per-cell
    VMEM is O(block_q + block_k) regardless of T (the previous form held
    the full [tk, d] K/V blocks per cell). Fully-masked chunks skip their
    compute via pl.when (the former dynamic trip-count clamp). ``window``
    (causal only) lets a query see the ``window`` positions up to its own:
    chunks wholly before a q-block's first visible position are skipped
    like those after its last."""
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    j = pl.program_id(2)
    q_off = qo_ref[0, 0] + pl.program_id(1) * bq
    k_off = ko_ref[0, 0] + j * bk

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, _NEG_BIG)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    def compute():
        # MXU inputs stay in their storage dtype: bf16 x bf16 -> f32 is the
        # MXU's native full-rate mode, while a pre-cast to f32 forces the
        # multi-pass f32 path (~3-6x slower; measured round 5 — the kernel
        # sat at ~6.5 TFLOP/s with the casts). preferred_element_type keeps
        # the ACCUMULATION in f32 either way, which is all flash needs.
        q = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        m = m_acc[:, 0]
        l = l_acc[:, 0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(q, kb),
        ) * scale
        if causal:
            q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = q_pos >= k_pos
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, _NEG_BIG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        # explicit zero for masked entries: when a row is fully masked within
        # a VISITED block, s == m_new == the sentinel and exp(s - m_new)
        # would be 1, polluting l/acc with mean-of-V garbage
        p = jnp.where(s <= _NEG_BIG / 2, 0.0, jnp.exp(s - m_new[:, None]))
        # p rides the MXU in v's dtype (f32 p x bf16 v would hit the slow
        # path); the f32->bf16 rounding of p is the same concession every
        # production TPU flash kernel makes, and the accumulator stays f32
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(vb),
        )
        m_acc[...] = jnp.broadcast_to(m_new[:, None], m_acc.shape)
        l_acc[...] = jnp.broadcast_to(
            (l * corr + jnp.sum(p, axis=-1))[:, None], l_acc.shape)
        o_acc[...] = o_acc[...] * corr[:, None] + pv

    if causal and window is not None:
        # ... and chunks whose last position lies before the first q
        # position's window
        pl.when((q_off + bq - 1 >= k_off)
                & (k_off + bk - 1 > q_off - window))(compute)
    elif causal:
        # chunks whose first position is beyond the last q position never
        # contribute — skip the math (the DMA still streams; same traffic
        # as the old full-block fetch)
        pl.when(q_off + bq - 1 >= k_off)(compute)
    else:
        compute()

    @pl.when(j == n_k - 1)
    def _flush():
        m = m_acc[:, 0]
        l = l_acc[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (o_acc[...] / l_safe[:, None]).astype(o_ref.dtype)
        # rows with no visible keys get lse = -inf-ish; backward masks them
        # out. lse rides a lane-broadcast [block_q, _LANE] tile (a
        # [1, block_q] block violates Mosaic's sublane rule), like the
        # reference TPU flash kernel's l/m.
        lse = jnp.where(l == 0.0, _NEG_BIG, m + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _kv_clamped_map(delta, block_q, block_k, n_k, group=1, window=None):
    """Streaming-side index map for grids ``(bh, q-block i, k-chunk j)``:
    chunks past q-block i's last visible chunk alias that chunk (same
    block index -> the pipeline skips the copy). The kernel's pl.when
    skips their compute by the true j, so values are unchanged.
    ``delta=None`` (traced offsets / non-causal) -> plain streaming map.
    ``group`` query heads read one KV head (``bh`` counts the query's);
    with a ``window`` the chunks wholly before q-block i's first visible
    position alias its first visible chunk the same way."""
    if delta is None:
        return lambda b, i, j: (b // group, j, 0)

    def kv_map(b, i, j):
        vis = (delta + (i + 1) * block_q - 1) // block_k
        j = jnp.minimum(j, vis)
        if window is not None:
            j = jnp.maximum(j, (delta + i * block_q - window + 1) // block_k)
        return (b // group, jnp.clip(j, 0, n_k - 1), 0)
    return kv_map


def _q_clamped_map(delta, block_q, block_k, n_q):
    """Streaming-side index map for grids ``(bh, k-block j, q-chunk i)``:
    q-chunks wholly before k-block j's first visible chunk alias it.
    ``delta=None`` -> plain streaming map."""
    if delta is None:
        return lambda b, j, i: (b, i, 0)

    def q_map(b, j, i):
        first = (j * block_k - delta) // block_q
        return (b, jnp.clip(jnp.maximum(i, first), 0, n_q - 1), 0)
    return q_map


def _fwd(q, k, v, q_offset, k_offset, *, scale, causal, block_q, block_k,
         interpret, out_dtype=None, static_delta=None, window=None):
    bh, tq, d = q.shape
    group = bh // k.shape[0]
    tk = k.shape[1]
    n_k = tk // block_k
    # k-chunk INNERMOST (sequential: the online-softmax scratch accumulates
    # over it); o/lse blocks are indexed by (b, i) only and flush once
    grid = (bh, tq // block_q, n_k)
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    ko = jnp.asarray(k_offset, jnp.int32).reshape(1, 1)
    smem = _smem_spec()
    if group == 1 and window is None:
        kv_map = _kv_clamped_map(static_delta, block_q, block_k, n_k)
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   n_k=n_k)
    else:
        kv_map = _kv_clamped_map(static_delta, block_q, block_k, n_k,
                                 group, window)
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   n_k=n_k, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            # out_dtype=f32 lets ring callers merge partial block outputs
            # without a bf16 round-trip (q/k/v still feed the MXU in their
            # input dtype; the kernel accumulates f32 regardless)
            jax.ShapeDtypeStruct((bh, tq, d), out_dtype or q.dtype,
                                 vma=_out_vma(qo, ko, q, k, v)),
            jax.ShapeDtypeStruct((bh, tq, _LANE), jnp.float32,
                                 vma=_out_vma(qo, ko, q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),       # unnormalized acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qo, ko, q, k, v)
    return out, lse[..., 0]


# --------------------------------------------------------------------------- #
# Backward                                                                    #
# --------------------------------------------------------------------------- #

def _bwd_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, scale: float, causal: bool,
                   n_k: int):
    """Grid ``(bh, q-block, k-chunk)``, k-chunk INNERMOST: dq accumulates
    in f32 VMEM scratch across the k sweep and flushes once — per-cell
    VMEM is O(block) regardless of T (see _fwd_kernel / _bwd_dkv_kernel;
    all three kernels share the structure)."""
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    j = pl.program_id(2)
    q_off = qo_ref[0, 0] + pl.program_id(1) * bq
    k_off = ko_ref[0, 0] + j * bk

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute():
        # storage-dtype MXU inputs, f32 accumulation — see _fwd_kernel
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]     # lane-broadcast [block_q, _LANE]
        delta = delta_ref[0, :, 0]
        kb = k_ref[0]
        vb = v_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(q, kb),
        ) * scale
        if causal:
            q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_BIG)
        # masked entries must not resurrect when lse is the -inf sentinel
        # (fully-masked row): exp(-1e30 - (-1e30)) == 1 otherwise
        p = jnp.where(s <= _NEG_BIG / 2, 0.0, jnp.exp(s - lse[:, None]))
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(do, vb),
        )
        ds = p * (dp - delta[:, None])
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(kb),
        )

    if causal:
        # chunks wholly after the last q position contribute nothing
        pl.when(q_off + bq - 1 >= k_off)(compute)
    else:
        compute()

    @pl.when(j == n_k - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, n_q: int):
    """Grid ``(bh, k-block, q-chunk)``, q-chunk INNERMOST: the dk/dv output
    block for (b, k-block) stays VMEM-resident across the whole q sweep,
    accumulating in the f32 scratch, and flushes once at the last chunk.

    The previous form held the FULL [tq, d] q/do and [tq, 128] lse/delta
    blocks per grid cell and streamed q inside a fori_loop — its VMEM
    footprint grew linearly with tq and OOM'd the v5e backward at
    T = 16384 (AOT-verified); chunked via the grid, per-cell VMEM is
    O(block_q + block_k) regardless of tq."""
    bk, d = k_ref.shape[1], k_ref.shape[2]
    bq = q_ref.shape[1]
    i = pl.program_id(2)
    q_off = qo_ref[0, 0] + i * bq
    k_off = ko_ref[0, 0] + pl.program_id(1) * bk

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute():
        # storage-dtype MXU inputs, f32 accumulation — see _fwd_kernel
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(qb, kb),
        ) * scale
        if causal:
            q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_BIG)
        p = jnp.where(s <= _NEG_BIG / 2, 0.0, jnp.exp(s - lse[:, None]))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(dob),
        )
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(dob, vb),
        )
        ds = p * (dp - delta[:, None])
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(qb),
        )

    if causal:
        # q chunks wholly before this k block see nothing of it
        pl.when(q_off + bq - 1 >= k_off)(compute)
    else:
        compute()

    @pl.when(i == n_q - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_call(q, k, v, do, lse, delta, qo2, ko2, *, scale, causal, block_q,
             block_k, interpret, grad_dtype=None, static_delta=None):
    """dq for one (q-range x k-range) pair, folded ``[B*H, T, D]`` layout —
    shared by the full backward and the ring backward's per-block calls
    (which pass ``grad_dtype=f32`` to accumulate across blocks losslessly)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    # lane-broadcast the row stats to the Mosaic-tileable layout (see _fwd)
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANE))
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANE))
    smem = _smem_spec()
    n_k = tk // block_k
    kv_map = _kv_clamped_map(static_delta, block_q, block_k, n_k)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          n_k=n_k),
        grid=(bh, tq // block_q, n_k),
        in_specs=[
            smem, smem,
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (bh, tq, d), grad_dtype or q.dtype,
            vma=_out_vma(qo2, ko2, q, k, v, do)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qo2, ko2, q, k, v, do, lse, delta)


def _dkv_call(q, k, v, do, lse, delta, qo2, ko2, *, scale, causal, block_q,
              block_k, interpret, grad_dtype=None, static_delta=None):
    """(dk, dv) for one (q-range x k-range) pair, folded layout — see
    :func:`_dq_call`."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANE))
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANE))
    smem = _smem_spec()
    n_q = tq // block_q
    q_map = _q_clamped_map(static_delta, block_q, block_k, n_q)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          n_q=n_q),
        # q-chunk is the INNERMOST grid dim: the (b, j) output block stays
        # resident while the scratch accumulates over every q chunk
        grid=(bh, tk // block_k, n_q),
        in_specs=[
            smem, smem,
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, _LANE), q_map),
            pl.BlockSpec((1, block_q, _LANE), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), grad_dtype or k.dtype,
                                 vma=_out_vma(qo2, ko2, q, k, v, do)),
            jax.ShapeDtypeStruct((bh, tk, d), grad_dtype or v.dtype,
                                 vma=_out_vma(qo2, ko2, q, k, v, do)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        # the q-chunk dim accumulates into the scratch -> sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qo2, ko2, q, k, v, do, lse, delta)


def _bwd(scale, causal, block_q, block_k, interpret, static_delta, res, g):
    q, k, v, out, lse, qo, ko = res
    do, _ = g  # cotangent of (out, lse); lse cotangent unused
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qo2 = jnp.asarray(qo, jnp.int32).reshape(1, 1)
    ko2 = jnp.asarray(ko, jnp.int32).reshape(1, 1)
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              interpret=interpret, static_delta=static_delta)
    dq = _dq_call(q, k, v, do, lse, delta, qo2, ko2, **kw)
    dk, dv = _dkv_call(q, k, v, do, lse, delta, qo2, ko2, **kw)
    return dq, dk, dv, None, None


# --------------------------------------------------------------------------- #
# Public entry                                                                #
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_offset, k_offset, scale, causal, block_q, block_k,
           interpret, static_delta):
    out, _ = _fwd(q, k, v, q_offset, k_offset, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, interpret=interpret,
                  static_delta=static_delta)
    return out


def _flash_fwd(q, k, v, q_offset, k_offset, scale, causal, block_q, block_k,
               interpret, static_delta):
    out, lse = _fwd(q, k, v, q_offset, k_offset, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                    static_delta=static_delta)
    return out, (q, k, v, out, lse, q_offset, k_offset)


def _flash_bwd(scale, causal, block_q, block_k, interpret, static_delta,
               res, g):
    dq, dk, dv, _, _ = _bwd(scale, causal, block_q, block_k, interpret,
                            static_delta, res, (g, None))
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    k_offset=0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
):
    """Blockwise (flash) attention, layout ``[B, T, H, D]`` like
    :func:`chainermn_tpu.parallel.sequence.full_attention`.

    ``q_offset``/``k_offset`` are the *global* positions of ``q[:, 0]`` /
    ``k[:, 0]`` for causal masking under sequence sharding (may be traced).
    Differentiable (custom VJP, flash backward kernels). Runs compiled on
    TPU, interpreted elsewhere (``interpret=None`` auto-detects).

    Two forms are forward only (a served model's prefill; no VJP is
    defined for them): ``k``/``v`` with fewer heads than ``q`` (grouped KV
    heads, ``Hkv`` dividing ``H``: query head ``g`` reads KV head
    ``g // (H // Hkv)``, nothing is repeated in memory), and ``window``
    (causal only): position ``t`` sees ``t - window < j <= t``, and
    k-chunks wholly outside a q-block's window are neither computed on
    nor, with static offsets, copied.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    hk = k.shape[2]
    if h % hk:
        raise ValueError(f"{h} query heads do not divide over {hk} KV heads")
    if window is not None and not causal:
        raise ValueError("window needs causal=True")
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = kernels_interpreted()
    bq, bk = _pick_blocks(tq, tk, block_q, block_k)
    if bq < min(8, tq) or bk < min(8, tk):
        # awkward lengths (no usable divisor): blockwise degenerates below
        # hardware tile minimums — use the XLA path, same semantics
        from chainermn_tpu.parallel.sequence import full_attention

        static_zero_offsets = (
            isinstance(q_offset, (int, np.integer)) and q_offset == 0
            and isinstance(k_offset, (int, np.integer)) and k_offset == 0
        )
        if hk != h or window is not None:
            if not (static_zero_offsets and tq == tk):
                raise ValueError(
                    f"flash_attention: lengths (tq={tq}, tk={tk}) have no "
                    "usable block divisor and the XLA fallback for grouped "
                    "KV heads or a window takes whole sequences from "
                    "position 0 — pad the sequence to a multiple of 8")
            k, v = (jnp.repeat(x, h // hk, axis=2) for x in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32) * scale
            i = jnp.arange(tq)
            seen = i[:, None] >= i[None, :] if causal else True
            if window is not None:
                seen = seen & (i[:, None] - i[None, :] < window)
            p = jax.nn.softmax(jnp.where(seen, s, _NEG_BIG), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
                              ).astype(q.dtype)
        if not causal or (static_zero_offsets and tq == tk):
            return full_attention(q, k, v, causal=causal, scale=scale)
        raise ValueError(
            f"flash_attention: sequence lengths (tq={tq}, tk={tk}) have no "
            "usable block divisor and the offset-causal XLA fallback is not "
            "implemented — pad the sequence to a multiple of 8"
        )

    if hk != h or window is not None:
        (qf,) = _fold_args(b, h, d, q)
        kf, vf = _fold_args(b, hk, d, k, v)
        out, _ = _fwd(qf, kf, vf, jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32), scale=float(scale),
                      causal=bool(causal), block_q=bq, block_k=bk,
                      interpret=bool(interpret),
                      static_delta=_static_delta(causal, q_offset, k_offset),
                      window=None if window is None else int(window))
        return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    qf, kf, vf = _fold_args(b, h, d, q, k, v)
    out = _flash(qf, kf, vf,
                 jnp.asarray(q_offset, jnp.int32),
                 jnp.asarray(k_offset, jnp.int32),
                 float(scale), bool(causal), bq, bk, bool(interpret),
                 _static_delta(causal, q_offset, k_offset))
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------- #
# Block-level entries for ring attention                                      #
# --------------------------------------------------------------------------- #
# Ring attention (parallel/sequence.py) computes attention against one K/V
# block per step and merges partials with the online-softmax recurrence; it
# owns its own custom VJP at the ring level, so these entries are PRIMAL
# only — the forward returns the (out, lse) pair the merge needs, and the
# backward pieces take the ring's final lse/delta and return one block's
# gradient contributions. All in model layout [B, T, H, D] (lse [B, H, T]).

def _check_blocks(bq, bk, tq, tk):
    """Ring callers have no XLA fallback (the custom VJP is built on the
    kernels), so reject un-tileable lengths loudly instead of letting
    Pallas fail with an obscure Mosaic error."""
    if bq < min(8, tq) or bk < min(8, tk):
        raise ValueError(
            f"ring flash attention: shard lengths (tq={tq}, tk={tk}) have "
            "no usable block divisor >= 8 — pad the per-shard sequence to a "
            "multiple of 8 (zigzag chunks: a multiple of 16)"
        )


def flash_fwd_with_lse(q, k, v, *, causal=False, scale=None, q_offset=0,
                       k_offset=0, block_q=None, block_k=None, interpret=None,
                       out_dtype=None):
    """Primal-only flash forward returning ``(out, lse)``.

    ``out [B, Tq, H, D]`` (in ``out_dtype``, default ``q.dtype`` — ring
    callers pass f32 to merge without a bf16 round-trip), ``lse [B, H, Tq]``
    (f32; fully-masked rows hold the -1e30 sentinel, which the lse-weighted
    merge turns into a zero contribution). Causal masking uses global
    positions via the (possibly traced) offsets; fully-masked chunks skip
    their compute (pl.when), and with STATIC int offsets their DMA too
    (clamped index maps, see _static_delta). Traced-offset callers still
    pay the masked chunks' DMA — ring callers that KNOW a whole block is
    invisible should skip the call, not lean on the kernel."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = kernels_interpreted()
    bq, bk = _pick_blocks(tq, tk, block_q, block_k)
    _check_blocks(bq, bk, tq, tk)
    qf, kf, vf = _fold_args(b, h, d, q, k, v)
    out, lse = _fwd(qf, kf, vf,
                    jnp.asarray(q_offset, jnp.int32),
                    jnp.asarray(k_offset, jnp.int32),
                    scale=float(scale), causal=bool(causal), block_q=bq,
                    block_k=bk, interpret=bool(interpret),
                    out_dtype=out_dtype,
                    static_delta=_static_delta(causal, q_offset, k_offset))
    return (out.reshape(b, h, tq, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, tq))


def flash_block_grads(q, k, v, do, lse, delta, *, causal=False, scale=None,
                      q_offset=0, k_offset=0, block_q=None, block_k=None,
                      interpret=None, grad_dtype=jnp.float32):
    """One block's gradient contributions ``(dq, dk, dv)`` given the FINAL
    (globally merged) ``lse [B, H, Tq]`` and ``delta = rowsum(do * out)
    [B, H, Tq]`` — the flash backward decomposes over K/V blocks once those
    are fixed, which is exactly what the ring backward's rotation needs.
    Layouts as :func:`flash_fwd_with_lse`. Gradients come back in
    ``grad_dtype`` (default f32) because the ring accumulates them across
    blocks; cast once at the end."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = kernels_interpreted()
    bq, bk = _pick_blocks(tq, tk, block_q, block_k)
    _check_blocks(bq, bk, tq, tk)
    qf, kf, vf, dof = _fold_args(b, h, d, q, k, v, do)
    lsef = lse.reshape(b * h, tq)
    deltaf = delta.reshape(b * h, tq)
    qo2 = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    ko2 = jnp.asarray(k_offset, jnp.int32).reshape(1, 1)
    kw = dict(scale=float(scale), causal=bool(causal), block_q=bq,
              block_k=bk, interpret=bool(interpret), grad_dtype=grad_dtype,
              static_delta=_static_delta(causal, q_offset, k_offset))
    dq = _dq_call(qf, kf, vf, dof, lsef, deltaf, qo2, ko2, **kw)
    dk, dv = _dkv_call(qf, kf, vf, dof, lsef, deltaf, qo2, ko2, **kw)
    unfold = lambda x: x.reshape(b, h, x.shape[1], d).transpose(0, 2, 1, 3)
    return unfold(dq), unfold(dk), unfold(dv)
