"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has **no** long-context machinery (SURVEY.md S2.16/S5: it
predates attention; its closest shape is the alltoall channel-parallel
convolution). These are the TPU-first extensions the rebuild owes
first-class support for long sequences:

- **Ring attention** (:func:`ring_attention`): the sequence axis is sharded
  over a mesh axis; K/V blocks rotate around the ring via ``lax.ppermute``
  while each device's Q stays put, merging partial results with the
  flash-attention online-softmax recurrence. Comm volume per step is one
  K/V block over ICI neighbor links — the collective pattern overlaps with
  the blockwise matmuls (XLA pipelines the ppermute with the einsums).
- **Ulysses attention** (:func:`ulysses_attention`): ``lax.all_to_all``
  re-shards from sequence-sharded to head-sharded, runs exact local
  attention per head group, and all-to-alls back — the same collective
  shape as the reference's channel-parallel conv example, applied to heads.

Both are *traced* functions: call them inside ``shard_map``/``pjit`` over
the communicator's mesh (e.g. via ``comm.shard_map``). Both are exact —
they compute the same result as full attention on the gathered sequence
(tested against the single-device reference), and both differentiate
(``ppermute``/``all_to_all`` have transposed-communication VJPs, the same
property the reference's differentiable collectives hand-implement).

Layouts follow the TPU-friendly convention ``[batch, seq, heads, head_dim]``
with contractions in f32 (``preferred_element_type``) so bf16 inputs hit the
MXU without accumulating in bf16.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


_NEG_BIG = -1e30  # finite "minus infinity": avoids inf-inf NaNs in masked rows


def chunk_spans(start: int, total: int, chunk_len: int
                ) -> list[tuple[int, int]]:
    """Partition token range ``[start, total)`` into consecutive
    ``(offset, length)`` spans of at most ``chunk_len`` tokens.

    The one sequence-partitioning arithmetic shared by both consumers of
    "process a long sequence in bounded pieces": sequence-parallel
    sharding plans (where each span is a shard's local window) and the
    serving engine's chunked prefill (where each span is one scheduler
    step's device call). Pure host math — every span is non-empty, spans
    tile the range exactly, and only the last may be short."""
    start, total, chunk_len = int(start), int(total), int(chunk_len)
    if chunk_len < 1:
        raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
    spans = []
    frontier = start
    while frontier < total:
        clen = min(chunk_len, total - frontier)
        spans.append((frontier, clen))
        frontier += clen
    return spans


def _vary_to(x, vma):
    """pcast ``x`` to varying over exactly the axes in ``vma`` it does not
    already vary on. A plain ``pcast(..., to='varying')`` on a value that
    already carries some of the axes raises ("Unsupported pcast
    from=varying, to='varying'") — hit once the flash kernels started
    propagating input vma to their outputs (round 5)."""
    need = tuple(a for a in vma if a not in jax.typeof(x).vma)
    return lax.pcast(x, need, to="varying") if need else x


def _block_attend(q, k, v, *, scale, mask, m, l, o):
    """One flash-style block update.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; mask: [Tq, Tk] bool or None.
    (m, l, o): running max [B, H, Tq], denominator [B, H, Tq], unnormalized
    accumulator [B, Tq, H, D]. Returns updated (m, l, o).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, _NEG_BIG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])  # [B, H, Tq, Tk]
    l = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l, o


def ring_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    skip_masked_blocks: bool = True,
):
    """Exact attention over a sequence sharded along ``axis_name``.

    Args (all per-device shards, inside ``shard_map``):
      q, k, v: ``[B, T_local, H, D]`` — the local sequence block.
      causal: apply a causal mask over *global* positions (block offsets are
        derived from ``lax.axis_index``; shard i holds positions
        ``[i*T_local, (i+1)*T_local)``).

    Returns ``[B, T_local, H, D]`` in ``q.dtype``.
    """
    if not isinstance(axis_name, str):
        raise ValueError(
            f"ring_attention needs a single named mesh axis, got {axis_name!r} "
            "— use a flat communicator (e.g. 'tpu') for sequence parallelism"
        )
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5

    q32 = q.astype(jnp.float32)
    # mark the accumulators as per-device state; without it the fori_loop
    # carry's replicated-ness changes across steps. Vary over the RING axis
    # plus every axis the inputs already vary on (under TP composition the
    # q/k/v carry the tensor axis's vma too; a ring-axis-only pcast would
    # make the carry types diverge after one iteration). With check_vma off
    # the vma sets are empty and this degenerates to the ring axis alone.
    vma = (frozenset({axis_name}) | jax.typeof(q).vma
           | jax.typeof(k).vma | jax.typeof(v).vma)
    _vary = lambda x: _vary_to(x, vma)
    m0 = _vary(jnp.full((b, h, t), _NEG_BIG, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, t), jnp.float32))
    o0 = _vary(jnp.zeros((b, t, h, d), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = my * t + jnp.arange(t)

    def body(step, carry):
        m, l, o, kb, vb = carry
        src = (my - step) % n  # origin rank of the block we currently hold
        if causal:
            k_pos = src * t + jnp.arange(t)
            mask = q_pos[:, None] >= k_pos[None, :]
            # Blocks from the future (src > my) are fully masked — skip the
            # einsums entirely instead of computing and discarding them.
            # NOTE this halves per-rank FLOPs but NOT wall-clock: the ring
            # barriers every step, so lockstep time is set by the busiest
            # rank (rank n-1 computes every step). zigzag_ring_attention
            # fixes the imbalance itself; this cond still saves energy and
            # helps when ranks aren't lockstep (e.g. CPU testing).
            # skip_masked_blocks=False keeps the round-3 compute-everything
            # behavior (benchmark baseline).
            if skip_masked_blocks:
                m, l, o = lax.cond(
                    src <= my,
                    lambda mlo: _block_attend(
                        q32, kb, vb, scale=scale, mask=mask,
                        m=mlo[0], l=mlo[1], o=mlo[2]
                    ),
                    lambda mlo: mlo,
                    (m, l, o),
                )
            else:
                m, l, o = _block_attend(
                    q32, kb, vb, scale=scale, mask=mask, m=m, l=l, o=o
                )
        else:
            m, l, o = _block_attend(
                q32, kb, vb, scale=scale, mask=None, m=m, l=l, o=o
            )
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return m, l, o, kb, vb

    # k/v stay in their input dtype through the ring: the ppermute per step
    # ships half the bytes for bf16 inputs, and _block_attend accumulates in
    # f32 regardless (preferred_element_type + local cast)
    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    # rows with no visible keys (never happens for causal with aligned
    # blocks, but keep the division safe)
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
# Ring attention with Pallas flash blocks                                     #
# --------------------------------------------------------------------------- #
# The XLA ring above materializes each [B, H, Tq, Tk] score tile via jnp
# einsums; on TPU the per-block computation should be the flash kernel
# (ops/flash_attention.py) so the two O(T)-memory paths compose: ring
# memory ACROSS devices, flash tiling WITHIN each block. AD cannot trace
# through pallas_call, so the ring owns a custom VJP:
#
# - forward: one primal flash call per incoming block (the kernel's causal
#   trip-count clamp skips fully-masked blocks for free); partials merge by
#   the lse-weighted rule o <- o*exp(lse-lse') + o_b*exp(lse_b-lse').
# - backward: the flash backward decomposes over K/V blocks once the FINAL
#   lse and delta = rowsum(do*out) are fixed, so a second rotation pass
#   computes per-block (dq, dk_b, dv_b) with the block kernels; dk/dv
#   accumulators ride the ring WITH their k/v block and arrive back at the
#   owner after n steps holding every rank's contribution.

def _zz_merge(o, lse, ob, lse_b):
    """lse-weighted merge of a partial block result into the running
    (o [B,T,H,D] f32, lse [B,H,T] f32) — the single home of the merge
    recurrence shared by the ring-flash and zigzag-flash forwards."""
    lse_new = jnp.logaddexp(lse, lse_b)
    w1 = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(lse_b - lse_new).transpose(0, 2, 1)[..., None]
    return o * w1 + ob * w2, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, scale):
    out, _ = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale)
    return out


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale):
    from chainermn_tpu.ops.flash_attention import flash_fwd_with_lse

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    vma = (frozenset({axis_name}) | jax.typeof(q).vma
           | jax.typeof(k).vma | jax.typeof(v).vma)
    _vary = lambda x: _vary_to(x, vma)
    o0 = _vary(jnp.zeros((b, t, h, d), jnp.float32))
    lse0 = _vary(jnp.full((b, h, t), _NEG_BIG, jnp.float32))

    def body(step, carry):
        o, lse, kb, vb = carry
        src = (my - step) % n
        ob, lse_b = flash_fwd_with_lse(
            q, kb, vb, causal=causal, scale=scale,
            q_offset=my * t, k_offset=src * t, out_dtype=jnp.float32,
        )
        o, lse = _zz_merge(o, lse, ob, lse_b)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return o, lse, kb, vb

    o, lse, _, _ = lax.fori_loop(0, n, body, (o0, lse0, k, v))
    return o.astype(q.dtype), lse


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, scale):
    out, lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(axis_name, causal, scale, res, do):
    from chainermn_tpu.ops.flash_attention import flash_block_grads

    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    # delta rows must pair with lse rows: [B, T, H] -> [B, H, T]
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)
    vma = (jax.typeof(q).vma | jax.typeof(do).vma
           | frozenset({axis_name}))
    _vary = lambda x: _vary_to(x, vma)
    dq0 = _vary(jnp.zeros((b, t, h, d), jnp.float32))
    dk0 = _vary(jnp.zeros((b, t, h, d), jnp.float32))
    dv0 = _vary(jnp.zeros((b, t, h, d), jnp.float32))

    def body(step, carry):
        dq, dka, dva, kb, vb = carry
        src = (my - step) % n
        dqb, dkb, dvb = flash_block_grads(
            q, kb, vb, do, lse, delta, causal=causal, scale=scale,
            q_offset=my * t, k_offset=src * t,
        )
        dq = dq + dqb
        dka = dka + dkb
        dva = dva + dvb
        # accumulators travel WITH their block; after n rotations both are
        # back at the block's owner carrying all ranks' contributions
        kb, vb, dka, dva = (lax.ppermute(x, axis_name, perm)
                            for x in (kb, vb, dka, dva))
        return dq, dka, dva, kb, vb

    dq, dka, dva, _, _ = lax.fori_loop(0, n, body, (dq0, dk0, dv0, k, v))
    return dq.astype(q.dtype), dka.astype(k.dtype), dva.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_flash_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
):
    """:func:`ring_attention` with Pallas flash kernels as the per-block
    computation — same semantics and layout, O(T) memory at BOTH levels
    (ring across devices, flash tiles within a block), fully-masked blocks
    skipped inside the kernel. Differentiable via a ring-level custom VJP
    (flash backward kernels in a second rotation pass). Off TPU the kernels
    run interpreted — use ``check_vma=False`` on the enclosing shard_map
    there, like plain ``'flash'``."""
    if not isinstance(axis_name, str):
        raise ValueError(
            f"ring_flash_attention needs a single named mesh axis, got "
            f"{axis_name!r}"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _ring_flash(q, k, v, axis_name, bool(causal), float(scale))


# --------------------------------------------------------------------------- #
# Zigzag ring with Pallas flash blocks                                        #
# --------------------------------------------------------------------------- #
# The balanced layout AND the kernel blocks — the long-context flagship
# composition. Every zigzag interaction decomposes into offset-causal or
# fully-visible chunk pairs, which is exactly what the flash kernel
# supports: the diagonal step is (qe vs ke causal) + (ql vs kl causal) +
# (ql vs ke full), and each off-diagonal step is one unmasked [t, c] or
# [c, t] call — equal FLOPs in both cond branches, so the balance property
# is preserved. Ring-level custom VJP like _ring_flash, with the same
# rotating dk/dv accumulators.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _zigzag_flash(q, k, v, axis_name, scale):
    out, _ = _zigzag_flash_fwd_pass(q, k, v, axis_name, scale)
    return out


def _zigzag_flash_fwd_pass(q, k, v, axis_name, scale):
    from chainermn_tpu.ops.flash_attention import flash_fwd_with_lse

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    if t % 2:
        raise ValueError(f"local sequence length {t} must be even")
    c = t // 2
    perm = [(i, (i + 1) % n) for i in range(n)]
    off_e = my * c                 # global offset of the early chunk
    off_l = (2 * n - 1 - my) * c   # ... and the late chunk

    def block(qc, kc, vc, *, causal, q_off=0, k_off=0):
        return flash_fwd_with_lse(
            qc, kc, vc, causal=causal, scale=scale, q_offset=q_off,
            k_offset=k_off, out_dtype=jnp.float32,
        )

    # diagonal: qe/ke causal + ql/kl causal + ql/ke full
    oe, lse_e = block(q[:, :c], k[:, :c], v[:, :c], causal=True,
                      q_off=off_e, k_off=off_e)
    ol1, lse_l1 = block(q[:, c:], k[:, c:], v[:, c:], causal=True,
                        q_off=off_l, k_off=off_l)
    ol2, lse_l2 = block(q[:, c:], k[:, :c], v[:, :c], causal=False)
    ol, lse_l = _zz_merge(ol1, lse_l1, ol2, lse_l2)
    o = jnp.concatenate([oe, ol], axis=1)
    lse = jnp.concatenate([lse_e, lse_l], axis=2)

    kb = lax.ppermute(k, axis_name, perm)
    vb = lax.ppermute(v, axis_name, perm)

    # BRANCH-FREE ring steps (round 5): the round-5 AOT schedule analysis
    # showed XLA will not hoist collective starts across a lax.cond, so a
    # cond-shaped body serializes the ring's permutes against the kernels
    # (PERF.md "Ring overlap"). Both former branches decompose into the
    # SAME two fully-visible (c x c) kernel calls with selected operands —
    # earlier-rank block: (q_e x k_e) + (q_l x k_e); later-rank block:
    # (q_l x k_e) + (q_l x k_l) — equal FLOPs (the balance property), no
    # control flow, so the scheduler overlaps the permutes like the plain
    # ring's. Only the cheap elementwise merges are select-routed.
    def body(step, carry):
        o, lse, kb, vb = carry
        earlier = my >= step  # the held block came from an earlier rank
        ke, ve, kl, vl = kb[:, :c], vb[:, :c], kb[:, c:], vb[:, c:]
        q_e, q_l = q[:, :c], q[:, c:]

        ob1, lse_b1 = block(jnp.where(earlier, q_e, q_l), ke, ve,
                            causal=False)
        ob2, lse_b2 = block(q_l, jnp.where(earlier, ke, kl),
                            jnp.where(earlier, ve, vl), causal=False)

        o_e, lse_e = o[:, :c], lse[:, :, :c]
        o_l, lse_l = o[:, c:], lse[:, :, c:]
        # call 1 merges into the half its q rows came from
        oe_m, lsee_m = _zz_merge(o_e, lse_e, ob1, lse_b1)
        ol_m, lsel_m = _zz_merge(o_l, lse_l, ob1, lse_b1)
        o_e = jnp.where(earlier, oe_m, o_e)
        lse_e = jnp.where(earlier, lsee_m, lse_e)
        o_l = jnp.where(earlier, o_l, ol_m)
        lse_l = jnp.where(earlier, lse_l, lsel_m)
        # call 2's q rows are always the late half
        o_l, lse_l = _zz_merge(o_l, lse_l, ob2, lse_b2)

        o = jnp.concatenate([o_e, o_l], axis=1)
        lse = jnp.concatenate([lse_e, lse_l], axis=2)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return o, lse, kb, vb

    o, lse, _, _ = lax.fori_loop(1, n, body, (o, lse, kb, vb))
    return o.astype(q.dtype), lse


def _zigzag_flash_fwd_rule(q, k, v, axis_name, scale):
    out, lse = _zigzag_flash_fwd_pass(q, k, v, axis_name, scale)
    return out, (q, k, v, out, lse)


def _zigzag_flash_bwd_rule(axis_name, scale, res, do):
    from chainermn_tpu.ops.flash_attention import flash_block_grads

    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    c = t // 2
    perm = [(i, (i + 1) % n) for i in range(n)]
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1)
    vma = jax.typeof(q).vma | jax.typeof(do).vma | frozenset({axis_name})
    _vary = lambda x: _vary_to(x, vma)
    off_e, off_l = my * c, (2 * n - 1 - my) * c

    def grads(qs, ks, vs, dos, lses, deltas, *, causal, q_off=0, k_off=0):
        return flash_block_grads(
            qs, ks, vs, dos, lses, deltas, causal=causal, scale=scale,
            q_offset=q_off, k_offset=k_off,
        )

    # diagonal contributions (same three pairs as forward)
    dqe, dke, dve = grads(q[:, :c], k[:, :c], v[:, :c], do[:, :c],
                          lse[:, :, :c], delta[:, :, :c], causal=True,
                          q_off=off_e, k_off=off_e)
    dql1, dkl, dvl = grads(q[:, c:], k[:, c:], v[:, c:], do[:, c:],
                           lse[:, :, c:], delta[:, :, c:], causal=True,
                           q_off=off_l, k_off=off_l)
    dql2, dke2, dve2 = grads(q[:, c:], k[:, :c], v[:, :c], do[:, c:],
                             lse[:, :, c:], delta[:, :, c:], causal=False)
    dq = _vary(jnp.concatenate([dqe, dql1 + dql2], axis=1))
    dka = _vary(jnp.concatenate([dke + dke2, dkl], axis=1))
    dva = _vary(jnp.concatenate([dve + dve2, dvl], axis=1))

    kb = lax.ppermute(k, axis_name, perm)
    vb = lax.ppermute(v, axis_name, perm)
    dka = lax.ppermute(dka, axis_name, perm)
    dva = lax.ppermute(dva, axis_name, perm)

    # Branch-free like the forward (see _zigzag_flash_fwd_pass): a lax.cond
    # body would serialize all four permutes against the kernels (XLA will
    # not hoist collective starts across control flow — round-5 AOT
    # schedule analysis, PERF.md "Ring overlap"). The two former branches
    # are the same two (c x c) kernel calls with selected operands:
    #   earlier: (q_e x k_e) + (q_l x k_e)   later: (q_l x k_e) + (q_l x k_l)
    # Only the cheap gradient scatter-adds are select-routed.
    def body(step, carry):
        dq, dka, dva, kb, vb = carry
        earlier = my >= step
        ke, ve, kl, vl = kb[:, :c], vb[:, :c], kb[:, c:], vb[:, c:]
        q_e, q_l = q[:, :c], q[:, c:]
        do_e, do_l = do[:, :c], do[:, c:]
        lse_e, lse_l = lse[:, :, :c], lse[:, :, c:]
        de, dl = delta[:, :, :c], delta[:, :, c:]

        dq1, dk1, dv1 = grads(jnp.where(earlier, q_e, q_l), ke, ve,
                              jnp.where(earlier, do_e, do_l),
                              jnp.where(earlier, lse_e, lse_l),
                              jnp.where(earlier, de, dl), causal=False)
        dq2, dk2, dv2 = grads(q_l, jnp.where(earlier, ke, kl),
                              jnp.where(earlier, ve, vl),
                              do_l, lse_l, dl, causal=False)

        zc = jnp.zeros((b, c, h, d), jnp.float32)
        # dq: call 1's rows are q_e (earlier) or q_l (later); call 2's
        # rows are always q_l
        dq = dq + jnp.concatenate(
            [jnp.where(earlier, dq1, zc),
             jnp.where(earlier, dq2, dq1 + dq2)], axis=1)
        # dk/dv: call 1 always hits the early K half; call 2 hits the
        # early half (earlier) or the late half (later)
        dka = dka + jnp.concatenate(
            [dk1 + jnp.where(earlier, dk2, zc),
             jnp.where(earlier, zc, dk2)], axis=1)
        dva = dva + jnp.concatenate(
            [dv1 + jnp.where(earlier, dv2, zc),
             jnp.where(earlier, zc, dv2)], axis=1)
        kb, vb, dka, dva = (lax.ppermute(x, axis_name, perm)
                            for x in (kb, vb, dka, dva))
        return dq, dka, dva, kb, vb

    dq, dka, dva, _, _ = lax.fori_loop(1, n, body, (dq, dka, dva, kb, vb))
    return dq.astype(q.dtype), dka.astype(k.dtype), dva.astype(v.dtype)


_zigzag_flash.defvjp(_zigzag_flash_fwd_rule, _zigzag_flash_bwd_rule)


def zigzag_flash_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
):
    """:func:`zigzag_ring_attention` with Pallas flash kernels as the block
    computation — balanced causal work AND O(T)-memory MXU tiles. Data must
    be zigzag-permuted (:func:`zigzag_permutation`). Off TPU the kernels
    run interpreted; use ``check_vma=False`` on the enclosing shard_map."""
    if not causal:
        return ring_flash_attention(q, k, v, axis_name, causal=False,
                                    scale=scale)
    if not isinstance(axis_name, str):
        raise ValueError(
            f"zigzag_flash_attention needs a single named mesh axis, got "
            f"{axis_name!r}"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _zigzag_flash(q, k, v, axis_name, float(scale))


def zigzag_permutation(t_global: int, n_shards: int):
    """Sequence permutation for the zigzag (striped-block) layout.

    The global sequence is split into ``2n`` chunks; shard ``i`` holds chunks
    ``(i, 2n-1-i)`` — one early and one late chunk — so each rank's causal
    workload is equal (the contiguous layout gives rank 0 one visible block
    and rank n-1 all n: the classic ring-attention imbalance).

    Returns an index array ``perm`` of length ``t_global`` such that
    ``x[:, perm]`` laid out contiguously over ``n_shards`` gives every shard
    its zigzag chunk pair. Apply the SAME permutation to tokens and targets
    (next-token pairing is preserved; a mean loss over tokens is
    permutation-invariant, so training needs no unpermute). Invert for
    outputs with ``jnp.argsort(perm)``.
    """
    if t_global % (2 * n_shards):
        raise ValueError(
            f"sequence length {t_global} must divide into 2*{n_shards} chunks"
        )
    c = t_global // (2 * n_shards)
    idx = []
    for i in range(n_shards):
        idx.append(jnp.arange(i * c, (i + 1) * c))
        j = 2 * n_shards - 1 - i
        idx.append(jnp.arange(j * c, (j + 1) * c))
    return jnp.concatenate(idx)


def zigzag_positions(rank, n_shards: int, t_local: int):
    """Global positions of shard ``rank``'s tokens under the zigzag layout
    (``rank`` may be traced, e.g. ``lax.axis_index``). Shape ``[t_local]`` —
    feed to position embeddings in place of the contiguous
    ``offset + arange`` base."""
    c = t_local // 2
    early = rank * c + jnp.arange(c)
    late = (2 * n_shards - 1 - rank) * c + jnp.arange(c)
    return jnp.concatenate([early, late])


def zigzag_ring_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
):
    """Causal ring attention over a **zigzag-sharded** sequence — the
    load-balanced form of :func:`ring_attention`.

    Each shard holds the chunk pair ``(i, 2n-1-i)`` of a ``2n``-chunk global
    sequence (lay data out with :func:`zigzag_permutation`). Per ring step
    every rank then does the SAME useful work — exactly half the chunk-pair
    interactions are visible, and they are computed without masks:

    - block from an earlier rank (``src < my``): all local queries attend the
      block's early chunk only (its late chunk is entirely in the future);
    - block from a later rank (``src > my``): only the local late chunk
      attends, but it sees the whole block;
    - the local (diagonal) block needs the one genuinely masked update.

    Total FLOPs are ~half of contiguous causal ring (which computes every
    masked block) and per-rank work is equal, so the per-step ppermute
    barrier no longer waits on a straggler. Exact: matches full attention on
    the unpermuted sequence (tested). Differentiable.
    """
    if not causal:
        # zigzag exists solely to balance the causal mask; unmasked ring
        # attention is layout-independent
        return ring_attention(q, k, v, axis_name, causal=False, scale=scale)
    if not isinstance(axis_name, str):
        raise ValueError(
            f"zigzag_ring_attention needs a single named mesh axis, got "
            f"{axis_name!r}"
        )
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    if t % 2:
        raise ValueError(f"local sequence length {t} must be even (chunk pair)")
    c = t // 2
    if scale is None:
        scale = d ** -0.5

    q32 = q.astype(jnp.float32)
    vma = (frozenset({axis_name}) | jax.typeof(q).vma
           | jax.typeof(k).vma | jax.typeof(v).vma)
    _vary = lambda x: _vary_to(x, vma)
    m = _vary(jnp.full((b, h, t), _NEG_BIG, jnp.float32))
    l = _vary(jnp.zeros((b, h, t), jnp.float32))
    o = _vary(jnp.zeros((b, t, h, d), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Step 0 — the diagonal block: the one masked update (positions are the
    # zigzag pair's, not contiguous).
    pos = zigzag_positions(my, n, t)
    mask0 = pos[:, None] >= pos[None, :]
    m, l, o = _block_attend(q32, k, v, scale=scale, mask=mask0, m=m, l=l, o=o)
    kb = lax.ppermute(k, axis_name, perm)
    vb = lax.ppermute(v, axis_name, perm)

    # Branch-free ring steps, like _zigzag_flash_fwd_pass: a lax.cond body
    # serializes the permutes against the block compute on TPU schedules
    # (XLA will not hoist collective starts across control flow — PERF.md
    # "Ring overlap"). Both former branches are the SAME two unmasked
    # (c x c) block updates with selected operands; the online-softmax
    # update is exact in any order, so chaining two updates equals the old
    # single wider update.
    def body(step, carry):
        m, l, o, kb, vb = carry
        # src = (my - step) % n; for step in [1, n) src < my <=> my >= step
        earlier = my >= step
        ke, ve, kl, vl = kb[:, :c], vb[:, :c], kb[:, c:], vb[:, c:]
        m_e, m_l = m[:, :, :c], m[:, :, c:]
        l_e, l_l = l[:, :, :c], l[:, :, c:]
        o_e, o_l = o[:, :c], o[:, c:]
        q_e, q_l = q32[:, :c], q32[:, c:]

        # call 1: (q_e x k_e) on the early state (earlier-rank block) or
        # (q_l x k_e) on the late state (later-rank block)
        m1, l1, o1 = _block_attend(
            jnp.where(earlier, q_e, q_l), ke, ve, scale=scale, mask=None,
            m=jnp.where(earlier, m_e, m_l),
            l=jnp.where(earlier, l_e, l_l),
            o=jnp.where(earlier, o_e, o_l),
        )
        # call 2 always updates the late state: from the ORIGINAL late
        # state when call 1 touched the early half, or chained on call 1's
        # output when both calls are late-row updates
        m2, l2, o2 = _block_attend(
            q_l, jnp.where(earlier, ke, kl), jnp.where(earlier, ve, vl),
            scale=scale, mask=None,
            m=jnp.where(earlier, m_l, m1),
            l=jnp.where(earlier, l_l, l1),
            o=jnp.where(earlier, o_l, o1),
        )
        m = jnp.concatenate([jnp.where(earlier, m1, m_e), m2], axis=2)
        l = jnp.concatenate([jnp.where(earlier, l1, l_e), l2], axis=2)
        o = jnp.concatenate([jnp.where(earlier, o1, o_e), o2], axis=1)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return m, l, o, kb, vb

    m, l, o, _, _ = lax.fori_loop(1, n, body, (m, l, o, kb, vb))
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_impl: str = "xla",
    head_chunks: int = 1,
):
    """Exact attention via all-to-all head re-sharding (DeepSpeed-Ulysses
    collective shape, done with one XLA ``all_to_all`` each way).

    Per-device shards ``[B, T_local, H, D]`` with ``H`` divisible by the
    axis size; internally each device holds the FULL sequence for ``H/n``
    heads, so memory per device is ``T_global * H/n`` — choose ring
    attention instead when the full sequence per device is too large.

    ``block_impl='flash'`` runs the local per-head attention through the
    Pallas kernel (O(T) memory for the scores instead of the XLA path's
    materialized ``[B, H/n, T, T]`` tile — at long T that tile, not the
    K/V, is what OOMs first); the collectives are unchanged and
    differentiation works through the kernel's custom VJP + the
    ``all_to_all`` transpose. Off TPU the kernel runs interpreted (use
    ``check_vma=False`` on the enclosing shard_map, like 'flash').

    ``head_chunks > 1`` splits the local heads into that many groups and
    runs the exchange+attend+exchange pipeline per group, UNROLLED: group
    g+1's all_to_alls have no data dependency on group g's attention —
    the plain form's all_to_alls are provably un-hideable (exchange ->
    attend -> exchange are sequentially dependent). Exact for any
    chunking (heads are independent); per-group working memory drops by
    the same factor. NOTE the overlap is structural readiness, not a
    measured win on this toolchain: the current XLA TPU build lowers
    all_to_all synchronously (no -start/-done pair to schedule around;
    AOT-verified, PERF.md "Ring overlap"), so today the chunking buys
    memory granularity and future async toolchains the opportunity.
    """
    if not isinstance(axis_name, str):
        raise ValueError(
            f"ulysses_attention needs a single named mesh axis, got {axis_name!r} "
            "— use a flat communicator (e.g. 'tpu') for sequence parallelism"
        )
    if block_impl not in ("xla", "flash"):
        # a silent fallback to the XLA path would materialize the exact
        # O(T^2) score tile the flag exists to avoid
        raise ValueError(
            f"block_impl must be 'xla' or 'flash', got {block_impl!r}")
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"heads ({h}) must be divisible by axis size ({n})")
    if head_chunks < 1 or h % head_chunks or (h // head_chunks) % n:
        raise ValueError(
            f"head_chunks={head_chunks} must partition the {h} heads into "
            f"groups divisible by the axis size ({n})"
        )

    def to_heads(x):  # [B, T, Hg, D] -> [B, n*T, Hg/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):  # [B, n*T, Hg/n, D] -> [B, T, Hg, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def attend(qg, kg, vg):
        if block_impl == "flash":
            from chainermn_tpu.ops import flash_attention

            return flash_attention(qg, kg, vg, causal=causal, scale=scale)
        return full_attention(qg, kg, vg, causal=causal, scale=scale)

    hg = h // head_chunks
    outs = []
    for g in range(head_chunks):  # unrolled: groups are independent
        sl = slice(g * hg, (g + 1) * hg)
        outs.append(to_seq(attend(
            to_heads(q[:, :, sl]), to_heads(k[:, :, sl]),
            to_heads(v[:, :, sl]))))
    return outs[0] if head_chunks == 1 else jnp.concatenate(outs, axis=2)


def ulysses_flash_attention(q, k, v, axis_name: str, *, causal: bool = False,
                            scale: Optional[float] = None):
    """:func:`ulysses_attention` with the Pallas flash kernel as the local
    attention (``block_impl='flash'``)."""
    return ulysses_attention(q, k, v, axis_name, causal=causal, scale=scale,
                             block_impl="flash")


def cached_attention(q, kbuf, vbuf, pos_offset, *, scale: Optional[float] = None):
    """Decode-time attention: ``S`` new queries against a static KV buffer.

    ``q [B, S, H, D]`` holds queries for global positions ``pos_offset ..
    pos_offset+S-1``; ``kbuf/vbuf [B, Tc, H, D]`` are the cache buffers
    whose first ``pos_offset+S`` rows are valid (later rows are masked by
    position, so their contents — typically zeros — never contribute).
    Static shapes throughout: the compiled program is one [S, Tc] score
    tile per head, O(Tc*D) per decoded token instead of the O(Tc^2)
    re-forward of cacheless decoding. Shared by the dense and
    tensor-parallel decode paths (``pos_offset`` may be traced).

    ``pos_offset`` may also be a ``[B]`` vector of per-sequence bases: each
    batch row then decodes at its OWN position — the continuous-batching
    shape, where one call advances every cache slot one token regardless of
    how far along each slot's sequence is."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kbuf,
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(kbuf.shape[1])
    if jnp.ndim(pos_offset) == 0:
        q_pos = pos_offset + jnp.arange(q.shape[1])          # [S]
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None]  # [1,1,S,Tc]
    else:
        q_pos = pos_offset[:, None] + jnp.arange(q.shape[1])[None]  # [B, S]
        mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]  # [B,1,S,Tc]
    s = jnp.where(mask, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vbuf.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _dequant_cached_attention(q, k8, k_sc, v8, v_sc, pos_offset, *,
                              scale: Optional[float] = None):
    """:func:`cached_attention` over an int8 K/V view with the dequant
    scales FOLDED into the contractions instead of materialized: the QK
    product runs on the raw int8 rows and its f32 scores are multiplied
    by ``k_sc`` per key column; the probabilities are multiplied by
    ``v_sc`` per key row before the PV product. Same math by linearity
    (the scales are per-row constants along the contracted dims), but
    the ``[B, T, H, D]`` dequantized f32 view never exists — the read
    path moves int8 rows plus the f32 scale vectors, preserving the
    ``kv_quant='int8'`` bandwidth win at read time (PERF.md "Paged-decode
    kernel"). ``k8``/``v8`` are ``[B, T, H, D]`` int8, ``k_sc``/``v_sc``
    their ``[B, T, H]`` f32 scales; masking is identical to
    :func:`cached_attention`."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k8.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = s * jnp.moveaxis(k_sc, 2, 1)[:, :, None, :]           # [B,H,1,T]
    k_pos = jnp.arange(k8.shape[1])
    if jnp.ndim(pos_offset) == 0:
        q_pos = pos_offset + jnp.arange(q.shape[1])
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None]
    else:
        q_pos = pos_offset[:, None] + jnp.arange(q.shape[1])[None]
        mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]
    s = jnp.where(mask, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    p = p * jnp.moveaxis(v_sc, 2, 1)[:, :, None, :]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v8.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_scale_shape(n_blocks: int, block_size: int, heads: int) -> tuple:
    """Shape of an int8 block store's scale arrays, ``[n_blocks, 1, W]``: a
    block's ``block_size * heads`` scales in ONE row (row ``t`` of the
    block, head ``h`` in column ``t * heads + h``) brought to whole tiles
    of 128 lanes, zeros after them. The chip tiles such an array row by
    row, so a block's scales are contiguous: the decode kernel copies them
    out, and :func:`~chainermn_tpu.parallel.paged_kernel.write_scale_rows`
    writes them, as they lie. Padded in the store itself the pad costs no
    pass a call (nor memory: the tiled layout holds a narrower row in whole
    lanes anyway)."""
    return (n_blocks, 1, -(-block_size * heads // 128) * 128)


def paged_store_shape(n_blocks: int, block_size: int, heads: int,
                      head_dim: int, quant: str = "none") -> tuple:
    """Shape of a block store's K (or V) array: ``[n_blocks, block_size,
    heads, head_dim]``, but for an int8 store of fewer than 4 heads ``[n_blocks,
    block_size * heads, head_dim]``, row ``t`` of head ``h`` in row ``t *
    heads + h``. That is the view the decode kernel takes of any store (a
    block as one tile of rows); with 4 heads or more the chip lays the
    four-dimensional array out so that the view is free, with fewer it packs
    four int8 rows of ONE head into a word (tokens before heads), and every
    program that wrote a row or ran the kernel then relaid the whole store
    (ten passes over it a decode step at 2 KV heads of 256, PERF.md section
    6, PR 35). Held folded, the array is what the kernel reads and the write
    scatters into, as it lies."""
    if quant == "int8" and heads < 4:
        return (n_blocks, block_size * heads, head_dim)
    return (n_blocks, block_size, heads, head_dim)


def _store_block_size(store, heads: int) -> int:
    """Tokens a block of ``store`` holds (see :func:`paged_store_shape`)."""
    return store.shape[1] if store.ndim == 4 else store.shape[1] // heads


def _unfolded(rows, heads: int):
    """Blocks gathered from a store, ``[n, bs, heads, D]`` whichever way the
    store holds them. Only ever what was gathered."""
    if rows.ndim == 4:
        return rows
    return rows.reshape(rows.shape[0], -1, heads, rows.shape[-1])


def fold_block_scales(sc):
    """``[n, bs, H]`` per-row-per-head scales as rows of a scale array:
    ``[n, 1, W]``, as :func:`paged_scale_shape` has it."""
    n, bs, h = sc.shape
    width = paged_scale_shape(n, bs, h)[2]
    return jnp.pad(sc.reshape(n, 1, bs * h),
                   ((0, 0), (0, 0), (0, width - bs * h)))


def unfold_block_scales(gathered, bs: int, h: int):
    """Rows gathered from a scale array, ``[n, 1, W]``, as ``[n, bs, H]``.
    Only ever what was gathered: reshaping the array itself would copy it
    whole."""
    return gathered[:, 0, :bs * h].reshape(-1, bs, h)


def paged_update_cache_and_attend(kv_cache, q, k, v, pos_offset, *,
                                  scale: Optional[float] = None):
    """The paged twin of :func:`update_cache_and_attend`: K/V live in a
    shared **block store** instead of dense per-sequence regions, and each
    batch row reaches its own sequence through a **block table**.

    ``kv_cache`` is a dict with:

    - ``'k'``/``'v'``: the store, ``[n_blocks, block_size, H, D]`` — one
      pool of fixed-size token blocks shared by every sequence (and, in
      the serving engine, by the prefix cache: a cached prefix is just a
      table entry, not a copy);
    - ``'table'``: ``[B, max_blocks]`` int32 — row ``b``'s ``j``-th entry
      is the store block holding positions ``[j*bs, (j+1)*bs)`` of
      sequence ``b``. Entries for not-yet-written spans may be junk (by
      convention a reserved scratch block): the position mask hides every
      row at positions beyond the query, exactly like the dense path's
      stale-rows argument;
    - optional ``'k_scale'``/``'v_scale'``: f32 of :func:`paged_scale_shape`,
      ``[n_blocks, 1, W]`` — present iff the store is int8-quantized. Each
      resident row carries one symmetric scale per head
      (``x ≈ x_q * scale``), row ``t`` of a block's head ``h`` in
      column ``t * H + h``: a block's scales are ONE row of whole lanes,
      the shape in which the write (the touched blocks' rows patched in
      place) and the decode kernel (one contiguous copy a block) take the
      array as it lies, so no program relays it. Writes quantize, the
      attention dequantizes in-program.
    - optional ``'valid'``: ``[B]`` int32 — per-row count of *leading*
      query positions whose K/V rows should actually land in the store.
      Rows ``j >= valid[b]`` are redirected into the scratch block
      (block 0) instead: the speculative verify window feeds ``k+1``
      rows per slot but slots near their cache limit may only have
      headroom for fewer, and without the redirect the clamped
      ``pos // bs`` table lookup would silently overwrite a *live* row.
      The attention itself is unaffected (the position mask already
      hides rows beyond each query).

    Writes scatter the ``S`` new rows through the table
    (``store[table[b, p//bs], p%bs] = kv[b, p]``); the attention gathers
    each row's table span back into a per-sequence view and runs the same
    position-masked :func:`cached_attention`. The gathered span is the
    full ``max_blocks`` when positions are traced (the serving engine's
    compiled bodies — shapes must not depend on values), but callers with
    CONCRETE positions get the span tightened to the batch-max active
    block count ``ceil(max(lengths)/bs)``: fully-masked table tail
    entries are provably never read, so they are not gathered either.
    Per-row valid lengths are ``pos_offset + S`` (post-write); a
    ``'lengths'`` entry in ``kv_cache`` overrides them.

    An int8 store's dequant scales fold into the attention contractions
    per-block (scores scaled after the QK product, probabilities before
    the PV product) — the dequantized f32 dense view is never
    materialized, read bytes stay int8-sized.

    A ``'window'`` entry (a static int) marks a window layer: the table
    row is then a ring (the block of positions ``[j*bs, (j+1)*bs)`` sits
    in entry ``j % max_blocks``, see :func:`paged_write_kv`) and a query at
    position ``t`` sees ``t - window < j <= t``. A store with fewer heads
    than ``q`` holds grouped KV heads (query head ``g`` reads KV head
    ``g // (H // Hkv)``). Both go through :func:`_paged_gather_attention`
    or, with ``'use_kernel'``, the kernel's ``first``.

    A truthy ``'use_kernel'`` entry routes the read side through the
    fused Pallas kernel (:func:`chainermn_tpu.parallel.paged_kernel.
    paged_attend`): one program per row walks that row's
    ``ceil(len/bs)`` live blocks in chunks, copying them from the store
    itself while it computes, with the dequant and the online softmax in
    the same pass; the table's dead tail is neither copied nor looked
    through. The scatter of the rows (write side) is XLA on every path — it
    moves ``S`` rows, the kernel owns the O(length) read; an int8 store's
    scales are written by a kernel of their own on every path
    (:func:`paged_write_kv`). ``'use_kernel'``
    must be a static Python bool (it selects a trace, it is not an
    operand).

    Static shapes throughout — table contents change, programs never
    recompile. Returns ``(out, new_cache)`` where ``new_cache`` carries
    the updated store (and scales) WITHOUT the table: the table is
    host-managed state threaded in per call."""
    table = kv_cache["table"]
    quant = "k_scale" in kv_cache
    hk = k.shape[2]
    bs = _store_block_size(kv_cache["k"], hk)
    b, s = q.shape[0], q.shape[1]
    if jnp.ndim(pos_offset) == 0:
        pos_offset = jnp.full((b,), pos_offset, jnp.int32)
    new_cache = paged_write_kv(kv_cache, k, v, pos_offset)
    new_k, new_v = new_cache["k"], new_cache["v"]
    new_ks, new_vs = new_cache.get("k_scale"), new_cache.get("v_scale")

    lengths = kv_cache.get("lengths")
    if lengths is None:
        lengths = pos_offset + s                              # post-write
    m_used = table.shape[1]
    window = kv_cache.get("window")
    if window is None and not isinstance(lengths, jax.core.Tracer):
        # concrete positions: tighten the span to the batch-max active
        # block count — the masked tail is provably never read
        m_used = max(1, min(m_used, -(-int(jnp.max(lengths)) // bs)))

    if kv_cache.get("use_kernel"):
        from chainermn_tpu.parallel.paged_kernel import paged_attend
        # a window layer's rows start at their first visible position and
        # go round the whole table row; any other's are cut at ``m_used``
        where = ({"max_blocks": m_used} if window is None else
                 {"first": jnp.maximum(lengths - s + 1 - window, 0)})
        out = paged_attend(q, new_k, new_v, table, lengths,
                           k_scale=new_ks, v_scale=new_vs, scale=scale,
                           kv_heads=hk, **where)
    elif window is not None or q.shape[2] != hk:
        out = _paged_gather_attention(
            q, new_k, new_ks, new_v, new_vs, table, pos_offset,
            window=window, scale=scale, kv_heads=hk)
    else:
        flat = table[:, :m_used].reshape(-1)                  # [B*m]

        def gather(store, scales):
            rows = _unfolded(jnp.take(store, flat, axis=0), hk)
            rows = rows.reshape((b, -1) + rows.shape[2:])  # [B, m*bs, H, D]
            if not quant:
                return rows.astype(q.dtype), None
            sc = unfold_block_scales(jnp.take(scales, flat, axis=0), bs, hk)
            return rows, sc.reshape(rows.shape[:3])

        kbuf, ksc = gather(new_k, new_ks)
        vbuf, vsc = gather(new_v, new_vs)
        if quant:
            out = _dequant_cached_attention(q, kbuf, ksc, vbuf, vsc,
                                            pos_offset, scale=scale)
        else:
            out = cached_attention(q, kbuf, vbuf, pos_offset, scale=scale)
    return out, new_cache


def paged_write_kv(kv_cache, k, v, pos_offset):
    """The write side of :func:`paged_update_cache_and_attend` alone: the
    ``S`` new K/V rows of each batch row scattered through its table into
    the store (quantized where the store is int8), ``{'k', 'v'[,
    'k_scale', 'v_scale']}`` handed back without the table. A prefill
    that attends its own fresh K/V (a flash kernel over the prompt) calls
    this for the store and nothing else. An int8 store's scales go in
    through :func:`~chainermn_tpu.parallel.paged_kernel.write_scale_rows`
    on every path (interpreted off the chip): the only in-place write of
    their layout there is.

    ``kv_cache['valid']`` (``[B]``) sends the rows past each sequence's
    count to the scratch block. ``kv_cache['window']`` (a window layer)
    makes the table row a ring: the block of positions ``[j*bs, (j+1)*bs)``
    sits in entry ``j % max_blocks``; with ``valid`` beside it only the
    last ``max_blocks`` blocks of the ``valid[b]`` rows are written, the
    others go to scratch: two blocks of one call that share an entry would
    leave it to the scatter's order which of them stays."""
    store_k, store_v = kv_cache["k"], kv_cache["v"]
    table = kv_cache["table"]
    quant = "k_scale" in kv_cache
    h = k.shape[2]
    bs = _store_block_size(store_k, h)
    b, s = k.shape[0], k.shape[1]
    if jnp.ndim(pos_offset) == 0:
        pos_offset = jnp.full((b,), pos_offset, jnp.int32)
    pos = pos_offset[:, None] + jnp.arange(s)[None, :]        # [B, S]
    ring = table.shape[1] if "window" in kv_cache else None
    entry = pos // bs if ring is None else (pos // bs) % ring
    blk = jnp.take_along_axis(table, entry, axis=1).reshape(-1)
    off = (pos % bs).reshape(-1)
    valid = kv_cache.get("valid")
    if valid is not None:
        # redirect rows past each sequence's valid count into the scratch
        # block so a clamped table lookup can never clobber a live row
        rv = jnp.arange(s)[None, :] < valid[:, None]
        if ring is not None:
            newest = (pos_offset + valid - 1) // bs
            rv = rv & (pos // bs > newest[:, None] - ring)
        rv = rv.reshape(-1)
        blk = jnp.where(rv, blk, 0)
        off = jnp.where(rv, off, 0)

    if store_k.ndim == 3:
        # a store held folded (paged_store_shape): head g of row t is row
        # t * H + g of its block
        at = (blk[:, None], off[:, None] * h + jnp.arange(h)[None, :])
    else:
        at = (blk, off)

    def rows_of(store, rows):
        """The store with the call's rows in it, and the rows' scales."""
        rows = rows.reshape((b * s,) + rows.shape[2:])        # [B*S, H, D]
        if not quant:
            return store.at[at].set(rows.astype(store.dtype)), None
        r32 = rows.astype(jnp.float32)
        # symmetric per-row-per-head scale; the epsilon keeps all-zero
        # rows (warmup, padding) from dividing by zero
        sc = jnp.maximum(jnp.max(jnp.abs(r32), axis=-1) / 127.0, 1e-8)
        q8 = jnp.clip(jnp.round(r32 / sc[..., None]), -127, 127)
        return store.at[at].set(q8.astype(jnp.int8)), sc

    new_k, k_sc = rows_of(store_k, k)
    new_v, v_sc = rows_of(store_v, v)
    new_cache = {"k": new_k, "v": new_v}
    if not quant:
        return new_cache

    # the scales go in by the block: of those a sequence's S rows touch (its
    # first block and the ``n_t - 1`` after it) the rows written now take
    # the fresh scales, the others keep theirs. Row ``i`` of the call sits
    # in touched block ``(lead + i) // bs`` at row ``(lead + i) % bs``, so
    # what a block takes is one run of its rows, ``[lo, hi)``
    lead = (pos_offset % bs)[:, None]                         # [B, 1]
    n_t = (s + bs - 2) // bs + 1
    n_rows = s if valid is None else jnp.minimum(valid, s)[:, None]
    first = jnp.arange(n_t)[None, :] * bs - lead     # call's row at row 0
    lo = jnp.clip(-first, 0, bs)                              # [B, n_t]
    hi = jnp.clip(n_rows - first, 0, bs)
    t_entry = pos_offset[:, None] // bs + jnp.arange(n_t)[None, :]
    if ring is not None:
        if valid is not None:
            # of the valid rows the last ring of blocks alone (as above)
            hi = jnp.where(t_entry > newest[:, None] - ring, hi, lo)
        t_entry = t_entry % ring
    # a touched block that takes no row (a prompt's padding, the blocks a
    # ring has dropped) or lies outside the store (a clamped lookup, whose
    # rows the scatter above drops) is the scratch block
    t_blk = jnp.take_along_axis(table, t_entry, axis=1)
    t_blk = jnp.where((hi > lo) & (t_blk > 0) & (t_blk < store_k.shape[0]),
                      t_blk, 0)

    def fresh(sc):
        """The call's scales ``[B*S, H]`` at each row of the touched
        blocks (the call's nearest where none sits): ``[B*n_t, 1, W]``."""
        sc = sc.reshape(b, s, h)
        if s == 1:
            sc = jnp.broadcast_to(sc, (b, n_t * bs, h))
        else:
            at = jnp.clip(jnp.arange(n_t * bs)[None, :] - lead, 0, s - 1)
            sc = jnp.take_along_axis(sc, at[:, :, None], axis=1)
        return fold_block_scales(sc.reshape(b * n_t, bs, h))

    # XLA writes a row of these arrays only after relaying all of them (a
    # pass over every array of every layer, a decode step: PERF.md §6, PR
    # 32), so on every path the rows go in through the kernel
    from chainermn_tpu.parallel.paged_kernel import write_scale_rows
    new_cache["k_scale"], new_cache["v_scale"] = write_scale_rows(
        kv_cache["k_scale"], kv_cache["v_scale"], t_blk.reshape(-1),
        (lo * h).reshape(-1), (hi * h).reshape(-1), fresh(k_sc), fresh(v_sc))
    return new_cache


def _paged_gather_attention(q, store_k, k_scale, store_v, v_scale, table,
                            pos_offset, *, window=None,
                            scale: Optional[float] = None,
                            kv_heads: Optional[int] = None):
    """The XLA read path for what the plain one does not cover: grouped KV
    heads (``q [B, S, H, D]`` over a store of ``Hkv`` heads, query head
    ``g`` reading ``g // (H // Hkv)``) and a window layer's rows, whose
    blocks go round the table row and which see the ``window`` positions
    up to their own. Every table entry is gathered; an entry's
    block is the newest one that maps to it at the query's position. The
    reference the kernel is tested against, and the path an engine without
    the kernel serves from: it forms ``[B, Hkv, G, S, entries * bs]``
    scores."""
    b, s, h, d = q.shape
    hk = kv_heads if store_k.ndim == 3 else store_k.shape[2]
    bs = _store_block_size(store_k, hk)
    g = h // hk
    if scale is None:
        scale = d ** -0.5
    n = table.shape[1]
    flat = table.reshape(-1)

    def gather(x):
        rows = _unfolded(jnp.take(x, flat, axis=0), hk)
        return rows.reshape((b, n * bs) + rows.shape[2:])

    def scales(x):
        gathered = unfold_block_scales(jnp.take(x, flat, axis=0), bs, hk)
        return gathered.reshape(b, n * bs, hk)

    kbuf, vbuf = gather(store_k), gather(store_v)
    qg = q.reshape(b, s, hk, g, d)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kbuf.astype(q.dtype),
                    preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        sc = sc * jnp.moveaxis(scales(k_scale), 2, 1)[:, :, None, None, :]
    q_pos = pos_offset[:, None] + jnp.arange(s)[None, :]      # [B, S]
    entry = (jnp.arange(n * bs) // bs)[None, None, :]         # [1, 1, K]
    row = (jnp.arange(n * bs) % bs)[None, None, :]
    cur = (q_pos // bs)[:, :, None]                           # [B, S, 1]
    if window is None:
        k_pos = entry * bs + row
        mask = k_pos <= q_pos[:, :, None]
    else:
        k_pos = (cur - (cur - entry) % n) * bs + row
        mask = ((k_pos >= 0) & (k_pos <= q_pos[:, :, None])
                & (k_pos > q_pos[:, :, None] - window))
    sc = jnp.where(mask[:, None, None], sc, _NEG_BIG)
    p = jax.nn.softmax(sc, axis=-1)
    if v_scale is not None:
        p = p * jnp.moveaxis(scales(v_scale), 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vbuf.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


def update_cache_and_attend(kv_cache, q, k, v, pos_offset, *,
                            scale: Optional[float] = None):
    """Write ``S`` new K/V rows into the cache at ``pos_offset`` and attend
    the matching queries against the updated buffers — the one shared
    decode-step body for the dense and tensor-parallel cached paths.
    Returns ``(out, new_cache)`` with ``new_cache`` the same ``{'k','v'}``
    dict shape. Causal by construction (the position mask).

    A ``[B]`` ``pos_offset`` writes each batch row's K/V at that row's own
    position (vmapped per-row update) — the slot-pool decode step, where
    every slot sits at a different depth in its sequence.

    A ``kv_cache`` carrying a ``'table'`` entry takes the **paged** path
    (:func:`paged_update_cache_and_attend`): the buffers are then a shared
    block store indexed per row through the block table."""
    if "table" in kv_cache:
        return paged_update_cache_and_attend(kv_cache, q, k, v, pos_offset,
                                             scale=scale)
    if jnp.ndim(pos_offset) == 0:
        kbuf = lax.dynamic_update_slice(
            kv_cache["k"], k.astype(kv_cache["k"].dtype),
            (0, pos_offset, 0, 0))
        vbuf = lax.dynamic_update_slice(
            kv_cache["v"], v.astype(kv_cache["v"].dtype),
            (0, pos_offset, 0, 0))
    else:
        row_update = jax.vmap(
            lambda buf, new, p: lax.dynamic_update_slice(buf, new, (p, 0, 0)))
        kbuf = row_update(kv_cache["k"], k.astype(kv_cache["k"].dtype),
                          pos_offset)
        vbuf = row_update(kv_cache["v"], v.astype(kv_cache["v"].dtype),
                          pos_offset)
    out = cached_attention(q, kbuf, vbuf, pos_offset, scale=scale)
    return out, {"k": kbuf, "v": vbuf}


def full_attention(q, k, v, *, causal: bool = False, scale: Optional[float] = None,
                   precision=None):
    """Single-device exact attention, same layout/semantics — the reference
    implementation the parallel variants are tested against, and the
    fallback when no sequence axis is sharded.

    ``precision``: forwarded to the einsums. TPU matmuls at the default
    precision round f32 operands through bf16 passes (~1e-3 abs error) —
    oracle uses (e.g. the on-chip parity battery) pass ``"highest"`` so the
    reference is actually f32-accurate."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32,
                   precision=precision)
    s = s * scale
    if causal:
        t, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None, :, :], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32, precision=precision)
    return out.astype(q.dtype)


def sequence_parallel_attention(
    kind: str,
    axis_name: Optional[str],
    *,
    causal: bool = False,
    scale: Optional[float] = None,
):
    """Pick an attention implementation by name: ``'ring'`` |
    ``'ring_flash'`` (ring with Pallas kernel blocks) | ``'zigzag'``
    (load-balanced causal ring; data must be zigzag-permuted) |
    ``'ulysses'`` | ``'full'`` | ``'flash'``. Returns ``f(q, k, v) -> o``
    for use inside a traced step. ``'flash'`` is the Pallas-kernel local
    attention (:mod:`chainermn_tpu.ops.flash_attention`) — same semantics
    as ``'full'``, O(T) memory; use it when the sequence is NOT sharded."""
    if kind == "flash":
        if axis_name is not None:
            raise ValueError(
                "attention='flash' is local (unsharded-sequence) attention; "
                "it cannot attend across a sharded sequence axis "
                f"({axis_name!r}) — use 'ring' or 'ulysses' there"
            )
        from chainermn_tpu.ops import flash_attention

        return functools.partial(flash_attention, causal=causal, scale=scale)
    if kind == "full" or axis_name is None:
        return functools.partial(full_attention, causal=causal, scale=scale)
    if kind not in ("ring", "ring_flash", "zigzag", "zigzag_flash",
                    "ulysses", "ulysses_flash"):
        raise ValueError(
            f"unknown attention kind {kind!r}; use "
            "ring|ring_flash|zigzag|zigzag_flash|ulysses|ulysses_flash|"
            "full|flash"
        )
    impl = {"ring": ring_attention, "ring_flash": ring_flash_attention,
            "zigzag": zigzag_ring_attention,
            "zigzag_flash": zigzag_flash_attention,
            "ulysses": ulysses_attention,
            "ulysses_flash": ulysses_flash_attention}[kind]

    def f(q, k, v):
        try:
            lax.axis_size(axis_name)
        except NameError:
            # axis not bound: we're outside shard_map (flax init, eval on a
            # gathered sequence) — the whole sequence is local, so exact
            # full attention IS the correct semantics (params are identical).
            # CAVEAT for 'zigzag': data fed to the sharded model is
            # zigzag-PERMUTED; outside the mesh, un-permute it first
            # (jnp.argsort(zigzag_permutation(...))) or these causal
            # positions are wrong. Init is value-independent, so module
            # construction is unaffected.
            return full_attention(q, k, v, causal=causal, scale=scale)
        return impl(q, k, v, axis_name, causal=causal, scale=scale)

    return f
