"""Pallas TPU kernels for the gated delta rule (the linear-attention layer
of :mod:`chainermn_tpu.models.qwen3_next`): over whole prompts
(:func:`chunk_gated_delta`) and one token a row on the state store
(:func:`recurrent_gated_delta`).

Per value head the rule keeps a float32 state ``S [dk, dv]`` from zero and,
token by token, ``S = exp(g_t) S``, ``d = beta_t (v_t - S^T k_t)``,
``S = S + k_t d^T``, ``o_t = S^T q_t``. Over a prompt it runs in chunks of
:data:`CHUNK` tokens (arXiv:2412.06464, section 3.3): inside a chunk the
tokens' updates are one unit-triangular system, solved by doubling, and the
state carries from one chunk to the next. The XLA form of the same
(``chunk_gated_delta_rule`` in the model) writes every chunk's ``[64, 64]``
matrices of every head to HBM and carries the state through a ``lax.scan``;
here:

- **one program per (row, pair of value heads of one key head)**, walking
  the row's chunks in order (the grid's last axis, sequential): the two
  states are a VMEM scratch, zeroed at the row's first chunk and written out
  once after its last live one. Nothing of a chunk goes through HBM but its
  operands and its output;
- **the pair side by side in the 128 lanes**: a chunk's ``[64, 64]``
  matrices of the two heads are one ``[64, 128]`` array, so a product of
  two heads' matrices is one product against a block-diagonal ``[128,
  128]``: half the pushes through the MXU of a head at a time, where these
  products are most of the kernel's work;
- **operands in the layer's own layout**: ``q``, ``k`` and ``v`` are read
  straight out of the convolution's output, ``[B, T, 2 Hk dk + Hv dv]``,
  and the output is ``[B, T, Hv * dv]``. Value heads ``2p`` and ``2p + 1``
  read key head ``2p // (Hv / Hk)`` as one column block of ``dk`` lanes,
  chosen by the index map: no slice of ``v``, no repeat of the key heads,
  no move to a head-major layout. The kernel norms ``q`` and ``k`` itself
  (their rows' sums of squares are a product against ones), so no normed
  copy of them is written either. ``g`` and ``beta`` go in as ``[B, Hv /
  2, N, 2 C]`` (a pair's chunk is a row of lanes), a row's whole prompt a
  block;
- **only live chunks**: ``valid [B]`` is a scalar-prefetch operand. Chunks at
  or past ``cdiv(valid[b], C)`` are neither copied nor computed: their index
  maps repeat the last live chunk, which the pipeline does not copy again,
  and their output blocks are zeros. Positions at or past ``valid[b]``
  inside the last live chunk take ``g = beta = 0``, read as zeros and give
  zeros, so they leave the state as it is. A row with ``valid == 0`` walks
  nothing and hands back a zero state;
- **float32 throughout, every product at ``HIGHEST``**. What must go from
  lanes to sublanes (a chunk's cumulative gates and ``beta``, one value a
  position) does so through products against the identity and triangles of
  ones, exact at this precision: the chip's lane rotations and transposes
  sit in the chain of dependent steps and cost more than the products;
- **the state first**: with ``x = beta (v - e^{gc} k S)``, the new values
  are ``(I + A)^{-1} x`` and the output ``e^{gc} q S + (q k^T * decay)
  (I + A)^{-1} x``, so a chunk reads ``S`` through one product, ``[k; q]
  S``, and no ``u`` or ``w`` is formed.

One token a row (a decode step), the XLA form (``recurrent_gated_delta_step``
in the model) must form ``S^T k`` before it can write the new state, so it
passes over the whole store twice: one fusion reads it, a second reads it
again and writes it. :func:`recurrent_gated_delta` makes one pass: a program
copies a batch row's states into VMEM, forms ``S^T k`` and ``S^T q`` (sums
down the sublanes), ``d``, the output and the new state from the copy, and
writes the new state back over the old (``input_output_aliases``). It walks
the batch rows only, so the store's rows past them (the scratch row) are
neither read nor written; ``q`` and ``k`` are read at key-head width and
normed in the kernel, as above. The copies bind it: on the v5e it takes
what a kernel that only copies the same blocks takes (PERF.md).

Off TPU the kernels run in Pallas interpret mode
(:func:`~chainermn_tpu.ops.flash_attention.kernels_interpreted`), which takes
any head width; Mosaic takes heads whose widths are whole tiles of 128 lanes,
and a layer runs the kernels at those (:func:`kernel_takes`,
:func:`decode_kernel_takes`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.flash_attention import (
    _LANE,
    _out_vma,
    kernels_interpreted,
)

# tokens a chunk holds: two heads' chunks fill the 128 lanes
CHUNK = 64


def _pairs(k_heads: int, v_heads: int, dk: int, dv: int) -> bool:
    """An even number of value heads a key head (a program takes two), and
    ``v`` starting on a whole pair of value heads' columns."""
    return (v_heads % k_heads == 0 and v_heads // k_heads % 2 == 0
            and k_heads * dk % dv == 0)


def kernel_takes(k_heads: int, v_heads: int, dk: int, dv: int) -> bool:
    """Whether a layer runs its whole prompts through
    :func:`chunk_gated_delta`: pairs of value heads (:func:`_pairs`) whose
    column blocks of ``q``, ``k``, ``v`` and the output are whole tiles of
    lanes, as Mosaic copies them. The interpreter takes narrower heads too,
    which the tests give the kernel directly; a layer of such heads runs
    the XLA form."""
    return (_pairs(k_heads, v_heads, dk, dv)
            and dk % _LANE == 0 and dv % _LANE == 0)


def _dot(x, y, dims):
    return jax.lax.dot_general(x, y, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _pair_kernel(valid_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                 o_ref, s_out_ref, s_ref, *, chunk: int, eps: float):
    """Chunk ``n`` of row ``b`` for a pair of value heads. A ``[C, 2C]``
    array holds head 0's ``[C, C]`` matrix in its first ``C`` lanes and
    head 1's in the rest; ``s_ref [2, dk, dv]`` carries the two states
    across the row's chunks."""
    b, n = pl.program_id(0), pl.program_id(2)
    c, w = chunk, 2 * chunk
    dv = o_ref.shape[2] // 2
    length = valid_ref[b]
    live = pl.cdiv(length, c)

    @pl.when(n == 0)
    def _zero():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(n < live)
    def _chunk():
        iota = jax.lax.broadcasted_iota
        real = n * c + iota(jnp.int32, (c, 1), 0) < length         # [C, 1]
        row = iota(jnp.int32, (c, w), 0)
        lane = iota(jnp.int32, (c, w), 1)
        col, left = lane % c, lane < c                             # [C, 2C]
        sub8, lane8 = iota(jnp.int32, (8, w), 0), iota(jnp.int32, (8, w), 1)
        real8 = n * c + lane8 % c < length
        g8 = jnp.where((sub8 == 0) & real8, g_ref[0, 0, pl.ds(n, 1), :], 0.0)
        beta = jnp.where(real8, beta_ref[0, 0, pl.ds(n, 1), :], 0.0)[0:1]
        # along the lanes: cumulative gates, the gates after each position
        # and each head's total over the chunk (dv lanes a head)
        r2, c2 = iota(jnp.int32, (w, w), 0), iota(jnp.int32, (w, w), 1)
        same = r2 // c == c2 // c
        heads = (iota(jnp.int32, (w, 2 * dv), 0) // c
                 == iota(jnp.int32, (w, 2 * dv), 1) // dv)
        sums = _dot(g8, jnp.concatenate(
            [same & (r2 <= c2), same & (r2 > c2), heads],
            axis=1).astype(jnp.float32), ((1,), (0,)))[0:1]
        gc, after, total = sums[:, :w], sums[:, w:2 * w], sums[:, 2 * w:]
        # and down the sublanes, [2C, 8]: row h*C + i is head h's position i
        cols = _dot((r2 == c2).astype(jnp.float32), jnp.where(
            sub8 == 0, gc, jnp.where(sub8 == 1, beta, jnp.where(
                sub8 == 2, after, 0.0))), ((1,), (1,)))
        per_head = [cols[h * c:(h + 1) * c] for h in range(2)]   # [C, 8]
        gc_col = jnp.where(left, per_head[0][:, 0:1], per_head[1][:, 0:1])
        beta_col = jnp.where(left, per_head[0][:, 1:2], per_head[1][:, 1:2])
        # every exponent is of a non-positive sum of g
        seen = row >= col
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, gc_col - gc, 0.0)),
                          0.0)
        # q and k normed (q scaled by dk^-1/2), the rows' sums of squares
        # a product against ones
        raw = jnp.concatenate([k_ref[0], q_ref[0]])                # [2C, dk]
        dk = raw.shape[1]
        normed = raw * jax.lax.rsqrt(_dot(
            raw * raw, jnp.ones((dk, dk), jnp.float32), ((1,), (0,))) + eps)
        k = jnp.where(real, normed[:c], 0.0)
        kq = jnp.concatenate(
            [k, jnp.where(real, normed[c:] * dk ** -0.5, 0.0)])
        kkqk = _dot(kq, jnp.concatenate([k, k], axis=0), ((1,), (1,)))

        def block_diag(x):      # [C, 2C] -> [2C, 2C], a head a block
            return jnp.concatenate([jnp.where(left, x, 0.0),
                                    jnp.where(left, 0.0, x)], axis=0)

        # (I + A)^{-1}, A strictly lower: the product of I + (-A)^(2^j);
        # (-A)^s is zero in its first s rows, which the products skip
        nil = jnp.where(row > col, -(beta_col * kkqk[:c] * decay), 0.0)
        inv = (row == col).astype(jnp.float32) + nil
        for j in range(max(c - 1, 1).bit_length() - 1):
            lo = 2 ** (j + 1) // 8 * 8
            nil = _dot(nil[lo:], block_diag(nil), ((1,), (0,)))
            if lo:
                nil = jnp.concatenate([jnp.zeros((lo, w), jnp.float32), nil])
            tail = inv[lo:] + _dot(inv[lo:], block_diag(nil), ((1,), (0,)))
            inv = jnp.concatenate([inv[:lo], tail]) if lo else tail
        zero = jnp.zeros((c, dv), jnp.float32)
        ks_qs, xs = [], []
        for h in range(2):
            ks_qs.append(_dot(kq, s_ref[h], ((1,), (0,))))         # [2C, dv]
            v = jnp.where(real, v_ref[0, :, h * dv:(h + 1) * dv], 0.0)
            xs.append(per_head[h][:, 1:2] * (
                v - jnp.exp(per_head[h][:, 0:1]) * ks_qs[h][:c]))
        # the new values of both heads, then what the chunk adds to o
        new = _dot(inv, jnp.concatenate(
            [jnp.concatenate([xs[0], zero], axis=1),
             jnp.concatenate([zero, xs[1]], axis=1)], axis=0), ((1,), (0,)))
        local = _dot(kkqk[c:] * decay, jnp.concatenate(
            [jnp.concatenate([new[:, :dv], zero], axis=1),
             jnp.concatenate([zero, new[:, dv:]], axis=1)], axis=0),
            ((1,), (0,)))                                          # [C, 2dv]
        for h in range(2):
            part = slice(h * dv, (h + 1) * dv)
            o = jnp.exp(per_head[h][:, 0:1]) * ks_qs[h][c:] + local[:, part]
            o_ref[0, :, part] = jnp.where(real, o, 0.0)
            s_ref[h] = s_ref[h] * jnp.exp(total[:, part]) + _dot(
                k * jnp.exp(per_head[h][:, 2:3]), new[:, part],
                ((0,), (0,)))

    @pl.when(n >= live)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n == jnp.maximum(live, 1) - 1)
    def _final():
        s_out_ref[0] = s_ref[...]


def chunk_gated_delta(qkv, g, beta, valid=None, *, k_heads: int, dk: int,
                      eps: float = 1e-6, interpret: Optional[bool] = None):
    """The gated delta rule over whole prompts from a zero state.

    - ``qkv``: ``[B, T, 2 Hk dk + Hv dv]`` float32, one run of columns a
      token: ``q`` and ``k`` on ``k_heads`` key heads of ``dk``, then ``v``
      on the value heads. ``q`` and ``k`` are normed here, ``x *
      rsqrt(sum x^2 + eps)`` a head, and ``q`` scaled by ``dk^-1/2``;
    - ``g``, ``beta``: ``[B, T, Hv]`` float32; value head ``h`` reads key
      head ``h // (Hv / Hk)``, an even number of them a key head
      (:func:`_pairs`; on the chip, :func:`kernel_takes`);
    - ``valid``: ``[B]`` int32, the real tokens of each row (``None``: all
      ``T``). Positions at or past it neither read nor change anything, and
      their outputs are zeros.

    Returns ``(o [B, T, Hv, dv], final state [B, Hv, dk, dv])`` in float32:
    the state after each row's last real token. Off TPU runs in interpret
    mode by default."""
    b, t, width = qkv.shape
    hv = g.shape[-1]
    dv = (width - 2 * k_heads * dk) // hv
    if not _pairs(k_heads, hv, dk, dv):
        raise ValueError(f"{hv} value heads of {dv} on {k_heads} key heads "
                         f"of {dk}: a program takes two value heads of one "
                         "key head")
    if valid is None:
        valid = jnp.full((b,), t, jnp.int32)
    if interpret is None:
        interpret = kernels_interpreted()
    o, state = _rule(qkv, g, beta, jnp.asarray(valid, jnp.int32),
                     k_heads=k_heads, dk=dk, eps=float(eps),
                     interpret=bool(interpret))
    return o.reshape(b, t, hv, dv), state


@functools.partial(jax.jit, static_argnames=("k_heads", "dk", "eps",
                                             "interpret"), inline=True)
def _rule(qkv, g, beta, valid, *, k_heads: int, dk: int, eps: float,
          interpret: bool):
    """:func:`chunk_gated_delta` with its defaults filled in. The layers of
    a model call it with one set of shapes: ``jit`` traces the kernel once
    and ``inline`` writes it into the caller's trace under the caller's
    names (``.../gdn/recurrence``)."""
    b, t, hv = g.shape
    dv = (qkv.shape[-1] - 2 * k_heads * dk) // hv
    group = hv // k_heads                      # value heads a key head
    c = CHUNK
    pad = -t % c
    if pad:
        qkv, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                        for x in (qkv, g, beta))
    n = (t + pad) // c
    # [B, T, Hv] -> [B, Hv/2, N, 2C]: a pair's chunk is a row of lanes,
    # head 2p's positions then head 2p + 1's
    g, beta = (x.reshape(b, n, c, hv // 2, 2).transpose(0, 3, 1, 4, 2)
               .reshape(b, hv // 2, n, 2 * c) for x in (g, beta))

    def chunk_of(b_, n_, valid_ref):
        # past the last live chunk, the last live one again: not copied
        return jnp.minimum(n_, jnp.maximum(pl.cdiv(valid_ref[b_], c), 1) - 1)

    # column blocks of qkv: q's key heads, then k's, then v's pairs
    q_spec, k_spec = (pl.BlockSpec(
        (1, c, dk), lambda b_, p, n_, vr, at=at: (
            b_, chunk_of(b_, n_, vr), at + 2 * p // group))
        for at in (0, k_heads))
    v_spec = pl.BlockSpec(
        (1, c, 2 * dv), lambda b_, p, n_, vr: (
            b_, chunk_of(b_, n_, vr), k_heads * dk // dv + p))
    gb_spec = pl.BlockSpec((1, 1, n, 2 * c),
                           lambda b_, p, n_, vr: (b_, p, 0, 0))
    o_spec = pl.BlockSpec((1, c, 2 * dv), lambda b_, p, n_, vr: (b_, n_, p))
    s_spec = pl.BlockSpec((1, 2, dk, dv), lambda b_, p, n_, vr: (b_, p, 0, 0))
    vma = _out_vma(qkv, g, beta, valid)
    o, state = pl.pallas_call(
        functools.partial(_pair_kernel, chunk=c, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hv // 2, n),
            in_specs=[q_spec, k_spec, v_spec, gb_spec, gb_spec],
            out_specs=[o_spec, s_spec],
            scratch_shapes=[pltpu.VMEM((2, dk, dv), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, t + pad, hv * dv), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, hv, dk, dv), jnp.float32, vma=vma)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(valid, qkv, qkv, qkv, g, beta)
    return (o[:, :t] if pad else o), state


def decode_kernel_takes(k_heads: int, v_heads: int, dk: int, dv: int) -> bool:
    """Whether a layer runs its decode step through
    :func:`recurrent_gated_delta`: value heads a whole number a key head,
    each head's ``[dk, dv]`` tile of the state whole tiles of lanes, as
    Mosaic copies it. Narrower heads (the tests' small model) run the step
    in XLA."""
    return v_heads % k_heads == 0 and dk % _LANE == 0 and dv % _LANE == 0


def _step_kernel(qkv_ref, g_ref, beta_ref, s_ref, o_ref, s_out_ref, *,
                 k_heads: int, dk: int, eps: float):
    """One batch row of the step: ``qkv_ref [rb, C]``, ``g_ref`` and
    ``beta_ref [rb, Hv]`` hold ``rb`` batch rows of which this program's is
    ``program % rb``; ``o_ref [1, Hv, dv]`` its output, ``s_ref`` and
    ``s_out_ref [1, Hv, dk, dv]`` its states, one buffer in HBM."""
    _, hv, _, dv = s_ref.shape
    group = hv // k_heads
    at = pl.ds(pl.program_id(0) % qkv_ref.shape[0], 1)
    width = -(-2 * k_heads // 8) * 8
    sub = jax.lax.broadcasted_iota(jnp.int32, (width, dk), 0)
    lane_zeros = jnp.zeros((1, dv), jnp.float32)
    x = qkv_ref[at, :]                                            # [1, C]
    decay = jnp.exp(g_ref[at, :])                                 # [1, Hv]
    beta = beta_ref[at, :]
    # q and k of each key head normed, q scaled by dk^-1/2, then as
    # columns: row j of [width, dk] is head j (q's, then k's)
    heads = []
    for j in range(2 * k_heads):
        y = x[:, j * dk:(j + 1) * dk]
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True) + eps)
        heads.append(y * dk ** -0.5 if j < k_heads else y)
    cols = sum(jnp.where(sub == j, y, 0.0)
               for j, y in enumerate(heads)).T                    # [dk, W]
    for j in range(k_heads):
        kq = jnp.sum(heads[j] * heads[k_heads + j], axis=1,
                     keepdims=True)                               # [1, 1]
        qc = jnp.broadcast_to(cols[:, j:j + 1], (dk, dv))
        kc = jnp.broadcast_to(cols[:, k_heads + j:k_heads + j + 1], (dk, dv))
        for h in range(j * group, (j + 1) * group):
            s = s_ref[0, h]                                       # [dk, dv]
            # along the lanes first (Mosaic broadcasts [1, 1] along one
            # axis at a time), then down the sublanes where used
            e = decay[:, h:h + 1] + lane_zeros
            at_v = 2 * k_heads * dk + h * dv
            d = beta[:, h:h + 1] * (
                x[:, at_v:at_v + dv]
                - e * jnp.sum(s * kc, axis=0, keepdims=True))
            o_ref[0, h:h + 1, :] = (
                e * jnp.sum(s * qc, axis=0, keepdims=True) + d * kq)
            s_out_ref[0, h] = e * s + kc * d


def recurrent_gated_delta(qkv, g, beta, state, *, k_heads: int, dk: int,
                          eps: float = 1e-6,
                          interpret: Optional[bool] = None):
    """One token of the gated delta rule for each batch row, on the state
    store in place.

    - ``qkv``: ``[B, 2 Hk dk + Hv dv]`` float32, a row's token as the
      convolution gives it: ``q`` and ``k`` on ``k_heads`` key heads of
      ``dk`` (normed here, ``x * rsqrt(sum x^2 + eps)`` a head, and ``q``
      scaled by ``dk^-1/2``), then ``v``; value head ``h`` reads key head
      ``h // (Hv / Hk)``;
    - ``g``, ``beta``: ``[B, Hv]`` float32;
    - ``state``: ``[R, Hv, dk, dv]`` float32, ``R >= B``: batch row ``i``
      advances store row ``i``. Rows past ``B`` are neither read nor
      written, and a batch row with ``g = 0`` and ``beta = 0`` keeps its
      state as it is.

    Returns ``(o [B, Hv, dv], new state [R, Hv, dk, dv])``; the new state
    is the same buffer as ``state`` (``input_output_aliases``), so a caller
    that donates the store updates it where it lies. Off TPU runs in
    interpret mode by default."""
    hv = state.shape[1]
    if hv % k_heads:
        raise ValueError(f"{hv} value heads on {k_heads} key heads: a key "
                         "head is read by a whole number of value heads")
    if interpret is None:
        interpret = kernels_interpreted()
    return _step(qkv, g, beta, state, k_heads=k_heads, dk=dk,
                 eps=float(eps), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("k_heads", "dk", "eps",
                                             "interpret"), inline=True)
def _step(qkv, g, beta, state, *, k_heads: int, dk: int, eps: float,
          interpret: bool):
    """:func:`recurrent_gated_delta` with its defaults filled in, traced
    once for a model's layers and written into the caller's trace under
    the caller's names (``.../gdn/recurrence``), as :func:`_rule`.

    A grid step takes one store row (the published 32 value heads: 2 MB
    each way); the batch's small operands go in blocks of ``rb`` rows (8,
    or the whole batch where 8 does not divide it), as Mosaic tiles them,
    so a block of them is copied once for the grid steps that read it."""
    b, c = qkv.shape
    hv, _, dv = state.shape[1:]
    rb = 8 if b % 8 == 0 else b
    small = lambda width: pl.BlockSpec((rb, width), lambda i: (i // rb, 0))
    s_spec = pl.BlockSpec((1, hv, dk, dv), lambda i: (i, 0, 0, 0))
    vma = _out_vma(qkv, g, beta, state)
    return pl.pallas_call(
        functools.partial(_step_kernel, k_heads=k_heads, dk=dk, eps=eps),
        grid=(b,),
        in_specs=[small(c), small(hv), small(hv), s_spec],
        out_specs=[pl.BlockSpec((1, hv, dv), lambda i: (i, 0, 0)),
                   s_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hv, dv), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct(state.shape, jnp.float32, vma=vma)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(qkv, g, beta, state)
