"""Tensor parallelism (Megatron-style) over a mesh axis — TPU extension.

The reference's only tensor-parallel construct is the channel-parallel
convolution example (SURVEY.md S2.16: "no general TP engine"); this module
provides the general engine for transformer-shaped models: column-parallel
and row-parallel projections whose composition moves ONE ``psum`` per MLP
and one per attention block (the Megatron f/g schedule), with the backward
collectives derived by autodiff instead of hand-written.

Layout convention (mirrors :mod:`chainermn_tpu.parallel.moe`): parameters are
declared with their GLOBAL shapes — ordinary ``model.init`` outside
``shard_map`` gives the correct initialization distribution and replicated
storage — and each rank slices its block at apply time by axis index.
Storage is therefore replicated (flax validates param shapes against the
declaration, so shard_map in_specs cannot feed these modules local-shape
leaves); TP here buys *compute* and *activation* sharding. Weights-at-rest
sharding is the partitioner's job — the :mod:`chainermn_tpu.parallel.fsdp`
layout under plain ``jit`` — not a shard_map in_spec trick.

Training with TP layers — the **global-objective pattern** (tested leaf-exact
in ``tests/parallel_tests/test_tensor.py``)::

    def loss(params):                       # params INVARIANT (no pcast)
        local = local_loss(model.apply(params, x))
        return global_objective(local, (dp_axis, tp_axis))

    grads = jax.grad(loss)(params)          # exact global grads, replicated

With invariant params and an invariant (pmean'd) loss, shard_map's
replication tracking assembles every leaf's exact global gradient: sliced
leaves psum their zero-padded slice cotangents, replicated-compute leaves
(row bias, embeddings, layernorms) average their identical copies — no
per-leaf bookkeeping in user code. Do NOT ``pcast`` the params to varying
here (the canonical DP step's trick): with a ``psum`` inside the forward, a
varying loss differentiates the SUM of per-rank losses, which inflates every
pre-psum leaf's gradient by ``n_tp``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax


class ColumnParallelDense(nn.Module):
    """``y = x @ W[:, my_slice] + b[my_slice]`` — output feature-sharded.

    ``features`` is the GLOBAL output width; the module returns the local
    ``features / n`` slice. No communication in forward; the backward's
    input-gradient psum is inserted by shard_map's replication tracking
    (Megatron's "f" identity). ``kernel``/``bias`` are *sliced* leaves for
    :func:`tp_grad_mean`.
    """

    features: int
    axis_name: str
    use_bias: bool = True
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        n = lax.axis_size(self.axis_name)
        if self.features % n:
            raise ValueError(
                f"global features {self.features} not divisible by "
                f"tensor-axis size {n}"
            )
        local_f = self.features // n
        w = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), self.compute_dtype,
        )
        r = lax.axis_index(self.axis_name)
        w = lax.dynamic_slice_in_dim(w, r * local_f, local_f, axis=-1)
        y = x.astype(self.compute_dtype) @ w
        if self.use_bias:
            b = self.param("bias", nn.initializers.zeros,
                           (self.features,), self.compute_dtype)
            b = lax.dynamic_slice_in_dim(b, r * local_f, local_f, axis=-1)
            y = y + b
        return y


class RowParallelDense(nn.Module):
    """``y = psum_tp(x_local @ W[my_slice, :]) + b`` — input feature-sharded,
    output replicated. The one forward collective of the pair (Megatron's
    "g"). ``kernel`` is a *sliced* leaf; ``bias`` adds after the psum on
    every rank identically, so it is a *replicated-compute* leaf.
    """

    features: int
    axis_name: str
    in_features: Optional[int] = None  # GLOBAL input width (default: local*n)
    use_bias: bool = True
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        n = lax.axis_size(self.axis_name)
        local_in = x.shape[-1]
        global_in = self.in_features or local_in * n
        if global_in % n:
            raise ValueError(
                f"global in_features {global_in} not divisible by "
                f"tensor-axis size {n}"
            )
        if global_in // n != local_in:
            raise ValueError(
                f"input is {local_in}-wide locally but global in_features "
                f"{global_in} / {n} ranks = {global_in // n}"
            )
        w = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (global_in, self.features), self.compute_dtype,
        )
        r = lax.axis_index(self.axis_name)
        w = lax.dynamic_slice_in_dim(w, r * local_in, local_in, axis=0)
        y = lax.psum(x.astype(self.compute_dtype) @ w, self.axis_name)
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros,
                               (self.features,), self.compute_dtype)
        return y


class TensorParallelMLP(nn.Module):
    """column(d_ff) -> activation -> row(d_model): one psum total."""

    d_model: int
    d_ff: int
    axis_name: str
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = ColumnParallelDense(self.d_ff, self.axis_name,
                                compute_dtype=self.compute_dtype)(x)
        h = nn.gelu(h)
        return RowParallelDense(self.d_model, self.axis_name,
                                in_features=self.d_ff,
                                compute_dtype=self.compute_dtype)(h)


class TensorParallelAttention(nn.Module):
    """Multi-head attention with HEADS sharded over the tensor axis:
    column-parallel qkv (each rank computes its ``n_heads/n`` heads),
    local attention, row-parallel output projection (one psum).

    The inner attention is pluggable exactly like ``TransformerBlock``'s
    (``attention='full'|'ring'|'ulysses'|'flash'`` + ``sequence_axis``): the
    sequence-parallel kinds operate per-head, so TP (heads over one mesh
    axis) composes with SP/CP (sequence over another) with no extra code.
    """

    d_model: int
    n_heads: int
    axis_name: str
    causal: bool = True
    attention: str = "full"
    sequence_axis: Optional[str] = None
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, pos_offset=0, kv_cache=None):
        from chainermn_tpu.parallel.sequence import sequence_parallel_attention

        if kv_cache is not None and self.sequence_axis is not None:
            raise ValueError(
                "kv_cache decoding needs an unsharded sequence — rebuild "
                "without sequence_axis for inference"
            )
        if kv_cache is not None and not self.causal:
            raise ValueError(
                "kv_cache decoding is causal by construction (the position "
                "mask); causal=False with a cache would silently mask "
                "attention to later cached positions"
            )
        n = lax.axis_size(self.axis_name)
        if self.n_heads % n:
            raise ValueError(
                f"n_heads {self.n_heads} not divisible by tensor-axis size {n}"
            )
        if self.d_model % self.n_heads:
            raise ValueError(
                f"d_model {self.d_model} not divisible by n_heads {self.n_heads}"
            )
        d_head = self.d_model // self.n_heads
        local_h = self.n_heads // n
        qkv = ColumnParallelDense(
            3 * self.d_model, self.axis_name,
            compute_dtype=self.compute_dtype, name="qkv_tpcol",
        )(x)
        # local width is 3 * local_h * d_head. The global feature order is
        # thereby DEFINED as (rank, 3, local_head, d_head)-major: rank r's
        # contiguous slice is its own (q, k, v) block for its own heads.
        # Init is i.i.d., so this ordering is as valid as torch/flax's
        # (3, head, d_head); parity tests permute accordingly. NOTE this
        # bakes the TP degree into the stored kernel — restoring a
        # checkpoint at a DIFFERENT degree needs reshard_tp_qkv (restoring
        # unpermuted silently scrambles q/k/v across heads).
        b, t = qkv.shape[0], qkv.shape[1]
        qkv = qkv.reshape(b, t, 3, local_h, d_head)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if kv_cache is not None:
            # per-rank cache over LOCAL heads [B, Tc, local_h, d_head]
            from chainermn_tpu.parallel.sequence import update_cache_and_attend

            o, new_cache = update_cache_and_attend(kv_cache, q, k, v,
                                                   pos_offset)
        else:
            attn_fn = sequence_parallel_attention(
                self.attention, self.sequence_axis, causal=self.causal
            )
            o = attn_fn(q, k, v)
        o = o.reshape(b, t, local_h * d_head)
        out = RowParallelDense(
            self.d_model, self.axis_name, in_features=self.d_model,
            compute_dtype=self.compute_dtype, name="proj_tprow",
        )(o)
        return (out, new_cache) if kv_cache is not None else out


def reshard_tp_qkv(tree, n_heads: int, d_head: int, old_tp: int,
                   new_tp: int):
    """Permute a :class:`TensorParallelAttention` checkpoint between TP
    degrees.

    The fused qkv kernel's column order is DEFINED as
    ``(rank, 3, local_head, d_head)``-major (see the module body), which
    bakes the tensor-axis size into the stored weights: restoring a
    checkpoint trained at one TP degree into a different degree (or into a
    dense block) silently scrambles q/k/v across heads. This helper
    re-orders every ``qkv_tpcol`` kernel/bias in ``tree`` from the
    ``old_tp`` layout to the ``new_tp`` layout via the degree-independent
    canonical ``(3, head, d_head)`` order (head ownership is contiguous:
    rank ``r`` owns heads ``[r*h/n, (r+1)*h/n)``). The row-parallel
    ``proj_tprow`` needs no permutation — its rows are head-major at every
    degree. Raises if either degree does not divide ``n_heads``.
    """
    import jax

    if n_heads % old_tp or n_heads % new_tp:
        raise ValueError(
            f"n_heads {n_heads} must divide by both TP degrees "
            f"({old_tp}, {new_tp})")
    width = 3 * n_heads * d_head

    def to_canonical(cols, n):
        # [..., (rank, 3, lh, dh)] -> [..., (3, head, dh)]
        lead = cols.shape[:-1]
        c = cols.reshape(*lead, n, 3, n_heads // n, d_head)
        c = jnp.moveaxis(c, -4, -3)          # [..., 3, n, lh, dh]
        return c.reshape(*lead, 3, n_heads, d_head)

    def from_canonical(c, n):
        lead = c.shape[:-3]
        c = c.reshape(*lead, 3, n, n_heads // n, d_head)
        c = jnp.moveaxis(c, -3, -4)          # [..., n, 3, lh, dh]
        return c.reshape(*lead, width)

    n_fixed = 0

    def fix(path, leaf):
        nonlocal n_fixed
        keys = jax.tree_util.keystr(path)
        if "qkv_tpcol" not in keys:
            return leaf
        if leaf.shape[-1] != width:
            # a silent skip here would reproduce the exact scramble this
            # helper exists to prevent (wrong n_heads/d_head passed)
            raise ValueError(
                f"qkv_tpcol leaf at {keys} has last dim {leaf.shape[-1]} "
                f"but n_heads={n_heads}, d_head={d_head} imply "
                f"3*h*dh={width} — wrong head geometry for this checkpoint")
        n_fixed += 1
        return from_canonical(to_canonical(leaf, old_tp), new_tp)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = jax.tree_util.tree_unflatten(
        treedef, [fix(p, l) for p, l in flat])
    if n_fixed == 0:
        raise ValueError(
            "reshard_tp_qkv found no 'qkv_tpcol' leaves in the tree — "
            "nothing was resharded (wrong tree, or a dense checkpoint that "
            "needs no permutation)")
    return out


def vocab_parallel_cross_entropy(local_logits, targets, axis_name: str):
    """Per-token cross entropy over a VOCAB-SHARDED logits tensor, without
    ever materializing the full ``[..., vocab]`` logits (the classic
    large-vocab memory win of a vocab-parallel head).

    ``local_logits [..., V/n]`` is rank ``r``'s contiguous vocab slice
    ``[r*V/n, (r+1)*V/n)`` — e.g. the output of
    ``ColumnParallelDense(vocab_size, axis)``; ``targets`` hold GLOBAL vocab
    ids. Three scalar-per-token collectives: pmax for the stable shift, psum
    of the local sum-exp for the denominator, and a masked psum that routes
    each target's logit from the one rank whose shard holds it. Output is
    invariant over ``axis_name`` (matches
    ``optax.softmax_cross_entropy_with_integer_labels`` on the gathered
    logits — pinned in tests), and autodiff through it yields the sharded
    head's exact gradients under the global-objective pattern.
    """
    r = lax.axis_index(axis_name)
    v_local = local_logits.shape[-1]
    logits = local_logits.astype(jnp.float32)
    start = r * v_local
    gmax = lax.pmax(
        lax.stop_gradient(jnp.max(logits, axis=-1)), axis_name
    )
    shifted = logits - gmax[..., None]
    denom = lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), axis_name)
    in_shard = (targets >= start) & (targets < start + v_local)
    local_idx = jnp.clip(targets - start, 0, v_local - 1)
    t_local = jnp.take_along_axis(shifted, local_idx[..., None], axis=-1)[..., 0]
    t_logit = lax.psum(jnp.where(in_shard, t_local, 0.0), axis_name)
    return jnp.log(denom) - t_logit


def global_objective(local_loss, axes):
    """``pmean`` the per-rank loss over every mesh axis it still varies on —
    the closing line of the global-objective pattern (module docstring).

    Why not a plain ``lax.pmean(local, axes)``: after a row-parallel psum the
    loss is already invariant over the tensor axis, and JAX rejects reducing
    an axis the value does not vary on; which axes remain varying depends on
    the model's final layers. This reduces exactly the still-varying subset
    (``jax.typeof(...).vma``), so one call is correct for pure-TP, pure-DP,
    and hybrid steps alike.
    """
    import jax

    if isinstance(axes, str):
        axes = (axes,)
    # The pattern is built ON vma tracking: with check_vma=False every value
    # reads as vma-empty, no pmean would ever fire, and the "grads" would be
    # per-rank garbage — fail loudly instead (axis_index is varying by
    # construction, so an empty vma on it means tracking is off).
    if not jax.typeof(lax.axis_index(axes[0])).vma:
        raise ValueError(
            "global_objective requires replication (vma) tracking, but this "
            "shard_map was built with check_vma=False — the global-objective "
            "gradient pattern cannot work there (no automatic psum assembly)"
        )
    vary = tuple(a for a in axes if a in jax.typeof(local_loss).vma)
    return lax.pmean(local_loss, vary) if vary else local_loss


__all__ = [
    "ColumnParallelDense",
    "RowParallelDense",
    "TensorParallelMLP",
    "TensorParallelAttention",
    "global_objective",
    "vocab_parallel_cross_entropy",
]
