"""Transformer LM — the long-context flagship family.

No counterpart in the reference (it predates attention; SURVEY.md S2.16
marks SP/CP absent) — this is the TPU-first extension workload that
exercises sequence parallelism end to end. Design notes:

- layout ``[batch, seq, heads, head_dim]``; params f32, compute bf16 by
  default (casts fuse into the MXU matmuls);
- attention is pluggable (``'full' | 'ring' | 'zigzag' | 'ulysses' |
  'flash'`` from :mod:`chainermn_tpu.parallel.sequence`) so the same module
  runs single-chip or sequence-sharded inside ``comm.shard_map`` with the
  sequence axis in the batch ``PartitionSpec``;
- static shapes, ``nn.scan``-free explicit layer stack (layer count is a
  Python constant — XLA sees a straight-line program it can pipeline).
"""

from __future__ import annotations

from typing import Optional

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.parallel.moe import ExpertParallelMLP
from chainermn_tpu.parallel.sequence import (
    paged_scale_shape,
    paged_store_shape,
    sequence_parallel_attention,
)


@dataclasses.dataclass(frozen=True)
class KVCacheKind:
    """One kind of KV state a served model keeps, as its
    ``kv_cache_spec()`` tells the serving engine: which layers are of the
    kind, the KV heads and head size of a stored row, and ``window`` — the
    positions a layer of the kind sees back from its own (``None``: every
    one, so the state grows with the sequence). The engine keeps a block
    store, a table and a block budget per kind."""

    name: str
    layers: tuple
    kv_heads: int
    head_dim: int
    window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class SlotStateKind:
    """A kind of state that is not rows of a block store: what the layers
    of the kind keep of a sequence has one size however long it is (a
    linear-attention layer's recurrent state, the last inputs of its short
    convolution). ``arrays`` names them, ``((key, shape a slot, dtype
    name), ...)``. The engine keeps for each layer of the kind one array a
    key, a row a slot and one scratch row behind them for rows that hold no
    request: no pool, no table, no trie, and one unit a slot at admission.
    A prefill writes a row's state after its last real token, a decode step
    advances the active slots' rows in place, and the next prefill into a
    freed slot starts from zero and never reads what was there. A prompt
    cannot be continued at an offset without the state at that offset.
    ``chunk``: the tokens a chunk of the kind's whole-prompt form holds,
    where a prefill walks a row's prompt chunk by chunk (``None``: it does
    not); the engine counts the chunks a program walks and skips.
    ``decode_kernel``: whether a decode step advances the kind's rows in
    one kernel (else in XLA); the engine counts the rows under each."""

    name: str
    layers: tuple
    arrays: tuple
    chunk: Optional[int] = None
    decode_kernel: bool = False


class TransformerBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    attention: str = "full"
    sequence_axis: Optional[str] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    # moe_experts > 0 replaces this block's dense FFN with an expert-parallel
    # routed MLP over ``moe_axis`` (see parallel.moe); the block THEN returns
    # ``(x, aux_loss)`` instead of ``x`` — dense blocks keep the original
    # single-array contract so existing callers are unaffected.
    moe_experts: int = 0
    moe_axis: Optional[str] = None
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    # 'ep' = shard_map ExpertParallelMLP (explicit all_to_all; needs
    # moe_axis bound); 'gshard' = einsum-dispatch GShardMoE for plain-jit
    # GSPMD execution (expert stacks shardable at rest; see parallel/gspmd)
    moe_impl: str = "ep"
    # tensor_axis set -> Megatron-style block: head-sharded attention +
    # column/row FFN from parallel.tensor, one psum each. Train with the
    # global-objective pattern (tensor.py docstring), NOT the pcast/varying
    # gradient pattern of the dense blocks.
    tensor_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, pos_offset=0, kv_cache=None):
        dt = self.compute_dtype
        d_head = self.d_model // self.n_heads
        if kv_cache is not None:
            if self.sequence_axis is not None:
                raise ValueError(
                    "kv_cache decoding does not support sequence-sharded "
                    "blocks — rebuild with sequence_axis=None for inference"
                )
            if self.moe_experts and self.moe_impl != "gshard":
                raise ValueError(
                    "kv_cache decoding supports MoE only via "
                    "moe_impl='gshard' (plain-jit dispatch); the shard_map "
                    "'ep' implementation needs an axis context the decode "
                    "loop does not bind"
                )

        h = nn.LayerNorm(dtype=dt)(x)
        if self.tensor_axis is not None:
            if self.moe_experts:
                # guard here too (not only in TransformerLM): the TP branch
                # would otherwise silently train a dense FFN instead of the
                # experts AND return a bare array where the MoE contract
                # promises (x, aux_loss)
                raise ValueError(
                    "tensor_axis and moe_experts are mutually exclusive "
                    "on a TransformerBlock"
                )
            from chainermn_tpu.parallel.tensor import (
                TensorParallelAttention,
                TensorParallelMLP,
            )

            attn_out = TensorParallelAttention(
                d_model=self.d_model, n_heads=self.n_heads,
                axis_name=self.tensor_axis, causal=True,
                attention=self.attention, sequence_axis=self.sequence_axis,
                compute_dtype=dt, name="attn",
            )(h, pos_offset=pos_offset, kv_cache=kv_cache)
            if kv_cache is not None:
                attn_out, new_cache = attn_out
            x = x + attn_out
            h = nn.LayerNorm(dtype=dt)(x)
            x = x + TensorParallelMLP(
                d_model=self.d_model, d_ff=self.d_ff,
                axis_name=self.tensor_axis, compute_dtype=dt, name="mlp",
            )(h)
            return (x, new_cache) if kv_cache is not None else x

        qkv = nn.DenseGeneral((3, self.n_heads, d_head), dtype=dt, name="qkv")(h)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        if kv_cache is not None:
            from chainermn_tpu.parallel.sequence import update_cache_and_attend

            o, new_cache = update_cache_and_attend(kv_cache, q, k, v,
                                                   pos_offset)
        else:
            attn_fn = sequence_parallel_attention(
                self.attention, self.sequence_axis, causal=True
            )
            o = attn_fn(q, k, v)
        x = x + nn.DenseGeneral(self.d_model, axis=(-2, -1), dtype=dt, name="proj")(o)

        h = nn.LayerNorm(dtype=dt)(x)
        if self.moe_experts:
            if self.moe_impl not in ("ep", "gshard"):
                raise ValueError(
                    f"moe_impl must be 'ep' or 'gshard', got "
                    f"{self.moe_impl!r}"
                )
            if self.moe_impl == "gshard":
                from chainermn_tpu.parallel.moe import GShardMoE

                y, aux = GShardMoE(
                    n_experts=self.moe_experts, d_model=self.d_model,
                    d_ff=self.d_ff,
                    capacity_factor=self.moe_capacity_factor,
                    top_k=self.moe_top_k,
                    compute_dtype=dt, name="moe",
                )(h)
            else:
                y, aux = ExpertParallelMLP(
                    n_experts=self.moe_experts, d_model=self.d_model,
                    d_ff=self.d_ff, axis_name=self.moe_axis,
                    capacity_factor=self.moe_capacity_factor,
                    top_k=self.moe_top_k,
                    compute_dtype=dt, name="moe",
                )(h)
            if kv_cache is not None:
                # decode: the cache replaces the aux loss in the contract
                # (inference adds no balance objective)
                return x + y, new_cache
            return x + y, aux
        h = nn.Dense(self.d_ff, dtype=dt)(h)
        h = nn.gelu(h)
        x = x + nn.Dense(self.d_model, dtype=dt)(h)
        return (x, new_cache) if kv_cache is not None else x


class TransformerLM(nn.Module):
    """Decoder-only LM. ``__call__(tokens[B, T_local], pos_offset)`` ->
    logits ``[B, T_local, vocab]``; when sequence-sharded, ``pos_offset`` is
    each shard's global position base (pass ``axis_index * T_local`` inside
    the traced step) — EXCEPT under ``attention='zigzag'``, whose shards are
    not contiguous: pass the full ``[T_local]`` position vector from
    :func:`~chainermn_tpu.parallel.sequence.zigzag_positions` instead
    (``training._shard_positions`` picks the right form automatically)."""

    vocab_size: int
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: Optional[int] = None
    max_len: int = 65536
    attention: str = "full"
    sequence_axis: Optional[str] = None
    compute_dtype: jnp.dtype = jnp.bfloat16
    # MoE: every ``moe_every``-th block routes its FFN over ``moe_axis``
    # experts (0 = dense everywhere). Train with return_aux=True and add
    # the aux loss (jit_lm_train_step does this automatically).
    moe_experts: int = 0
    moe_axis: Optional[str] = None
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1  # 1 = Switch routing, 2 = GShard top-2
    # 'ep': shard_map ExpertParallelMLP over moe_axis (explicit all_to_all).
    # 'gshard': einsum-dispatch GShardMoE for the plain-jit GSPMD step
    # (parallel/gspmd) — expert stacks shard at rest, no moe_axis needed.
    moe_impl: str = "ep"
    # Megatron-style tensor parallelism: heads + FFN width sharded over this
    # mesh axis in every block (embeddings and lm_head stay replicated).
    # Train with the global-objective pattern (parallel/tensor.py docstring).
    tensor_axis: Optional[str] = None
    # With tensor_axis: shard the LM head over the vocab too. __call__ then
    # returns LOCAL logits [B, T, vocab/n] (rank r's contiguous vocab slice)
    # — full [B, T, vocab] logits are never materialized. Train against
    # parallel.tensor.vocab_parallel_cross_entropy (jit_lm_train_step does
    # this automatically); for inference, all_gather the last axis.
    vocab_parallel_head: bool = False
    # Rematerialize each block's forward in the backward pass
    # (jax.checkpoint via nn.remat): stored-for-backward activations drop
    # from ~12 tensors/block to the block BOUNDARY only, trading ~1/3 more
    # forward FLOPs for O(n_layers * B*T*d) less HBM — the standard TPU
    # memory lever for long context / large token batches (e.g. a
    # 220M-param model at T=2048 B=32 stores ~18 GB without remat:
    # past a 16 GB v5e chip; with it, well inside). Training only —
    # kv_caches decode has no backward and ignores it.
    remat: bool = False

    def kv_cache_spec(self) -> tuple:
        """One kind: every layer keeps every token, a K and a V row of
        ``n_heads`` heads each."""
        return (KVCacheKind("full", tuple(range(self.n_layers)),
                            self.n_heads, self.d_model // self.n_heads),)

    @nn.compact
    def __call__(self, tokens, pos_offset=0, return_aux: bool = False,
                 kv_caches=None, return_hidden: bool = False,
                 logits_at=None):
        if self.tensor_axis is not None and self.moe_experts:
            raise ValueError(
                "tensor_axis and moe_experts are mutually exclusive: the MoE "
                "blocks' expert axis and the TP axis would need a combined "
                "gradient pattern this model does not define"
            )
        if self.vocab_parallel_head and self.tensor_axis is None:
            raise ValueError("vocab_parallel_head needs tensor_axis")
        if kv_caches is not None:
            if self.sequence_axis is not None:
                raise ValueError(
                    "kv_caches decoding does not support sequence-sharded "
                    "models — rebuild with sequence_axis=None for inference"
                )
            if self.moe_experts and self.moe_impl != "gshard":
                raise ValueError(
                    "kv_caches decoding supports MoE only via "
                    "moe_impl='gshard' — rebuild the model with "
                    "moe_impl='gshard' for inference (same params: the "
                    "expert stacks are identical)"
                )
        d_ff = self.d_ff or 4 * self.d_model
        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=self.compute_dtype, name="embed")(tokens)
        # pos_offset: scalar base (contiguous shard), a [T_local] vector of
        # explicit global positions (zigzag layout — each shard holds one
        # early and one late chunk, so its positions are not contiguous),
        # OR a [B, T] matrix of per-SEQUENCE positions (continuous-batching
        # decode: every cache slot sits at its own depth, so one call
        # advances all slots with per-row position bases).
        if jnp.ndim(pos_offset) == 0:
            pos = pos_offset + jnp.arange(tokens.shape[1])
        else:
            pos = pos_offset
        pe = nn.Embed(self.max_len, self.d_model,
                      dtype=self.compute_dtype, name="pos_embed")(pos)
        x = x + (pe if jnp.ndim(pos_offset) == 2 else pe[None])
        # blocks only consume positions on the cache path, where each batch
        # row needs its scalar base: column 0 of the per-sequence matrix
        # (decode steps are contiguous within one call)
        block_pos = pos_offset[:, 0] if jnp.ndim(pos_offset) == 2 else pos_offset
        aux_total = jnp.float32(0.0)
        new_caches = []
        # nn.remat wraps the block's apply in jax.checkpoint; decode
        # (kv_caches) has no backward to save for, so skip the wrapper and
        # its prevent_cse pessimization there.
        block_cls = (nn.remat(TransformerBlock)
                     if self.remat and kv_caches is None else TransformerBlock)
        for i in range(self.n_layers):
            is_moe = self.moe_experts and (i % self.moe_every == self.moe_every - 1)
            block = block_cls(
                self.d_model, self.n_heads, d_ff,
                attention=self.attention, sequence_axis=self.sequence_axis,
                compute_dtype=self.compute_dtype,
                moe_experts=self.moe_experts if is_moe else 0,
                moe_axis=self.moe_axis,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_top_k=self.moe_top_k,
                moe_impl=self.moe_impl,
                tensor_axis=self.tensor_axis,
                name=f"block_{i}",
            )
            if kv_caches is not None:
                x, c = block(x, block_pos, kv_cache=kv_caches[i])
                new_caches.append(c)
                continue
            out = block(x, block_pos)
            x, aux = out if is_moe else (out, 0.0)
            aux_total = aux_total + aux
        x = nn.LayerNorm(dtype=self.compute_dtype)(x)
        if return_hidden:
            # pre-head hidden states for a fused/chunked head+loss (see
            # ops.losses.chunked_softmax_cross_entropy): the [B, T, vocab]
            # f32 logits are the train step's largest tensor pair and this
            # path never builds them
            if self.vocab_parallel_head:
                raise ValueError(
                    "return_hidden composes with the replicated lm_head "
                    "(the fused CE applies it itself); the vocab-parallel "
                    "head already avoids full logits — use "
                    "vocab_parallel_cross_entropy instead"
                )
            if kv_caches is not None:
                raise ValueError("return_hidden is a training-loss path; "
                                 "decode wants logits")
            return (x, aux_total) if return_aux else x
        if logits_at is not None:
            # a prefill samples from one position a row: only that row of
            # the hidden states goes through the head, logits [B, vocab]
            x = jnp.take_along_axis(
                x, logits_at[:, None, None], axis=1)[:, 0]
        if self.vocab_parallel_head:
            from chainermn_tpu.parallel.tensor import ColumnParallelDense

            logits = ColumnParallelDense(
                self.vocab_size, self.tensor_axis,
                compute_dtype=self.compute_dtype, name="lm_head",
            )(x)
        else:
            logits = nn.Dense(self.vocab_size, dtype=self.compute_dtype,
                              name="lm_head")(x)
        logits = logits.astype(jnp.float32)
        if kv_caches is not None:
            return logits, new_caches
        if return_aux:
            return logits, aux_total
        return logits


def init_kv_caches(model: TransformerLM, batch: int, cache_len: int,
                   *, local_heads: Optional[int] = None):
    """Zeroed per-layer KV cache buffers for :meth:`TransformerLM.__call__`'s
    ``kv_caches`` argument: a list of ``{'k','v'}`` dicts shaped
    ``[batch, cache_len, heads, d_head]`` in the model's compute dtype.
    Tensor-parallel decode (inside ``shard_map``) passes
    ``local_heads=n_heads // tp_size`` for the per-rank buffers."""
    h = local_heads or model.n_heads
    dh = model.d_model // model.n_heads
    z = lambda: jnp.zeros((batch, cache_len, h, dh), model.compute_dtype)
    return [{"k": z(), "v": z()} for _ in range(model.n_layers)]


def init_paged_kv_caches(model, n_blocks, block_size: int, *,
                         local_heads: Optional[int] = None,
                         quant: str = "none"):
    """Zeroed per-layer **paged** KV block stores: a list of ``{'k','v'}``
    dicts shaped ``[n_blocks, block_size, heads, d_head]`` (an int8 store of
    fewer than 4 heads folds rows and heads into one axis,
    :func:`~chainermn_tpu.parallel.sequence.paged_store_shape`) in the model's
    layer order, heads and head size as the model's ``kv_cache_spec()``
    gives them for the layer's kind; ``n_blocks`` is one count, or one per
    kind in the spec's order. Within a kind it is one pool of
    fixed-size token blocks shared by every sequence, addressed through a
    ``[B, max_blocks]`` block table the caller threads into each layer
    dict as its ``'table'`` entry (see
    :func:`~chainermn_tpu.parallel.sequence.paged_update_cache_and_attend`).
    ``quant='int8'`` stores int8 rows plus per-row-per-head f32
    ``'k_scale'``/``'v_scale'`` arrays (``x ≈ x_q * scale`` — ~2x less KV
    memory per resident token; dequantized inside the attention), held as
    ``paged_scale_shape(n_blocks, block_size, heads)``, ``[n_blocks, 1,
    W]``: a block's scales in one row of whole lanes, row ``t`` of head
    ``h`` in column ``t * heads + h``. It is the one shape that the write
    (``paged_kernel.write_scale_rows``) and the decode kernel both take as
    it lies; as ``[n_blocks, block_size, heads]`` every program that wrote
    a row relaid the whole array three times (PERF.md §6, PR 32).
    Tensor-parallel decode passes ``local_heads=n_heads // tp_size``."""
    if quant not in ("none", "int8"):
        raise ValueError(f"quant must be 'none' or 'int8', got {quant!r}")
    spec = model.kv_cache_spec()
    if isinstance(n_blocks, int):
        n_blocks = (n_blocks,) * len(spec)
    dt = jnp.int8 if quant == "int8" else model.compute_dtype

    def layer(n, h, dh):
        shape = paged_store_shape(n, block_size, h, dh, quant)
        d = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if quant == "int8":
            shape = paged_scale_shape(n, block_size, h)
            d["k_scale"] = jnp.zeros(shape, jnp.float32)
            d["v_scale"] = jnp.zeros(shape, jnp.float32)
        return d

    def of_kind(kind, n):
        if isinstance(kind, SlotStateKind):
            return {key: jnp.zeros((n,) + tuple(shape), jnp.dtype(dtype))
                    for key, shape, dtype in kind.arrays}
        return layer(n, local_heads or kind.kv_heads, kind.head_dim)

    layers = {i: of_kind(kind, n)
              for kind, n in zip(spec, n_blocks) for i in kind.layers}
    return [layers[i] for i in range(len(layers))]


def generate(
    model: TransformerLM,
    params,
    prompt,
    n_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng=None,
    use_cache: bool = True,
    comm=None,
    eos_id: Optional[int] = None,
):
    """Autoregressive decoding for :class:`TransformerLM` (inference utility
    beyond the reference, which has no generation loop; completes the LM
    family's user surface).

    ``prompt [B, T0]`` ints; returns ``[B, T0 + n_tokens]``. ``temperature=0``
    is greedy (deterministic); otherwise softmax sampling at the given
    temperature with ``rng``, optionally truncated to the ``top_k`` most
    probable tokens and/or the smallest set whose cumulative probability
    reaches ``top_p`` (nucleus sampling; both filters compose, top-k
    first). Compiled per (model, shapes, sampler config) — repeat calls
    with the same shapes reuse the compile.

    ``use_cache=True`` (default): one full prefill over the prompt fills a
    static ``[B, T0+n_tokens]`` KV cache per layer, then each step runs ONE
    token through the model against the cache — O(T*d) per token. The
    greedy token sequence is identical to the cacheless path (pinned in
    tests). ``use_cache=False`` keeps the round-3 re-forward-the-buffer
    loop (O(T^2) attention per token) as the independent reference.

    Tensor-parallel models (``tensor_axis``, incl. ``vocab_parallel_head``):
    pass ``comm=`` (the communicator whose mesh axis the model was built
    on) — the whole decode loop then runs inside its ``shard_map`` with
    per-rank local-head caches; a vocab-parallel head's local logits are
    ``all_gather``\\ ed (one ``[B, vocab]`` row per step) for sampling.

    MoE models decode with ``moe_impl='gshard'`` (plain-jit einsum
    dispatch; an ``'ep'``-trained model rebuilds as gshard on the SAME
    params — the expert stacks are identical). Use the cached path: the
    cacheless reference routes the zero-padded buffer through the gate,
    so with a tight ``capacity_factor`` padding competes with real tokens
    for expert capacity and the two paths can diverge (a warning fires).
    Sequence-sharded models still need a dense rebuild for inference.

    GSPMD at-rest layouts decode as-is: the decode loop is plain jit, so
    params placed by :func:`~chainermn_tpu.parallel.gspmd.megatron_shard`
    run under the partitioner, which inserts the gathers the Megatron
    layout needs (pinned by ``test_generate_with_megatron_layout``).

    ``eos_id``: early-stop token. Once a sequence samples it, every later
    position in that row is written as pad (0) instead of the sampled
    token — the row stops contributing changed tokens while the batch
    keeps its static shape (pure ``jnp.where`` masking, no recompile, no
    shape change). The decode loop still runs ``n_tokens`` steps (finished
    rows feed pad through the model), so cached/cacheless/TP parity is
    preserved; per-request wall-clock retirement on EOS is the serving
    engine's job (:mod:`chainermn_tpu.serving`), whose slot-retirement
    contract depends on exactly this masking.
    """
    if model.sequence_axis is not None:
        raise ValueError(
            "generate() does not support sequence-sharded models: rebuild "
            "with sequence_axis=None (attention='full') for inference"
        )
    if model.moe_experts and model.moe_impl != "gshard":
        raise ValueError(
            "generate() supports MoE only via moe_impl='gshard' — rebuild "
            "the model with moe_impl='gshard' for inference (same params)"
        )
    if temperature and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if (top_k or top_p < 1.0) and not temperature:
        raise ValueError(
            "top_k/top_p filter the sampling distribution; with "
            "temperature=0 (greedy) they have no effect — pass a "
            "temperature > 0"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if not 0 <= top_k <= model.vocab_size:
        raise ValueError(
            f"top_k must be in [0, vocab_size={model.vocab_size}], got "
            f"{top_k} (0 disables the filter)"
        )
    if eos_id is not None:
        eos_id = int(eos_id)  # normalize for the compiled-fn cache key
        if not 0 <= eos_id < model.vocab_size:
            raise ValueError(
                f"eos_id must be in [0, vocab_size={model.vocab_size}), "
                f"got {eos_id}"
            )
    if model.moe_experts and not use_cache:
        import warnings

        warnings.warn(
            "cacheless decode of an MoE model routes the zero-padded "
            "buffer positions through the gate, so padding competes for "
            "expert capacity: tokens can differ from the cached path "
            "(which routes only real tokens) unless capacity_factor is "
            "ample. Prefer use_cache=True for MoE decoding.",
            stacklevel=2,
        )
    b, t0 = prompt.shape
    if t0 + n_tokens > model.max_len:
        raise ValueError(
            f"{t0 + n_tokens} tokens exceed max_len={model.max_len}"
        )
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if model.tensor_axis is not None:
        if comm is None or not use_cache:
            raise ValueError(
                "tensor-parallel generate() needs comm= and use_cache=True "
                "(the decode loop runs inside the communicator's shard_map)"
            )
        run = _generate_tp_fn(model, int(n_tokens), float(temperature),
                              int(top_k), float(top_p), b, int(t0),
                              jnp.dtype(prompt.dtype).name, comm, eos_id)
        return run(params, prompt, rng)
    fn = _generate_cached_fn if use_cache else _generate_fn
    run = fn(model, int(n_tokens), float(temperature), int(top_k),
             float(top_p), b, int(t0), jnp.dtype(prompt.dtype).name, eos_id)
    return run(params, prompt, rng)


def _sampler(temperature, top_k=0, top_p=1.0):
    """(logits [B, V], key) -> (token [B], key); the split sequence is
    identical between the cached and cacheless paths so sampled outputs
    match too (given equal logits).

    Filters compose in the standard order: temperature scaling, then top-k
    truncation, then nucleus (top-p) truncation of what remains. Top-p
    always keeps at least the most probable token (the mask keeps entries
    whose cumulative probability BEFORE them is < p)."""

    def sample(lg, key):
        key, sub = jax.random.split(key)
        if not temperature:
            return jnp.argmax(lg, axis=-1), key
        lg = lg / temperature
        if top_k:
            kth = lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        if top_p < 1.0:
            srt = jnp.sort(lg, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                             keepdims=True)
            lg = jnp.where(lg < cutoff, -jnp.inf, lg)
        return jax.random.categorical(sub, lg, axis=-1), key

    return sample


def _eos_tracker(eos_id, b):
    """(init_done, mask_fn) for EOS early-stop: ``init_done(first)`` flags
    rows whose FIRST generated token is EOS; ``mask_fn(done, nxt)`` returns
    ``(write, new_done)`` — pad (0) for already-done rows, and the done set
    grown by rows that just sampled EOS. With ``eos_id=None`` both are
    identity/always-false, compiling to nothing."""
    if eos_id is None:
        return (lambda first: jnp.zeros((b,), bool),
                lambda done, nxt: (nxt, done))

    def mask(done, nxt):
        return jnp.where(done, jnp.zeros_like(nxt), nxt), done | (nxt == eos_id)

    return (lambda first: first == eos_id), mask


@functools.lru_cache(maxsize=32)
def _generate_cached_fn(model, n_tokens, temperature, top_k, top_p, b, t0,
                        dtype_name, eos_id=None):
    """KV-cached decode: one prefill over the prompt, then one token per
    step against the static cache. Compiled per (model, shape, sampler)
    key. NOTE the lru_cache retains compiled programs closed over param
    SHAPES only (params are arguments), but each entry still holds a
    full decode executable — bounded by maxsize."""
    total = t0 + n_tokens
    dtype = jnp.dtype(dtype_name)
    sample = _sampler(temperature, top_k, top_p)
    init_done, eos_mask = _eos_tracker(eos_id, b)

    @jax.jit
    def run(params, prompt, rng):
        caches = init_kv_caches(model, b, total)
        buf = jnp.zeros((b, total), dtype).at[:, :t0].set(prompt)
        logits, caches = model.apply(params, prompt, 0, kv_caches=caches)
        nxt, key = sample(logits[:, -1], rng)
        buf = buf.at[:, t0].set(nxt.astype(dtype))
        done = init_done(nxt)

        def step(carry, i):
            buf, caches, key, done = carry
            tok = lax.dynamic_slice_in_dim(buf, i, 1, axis=1)
            lg, caches = model.apply(params, tok, i, kv_caches=caches)
            nxt, key = sample(lg[:, 0], key)
            write, done = eos_mask(done, nxt)
            buf = lax.dynamic_update_slice(
                buf, write[:, None].astype(dtype), (0, i + 1))
            return (buf, caches, key, done), None

        (buf, _, _, _), _ = lax.scan(
            step, (buf, caches, key, done), jnp.arange(t0, total - 1))
        return buf

    return run


@functools.lru_cache(maxsize=8)
def _generate_tp_fn(model, n_tokens, temperature, top_k, top_p, b, t0,
                    dtype_name, comm, eos_id=None):
    """Tensor-parallel cached decode: the same loop as
    :func:`_generate_cached_fn` traced INSIDE ``comm.shard_map`` — per-rank
    caches hold the rank's local heads, and a vocab-parallel head's local
    logits are all_gather'ed (one [B, vocab] row per step) before sampling.
    Keyed on the communicator by identity — reuse the same comm object to
    reuse the compile."""
    from jax.sharding import PartitionSpec as P

    total = t0 + n_tokens
    dtype = jnp.dtype(dtype_name)
    sample = _sampler(temperature, top_k, top_p)
    axis = model.tensor_axis
    n_tp = comm.mesh.shape[axis]
    if model.n_heads % n_tp:
        raise ValueError(
            f"n_heads {model.n_heads} not divisible by tensor-axis size {n_tp}"
        )
    local_h = model.n_heads // n_tp
    init_done, eos_mask = _eos_tracker(eos_id, b)

    def body(params, prompt, rng):
        def last_logits(tokens, offset, caches):
            """Logits at the LAST input position, [B, vocab] — sliced
            before the vocab all_gather so prefill ships one row per batch
            element, not [B, T0, vocab]."""
            lg, caches = model.apply(params, tokens, offset,
                                     kv_caches=caches)
            lg = lg[:, -1]
            if model.vocab_parallel_head:
                lg = lax.all_gather(lg, axis, axis=-1, tiled=True)
            return lg, caches

        caches = init_kv_caches(model, b, total, local_heads=local_h)
        buf = jnp.zeros((b, total), dtype).at[:, :t0].set(prompt)
        logits, caches = last_logits(prompt, 0, caches)
        nxt, key = sample(logits, rng)
        buf = buf.at[:, t0].set(nxt.astype(dtype))
        done = init_done(nxt)

        def step(carry, i):
            buf, caches, key, done = carry
            tok = lax.dynamic_slice_in_dim(buf, i, 1, axis=1)
            lg, caches = last_logits(tok, i, caches)
            nxt, key = sample(lg, key)
            write, done = eos_mask(done, nxt)
            buf = lax.dynamic_update_slice(
                buf, write[:, None].astype(dtype), (0, i + 1))
            return (buf, caches, key, done), None

        (buf, _, _, _), _ = lax.scan(
            step, (buf, caches, key, done), jnp.arange(t0, total - 1))
        return buf

    return jax.jit(comm.shard_map(
        body, in_specs=(P(), P(), P()), out_specs=P(), check_vma=False,
    ))


@functools.lru_cache(maxsize=32)
def _generate_fn(model, n_tokens, temperature, top_k, top_p, b, t0,
                 dtype_name, eos_id=None):
    """The cacheless reference decode (round-3 behavior): re-runs the full
    forward over the whole buffer per token — O(T^2) attention x T tokens.
    Kept as the independent correctness reference for the cached path.
    One compiled decode program per (model, shape, sampler) key —
    flax modules are frozen/hashable, so they key an lru_cache directly."""
    total = t0 + n_tokens
    dtype = jnp.dtype(dtype_name)
    sample = _sampler(temperature, top_k, top_p)
    _, eos_mask = _eos_tracker(eos_id, b)

    @jax.jit
    def run(params, prompt, rng):
        buf = jnp.zeros((b, total), dtype).at[:, :t0].set(prompt)
        done = jnp.zeros((b,), bool)  # every token is sampled inside the scan

        def step(carry, i):
            buf, key, done = carry
            logits = model.apply(params, buf)      # [B, total, V]
            # the token at position i is predicted from the logits at i-1
            nxt_logits = lax.dynamic_slice_in_dim(logits, i - 1, 1, axis=1)[:, 0]
            nxt, key = sample(nxt_logits, key)
            write, done = eos_mask(done, nxt)
            buf = buf.at[:, i].set(write.astype(buf.dtype))
            return (buf, key, done), None

        (out, _, _), _ = lax.scan(step, (buf, rng, done), jnp.arange(t0, total))
        return out

    return run
