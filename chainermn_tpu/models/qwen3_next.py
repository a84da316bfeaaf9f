"""Qwen3-Next-style decoder: three Gated DeltaNet (linear-attention) layers
to one gated full-attention layer, and in every layer a dropless top-k
mixture of small SiLU-gated experts with a gated shared expert, of which
this chip may hold a share.

The layer, as the published ``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct`` names its sizes (no biases anywhere,
embedding and head untied; layer ``i`` is a full-attention layer where
``(i + 1) % full_attention_interval == 0`` and a linear-attention layer
elsewhere)::

    a = RMSNorm_1(x_in)

    linear-attention layer (Gated DeltaNet, arXiv:2412.06464):
      q, k [Hk, dk], v, z [Hv, dv] = a @ W_qkvz ;  b, al [Hv] = a @ W_ba
      q, k, v = split(silu(causal depthwise conv_K(concat(q, k, v))))
      beta = sigmoid(b) ;  g = -exp(A_log) * softplus(al + dt_bias)
      q = l2norm(q) / sqrt(dk) ;  k = l2norm(k)     # value head h reads
                                                    # key head h // (Hv/Hk)
      per value head, S [dk, dv] in float32 from 0:
          S = exp(g_t) S ;  d = beta_t (v_t - S^T k_t)
          S = S + k_t d^T ;  o_t = S^T q_t
      x = x_in + (RMSNorm_dv(o) * silu(z)) @ W_out

    full-attention layer:
      q, gate [H, D] = a @ W_q ;  k, v [Hkv, D] = a @ W_k, a @ W_v
      q, k = RoPE(RMSNorm_D(q), RMSNorm_D(k))       # the first
                                                    # partial_rotary_factor
                                                    # of each head
      o_h = softmax(q_h k^T / sqrt(D) over j <= t) v   # head h reads h // G
      x = x_in + (o * sigmoid(gate)) @ W_o

    m = RMSNorm_2(x)
    y = sum_{e in top-k of softmax(m @ W_router), e held here}
            p_e / sum_top p * expert_e(m)  +  sigmoid(m @ w_sg) * shared(m)
    x_out = x + y

The published norm is ``x * rsqrt(mean x^2 + eps) * (1 + w)``; the model
holds ``scale = 1 + w`` as the one leaf (a parametrisation). The published
weights interleave q, k, v, z by key head inside ``W_qkvz`` (b, a inside
``W_ba``, query and gate by head inside ``W_q``); here each is one run of
columns, a permutation that a checkpoint loader would apply.

:class:`GatedDeltaNet` has two forms that agree (tests hold them equal): a
whole sequence from a zero state runs the chunked form (chunks of
``CHUNK``: inside a chunk one triangular system, solved by doubling;
between chunks the state carries over), one token a row runs the recurrence
on the state it is given. Each form is one Pallas kernel at heads of whole
lanes (the published 128) and XLA at narrower ones (the tests' small
model). The chunked form:
:func:`~chainermn_tpu.ops.gated_delta.chunk_gated_delta` (the state in
VMEM across a prompt's chunks, only the live chunks walked;
:func:`~chainermn_tpu.ops.gated_delta.kernel_takes`), else
:func:`chunk_gated_delta_rule`. One token a row:
:func:`~chainermn_tpu.ops.gated_delta.recurrent_gated_delta` (each batch
row's state read once and written once where it lies in the store;
:func:`~chainermn_tpu.ops.gated_delta.decode_kernel_takes`), else
:func:`recurrent_gated_delta_step` over the whole store. Serving:
``kv_cache_spec()`` tells the engine two kinds of state, the full layers'
K/V rows in a block store and the linear layers' ``S`` and the
convolution's last inputs, a row a slot
(:class:`~chainermn_tpu.models.transformer.SlotStateKind`). With
``kv_caches`` the model takes whole fresh prompts from position 0 or one
token a row; :class:`~chainermn_tpu.serving.ServingEngine` refuses what
would continue a prompt at an offset.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.models.smallthinker import (
    attend_through_cache,
    rope,
    rope_inv_freq,
    token_positions,
)
from chainermn_tpu.models.transformer import KVCacheKind, SlotStateKind
from chainermn_tpu.ops.gated_delta import (
    CHUNK,
    chunk_gated_delta,
    decode_kernel_takes,
    kernel_takes,
    recurrent_gated_delta,
)
from chainermn_tpu.parallel.moe import DroplessMoE

_HIGHEST = lax.Precision.HIGHEST


def _l2norm(x, eps: float = 1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def recurrent_gated_delta_step(state, q, k, v, g, beta):
    """One token of the gated delta rule on every row's state: ``state [R,
    H, dk, dv]`` float32, ``q, k [R, H, dk]`` (normed, ``q`` scaled), ``v
    [R, H, dv]``, ``g, beta [R, H]``; ``(o [R, H, dv], new state)``. A row
    with ``g = 0`` and ``beta = 0`` keeps its state as it is. The state is
    read twice and written once, nothing of its size is kept beside it: the
    two reads against ``k`` and ``q`` are one pass, and ``o`` follows from
    them (``S_t^T q = exp(g) S^T q + d (k . q)``) without a pass over the
    new state. A layer's decode step at narrow heads; at heads of whole
    lanes the kernel (:func:`~chainermn_tpu.ops.gated_delta.
    recurrent_gated_delta`) computes the same and is held to it."""
    decay = jnp.exp(g)[..., None]                              # [R, H, 1]
    sk = jnp.sum(state * k[..., None], axis=-2)                # S^T k
    sq = jnp.sum(state * q[..., None], axis=-2)                # S^T q
    d = beta[..., None] * (v - decay * sk)
    o = decay * sq + d * jnp.sum(k * q, axis=-1, keepdims=True)
    new = decay[..., None] * state + k[..., None] * d[..., None, :]
    return o, new


def recurrent_gated_delta_rule(q, k, v, g, beta, state=None):
    """The recurrence over a sequence, a token at a time: ``q, k [B, T, H,
    dk]``, ``v [B, T, H, dv]``, ``g, beta [B, T, H]``, float32; ``(o [B, T,
    H, dv], final state [B, H, dk, dv])``. What the chunked form is held
    against."""
    b, _, h, dk = q.shape
    if state is None:
        state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def step(s, xs):
        o, s = recurrent_gated_delta_step(s, *xs)
        return s, o

    state, o = lax.scan(step, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule over whole sequences from a zero state, in
    chunks (arXiv:2412.06464, section 3.3), in XLA: shapes as
    :func:`recurrent_gated_delta_rule`. The layer's form for heads the
    kernel does not take (:func:`~chainermn_tpu.ops.gated_delta.
    kernel_takes`). Inside a chunk the tokens' updates
    ``u_i = beta_i (v_i - sum_{j<i} decay_ij (k_i . k_j) u_j)`` are one
    unit-triangular system ``(I + L) U = beta V``; ``L`` is strictly lower
    and so ``-L`` is nilpotent, and ``(I + L)^-1`` is the product of ``I +
    (-L)^(2^j)`` for ``j < log2(chunk)``: doublings, all of them products
    for the MXU, no loop over a chunk's rows. Between chunks a scan carries
    the state. Float32, products at full precision; every exponent is of a
    non-positive sum of ``g``, so nothing overflows however fast a head
    forgets. A position with ``g = 0`` and ``beta = 0`` (a bucket row's
    padding) leaves the state as it is."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(x):                       # [B, T, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
    t_of = lambda x: jnp.swapaxes(x, -1, -2)
    gc = jnp.cumsum(g, axis=-1)                               # [B, H, N, C]
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    diff = gc[..., :, None] - gc[..., None, :]                # [.., C, C]
    decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, diff, 0.0)),
                      0.0)
    kb = k * beta[..., None]
    lower = jnp.where(row > col, mm(kb, t_of(k)) * decay, 0.0)
    nil = -lower
    inv = jnp.eye(chunk, dtype=nil.dtype) + nil
    for _ in range(max(chunk - 1, 1).bit_length() - 1):
        nil = mm(nil, nil)
        inv = inv + mm(inv, nil)
    u = mm(inv, v * beta[..., None])                          # [.., C, dv]
    w = mm(inv, kb * jnp.exp(gc)[..., None])                  # [.., C, dk]
    local = mm(q, t_of(k)) * decay                            # j <= i
    q_in = q * jnp.exp(gc)[..., None]
    tail = jnp.exp(gc[..., -1:] - gc)                         # [B, H, N, C]
    k_out = k * tail[..., None]
    last = jnp.exp(gc[..., -1])                               # [B, H, N]

    def step(s, xs):
        u_n, w_n, local_n, q_n, k_n, last_n = xs
        v_new = u_n - mm(w_n, s)
        o = mm(q_n, s) + mm(local_n, v_new)
        s = s * last_n[..., None, None] + mm(t_of(k_n), v_new)
        return s, o

    state, o = lax.scan(
        step, jnp.zeros((b, h, dk, dv), u.dtype),
        tuple(jnp.moveaxis(x, 2, 0)
              for x in (u, w, local, q_in, k_out, last)))
    o = jnp.moveaxis(o, 0, 2)                                 # [B, H, N, C, dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :t], state


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer of a layer, ``a [B, S, d] -> ([B, S, d],
    new state)``. ``state`` is ``None`` (whole sequences from a zero state,
    nothing handed back) or a served layer's dict: ``S [rows, Hv, dk, dv]``
    float32 and ``conv [rows, K - 1, C]`` (the convolution's last inputs), a
    row a slot, ``valid [B]`` (the real tokens of each row) and, for whole
    fresh prompts, ``slots [B]`` (the store row each batch row's state
    after its last real token is written to). Without ``slots`` the batch
    rows ARE the store's first ``B`` rows, ``S == 1``, and the recurrence
    runs on them in place: a row with ``valid == 0`` keeps what it held.

    Under ``jax.named_scope`` the device operations read ``in_proj``,
    ``conv``, ``recurrence``, ``norm_gate`` and ``out_proj``."""

    d_model: int
    n_k_heads: int
    n_v_heads: int
    d_k: int
    d_v: int
    conv_kernel: int
    rms_norm_eps: float
    compute_dtype: jnp.dtype

    def decodes_in_kernel(self) -> bool:
        """Whether the decode step runs as one kernel
        (:func:`~chainermn_tpu.ops.gated_delta.decode_kernel_takes`: heads
        of whole lanes) or in XLA: the one place the form is chosen, read
        by ``__call__`` and by the model's state kind, under which the
        engine counts the rows a decode program advances."""
        return decode_kernel_takes(self.n_k_heads, self.n_v_heads, self.d_k,
                                   self.d_v)

    @nn.compact
    def __call__(self, a, state=None):
        dt = self.compute_dtype
        b, s, _ = a.shape
        hk, hv, dk, dv, kk = (self.n_k_heads, self.n_v_heads, self.d_k,
                              self.d_v, self.conv_kernel)
        key_dim, value_dim = hk * dk, hv * dv
        c = 2 * key_dim + value_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt,
                                         name=name)
        conv_w = self.param("conv_kernel", nn.initializers.normal(kk ** -0.5),
                            (kk, c))
        # the published start: A uniform in (0, 16), dt_bias ones
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, minval=1.0, maxval=16.0)), (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
        prefill = state is not None and "slots" in state
        decode = state is not None and not prefill
        if decode and s != 1:
            raise ValueError("a state without slots advances one token a row")
        valid = None if state is None else state["valid"]

        with jax.named_scope("in_proj"):
            qkvz = dense(c + value_dim, "qkvz_proj")(a)
            ba = dense(2 * hv, "ba_proj")(a).astype(jnp.float32)
            u, z = qkvz[..., :c], qkvz[..., c:]
        with jax.named_scope("conv"):
            if decode:
                past = state["conv"][:b]
            else:
                past = jnp.zeros((b, kk - 1, c), u.dtype)
            full = jnp.concatenate([past.astype(u.dtype), u], axis=1)
            w32 = conv_w.astype(jnp.float32)
            mixed = jax.nn.silu(sum(
                w32[j] * full[:, j:j + s].astype(jnp.float32)
                for j in range(kk)))
            if prefill:
                # the inputs at the row's last K - 1 real positions (zeros
                # before position 0): ``full`` holds u_t at t + K - 1
                at = valid[:, None] + jnp.arange(kk - 1)[None, :]
                new_conv = jnp.take_along_axis(full, at[:, :, None], axis=1)
            elif decode:
                new_conv = jnp.where(valid[:, None, None] > 0, full[:, 1:],
                                     past.astype(u.dtype))
        # the kernels take q, k and v as the convolution gives them; one
        # token a row, as a row of ``[B, C]``, shaped here so that the
        # convolution's fusion keeps its name
        kernel = (self.decodes_in_kernel() if decode
                  else kernel_takes(hk, hv, dk, dv))
        if decode and kernel:
            with jax.named_scope("conv"):
                mixed = mixed[:, 0]
        with jax.named_scope("recurrence"):
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(jnp.float32))
            if valid is not None:
                real = jnp.arange(s)[None, :] < valid[:, None]
                g = jnp.where(real[..., None], g, 0.0)
                beta = jnp.where(real[..., None], beta, 0.0)
            if not kernel:
                q = _l2norm(mixed[..., :key_dim].reshape(b, s, hk, dk))
                k = _l2norm(mixed[..., key_dim:2 * key_dim].reshape(
                    b, s, hk, dk))
                q, k = (jnp.repeat(x, hv // hk, axis=2)
                        for x in (q * dk ** -0.5, k))
                v = mixed[..., 2 * key_dim:].reshape(b, s, hv, dv)
            new_state = None
            if decode and kernel:
                # the batch rows' states read once and written once where
                # they lie; q, k and v read out of the convolution's
                # output, q and k normed, inside the kernel
                o, new_s = recurrent_gated_delta(
                    mixed, g[:, 0], beta[:, 0], state["S"], k_heads=hk,
                    dk=dk)
                o = o[:, None]
            elif decode:
                # the store's rows past the batch (the scratch row) ride
                # along with g = 0 and beta = 0, so the whole array is
                # read and written where it lies
                rows = state["S"].shape[0]
                fit = lambda x: jnp.pad(
                    x[:, 0], ((0, rows - b),) + ((0, 0),) * (x.ndim - 2))
                o, new_s = recurrent_gated_delta_step(
                    state["S"], *(fit(x) for x in (q, k, v, g, beta)))
                o = o[:b, None]
            elif kernel:
                # q, k and v read out of the convolution's output, q and k
                # normed, inside the kernel
                o, last = chunk_gated_delta(mixed, g, beta, valid,
                                            k_heads=hk, dk=dk)
            else:
                o, last = chunk_gated_delta_rule(q, k, v, g, beta)
            if decode:
                new_state = {
                    "S": new_s,
                    "conv": lax.dynamic_update_slice_in_dim(
                        state["conv"], new_conv.astype(state["conv"].dtype),
                        0, axis=0)}
            if prefill:
                new_state = {
                    "S": state["S"].at[state["slots"]].set(last),
                    "conv": state["conv"].at[state["slots"]].set(
                        new_conv.astype(state["conv"].dtype))}
        with jax.named_scope("norm_gate"):
            # a plain scale here, not 1 + w
            o = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=jnp.float32,
                           name="norm")(o)
            y = o * jax.nn.silu(z.reshape(b, s, hv, dv).astype(jnp.float32))
            y = y.astype(dt).reshape(b, s, value_dim)
        return dense(self.d_model, "out_proj")(y), new_state


class GatedAttention(nn.Module):
    """The full-attention mixer: per-head RMSNorm of q and k, rotary
    positions on the first ``rotary_dim`` entries of a head, and an
    elementwise sigmoid gate on the output, read from the query
    projection."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    rms_norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, a, pos, kv_cache=None):
        dt = self.compute_dtype
        b, s, _ = a.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt,
                                         name=name)
        norm = lambda name: nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt,
                                       name=name)
        qg = dense(2 * h * dh, "q_proj")(a)
        q = qg[..., :h * dh].reshape(b, s, h, dh)
        gate = jax.nn.sigmoid(qg[..., h * dh:].astype(jnp.float32))
        k = dense(hk * dh, "k_proj")(a).reshape(b, s, hk, dh)
        v = dense(hk * dh, "v_proj")(a).reshape(b, s, hk, dh)
        q, k = norm("q_norm")(q), norm("k_norm")(k)
        q, k = rope(q, k, pos, rope_inv_freq(self.rope_theta,
                                             self.rotary_dim))
        o, new_cache = attend_through_cache(q, k, v, pos, kv_cache, None)
        o = (o.reshape(b, s, h * dh).astype(jnp.float32) * gate).astype(dt)
        return dense(self.d_model, "o_proj")(o), new_cache


def _linear_mixer(m, **kw) -> GatedDeltaNet:
    """The linear-attention mixer of a block or a model ``m``: both carry
    its widths under the same names."""
    return GatedDeltaNet(
        d_model=m.d_model, n_k_heads=m.linear_k_heads,
        n_v_heads=m.linear_v_heads, d_k=m.linear_k_dim,
        d_v=m.linear_v_dim, conv_kernel=m.conv_kernel,
        rms_norm_eps=m.rms_norm_eps, compute_dtype=m.compute_dtype, **kw)


class Qwen3NextBlock(nn.Module):
    d_model: int
    linear: bool                    # a Gated DeltaNet layer
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    linear_k_heads: int
    linear_v_heads: int
    linear_k_dim: int
    linear_v_dim: int
    conv_kernel: int
    d_ff: int
    n_experts: int
    top_k: int
    held_experts: Optional[tuple]
    shared_d_ff: int
    rms_norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, kv_cache=None):
        dt = self.compute_dtype
        a = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm_1")(x)
        if self.linear:
            y, new_cache = _linear_mixer(self, name="gdn")(a, kv_cache)
        else:
            y, new_cache = GatedAttention(
                d_model=self.d_model, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                rotary_dim=self.rotary_dim, rope_theta=self.rope_theta,
                rms_norm_eps=self.rms_norm_eps, compute_dtype=dt,
                name="attn")(a, pos, kv_cache)
        x = x + y
        m = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm_2")(x)
        y = DroplessMoE(
            n_experts=self.n_experts, d_model=self.d_model, d_ff=self.d_ff,
            top_k=self.top_k, compute_dtype=dt, activation="silu",
            held=self.held_experts, shared_d_ff=self.shared_d_ff,
            shared_gate=True, name="moe")(m)
        return x + y, new_cache


class Qwen3NextLM(nn.Module):
    """``__call__(tokens [B, T], pos_offset)`` -> logits ``[B, T, vocab]``
    in float32; with ``kv_caches`` (a dict a layer: a paged cache for a
    full layer, a slot state for a linear layer, see the module docstring)
    ``(logits, new_caches)``; with ``logits_at [B]`` only those positions
    go through the head, logits ``[B, vocab]``.

    Layer ``i`` is a full-attention layer where ``(i + 1) %
    full_attention_interval == 0``. ``vocab_size`` is the rows of the
    embedding and the head held here, ``held_experts = (first, count)``
    this chip's share of the ``n_experts`` routed experts."""

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    linear_k_heads: int
    linear_v_heads: int
    linear_k_dim: int
    linear_v_dim: int
    d_ff: int
    n_experts: int
    top_k: int
    shared_d_ff: int
    held_experts: Optional[tuple] = None
    full_attention_interval: int = 4
    conv_kernel: int = 4
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    max_len: int = 16384
    compute_dtype: jnp.dtype = jnp.bfloat16
    # what ServingEngine asks of any model it serves; neither is offered
    sequence_axis: Optional[str] = None
    tensor_axis: Optional[str] = None

    def linear_layers(self) -> tuple:
        return tuple(i for i in range(self.n_layers)
                     if (i + 1) % self.full_attention_interval)

    def kv_cache_spec(self) -> tuple:
        linear = self.linear_layers()
        full = tuple(i for i in range(self.n_layers) if i not in linear)
        conv_width = (2 * self.linear_k_heads * self.linear_k_dim
                      + self.linear_v_heads * self.linear_v_dim)
        kinds = []
        if full:
            kinds.append(KVCacheKind("full", full, self.n_kv_heads,
                                     self.head_dim))
        if linear:
            kinds.append(SlotStateKind("linear", linear, (
                ("S", (self.linear_v_heads, self.linear_k_dim,
                       self.linear_v_dim), "float32"),
                ("conv", (self.conv_kernel - 1, conv_width),
                 jnp.dtype(self.compute_dtype).name)), CHUNK,
                _linear_mixer(self, parent=None).decodes_in_kernel()))
        return tuple(kinds)

    @nn.compact
    def __call__(self, tokens, pos_offset=0, kv_caches=None, logits_at=None):
        dt = self.compute_dtype
        b, t = tokens.shape
        pos = token_positions(pos_offset, b, t)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=dt,
                     name="embed")(tokens)
        linear = self.linear_layers()
        new_caches = []
        for i in range(self.n_layers):
            x, c = Qwen3NextBlock(
                d_model=self.d_model, linear=i in linear,
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim,
                rotary_dim=int(self.head_dim * self.partial_rotary_factor),
                rope_theta=self.rope_theta,
                linear_k_heads=self.linear_k_heads,
                linear_v_heads=self.linear_v_heads,
                linear_k_dim=self.linear_k_dim,
                linear_v_dim=self.linear_v_dim,
                conv_kernel=self.conv_kernel, d_ff=self.d_ff,
                n_experts=self.n_experts, top_k=self.top_k,
                held_experts=self.held_experts,
                shared_d_ff=self.shared_d_ff,
                rms_norm_eps=self.rms_norm_eps, compute_dtype=dt,
                name=f"block_{i}",
            )(x, pos, None if kv_caches is None else kv_caches[i])
            new_caches.append(c)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, logits_at[:, None, None], axis=1)[:, 0]
        x = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, dtype=dt,
                          name="lm_head")(x).astype(jnp.float32)
        if kv_caches is not None:
            return logits, new_caches
        return logits


__all__ = ["CHUNK", "GatedAttention", "GatedDeltaNet", "Qwen3NextBlock",
           "Qwen3NextLM", "chunk_gated_delta_rule",
           "recurrent_gated_delta_rule", "recurrent_gated_delta_step"]
