"""Tensor parallelism: serial parity of the column/row pair, attention with
sharded heads, and the global-objective gradient pattern.

The reference's only TP is the channel-parallel conv example (SURVEY.md
S2.16); these pin the general engine's contract: same global weights ->
bit-identical-ish outputs and gradients as the unsharded computation, with
exactly one psum per MLP / attention block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.parallel import (
    TensorParallelAttention,
    TensorParallelMLP,
)
from chainermn_tpu.parallel.tensor import global_objective
from chainermn_tpu.parallel.sequence import full_attention


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("tpu")


def _run_replicated(comm, fn, *args):
    """Trace fn on the mesh with every input replicated, output replicated."""
    sm = comm.shard_map(
        fn, in_specs=tuple(P() for _ in args), out_specs=P(),
    )
    return jax.jit(sm)(*args)


def test_mlp_matches_serial_dense(comm):
    d_model, d_ff, b, t = 16, 64, 4, 6
    mlp = TensorParallelMLP(d_model=d_model, d_ff=d_ff,
                            axis_name=comm.axis_name)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, t, d_model))
    params = _run_replicated(
        comm, lambda xx: mlp.init(jax.random.PRNGKey(1), xx), x
    )

    got = _run_replicated(comm, lambda p, xx: mlp.apply(p, xx), params, x)

    # serial semantics with the SAME global weights
    cp = params["params"]["ColumnParallelDense_0"]
    rp = params["params"]["RowParallelDense_0"]
    want = jax.nn.gelu(x @ cp["kernel"] + cp["bias"]) @ rp["kernel"] + rp["bias"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_attention_matches_serial(comm):
    n = comm.size
    d_model, n_heads, b, t = 32, 8, 2, 6
    assert n_heads % n == 0
    attn = TensorParallelAttention(d_model=d_model, n_heads=n_heads,
                                   axis_name=comm.axis_name, causal=True)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, t, d_model))
    params = _run_replicated(
        comm, lambda xx: attn.init(jax.random.PRNGKey(3), xx), x
    )
    got = _run_replicated(comm, lambda p, xx: attn.apply(p, xx), params, x)

    # serial: undo the (rank, 3, local_head, d_head)-major feature order
    d_head, local_h = d_model // n_heads, n_heads // n
    qkv_k = params["params"]["qkv_tpcol"]["kernel"]       # [D, 3*d_model]
    qkv_b = params["params"]["qkv_tpcol"]["bias"]
    qkv = x @ qkv_k + qkv_b
    qkv = qkv.reshape(b, t, n, 3, local_h, d_head)
    q = qkv[:, :, :, 0].reshape(b, t, n * local_h, d_head)
    k = qkv[:, :, :, 1].reshape(b, t, n * local_h, d_head)
    v = qkv[:, :, :, 2].reshape(b, t, n * local_h, d_head)
    o = full_attention(q, k, v, causal=True)
    # row kernel rows are (rank, local_head, d_head)-major == the o layout
    proj_k = params["params"]["proj_tprow"]["kernel"]     # [d_model, d_model]
    proj_b = params["params"]["proj_tprow"]["bias"]
    want = o.reshape(b, t, d_model) @ proj_k + proj_b
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_tp_grad_matches_serial(comm):
    """The global-objective pattern (tensor.py docstring) must reassemble the
    exact serial gradient for EVERY leaf: invariant params + pmean'd loss
    make replication tracking psum the zero-padded slice cotangents and
    average the replicated ones. (Differentiating a varying loss instead
    silently inflates every pre-psum leaf by n — the bug this test pins.)"""
    d_model, d_ff, b, t = 8, 32, 2, 4
    mlp = TensorParallelMLP(d_model=d_model, d_ff=d_ff,
                            axis_name=comm.axis_name)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, t, d_model))
    y = jax.random.normal(jax.random.PRNGKey(5), (b, t, d_model))
    params = _run_replicated(
        comm, lambda xx: mlp.init(jax.random.PRNGKey(6), xx), x
    )

    def tp_grads(p, xx, yy):
        def loss(pp):
            local = jnp.mean((mlp.apply(pp, xx) - yy) ** 2)
            return global_objective(local, comm.axis_name)

        return jax.grad(loss)(p)

    g_tp = jax.jit(comm.shard_map(
        tp_grads, in_specs=(P(), P(), P()), out_specs=P()
    ))(params, x, y)

    def serial_loss(p):
        cp, rp = p["params"]["ColumnParallelDense_0"], p["params"]["RowParallelDense_0"]
        out = (jax.nn.gelu(x @ cp["kernel"] + cp["bias"]) @ rp["kernel"]
               + rp["bias"])
        return jnp.mean((out - y) ** 2)

    g_serial = jax.grad(serial_loss)(params)
    flat_tp = jax.tree_util.tree_leaves_with_path(g_tp)
    flat_s = dict(
        (jax.tree_util.keystr(kp), l)
        for kp, l in jax.tree_util.tree_leaves_with_path(g_serial)
    )
    assert flat_tp
    for kp, l in flat_tp:
        key = jax.tree_util.keystr(kp)
        np.testing.assert_allclose(
            np.asarray(l), np.asarray(flat_s[key]),
            rtol=1e-4, atol=1e-6, err_msg=key,
        )


@pytest.mark.slow  # ~11s; TP training parity stays tier-1 via test_tp_lm_vocab_parallel_head_trains — keep tier-1 inside its timeout
def test_tp_transformer_lm_trains(comm):
    """TransformerLM(tensor_axis=...) through jit_lm_train_step: the TP
    dispatch path, global-objective grads, plain optax optimizer. Loss must
    decrease and params stay replicated-identical across steps."""
    import optax

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.training import jit_lm_train_step

    lm = TransformerLM(
        vocab_size=32, d_model=16, n_heads=8, n_layers=2, max_len=64,
        tensor_axis=comm.axis_name, compute_dtype=jnp.float32,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(10), (4, 12), 0, 32)
    params = _run_replicated(
        comm, lambda tt: lm.init(jax.random.PRNGKey(11), tt), tokens
    )
    opt = optax.adam(1e-2)
    state = jax.jit(opt.init)(params)
    step = jit_lm_train_step(lm, opt, comm, donate=False)
    losses = []
    for _ in range(5):
        params, state, lval, _ = step(params, state, tokens, tokens)
        losses.append(float(lval))
    assert losses[-1] < losses[0], losses


def test_vocab_parallel_cross_entropy_matches_optax(comm):
    """Sharded-vocab CE must equal optax CE on the gathered logits, value
    AND gradient, for targets landing in every shard (incl. edges)."""
    import optax

    from chainermn_tpu.parallel.tensor import vocab_parallel_cross_entropy

    n = comm.size
    v_local, b, t = 5, 3, 4
    vocab = n * v_local
    rng = np.random.RandomState(0)
    full_logits = jnp.asarray(rng.randn(b, t, vocab) * 3, jnp.float32)
    targets = jnp.asarray(rng.randint(0, vocab, (b, t)))
    # force shard-edge ids into the batch
    targets = targets.at[0, 0].set(0).at[0, 1].set(vocab - 1)
    targets = targets.at[0, 2].set(v_local - 1).at[0, 3].set(v_local)

    def vp(fl, tg):
        r = jax.lax.axis_index(comm.axis_name)
        local = jax.lax.dynamic_slice_in_dim(fl, r * v_local, v_local, axis=-1)
        return vocab_parallel_cross_entropy(local, tg, comm.axis_name)

    got = jax.jit(comm.shard_map(
        vp, in_specs=(P(), P()), out_specs=P()
    ))(full_logits, targets)
    want = optax.softmax_cross_entropy_with_integer_labels(
        full_logits, targets
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)

    # gradient parity wrt the full logits (assembled from the sharded bwd)
    def vp_loss(fl):
        return global_objective(jnp.mean(vp(fl, targets)), comm.axis_name)

    g_got = jax.jit(comm.shard_map(
        lambda fl: jax.grad(vp_loss)(fl), in_specs=P(), out_specs=P()
    ))(full_logits)
    g_want = jax.grad(
        lambda fl: optax.softmax_cross_entropy_with_integer_labels(
            fl, targets
        ).mean()
    )(full_logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-4, atol=1e-7)


def test_tp_lm_vocab_parallel_head_trains(comm):
    """TransformerLM(tensor_axis, vocab_parallel_head=True): local logits
    [B,T,V/n], sharded-vocab CE in the TP step, loss decreases."""
    import optax

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.training import jit_lm_train_step

    lm = TransformerLM(
        vocab_size=32, d_model=16, n_heads=8, n_layers=1, max_len=64,
        tensor_axis=comm.axis_name, vocab_parallel_head=True,
        compute_dtype=jnp.float32,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(12), (4, 12), 0, 32)
    params = _run_replicated(
        comm, lambda tt: lm.init(jax.random.PRNGKey(13), tt), tokens
    )
    # the head kernel is the only [d_model, vocab] leaf; under the module's
    # global-shape convention it still inits full-size
    assert params["params"]["lm_head"]["kernel"].shape == (16, 32)
    opt = optax.adam(1e-2)
    state = jax.jit(opt.init)(params)
    step = jit_lm_train_step(lm, opt, comm, donate=False)
    losses = []
    for _ in range(5):
        params, state, lval, _ = step(params, state, tokens, tokens)
        losses.append(float(lval))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("sp_kind", ["ring", "zigzag", "ulysses"])
def test_tp_attention_composes_with_sp(comm, sp_kind):
    """The docstring claim that TP (heads over one axis) composes with
    sequence parallelism (sequence over another): on the hierarchical
    (inter x intra) mesh, heads shard over intra and the sequence over
    inter; output must match serial full attention with the same weights.
    (Ulysses additionally needs local_heads divisible by the sp size;
    zigzag additionally exercises its varying-predicate lax.cond under the
    extra tensor axis' vma.)"""
    from chainermn_tpu.parallel.sequence import zigzag_permutation

    hier = chainermn_tpu.create_communicator("hierarchical")
    axes = hier.axis_name
    if isinstance(axes, str):
        pytest.skip("hierarchical comm degenerated to one axis")
    sp_axis, tp_axis = axes  # sequence over inter, heads over intra
    n_sp = hier.mesh.shape[sp_axis]
    n_tp = hier.mesh.shape[tp_axis]
    d_model, n_heads, b = 32, 8, 2
    t = 4 * n_sp  # global sequence, shards 4 tokens per sp rank
    assert n_heads % n_tp == 0
    if sp_kind == "ulysses" and (n_heads // n_tp) % n_sp:
        pytest.skip("ulysses needs local_heads divisible by sp size")
    attn = TensorParallelAttention(
        d_model=d_model, n_heads=n_heads, axis_name=tp_axis, causal=True,
        attention=sp_kind, sequence_axis=sp_axis,
    )
    x = jax.random.normal(jax.random.PRNGKey(30), (b, t, d_model))
    # zigzag shards hold (early, late) chunk pairs of the PERMUTED sequence
    perm = (zigzag_permutation(t, n_sp) if sp_kind == "zigzag"
            else jnp.arange(t))
    inv = jnp.argsort(perm)

    # init under the mesh on one sequence shard (collectives inside)
    params = jax.jit(hier.shard_map(
        lambda xx: attn.init(jax.random.PRNGKey(31), xx),
        in_specs=P(None, sp_axis), out_specs=P(),
    ))(x[:, perm])
    got = jax.jit(hier.shard_map(
        lambda p, xx: attn.apply(p, xx),
        in_specs=(P(), P(None, sp_axis)), out_specs=P(None, sp_axis),
    ))(params, x[:, perm])[:, inv]

    # serial reference: same (rank, 3, local_head, d_head)-major layout
    d_head, local_h = d_model // n_heads, n_heads // n_tp
    qkv_k = params["params"]["qkv_tpcol"]["kernel"]
    qkv_b = params["params"]["qkv_tpcol"]["bias"]
    qkv = (x @ qkv_k + qkv_b).reshape(b, t, n_tp, 3, local_h, d_head)
    q = qkv[:, :, :, 0].reshape(b, t, n_heads, d_head)
    k = qkv[:, :, :, 1].reshape(b, t, n_heads, d_head)
    v = qkv[:, :, :, 2].reshape(b, t, n_heads, d_head)
    o = full_attention(q, k, v, causal=True)
    proj_k = params["params"]["proj_tprow"]["kernel"]
    proj_b = params["params"]["proj_tprow"]["bias"]
    want = o.reshape(b, t, d_model) @ proj_k + proj_b
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # ~8s; each axis pair (DP+SP, SP+TP, DP+TP) covered individually tier-1 — keep tier-1 inside its timeout
def test_3d_dp_sp_tp_lm_trains(comm):
    """Full hybrid: dp x sp x tp over a (2,2,2) mesh — TransformerLM with
    ring attention over sp, Megatron blocks + vocab-parallel head over tp,
    batch over dp. Dispatched through the public jit_lm_train_step."""
    import optax

    from chainermn_tpu.communicators import MeshCommunicator
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.parallel import make_3d_mesh
    from chainermn_tpu.training import jit_lm_train_step

    mesh = make_3d_mesh()
    if 1 in mesh.shape.values():
        pytest.skip("needs a genuine 3-way factorization of the device count")
    c3 = MeshCommunicator(mesh=mesh)
    n_dp, n_sp, n_tp = (mesh.shape[a] for a in ("dp", "sp", "tp"))
    if 8 % n_tp:
        pytest.skip(f"8 heads not divisible by tp={n_tp}")
    lm = TransformerLM(
        vocab_size=16 * n_tp, d_model=16, n_heads=8, n_layers=1, max_len=128,
        attention="ring", sequence_axis="sp", tensor_axis="tp",
        vocab_parallel_head=True, compute_dtype=jnp.float32,
    )
    b, t_local = 2 * n_dp, 6  # global seq = t_local * n_sp
    tokens = jax.random.randint(jax.random.PRNGKey(40),
                                (b, t_local * n_sp), 0, 16 * n_tp)
    params = jax.jit(c3.shard_map(
        lambda tt: lm.init(jax.random.PRNGKey(41), tt),
        in_specs=P("dp", "sp"), out_specs=P(),
    ))(tokens)
    opt = optax.adam(1e-2)
    state = jax.jit(opt.init)(params)
    step = jit_lm_train_step(lm, opt, c3, shard_sequence=True, donate=False)
    losses = []
    for _ in range(5):
        params, state, lval, _ = step(params, state, tokens, tokens)
        losses.append(float(lval))
    assert losses[-1] < losses[0], losses


def test_global_objective_rejects_vma_off(comm):
    """Under check_vma=False no pmean would ever fire and the pattern's
    grads would be silently wrong — it must raise instead."""
    def f(x):
        return global_objective(jnp.sum(x), comm.axis_name)[None]

    with pytest.raises(ValueError, match="check_vma=False"):
        jax.jit(comm.shard_map(
            f, in_specs=comm.data_spec, out_specs=comm.data_spec,
            check_vma=False,
        ))(jnp.ones((8, 2)))


def test_tp_lm_rejects_flash_off_tpu(comm):
    import optax

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.training import jit_lm_train_step

    lm = TransformerLM(vocab_size=16, d_model=16, n_heads=8, n_layers=1,
                       tensor_axis=comm.axis_name, attention="flash")
    with pytest.raises(ValueError, match="flash"):
        jit_lm_train_step(lm, optax.sgd(0.1), comm)


def test_tp_lm_rejects_full_attention_with_sequence_axis(comm):
    """'full' under a sharded sequence would silently compute block-diagonal
    attention — must be rejected, like the dense path does."""
    import optax

    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.training import jit_lm_train_step

    hier = chainermn_tpu.create_communicator("hierarchical")
    axes = hier.axis_name
    if isinstance(axes, str):
        pytest.skip("hierarchical comm degenerated to one axis")
    sp_axis, tp_axis = axes
    lm = TransformerLM(vocab_size=16, d_model=16, n_heads=8, n_layers=1,
                       tensor_axis=tp_axis, sequence_axis=sp_axis)
    with pytest.raises(ValueError, match="ring"):
        jit_lm_train_step(lm, optax.sgd(0.1), hier, shard_sequence=True)
    # and shard_sequence=False must not silently shard the sequence anyway
    lm_ring = TransformerLM(vocab_size=16, d_model=16, n_heads=8, n_layers=1,
                            attention="ring", tensor_axis=tp_axis,
                            sequence_axis=sp_axis)
    with pytest.raises(ValueError, match="shard_sequence=True"):
        jit_lm_train_step(lm_ring, optax.sgd(0.1), hier, shard_sequence=False)


def test_tp_lm_rejects_foreign_axis(comm):
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.training import jit_lm_train_step
    import optax

    lm = TransformerLM(vocab_size=8, d_model=8, n_heads=8, n_layers=1,
                       tensor_axis="nonexistent")
    with pytest.raises(ValueError, match="mesh axes"):
        jit_lm_train_step(lm, optax.sgd(0.1), comm)


def test_hybrid_dp_tp_step_trains(comm):
    """dp x tp over a 2-axis mesh: batch sharded over dp, weights sliced over
    tp, per-leaf grad reduction — loss decreases and params stay replicated."""
    hier = chainermn_tpu.create_communicator("hierarchical")
    axes = hier.axis_name
    if isinstance(axes, str):
        pytest.skip("hierarchical comm degenerated to one axis")
    dp_axis, tp_axis = axes
    d_model, d_ff = 8, 16
    mlp = TensorParallelMLP(d_model=d_model, d_ff=d_ff, axis_name=tp_axis)
    n_dp = hier.mesh.shape[dp_axis]
    xs = jax.random.normal(jax.random.PRNGKey(7), (2 * n_dp, 3, d_model))
    ys = jax.random.normal(jax.random.PRNGKey(8), (2 * n_dp, 3, d_model))
    params = jax.jit(hier.shard_map(
        lambda xx: mlp.init(jax.random.PRNGKey(9), xx[:1]),
        in_specs=P(dp_axis), out_specs=P()
    ))(xs)

    import optax

    opt = optax.sgd(0.1)
    state = jax.jit(opt.init)(params)

    def step(p, s, xx, yy):
        def loss(pp):
            local = jnp.mean((mlp.apply(pp, xx) - yy) ** 2)
            return global_objective(local, (dp_axis, tp_axis))

        lval, g = jax.value_and_grad(loss)(p)
        updates, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s2, lval

    jstep = jax.jit(hier.shard_map(
        step,
        in_specs=(P(), P(), P(dp_axis), P(dp_axis)),
        out_specs=(P(), P(), P()),
    ))
    losses = []
    for _ in range(5):
        params, state, lval = jstep(params, state, xs, ys)
        losses.append(float(lval))
    assert losses[-1] < losses[0], losses


def test_reshard_tp_qkv_between_degrees():
    """ADVICE r3: the qkv kernel's column order bakes in the TP degree —
    reshard_tp_qkv must permute a checkpoint so the serial qkv math at the
    NEW degree reproduces the old degree's q/k/v exactly, and round-trip."""
    from chainermn_tpu.parallel import reshard_tp_qkv

    h, dh, d_in = 8, 4, 16
    width = 3 * h * dh
    kern = jax.random.normal(jax.random.PRNGKey(0), (d_in, width))
    bias = jax.random.normal(jax.random.PRNGKey(1), (width,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, d_in))

    def serial_qkv(k, b, n):
        qkv = (x @ k + b).reshape(2, 5, n, 3, h // n, dh)
        return tuple(
            qkv[:, :, :, i].reshape(2, 5, h, dh) for i in range(3))

    tree8 = {"attn": {"qkv_tpcol": {"kernel": kern, "bias": bias}}}
    want = serial_qkv(kern, bias, 8)
    for new in (1, 2, 4):
        t2 = reshard_tp_qkv(tree8, h, dh, 8, new)
        got = serial_qkv(t2["attn"]["qkv_tpcol"]["kernel"],
                         t2["attn"]["qkv_tpcol"]["bias"], new)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        back = reshard_tp_qkv(t2, h, dh, new, 8)
        np.testing.assert_array_equal(
            np.asarray(back["attn"]["qkv_tpcol"]["kernel"]),
            np.asarray(kern))
    with pytest.raises(ValueError, match="divide"):
        reshard_tp_qkv(tree8, h, dh, 8, 3)
