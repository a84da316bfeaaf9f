"""Elastic resharded restore (ISSUE 10): a snapshot saved on one mesh
resumes on a different one.

Three worlds are pinned here:

- **same mesh** — restore is bit-exact (the plain path);
- **flat-DP world resize** (8 ranks -> 4 ranks) — the multi-node
  optimizer re-wrap via :func:`restore_train_state`; the wrapper pmeans
  grads explicitly, so 10-step loss parity is exact;
- **(d=8, m=1) -> (d=4, m=2) dp x tp** — the TP-degree change routes
  through the qkv column permutation. The permutation + re-slice are
  grad-free and assert exactly; the 10-step loss-parity run rides the TP
  global-objective gradients.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.deploy import (
    elastic_restore,
    restore_train_state,
    snapshot_meta,
)
from chainermn_tpu.extensions.sharded_checkpoint import ShardedCheckpointer
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.training import jit_lm_train_step

VOCAB, DMODEL, HEADS, LAYERS = 64, 32, 4, 2
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (8, 12), 0, VOCAB)


def _dense_model():
    return TransformerLM(vocab_size=VOCAB, d_model=DMODEL, n_heads=HEADS,
                         n_layers=LAYERS, max_len=32,
                         compute_dtype=jnp.float32)


def _tp_model():
    return TransformerLM(vocab_size=VOCAB, d_model=DMODEL, n_heads=HEADS,
                         n_layers=LAYERS, max_len=32, tensor_axis="intra",
                         compute_dtype=jnp.float32)


def _hier_comm(shape):
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(shape), ("inter", "intra"))
    return chainermn_tpu.create_communicator("hierarchical", mesh=mesh)


def _rep_init(comm, model):
    sm = comm.shard_map(lambda tt: model.init(jax.random.PRNGKey(1), tt),
                        in_specs=P(), out_specs=P())
    return jax.jit(sm)(TOKENS)


def _tree_equal(a, b):
    for (kp, la), (_, lb) in zip(jax.tree_util.tree_leaves_with_path(a),
                                 jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=jax.tree_util.keystr(kp))


def test_snapshot_meta_captures_mesh_and_head_geometry():
    comm = _hier_comm((4, 2))
    meta = snapshot_meta(comm=comm, model=_tp_model(), run="r1")
    assert meta["mesh_shape"] == (4, 2)
    assert meta["mesh_axes"] == ("inter", "intra")
    assert meta["n_heads"] == HEADS
    assert meta["d_head"] == DMODEL // HEADS
    assert meta["tp_degree"] == 2
    assert meta["run"] == "r1"
    # dense model on a flat comm: degree 1, no tensor axis consulted
    flat = chainermn_tpu.create_communicator("tpu")
    assert snapshot_meta(comm=flat, model=_dense_model())["tp_degree"] == 1


@pytest.mark.slow  # multi-second train+restore cycles: full-suite only, tier-1 keeps the sub-second reshard cases
def test_same_mesh_restore_is_bit_exact(tmp_path):
    """Unchanged mesh degrades to the plain maybe_restore path: every
    leaf restores bit-for-bit, through the elastic entry point."""
    model = _dense_model()
    comm = chainermn_tpu.create_communicator("tpu")
    params = comm.bcast_data(model.init(jax.random.PRNGKey(1), TOKENS[:1]))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-2), comm)
    state = jax.device_put(opt.init(params), comm.named_sharding())
    step = jit_lm_train_step(model, opt, comm, donate=False)
    params, state, _, _ = step(params, state, TOKENS, TOKENS)

    cp = ShardedCheckpointer(str(tmp_path / "ckpt"))
    cp.save(1, {"params": params, "opt": state},
            meta=snapshot_meta(comm=comm, model=model))
    restored, got = elastic_restore(
        cp, {"params": params, "opt": state}, comm=comm, model=model)
    assert got == 1
    _tree_equal(restored, {"params": params, "opt": state})


def test_restore_without_snapshot_returns_none(tmp_path):
    cp = ShardedCheckpointer(str(tmp_path / "empty"))
    state, got = elastic_restore(cp, {"x": jnp.zeros(3)})
    assert state is None and got is None


@pytest.mark.slow  # multi-second train+restore cycles: full-suite only, tier-1 keeps the sub-second reshard cases
def test_flat_dp_world_resize_loss_parity(tmp_path):
    """The optimizer re-wrap acceptance: snapshot trained on 8-way flat
    DP resumes on a 4-way world (new communicator, new multi-node
    wrapper around the same inner optax transform) and the next 10 steps
    reproduce the 8-way loss curve."""
    model = _dense_model()
    comm_a = chainermn_tpu.create_communicator("tpu")
    params = comm_a.bcast_data(model.init(jax.random.PRNGKey(1), TOKENS[:1]))
    opt_a = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-2),
                                                      comm_a)
    state = jax.device_put(opt_a.init(params), comm_a.named_sharding())
    step_a = jit_lm_train_step(model, opt_a, comm_a, donate=False)
    for _ in range(3):
        params, state, _, _ = step_a(params, state, TOKENS, TOKENS)

    cp = ShardedCheckpointer(str(tmp_path / "ckpt"))
    cp.save(3, {"params": params, "opt": state},
            meta=snapshot_meta(comm=comm_a, model=model))

    losses_a = []
    pa, sa = params, state
    for _ in range(10):
        pa, sa, loss, _ = step_a(pa, sa, TOKENS, TOKENS)
        losses_a.append(float(loss))

    comm_b = chainermn_tpu.create_communicator(
        "tpu", devices=jax.devices()[:4])
    tmpl = jax.device_put(model.init(jax.random.PRNGKey(2), TOKENS[:1]),
                          comm_b.named_sharding())
    opt_b = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-2),
                                                      comm_b)
    restored, got = restore_train_state(
        cp, params_template=tmpl, optimizer=opt_b, comm=comm_b, model=model)
    assert got == 3

    step_b = jit_lm_train_step(model, opt_b, comm_b, donate=False)
    losses_b = []
    pb, sb = restored["params"], restored["opt"]
    for _ in range(10):
        pb, sb, loss, _ = step_b(pb, sb, TOKENS, TOKENS)
        losses_b.append(float(loss))
    np.testing.assert_allclose(losses_a, losses_b, rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # multi-second train+restore cycles: full-suite only, tier-1 keeps the sub-second reshard cases
def test_tp_degree_change_permutes_and_matches_forward(tmp_path):
    """(8,1) -> (4,2): the grad-free core of the dp x tp move. The
    restored tree must compute the SAME function at degree 2 that the
    snapshot computed at degree 1 — and restoring WITHOUT the
    permutation must NOT (the column order really is degree-baked)."""
    model = _tp_model()
    comm_a = _hier_comm((8, 1))
    comm_b = _hier_comm((4, 2))
    params = _rep_init(comm_a, model)
    opt = optax.adam(1e-2)  # TP path: plain optax (global-objective grads)
    state = jax.jit(opt.init)(params)

    cp = ShardedCheckpointer(str(tmp_path / "ckpt"))
    cp.save(0, {"params": params, "opt": state},
            meta=snapshot_meta(comm=comm_a, model=model))
    assert cp.manifest()["tp_degree"] == 1

    tmpl_p = _rep_init(comm_b, model)
    tmpl = {"params": tmpl_p, "opt": jax.jit(opt.init)(tmpl_p)}
    restored, got = elastic_restore(cp, tmpl, comm=comm_b, model=model)
    assert got == 0

    def logits(comm, p):
        sm = comm.shard_map(lambda pp, tt: model.apply(pp, tt),
                            in_specs=(P(), P()), out_specs=P())
        return np.asarray(jax.jit(sm)(p, TOKENS))

    la = logits(comm_a, params)
    lb = logits(comm_b, restored["params"])
    np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-5)

    # the negative control: same snapshot re-laid WITHOUT the qkv
    # permutation scrambles q/k/v across heads at degree 2
    raw, _ = cp.maybe_restore(tmpl, shardings=NamedSharding(comm_b.mesh, P()))
    assert np.max(np.abs(logits(comm_b, raw["params"]) - la)) > 1e-2

    # and the restored state trains (plumbing: shardings + opt moments
    # survived the gather -> permute -> re-slice round trip)
    step_b = jit_lm_train_step(model, opt, comm_b, donate=False)
    pb, sb = restored["params"], restored["opt"]
    losses = []
    for _ in range(5):
        pb, sb, loss, _ = step_b(pb, sb, TOKENS, TOKENS)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_tp_degree_change_loss_parity_over_10_steps(tmp_path):
    """The full dp x tp acceptance (vma JAX only — see module docstring):
    train 3 steps on (8,1), snapshot, and the (4,2) restore's next 10
    losses match the (8,1) continuation's."""
    model = _tp_model()
    comm_a = _hier_comm((8, 1))
    comm_b = _hier_comm((4, 2))
    params = _rep_init(comm_a, model)
    opt = optax.adam(1e-2)
    state = jax.jit(opt.init)(params)
    step_a = jit_lm_train_step(model, opt, comm_a, donate=False)
    for _ in range(3):
        params, state, _, _ = step_a(params, state, TOKENS, TOKENS)

    cp = ShardedCheckpointer(str(tmp_path / "ckpt"))
    cp.save(3, {"params": params, "opt": state},
            meta=snapshot_meta(comm=comm_a, model=model))

    losses_a = []
    pa, sa = params, state
    for _ in range(10):
        pa, sa, loss, _ = step_a(pa, sa, TOKENS, TOKENS)
        losses_a.append(float(loss))

    tmpl_p = _rep_init(comm_b, model)
    tmpl = {"params": tmpl_p, "opt": jax.jit(opt.init)(tmpl_p)}
    restored, _ = elastic_restore(cp, tmpl, comm=comm_b, model=model)
    step_b = jit_lm_train_step(model, opt, comm_b, donate=False)
    losses_b = []
    pb, sb = restored["params"], restored["opt"]
    for _ in range(10):
        pb, sb, loss, _ = step_b(pb, sb, TOKENS, TOKENS)
        losses_b.append(float(loss))
    np.testing.assert_allclose(losses_a, losses_b, rtol=2e-3, atol=2e-4)


def test_manifest_less_snapshot_takes_the_plain_path(tmp_path):
    """Legacy snapshots (no .meta sidecar) restore exactly as before —
    elastic_restore assumes the degrees agree and stays bit-exact."""
    import shutil

    model = _dense_model()
    comm = chainermn_tpu.create_communicator("tpu")
    params = comm.bcast_data(model.init(jax.random.PRNGKey(1), TOKENS[:1]))
    path = str(tmp_path / "ckpt")
    cp = ShardedCheckpointer(path)
    cp.save(2, {"params": params})
    shutil.rmtree(path + ".meta")
    assert cp.manifest() is None
    restored, got = elastic_restore(cp, {"params": params},
                                    comm=comm, model=model)
    assert got == 2
    _tree_equal(restored, {"params": params})


def test_tp_degree_change_without_geometry_raises(tmp_path):
    """A degree change with no manifest head geometry (and none passed
    explicitly) must refuse — restoring unpermuted silently scrambles."""
    model = _tp_model()
    comm_a = _hier_comm((8, 1))
    comm_b = _hier_comm((4, 2))
    params = _rep_init(comm_a, model)
    cp = ShardedCheckpointer(str(tmp_path / "ckpt"))
    cp.save(0, {"params": params}, meta={"tp_degree": 1})  # no n_heads
    with pytest.raises(ValueError, match="head geometry"):
        elastic_restore(cp, {"params": params}, comm=comm_b,
                        tp_degree=2)


def test_reshard_fault_cut_point_fires(tmp_path):
    """deploy.reshard is a chaos cut-point: an armed injector aborts the
    restore before any state moves."""
    from chainermn_tpu.resilience.faults import FaultInjector, InjectedFault

    model = _dense_model()
    comm = chainermn_tpu.create_communicator("tpu")
    params = comm.bcast_data(model.init(jax.random.PRNGKey(1), TOKENS[:1]))
    cp = ShardedCheckpointer(str(tmp_path / "ckpt"))
    cp.save(0, {"params": params}, meta=snapshot_meta(comm=comm, model=model))

    inj = FaultInjector()
    inj.arm("deploy.reshard")
    with inj:
        with pytest.raises(InjectedFault):
            elastic_restore(cp, {"params": params}, comm=comm, model=model)
    # disarmed, the same call restores fine
    restored, got = elastic_restore(cp, {"params": params},
                                    comm=comm, model=model)
    assert got == 0 and restored is not None
