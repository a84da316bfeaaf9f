"""ZeRO-1 sharded optimizer state: parity with the unsharded multi-node
optimizer, memory sharding, and train-step integration."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.models import MLP
from chainermn_tpu.training import jit_train_step


@pytest.fixture(scope="module")
def comm():
    return chainermn_tpu.create_communicator("tpu")


def _setup(comm, optimizer):
    # f32 compute: the parity tests compare two independently-compiled
    # trajectories, and bf16 rounding differs per compilation (check_vma
    # changes fusion) — in bf16 a 1-ULP step-1 difference snowballs through
    # momentum into O(1) loss divergence and the comparison is meaningless
    model = MLP(n_units=16, n_out=4, compute_dtype=jnp.float32)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(4 * comm.size, 28, 28), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 4, 4 * comm.size))
    variables = comm.bcast_data(model.init(jax.random.PRNGKey(0), images[:1]))
    spec = getattr(optimizer, "state_spec", P())
    opt_state = jax.device_put(
        optimizer.init(variables["params"]), comm.named_sharding(*spec)
    )
    step = jit_train_step(model, optimizer, comm, donate=False)
    return step, variables, opt_state, images, labels


@pytest.mark.parametrize("inner", ["adam", "sgd_momentum"])
def test_zero_matches_unsharded(comm, inner):
    """ZeRO-1 must produce the SAME parameter trajectory as the plain
    multi-node optimizer wrapping the same inner optimizer."""
    make = (lambda: optax.adam(1e-3)) if inner == "adam" else (
        lambda: optax.sgd(0.05, momentum=0.9))

    ref_opt = chainermn_tpu.create_multi_node_optimizer(make(), comm)
    zero_opt = chainermn_tpu.create_zero_optimizer(make(), comm)
    step_r, vars_r, st_r, images, labels = _setup(comm, ref_opt)
    step_z, vars_z, st_z, _, _ = _setup(comm, zero_opt)

    for _ in range(4):
        vars_r, st_r, loss_r = step_r(vars_r, st_r, images, labels)
        vars_z, st_z, loss_z = step_z(vars_z, st_z, images, labels)
    # f32 compute keeps the two independently-compiled trajectories
    # comparable to float noise (check_vma=False changes fusion slightly)
    np.testing.assert_allclose(float(loss_z), float(loss_r), rtol=1e-5)
    for lr, lz in zip(jax.tree_util.tree_leaves(vars_r["params"]),
                      jax.tree_util.tree_leaves(vars_z["params"])):
        np.testing.assert_allclose(np.asarray(lz), np.asarray(lr),
                                   rtol=2e-5, atol=2e-6)


def test_zero_sharded_clip_matches_replicated_clip(comm):
    """clip_by_global_norm_sharded inside the ZeRO inner chain must clip by
    the TRUE global norm: same trajectory as replicated optax.chain(
    clip_by_global_norm, sgd) under the multi-node optimizer. A plain
    optax.clip_by_global_norm in the shard would use 1/n-shard norms and
    diverge — the documented ZeRO constraint this transform lifts."""
    max_norm = 0.05  # small enough that clipping actually engages

    ref_opt = chainermn_tpu.create_multi_node_optimizer(
        optax.chain(optax.clip_by_global_norm(max_norm),
                    optax.sgd(0.1, momentum=0.9)), comm
    )
    zero_opt = chainermn_tpu.create_zero_optimizer(
        optax.chain(
            chainermn_tpu.clip_by_global_norm_sharded(max_norm, comm),
            optax.sgd(0.1, momentum=0.9),
        ),
        comm,
    )
    step_r, vars_r, st_r, images, labels = _setup(comm, ref_opt)
    step_z, vars_z, st_z, _, _ = _setup(comm, zero_opt)
    for _ in range(4):
        vars_r, st_r, loss_r = step_r(vars_r, st_r, images, labels)
        vars_z, st_z, loss_z = step_z(vars_z, st_z, images, labels)
    np.testing.assert_allclose(float(loss_z), float(loss_r), rtol=1e-5)
    for lr, lz in zip(jax.tree_util.tree_leaves(vars_r["params"]),
                      jax.tree_util.tree_leaves(vars_z["params"])):
        np.testing.assert_allclose(np.asarray(lz), np.asarray(lr),
                                   rtol=2e-5, atol=2e-6)


def test_zero_state_is_sharded(comm):
    """Moment leaves must be rank-major [n, shard] and actually sharded —
    per-device optimizer memory is full/n (the ZeRO-1 claim)."""
    n = comm.size
    zero_opt = chainermn_tpu.create_zero_optimizer(optax.adam(1e-3), comm)
    params = {"w": jnp.zeros((n * 10, 3)), "b": jnp.zeros((5,))}
    state = jax.device_put(zero_opt.init(params),
                           comm.named_sharding(*zero_opt.state_spec))
    total = sum(l.size for l in jax.tree_util.tree_leaves(params))
    padded = total + ((-total) % n)
    mu = state[0].mu  # adam: ScaleByAdamState(count, mu, nu)
    assert mu.shape == (n, padded // n)
    # sharded placement: each device addresses 1/n of the moment bytes
    db = mu.sharding.shard_shape(mu.shape)
    assert db[0] == 1
    # count leaf got the rank axis too (single spec covers all leaves)
    assert state[0].count.shape == (n,)


def test_zero_rejects_hierarchical_and_split(comm):
    hier = chainermn_tpu.create_communicator("hierarchical")
    with pytest.raises(ValueError, match="flat"):
        chainermn_tpu.create_zero_optimizer(optax.adam(1e-3), hier)
    sub = comm.split([r % 2 for r in range(comm.size)])
    with pytest.raises(ValueError, match="split"):
        chainermn_tpu.create_zero_optimizer(optax.adam(1e-3), sub)


def test_zero_preserves_mixed_param_dtypes(comm):
    """Moments run in f32 internally, but updates must come back in each
    leaf's own dtype so bf16 params stay bf16 through apply_updates
    (VERDICT r1 #10)."""
    n = comm.size
    params = {
        "w16": jnp.full((n * 4,), 0.5, jnp.bfloat16),
        "w32": jnp.full((3, 3), 0.5, jnp.float32),
    }
    zero_opt = chainermn_tpu.create_zero_optimizer(optax.adam(1e-2), comm)
    state = jax.device_put(zero_opt.init(params),
                           comm.named_sharding(*zero_opt.state_spec))

    def body(params, state):
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        updates, state = zero_opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    step = jax.jit(comm.shard_map(
        body, in_specs=(P(), zero_opt.state_spec),
        out_specs=(P(), zero_opt.state_spec), check_vma=zero_opt.check_vma,
    ))
    new_params, _ = step(params, state)
    assert new_params["w16"].dtype == jnp.bfloat16
    assert new_params["w32"].dtype == jnp.float32
    # and the update actually moved the params
    assert float(np.asarray(new_params["w32"])[0, 0]) != 0.5


def test_zero_wire_dtype_halves_bytes(comm):
    """bf16 gradients must ride the wire in bf16: the ZeRO step's collective
    bytes (psum_scatter + all_gather) halve versus f32 gradients (VERDICT r2
    #7). Bytes are read via parse_hlo_collectives from the PRE-optimization
    HLO: XLA:CPU legalizes bf16 collectives to f32 (a test-backend artifact
    — TPU moves bf16 natively), so the compiled text would hide the wire
    dtype the program actually requests."""
    from chainermn_tpu.extensions import parse_hlo_collectives

    n = comm.size
    zero_opt = chainermn_tpu.create_zero_optimizer(optax.adam(1e-2), comm)

    def hlo_bytes(dtype):
        params = {"w": jnp.zeros((n * 256,), dtype)}
        state = jax.device_put(zero_opt.init(params),
                               comm.named_sharding(*zero_opt.state_spec))

        def body(params, state):
            grads = jax.tree_util.tree_map(jnp.ones_like, params)
            updates, state = zero_opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        step = jax.jit(comm.shard_map(
            body, in_specs=(P(), zero_opt.state_spec),
            out_specs=(P(), zero_opt.state_spec), check_vma=zero_opt.check_vma,
        ))
        hlo = step.lower(params, state).as_text(dialect="hlo")
        return parse_hlo_collectives(hlo)["total_bytes"]

    b32 = hlo_bytes(jnp.float32)
    b16 = hlo_bytes(jnp.bfloat16)
    assert b16 <= 0.55 * b32, (b16, b32)


def test_zero_explicit_wire_dtype_overrides(comm):
    """An explicit wire_dtype (or the communicator's allreduce_grad_dtype)
    compresses even f32 gradients, mirroring the reference's fp16 allreduce
    knob; the trajectory still tracks the uncompressed one loosely."""
    n = comm.size
    opt_c = chainermn_tpu.create_zero_optimizer(
        optax.sgd(0.1), comm, wire_dtype=jnp.bfloat16
    )
    params = {"w": jnp.full((n * 8,), 0.5, jnp.float32)}
    state = jax.device_put(opt_c.init(params),
                           comm.named_sharding(*opt_c.state_spec))

    def body(params, state):
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        updates, state = opt_c.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    step = jax.jit(comm.shard_map(
        body, in_specs=(P(), opt_c.state_spec),
        out_specs=(P(), opt_c.state_spec), check_vma=opt_c.check_vma,
    ))
    new_params, _ = step(params, state)
    # sgd(0.1) on grad=1 from 0.5 -> 0.4 (exactly representable in bf16)
    np.testing.assert_allclose(np.asarray(new_params["w"]), 0.4, rtol=1e-2)
    assert new_params["w"].dtype == jnp.float32  # leaf dtype restored


def test_zero_learns(comm):
    zero_opt = chainermn_tpu.create_zero_optimizer(optax.adam(2e-3), comm)
    step, variables, opt_state, images, labels = _setup(comm, zero_opt)
    losses = []
    for _ in range(5):
        variables, opt_state, loss = step(variables, opt_state, images, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_sharded_clip_replicated_grads_exact(comm):
    """ADVICE r3: composed against REPLICATED gradients inside a traced
    step, the sharded clip must not sum n identical replicas into a
    sqrt(n)-inflated norm — with vma tracking on it detects invariant
    leaves and matches plain optax clipping exactly."""
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.optimizers import clip_by_global_norm_sharded

    grads = {"w": jnp.full((4,), 3.0), "b": jnp.full((2,), 1.0)}
    want, _ = optax.clip_by_global_norm(1.0).update(grads, optax.EmptyState())

    def body(g):
        out, _ = clip_by_global_norm_sharded(1.0, comm).update(
            g, optax.EmptyState())
        return out

    got = jax.jit(comm.shard_map(body, in_specs=(P(),), out_specs=P()))(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6)


def test_sharded_clip_replicated_grads_split_comm(comm):
    """Same invariant-leaf correction on a split() sub-communicator: the
    reduce covers the GROUP, so the replica divisor must be the group size
    (dividing by the full mesh axis would under-clip)."""
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.optimizers import clip_by_global_norm_sharded

    sub = comm.split([0] * comm.size)       # one group of everyone
    halves = comm.split([r % 2 for r in range(comm.size)])  # two groups
    for c in (sub, halves):
        grads = {"w": jnp.full((4,), 3.0)}
        want, _ = optax.clip_by_global_norm(1.0).update(
            grads, optax.EmptyState())

        def body(g):
            out, _ = clip_by_global_norm_sharded(1.0, c).update(
                g, optax.EmptyState())
            # group-scoped psums leave replication statically unprovable
            # for P() outputs; a full-axis mean of the (identical) values
            # closes the inference without changing them
            return jax.tree_util.tree_map(
                lambda x: comm.allreduce(x, "mean"), out)

        got = jax.jit(comm.shard_map(
            body, in_specs=(P(),), out_specs=P()))(grads)
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.asarray(want["w"]), rtol=1e-6)
