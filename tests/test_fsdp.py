"""FSDP / ZeRO-3 sharded training (parallel/fsdp.py) on the 8-virtual-
device CPU mesh: sharded placement, loss/grad parity with the unsharded
oracle, and memory = sharded footprint."""

import numpy as np

import jax
import jax.numpy as jnp
import optax

from dist_keras_tpu.models.transformer import (
    Transformer,
    transformer_apply,
)
from dist_keras_tpu.ops.attention import attention
from dist_keras_tpu.parallel.fsdp import (
    fsdp_specs,
    make_fsdp_train_step,
    train_fsdp,
)
from dist_keras_tpu.parallel.mesh import WORKER_AXIS, worker_mesh


def _setup(seed=0):
    model = Transformer(input_dim=8, seq_len=16, d_model=64, n_heads=4,
                        n_layers=2, n_classes=2, seed=seed)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 16, 8)).astype(np.float32)
    y = (x[:, :, 0].mean(1) > 0).astype(np.int32)

    def apply_fn(p, xb):
        # jnp oracle attention: identical math sharded or not
        return transformer_apply(p, xb, model.cfg, attn_fn=attention)

    def loss_fn(logits, yb):
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(
            logp, yb[:, None].astype(jnp.int32), axis=-1).mean()

    return model, apply_fn, loss_fn, x, y


def test_fsdp_specs_shard_big_leaves_only():
    model, *_ = _setup()
    specs = fsdp_specs(model.params, axis_size=8)
    flat = jax.tree.leaves_with_path(
        specs, is_leaf=lambda s: hasattr(s, "index"))
    # big mats sharded, biases/LN replicated
    by_path = {jax.tree_util.keystr(p): s for p, s in flat}
    assert any(WORKER_AXIS in str(s) for s in by_path.values())
    blocks = model.params["blocks"][0]
    sp_w1 = fsdp_specs(blocks, 8)["w1"]
    assert WORKER_AXIS in str(sp_w1)
    sp_b2 = fsdp_specs(blocks, 8)["b2"]
    assert WORKER_AXIS not in str(sp_b2)


def test_fsdp_state_is_sharded_and_loss_matches_oracle():
    model, apply_fn, loss_fn, x, y = _setup()
    mesh = worker_mesh(8)
    init_fn, factory = make_fsdp_train_step(mesh, loss_fn, apply_fn)
    params, opt_state = init_fn(model.params)

    # every big leaf physically holds 1/8 per device
    w1 = params["blocks"][0]["w1"]
    shard_shape = w1.addressable_shards[0].data.shape
    assert np.prod(shard_shape) == w1.size // 8

    # oracle FIRST: step_fn donates its params/opt-state buffers, and
    # device_put may alias small replicated leaves with model.params
    tx = optax.adam(1e-3)
    params0 = jax.tree.map(np.asarray, model.params)

    def loss_of(p):
        return loss_fn(apply_fn(p, jnp.asarray(x)), jnp.asarray(y))

    loss_ref, grads = jax.value_and_grad(loss_of)(params0)
    upd, _ = tx.update(grads, tx.init(params0), params0)
    ref_params = optax.apply_updates(params0, upd)

    fn = factory(params, opt_state)
    from jax.sharding import NamedSharding, PartitionSpec as P

    xd = jax.device_put(jnp.asarray(x),
                        NamedSharding(mesh, P(WORKER_AXIS)))
    yd = jax.device_put(jnp.asarray(y),
                        NamedSharding(mesh, P(WORKER_AXIS)))
    p1, o1, loss_sharded = fn(params, opt_state, xd, yd)
    np.testing.assert_allclose(float(loss_sharded), float(loss_ref),
                               rtol=1e-5)
    got = np.asarray(p1["blocks"][0]["w1"])
    want = np.asarray(ref_params["blocks"][0]["w1"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    # updated params keep their sharded placement across steps
    assert p1["blocks"][0]["w1"].sharding.spec == w1.sharding.spec


def test_fsdp_trains():
    model, apply_fn, loss_fn, x, y = _setup()
    mesh = worker_mesh(8)
    _, losses = train_fsdp(mesh, apply_fn, loss_fn, model.params, x, y,
                           steps=30, optimizer=optax.adam(3e-3))
    assert losses[-1] < losses[0] * 0.7
