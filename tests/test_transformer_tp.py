"""dp x tp x sp transformer step vs the single-device oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dist_keras_tpu.models.transformer import (
    Transformer,
    init_transformer_params,
    transformer_apply,
    transformer_config,
)
from dist_keras_tpu.parallel.transformer_tp import (
    make_tp_mesh,
    make_tp_train_step,
    tp_transformer_forward,
    train_tp_transformer,
)

CFG = transformer_config(input_dim=6, seq_len=16, d_model=16, n_heads=4,
                         n_layers=2, d_ff=32, n_classes=3)


def _data(n=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, CFG["seq_len"], CFG["input_dim"]))
    x = x.astype(np.float32)
    y = rng.integers(0, CFG["n_classes"], n)
    return x, y


def test_single_device_transformer_forward():
    m = Transformer(cfg=CFG)
    x, _ = _data()
    out = m(x)
    assert out.shape == (8, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_transformer_serialization_round_trip():
    from dist_keras_tpu.utils import deserialize_model, serialize_model

    m = Transformer(cfg=CFG)
    m2 = deserialize_model(serialize_model(m))
    x, _ = _data()
    np.testing.assert_allclose(np.asarray(m(x)), np.asarray(m2(x)),
                               atol=1e-6)


@pytest.mark.parametrize("dp,tp,sp", [(2, 2, 2), (1, 4, 2), (4, 1, 2),
                                      (2, 4, 1)])
def test_tp_forward_matches_oracle(dp, tp, sp):
    mesh = make_tp_mesh(dp=dp, tp=tp, sp=sp)
    params = init_transformer_params(jax.random.PRNGKey(0), CFG)
    x, _ = _data()

    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from dist_keras_tpu.parallel.mesh import SEQ_AXIS, WORKER_AXIS
    from dist_keras_tpu.parallel.transformer_tp import param_specs

    fn = jax.jit(shard_map(
        lambda p, xx: tp_transformer_forward(p, xx, CFG),
        mesh=mesh,
        in_specs=(param_specs(params), P(WORKER_AXIS, SEQ_AXIS, None)),
        out_specs=P(WORKER_AXIS),
    ))
    got = fn(params, jnp.asarray(x))
    want = transformer_apply(params, jnp.asarray(x), CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


def test_tp_train_step_loss_matches_unsharded():
    """One adam step on the 2x2x2 mesh == one adam step single-device."""
    mesh = make_tp_mesh(dp=2, tp=2, sp=2)
    x, y = _data()
    tx = optax.adam(1e-2)

    step_factory, init_fn = make_tp_train_step(mesh, CFG, optimizer=tx)
    params, opt_state = init_fn(seed=0)
    fn = step_factory(params, opt_state)
    p1, o1, loss1 = fn(params, opt_state, jnp.asarray(x), jnp.asarray(y))

    # unsharded oracle
    params0, opt0 = init_fn(seed=0)

    def loss_fn(p):
        logits = transformer_apply(p, jnp.asarray(x), CFG)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(
            logp, jnp.asarray(y)[:, None], axis=-1).mean()

    loss0, grads = jax.value_and_grad(loss_fn)(params0)
    updates, _ = tx.update(grads, opt0, params0)
    want = optax.apply_updates(params0, updates)

    np.testing.assert_allclose(float(loss1), float(loss0), atol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3)


def test_tp_training_reduces_loss():
    mesh = make_tp_mesh(dp=2, tp=2, sp=2)
    x, y = _data(n=16, seed=3)
    _, losses = train_tp_transformer(mesh, CFG, x, y, steps=20,
                                     optimizer=optax.adam(3e-3))
    assert losses[-1] < losses[0]


def test_tp_remat_matches_plain():
    """remat=True in the sharded step: identical loss and updated params
    (pure memory/FLOP trade, collectives included in the recompute)."""
    import optax

    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=2, n_classes=3)
    mesh = make_tp_mesh(dp=2, tp=2, sp=2)
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(4, 8, 6)), np.float32)
    y = rng.integers(0, 3, 4).astype(np.int32)

    results = []
    for remat in (False, True, "mlp"):
        factory, init_fn = make_tp_train_step(
            mesh, cfg, optimizer=optax.sgd(0.1), causal=True, remat=remat)
        params, opt_state = init_fn(0)
        fn = factory(params, opt_state)
        p1, _, loss = fn(params, opt_state, jnp.asarray(x),
                         jnp.asarray(y))
        results.append((float(loss), p1))
    for other in results[1:]:
        np.testing.assert_allclose(results[0][0], other[0], rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6),
            results[0][1], other[1])
