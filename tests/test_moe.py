"""MoE / expert parallelism (parallel/moe.py) on the 8-virtual-device
CPU mesh: EP dispatch parity with the dense oracle, capacity-drop
semantics, gradients through the all_to_alls, and load-balance loss."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dist_keras_tpu.parallel.moe import (
    EXPERT_AXIS,
    init_moe_params,
    moe_param_specs,
    switch_moe_dense,
    switch_moe_ep,
)


D, FF, E = 16, 32, 8


def _params(seed=0):
    return init_moe_params(jax.random.PRNGKey(seed), D, FF, E)


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), (EXPERT_AXIS,))


def _tokens(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D),
                             jnp.float32)


def test_ep_matches_dense_oracle():
    """With ample capacity, the all_to_all dispatch computes exactly the
    dense mixture, block by block."""
    params = _params()
    mesh = _mesh()
    x = _tokens(8 * 32)  # 32 tokens per device

    specs = moe_param_specs()

    def body(p, xb):
        out, aux = switch_moe_ep(p, xb, capacity_factor=8.0)
        return out, jax.lax.pmean(aux, EXPERT_AXIS)

    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(specs, P(EXPERT_AXIS)),
        out_specs=(P(EXPERT_AXIS), P())))
    out_ep, _ = fn(params, x)

    # oracle: dense per 32-token block (same local capacity math)
    blocks = [switch_moe_dense(params, x[i * 32:(i + 1) * 32],
                               capacity_factor=8.0)[0]
              for i in range(8)]
    want = jnp.concatenate(blocks)
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_capacity_drops_tokens():
    """capacity_factor small enough forces drops: dropped tokens produce
    exactly zero output (the residual carries them)."""
    params = _params()
    x = _tokens(64, seed=3)
    out, _ = switch_moe_dense(params, x, capacity_factor=0.25)
    # capacity = ceil(64*0.25/8) = 2 slots/expert = at most 16 processed
    nonzero_rows = np.count_nonzero(
        np.abs(np.asarray(out)).sum(-1) > 1e-9)
    assert nonzero_rows <= 16
    ample, _ = switch_moe_dense(params, x, capacity_factor=8.0)
    assert np.count_nonzero(
        np.abs(np.asarray(ample)).sum(-1) > 1e-9) == 64


def test_ep_gradients_match_dense():
    params = _params()
    mesh = _mesh()
    x = _tokens(8 * 16, seed=1)

    specs = moe_param_specs()
    ep_loss = jax.jit(lambda p, xb: shard_map(
        lambda p_, x_: jax.tree.map(
            lambda v: jax.lax.pmean(v, EXPERT_AXIS) if v.ndim == 0 else v,
            (jnp.sum(switch_moe_ep(p_, x_, capacity_factor=8.0)[0] ** 2),)
        )[0],
        mesh=mesh, in_specs=(specs, P(EXPERT_AXIS)),
        out_specs=P())(p, xb))

    def dense_loss(p, xb):
        total = 0.0
        for i in range(8):
            blk = switch_moe_dense(p, xb[i * 16:(i + 1) * 16],
                                   capacity_factor=8.0)[0]
            total = total + jnp.sum(blk ** 2)
        return total / 8.0  # pmean over the axis averages block losses

    g_ep = jax.grad(ep_loss)(params, x)
    g_dn = jax.grad(dense_loss)(params, x)
    for k in g_ep:
        np.testing.assert_allclose(np.asarray(g_ep[k]),
                                   np.asarray(g_dn[k]),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=k)


def test_aux_loss_prefers_balance():
    """A uniform router gives aux == 1 (minimum); a collapsed router
    (all tokens to one expert) gives aux ~ E."""
    params = _params()
    x = _tokens(256, seed=2)
    params_uniform = dict(params, router=jnp.zeros((D, E)))
    _, aux_u = switch_moe_dense(params_uniform, x)
    assert abs(float(aux_u) - 1.0) < 0.2
    # collapse: positive features x positive col-0 router -> every token
    # routes to expert 0 (logits of other columns are strongly negative)
    x_pos = jnp.abs(x) + 0.5
    params_collapsed = dict(params, router=jnp.full((D, E), -10.0)
                            .at[:, 0].set(10.0))
    _, aux_c = switch_moe_dense(params_collapsed, x_pos)
    assert float(aux_c) > 4.0


def test_moe_transformer_trains():
    """transformer_config(moe_experts=4): the full MoE transformer trains
    end-to-end with the Switch objective; the plain apply path refuses
    MoE configs (the aux loss would be silently dropped)."""
    import numpy as np

    from dist_keras_tpu.models.transformer import (
        transformer_apply,
        transformer_config,
    )
    from dist_keras_tpu.ops.attention import attention
    from dist_keras_tpu.parallel.moe import make_moe_train_step

    cfg = transformer_config(input_dim=8, seq_len=16, d_model=32,
                             n_heads=2, n_layers=2, n_classes=2,
                             moe_experts=4, moe_capacity_factor=2.0)
    init_fn, step = make_moe_train_step(cfg, aux_weight=1e-2,
                                        attn_fn=attention)
    params, opt_state = init_fn(0)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 16, 8)), jnp.float32)
    y = jnp.asarray((np.asarray(x)[:, :, 0].mean(1) > 0).astype(np.int32))

    metrics0 = None
    for _ in range(40):
        params, opt_state, metrics = step(params, opt_state, x, y)
        if metrics0 is None:
            metrics0 = {k: float(v) for k, v in metrics.items()}
    assert float(metrics["nll"]) < metrics0["nll"] * 0.5
    assert np.isfinite(float(metrics["aux"]))

    with pytest.raises(ValueError, match="aux"):
        transformer_apply(params, x, cfg)


def test_moe_ep_transformer_step_trains_and_stays_sharded():
    """Full MoE transformer training with REAL expert parallelism: expert
    stacks sharded over the 8-device mesh, tokens batch-sharded, training
    converges, and expert leaves stay physically 1/8-per-device."""
    from dist_keras_tpu.models.transformer import transformer_config
    from dist_keras_tpu.ops.attention import attention
    from dist_keras_tpu.parallel.moe import make_moe_ep_train_step

    # input_dim != moe_experts: optimizer-spec matching is by shape, and
    # proj (input_dim, d) colliding with expert bias (E, d) is the
    # documented ambiguity hard-error
    cfg = transformer_config(input_dim=6, seq_len=12, d_model=32,
                             n_heads=2, n_layers=2, n_classes=2,
                             moe_experts=8, moe_capacity_factor=4.0)
    mesh = _mesh(8)
    factory, init_fn = make_moe_ep_train_step(
        mesh, cfg, aux_weight=1e-2, attn_fn=attention)
    params, opt_state = init_fn(0)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(32, 12, 6)), jnp.float32)
    y = jnp.asarray((np.asarray(x)[:, :, 0].mean(1) > 0).astype(np.int32))

    fn = factory(params, opt_state)
    first = None
    for _ in range(30):
        params, opt_state, m = fn(params, opt_state, x, y)
        if first is None:
            first = float(m["nll"])
    assert float(m["nll"]) < first * 0.5, (first, float(m["nll"]))

    w1 = params["blocks"][0]["moe"]["w1"]          # (8, d, ff)
    assert np.prod(w1.addressable_shards[0].data.shape) == w1.size // 8
    router = params["blocks"][0]["moe"]["router"]  # replicated
    assert np.prod(router.addressable_shards[0].data.shape) == router.size
