"""Pipeline parallelism (parallel/pipeline.py) on the 8-virtual-device
CPU mesh: GPipe schedule parity with sequential application, transformer
integration vs the single-device oracle, gradients, and microbatch
independence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dist_keras_tpu.models.transformer import (
    init_transformer_params,
    transformer_apply,
    transformer_apply_with_aux,
    transformer_config,
)
from dist_keras_tpu.parallel.pipeline import (
    PIPE_AXIS,
    gpipe_apply,
    pipeline_1f1b,
    pp_transformer_1f1b_grads,
    pp_transformer_apply,
    stack_blocks,
)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), (PIPE_AXIS,))


def test_gpipe_matches_sequential():
    """4 pipelined MLP stages == applying the 4 stages back to back."""
    p, d, b = 4, 8, 16
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(size=(p, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    mesh = _mesh(p)
    fn = jax.jit(shard_map(
        lambda w, xb: gpipe_apply(stage_fn, w[0], xb, num_microbatches=8),
        mesh=mesh, in_specs=(P(PIPE_AXIS), P()), out_specs=P()))
    got = fn(ws, x)

    want = x
    for i in range(p):
        want = stage_fn(ws[i], want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("num_microbatches", [4, 8, 16])
def test_gpipe_microbatch_invariance(num_microbatches):
    p, d, b = 4, 8, 16
    rng = np.random.default_rng(1)
    ws = jnp.asarray(rng.normal(size=(p, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    mesh = _mesh(p)
    fn = jax.jit(shard_map(
        lambda w, xb: gpipe_apply(stage_fn, w[0], xb,
                                  num_microbatches=num_microbatches),
        mesh=mesh, in_specs=(P(PIPE_AXIS), P()), out_specs=P()))
    got = fn(ws, x)
    want = x
    for i in range(p):
        want = stage_fn(ws[i], want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_pp_transformer_matches_oracle():
    """8 blocks over 4 stages == the single-device transformer, fwd and
    grads."""
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=8, n_classes=3)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)

    stacked = stack_blocks(params["blocks"])
    rest = {k: v for k, v in params.items() if k != "blocks"}
    mesh = _mesh(4)

    def fwd(rest_p, blocks_p, xb):
        return pp_transformer_apply(rest_p, blocks_p, xb, cfg,
                                    num_microbatches=4, causal=True)

    fn = jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(PIPE_AXIS), P()), out_specs=P()))
    got = fn(rest, stacked, x)
    want = transformer_apply(params, x, cfg, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)

    # gradients: pipelined loss grad == oracle grad (blocks + embeddings)
    def loss_pp(rest_p, blocks_p):
        logits = fn(rest_p, blocks_p, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    def loss_ref(rest_p, blocks_list):
        full = dict(rest_p, blocks=blocks_list)
        logits = transformer_apply(full, x, cfg, causal=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    g_pp = jax.grad(loss_pp, argnums=(0, 1))(rest, stacked)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(rest, params["blocks"])
    np.testing.assert_allclose(np.asarray(g_pp[0]["proj"]),
                               np.asarray(g_ref[0]["proj"]),
                               atol=2e-4, rtol=1e-3)
    g_ref_stacked = stack_blocks(g_ref[1])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3),
        g_pp[1], g_ref_stacked)


def test_pp_moe_transformer_matches_microbatched_oracle():
    """Pipelined MoE blocks: logits match the single-device MoE forward
    run per microbatch, and the pipelined aux is the per-microbatch mean
    (router statistics are per-microbatch under PP)."""
    m = 4
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=4, n_classes=3,
                             moe_experts=4, moe_capacity_factor=2.0)
    params = init_transformer_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 8, 6)), jnp.float32)

    stacked = stack_blocks(params["blocks"])
    rest = {k: v for k, v in params.items() if k != "blocks"}
    mesh = _mesh(4)

    fn = jax.jit(shard_map(
        lambda rest_p, blocks_p, xb: pp_transformer_apply(
            rest_p, blocks_p, xb, cfg, num_microbatches=m, causal=True,
            with_aux=True),
        mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P()),
        out_specs=(P(), P())))
    got_logits, got_aux = fn(rest, stacked, x)

    want_logits, want_aux = [], []
    for i in range(m):
        lg, ax = transformer_apply_with_aux(
            params, x[i * 2:(i + 1) * 2], cfg, causal=True)
        want_logits.append(lg)
        want_aux.append(ax)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.concatenate(want_logits),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(float(got_aux), np.mean(want_aux),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# 1F1B
# ---------------------------------------------------------------------------
def _deep_stage(w, h):
    """4 tanh-matmul sublayers per stage — deep enough that stored
    activations dominate memory."""
    def body(hc, wi):
        return jnp.tanh(hc @ wi), None

    h, _ = jax.lax.scan(body, h, w)
    return h


def test_1f1b_matches_autodiff():
    """1F1B manual backward == jax.grad through the sequential model."""
    p, layers, d, b, m = 4, 4, 16, 32, 8
    rng = np.random.default_rng(3)
    ws = jnp.asarray(rng.normal(size=(p, layers, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    mb = b // m
    ts = t.reshape(m, mb, d)

    def stage_fn(w, h):
        return _deep_stage(w, h), jnp.float32(0.0)

    def last_fn(h_mb, mi):
        def f(hm):
            return jnp.mean((hm - ts[mi]) ** 2) / m

        loss, dh = jax.value_and_grad(f)(h_mb)
        return loss, dh, {}

    def first_fn(dh_mb, mi):
        # scatter per-microbatch input cotangents so the test can
        # compare the full d loss / d x against autodiff
        return jnp.zeros((m, mb, d)).at[mi].set(dh_mb)

    mesh = _mesh(p)

    def run(ws_, xb):
        loss, aux, gacc, _, dxs = pipeline_1f1b(
            stage_fn, ws_[0], xb, m, last_fn, first_fn=first_fn)
        return loss, gacc[None], dxs

    loss_pp, g_pp, dx_pp = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(PIPE_AXIS), P()),
        out_specs=(P(), P(PIPE_AXIS), P())))(ws, x)

    def ref_loss(ws_, xb):
        h = xb
        for i in range(p):
            h = _deep_stage(ws_[i], h)
        return jnp.mean((h - t) ** 2)

    want_loss = ref_loss(ws, x)
    g_ref, dx_ref = jax.grad(ref_loss, argnums=(0, 1))(ws, x)
    np.testing.assert_allclose(float(loss_pp), float(want_loss),
                               atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(dx_pp).reshape(b, d), np.asarray(dx_ref),
        atol=1e-5, rtol=1e-4)


def test_1f1b_transformer_matches_oracle():
    """pp_transformer_1f1b_grads == jax.grad of the single-device
    transformer: loss, embedding/head grads, block grads."""
    m = 4
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=8, n_classes=3)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)

    stacked = stack_blocks(params["blocks"])
    rest = {k: v for k, v in params.items() if k != "blocks"}
    mesh = _mesh(4)

    def run(rest_p, blocks_p, xb, yb):
        loss, aux, rg, bg = pp_transformer_1f1b_grads(
            rest_p, blocks_p, xb, yb, cfg, num_microbatches=m,
            causal=True)
        return loss, rg, jax.tree.map(lambda g: g[None], bg)

    loss_pp, rg_pp, bg_pp = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P(), P()),
        out_specs=(P(), P(), P(PIPE_AXIS))))(rest, stacked, x, y)

    def ref_loss(full):
        logits = transformer_apply(full, x, cfg, causal=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    want_loss = ref_loss(params)
    g_ref = jax.grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss_pp), float(want_loss),
                               atol=1e-5, rtol=1e-5)
    for k in ("proj", "pos"):
        np.testing.assert_allclose(np.asarray(rg_pp[k]),
                                   np.asarray(g_ref[k]),
                                   atol=2e-4, rtol=1e-3, err_msg=k)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        {"ln_f": rg_pp["ln_f"], "head": rg_pp["head"]},
        {"ln_f": g_ref["ln_f"], "head": g_ref["head"]})
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            # (stages, L/stage, ...) -> (L, ...)
            np.asarray(a).reshape(np.asarray(b_).shape),
            np.asarray(b_), atol=2e-4, rtol=1e-3),
        bg_pp, stack_blocks(g_ref["blocks"]))


def test_1f1b_moe_matches_microbatched_oracle():
    """1F1B with MoE blocks: grads match jax.grad of the microbatched
    objective nll + aux_weight * mean-per-microbatch aux."""
    m, aw = 4, 1e-2
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=4, n_classes=3,
                             moe_experts=4, moe_capacity_factor=2.0)
    params = init_transformer_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)
    stacked = stack_blocks(params["blocks"])
    rest = {k: v for k, v in params.items() if k != "blocks"}
    mesh = _mesh(4)

    def run(rest_p, blocks_p, xb, yb):
        loss, aux, rg, bg = pp_transformer_1f1b_grads(
            rest_p, blocks_p, xb, yb, cfg, num_microbatches=m,
            causal=True, aux_weight=aw)
        return loss, aux, rg, jax.tree.map(lambda g: g[None], bg)

    loss_pp, aux_pp, rg_pp, bg_pp = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P(), P()),
        out_specs=(P(), P(), P(), P(PIPE_AXIS))))(rest, stacked, x, y)

    def ref_obj(full):
        nll = aux = 0.0
        for i in range(m):
            lg, ax = transformer_apply_with_aux(
                full, x[i * 2:(i + 1) * 2], cfg, causal=True)
            logp = jax.nn.log_softmax(lg)
            nll += -jnp.take_along_axis(
                logp, y[i * 2:(i + 1) * 2][:, None], axis=-1).mean() / m
            aux += ax / m
        return nll + aw * aux, (nll, aux)

    (obj, (nll_ref, aux_ref)), g_ref = jax.value_and_grad(
        ref_obj, has_aux=True)(params)
    np.testing.assert_allclose(float(loss_pp), float(nll_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux_pp), float(aux_ref),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(rg_pp["proj"]),
                               np.asarray(g_ref["proj"]),
                               atol=2e-4, rtol=1e-3)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a).reshape(np.asarray(b_).shape),
            np.asarray(b_), atol=2e-4, rtol=1e-3),
        bg_pp, stack_blocks(g_ref["blocks"]))


def test_1f1b_memory_below_gpipe():
    """The 1F1B schedule's peak temp memory stays below GPipe-by-autodiff
    at equal microbatch count (the whole point of 1F1B)."""
    p, layers, d, b, m = 4, 4, 128, 256, 16
    rng = np.random.default_rng(4)
    ws = jnp.asarray(rng.normal(size=(p, layers, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    mb = b // m
    ts = t.reshape(m, mb, d)
    mesh = _mesh(p)

    def stage_plain(w, h):
        return _deep_stage(w, h)

    def gpipe_loss(ws_, xb):
        y = gpipe_apply(stage_plain, ws_[0], xb, num_microbatches=m)
        return jnp.mean((y - t) ** 2)

    gpipe_grad = jax.jit(shard_map(
        jax.grad(gpipe_loss, argnums=0), mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()), out_specs=P(PIPE_AXIS)))

    def stage_fn(w, h):
        return _deep_stage(w, h), jnp.float32(0.0)

    def last_fn(h_mb, mi):
        def f(hm):
            return jnp.mean((hm - ts[mi]) ** 2) / m

        loss, dh = jax.value_and_grad(f)(h_mb)
        return loss, dh, {}

    def run_1f1b(ws_, xb):
        loss, aux, gacc, _, _ = pipeline_1f1b(
            stage_fn, ws_[0], xb, m, last_fn)
        return loss, gacc[None]

    f1b = jax.jit(shard_map(
        run_1f1b, mesh=mesh, in_specs=(P(PIPE_AXIS), P()),
        out_specs=(P(), P(PIPE_AXIS))))

    try:
        mem_g = gpipe_grad.lower(ws, x).compile().memory_analysis()
        mem_f = f1b.lower(ws, x).compile().memory_analysis()
        tg = getattr(mem_g, "temp_size_in_bytes", None)
        tf = getattr(mem_f, "temp_size_in_bytes", None)
    except Exception:
        tg = tf = None
    if not tg or not tf:
        pytest.skip("memory_analysis unavailable on this backend")
    assert tf < tg, (
        f"1F1B temp {tf} should be below GPipe-autodiff temp {tg}")


# ---------------------------------------------------------------------------
# round 4: the user-facing PP trainer surface + interleaved virtual stages
# ---------------------------------------------------------------------------
def test_pp_train_step_matches_oracle_sgd_step():
    """make_pp_train_step: loss AND the post-optimizer params equal the
    single-device oracle's (sgd makes the update algebra exact)."""
    import optax

    from dist_keras_tpu.parallel.pipeline import (
        make_pp_mesh,
        make_pp_train_step,
    )

    m = 4
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=8, n_classes=3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)

    mesh = make_pp_mesh(stages=4)
    factory, init_fn = make_pp_train_step(
        mesh, cfg, num_microbatches=m, optimizer=optax.sgd(0.1),
        causal=True)
    rest, blocks, opt_r, opt_b = init_fn(0)
    fn = factory(rest, blocks, opt_r, opt_b)
    rest2, blocks2, _, _, loss, aux = fn(rest, blocks, opt_r, opt_b, x, y)

    params = init_transformer_params(jax.random.PRNGKey(0), cfg)

    def ref_loss(full):
        logits = transformer_apply(full, x, cfg, causal=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    want_loss = float(ref_loss(params))
    g = jax.grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), want_loss, atol=1e-5,
                               rtol=1e-5)
    want_rest = {k: jax.tree.map(lambda p_, g_: p_ - 0.1 * g_,
                                 params[k], g[k])
                 for k in ("proj", "pos", "ln_f", "head")}
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        {k: rest2[k] for k in want_rest}, want_rest)
    want_blocks = jax.tree.map(lambda p_, g_: p_ - 0.1 * g_,
                               stack_blocks(params["blocks"]),
                               stack_blocks(g["blocks"]))
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        blocks2, want_blocks)


def test_pp_dp_composition_matches_pure_pp():
    """PP x DP on a (workers=2, stages=4) grid == pure PP (stages=4) on
    the same global batch: same losses, same final params."""
    import optax

    from dist_keras_tpu.parallel.pipeline import (
        make_pp_mesh,
        train_pp_transformer,
    )

    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=4, n_classes=3)
    rng = np.random.default_rng(1)
    x = np.asarray(rng.normal(size=(8, 8, 6)), np.float32)
    y = rng.integers(0, 3, 8).astype(np.int32)

    (rest_a, blocks_a), losses_a = train_pp_transformer(
        make_pp_mesh(stages=4), cfg, x, y, num_microbatches=4, steps=3,
        optimizer=optax.adam(1e-2), causal=True)
    (rest_b, blocks_b), losses_b = train_pp_transformer(
        make_pp_mesh(stages=4, dp=2), cfg, x, y, num_microbatches=4,
        steps=3, optimizer=optax.adam(1e-2), causal=True)
    np.testing.assert_allclose(losses_a, losses_b, atol=1e-5, rtol=1e-5)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3),
        (rest_a, blocks_a), (rest_b, blocks_b))


def test_interleaved_pp_partial_group_matches_oracle():
    """num_microbatches NOT divisible by P (and even < P): the partial
    last group still completes (round-4 review: the original tick budget
    silently dropped its outputs)."""
    from dist_keras_tpu.parallel.pipeline import (
        pp_transformer_interleaved_apply,
        stack_blocks_interleaved,
    )

    p, v = 4, 2
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=p * v, n_classes=3)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    mesh = _mesh(p)
    rest = {k: w for k, w in params.items() if k != "blocks"}
    chunks = stack_blocks_interleaved(params["blocks"], p, v)
    for m, b in [(6, 12), (3, 12), (2, 8)]:  # m % p != 0, incl. m < p
        x = jnp.asarray(rng.normal(size=(b, 8, 6)), jnp.float32)

        def run(rest_p, chunk_p, xb, m=m):
            return pp_transformer_interleaved_apply(
                rest_p, jax.tree.map(lambda a: a[0], chunk_p), xb, cfg,
                num_microbatches=m, virtual=v, causal=True)

        got = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P()),
            out_specs=P()))(rest, chunks, x)
        want = transformer_apply(params, x, cfg, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4,
                                   err_msg=f"m={m}")


@pytest.mark.parametrize("v", [2, 4])
def test_interleaved_pp_matches_oracle(v):
    """Interleaved virtual stages (v chunks per device, ring schedule):
    logits equal the single-device oracle."""
    from dist_keras_tpu.parallel.pipeline import (
        pp_transformer_interleaved_apply,
        stack_blocks_interleaved,
    )

    p, m = 4, 8
    L = p * v  # 1 block per chunk
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=L, n_classes=3)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 8, 6)), jnp.float32)

    chunks = stack_blocks_interleaved(params["blocks"], p, v)
    rest = {k: w for k, w in params.items() if k != "blocks"}
    mesh = _mesh(p)

    def run(rest_p, chunk_p, xb):
        return pp_transformer_interleaved_apply(
            rest_p, jax.tree.map(lambda a: a[0], chunk_p), xb, cfg,
            num_microbatches=m, virtual=v, causal=True)

    got = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P()),
        out_specs=P()))(rest, chunks, x)
    want = transformer_apply(params, x, cfg, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_interleaved_bubble_fraction_improves():
    """The analytic bubble shrinks with virtual stages — and the
    interleaved engine's tick count implements exactly that schedule:
    v*M + P - 1 ticks of 1/v-sized work vs M + P - 1 full-size ticks."""
    from dist_keras_tpu.parallel.pipeline import bubble_fraction

    p, m = 4, 8
    assert bubble_fraction(p, m, 2) < bubble_fraction(p, m, 1)
    assert bubble_fraction(p, m, 4) < bubble_fraction(p, m, 2)
    # normalized wall clock (ticks * work-per-tick): interleaving wins
    plain = (m + p - 1) * 1.0
    inter = (2 * m + p - 1) * 0.5
    assert inter < plain


def test_interleaved_pp_gradients_match_oracle():
    """Autodiff THROUGH the interleaved ring schedule (scan + ring
    ppermute + dynamic chunk indexing all transpose): loss gradients
    equal the single-device oracle's."""
    from dist_keras_tpu.parallel.pipeline import (
        pp_transformer_interleaved_apply,
        stack_blocks_interleaved,
    )

    p, v, m = 4, 2, 4
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=p * v, n_classes=3)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)

    chunks = stack_blocks_interleaved(params["blocks"], p, v)
    rest = {k: w for k, w in params.items() if k != "blocks"}
    mesh = _mesh(p)

    fn = jax.jit(shard_map(
        lambda rest_p, chunk_p, xb: pp_transformer_interleaved_apply(
            rest_p, jax.tree.map(lambda a: a[0], chunk_p), xb, cfg,
            num_microbatches=m, virtual=v, causal=True),
        mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P()), out_specs=P()))

    # differentiate the GLOBAL function (grad composes with the jitted
    # shard_map, like test_pp_transformer_matches_oracle)
    def loss_pp(rest_p, chunk_p):
        logits = fn(rest_p, chunk_p, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    def ref_loss(full):
        logits = transformer_apply(full, x, cfg, causal=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    g_pp = jax.grad(loss_pp, argnums=(0, 1))(rest, chunks)
    g_ref = jax.grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss_pp(rest, chunks)),
                               float(ref_loss(params)),
                               atol=1e-5, rtol=1e-5)
    for k in ("proj", "pos"):
        np.testing.assert_allclose(np.asarray(g_pp[0][k]),
                                   np.asarray(g_ref[k]),
                                   atol=2e-4, rtol=1e-3, err_msg=k)
    # chunk grads -> global block order via the interleaved layout
    want_chunks = stack_blocks_interleaved(g_ref["blocks"], p, v)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        g_pp[1], want_chunks)


# ---------------------------------------------------------------------------
# round 5: interleaved 1F1B (Megatron-complete PP — v virtual chunks per
# device + recompute-vjp backward in one ring schedule)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", [8, 16])
def test_interleaved_1f1b_matches_autodiff(m):
    """Interleaved 1F1B manual backward == jax.grad through the
    sequential model at (P=4, v=2): loss, chunk grads, input grads.
    Two microbatch counts exercise different stash-slot reuse patterns
    (any mod-slot aliasing would corrupt the recompute inputs)."""
    from dist_keras_tpu.parallel.pipeline import pipeline_interleaved_1f1b

    p, v, layers, d, b = 4, 2, 2, 16, 32
    rng = np.random.default_rng(5)
    # global chunk g holds `layers` tanh-matmul sublayers; device s's
    # chunk c is global chunk c*p + s (the interleaved layout)
    ws_g = jnp.asarray(rng.normal(size=(p * v, layers, d, d)) * 0.4,
                       jnp.float32)
    order = np.asarray([[c * p + s for c in range(v)] for s in range(p)])
    ws_dev = ws_g[order.reshape(-1)].reshape(p, v, layers, d, d)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    mb = b // m
    ts = t.reshape(m, mb, d)

    def stage_fn(w, h):
        return _deep_stage(w, h), jnp.float32(0.0)

    def last_fn(h_mb, mi):
        def f(hm):
            return jnp.mean((hm - ts[mi]) ** 2) / m

        loss, dh = jax.value_and_grad(f)(h_mb)
        return loss, dh, {}

    def first_fn(dh_mb, mi):
        return jnp.zeros((m, mb, d)).at[mi].set(dh_mb)

    mesh = _mesh(p)

    def run(ws_, xb):
        loss, aux, gacc, _, dxs = pipeline_interleaved_1f1b(
            stage_fn, ws_[0], xb, m, v, last_fn, first_fn=first_fn)
        return loss, gacc[None], dxs

    loss_pp, g_pp, dx_pp = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(PIPE_AXIS), P()),
        out_specs=(P(), P(PIPE_AXIS), P())))(ws_dev, x)

    def ref_loss(ws_, xb):
        h = xb
        for i in range(p * v):
            h = _deep_stage(ws_[i], h)
        return jnp.mean((h - t) ** 2)

    want_loss = ref_loss(ws_g, x)
    g_ref, dx_ref = jax.grad(ref_loss, argnums=(0, 1))(ws_g, x)
    np.testing.assert_allclose(float(loss_pp), float(want_loss),
                               atol=1e-6, rtol=1e-5)
    # device-layout grads -> global chunk order
    g_pp_global = np.asarray(g_pp).reshape(p * v, layers, d, d)[
        np.argsort(order.reshape(-1))]
    np.testing.assert_allclose(g_pp_global, np.asarray(g_ref),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(dx_pp).reshape(b, d), np.asarray(dx_ref),
        atol=1e-5, rtol=1e-4)


def test_interleaved_1f1b_transformer_matches_oracle():
    """pp_transformer_1f1b_grads(virtual=2) == jax.grad of the
    single-device transformer (P=4, v=2, M=8 — the VERDICT r4 target)."""
    from dist_keras_tpu.parallel.pipeline import stack_blocks_interleaved

    p, v, m = 4, 2, 8
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=p * v, n_classes=3)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m * 2, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, m * 2), jnp.int32)

    chunks = stack_blocks_interleaved(params["blocks"], p, v)
    rest = {k: w for k, w in params.items() if k != "blocks"}
    mesh = _mesh(p)

    def run(rest_p, chunk_p, xb, yb):
        loss, aux, rg, bg = pp_transformer_1f1b_grads(
            rest_p, jax.tree.map(lambda a: a[0], chunk_p), xb, yb, cfg,
            num_microbatches=m, causal=True, virtual=v)
        return loss, rg, jax.tree.map(lambda g: g[None], bg)

    loss_pp, rg_pp, bg_pp = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(), P(PIPE_AXIS), P(), P()),
        out_specs=(P(), P(), P(PIPE_AXIS))))(rest, chunks, x, y)

    def ref_loss(full):
        logits = transformer_apply(full, x, cfg, causal=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    want_loss = ref_loss(params)
    g_ref = jax.grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss_pp), float(want_loss),
                               atol=1e-5, rtol=1e-5)
    for k in ("proj", "pos"):
        np.testing.assert_allclose(np.asarray(rg_pp[k]),
                                   np.asarray(g_ref[k]),
                                   atol=2e-4, rtol=1e-3, err_msg=k)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        {"ln_f": rg_pp["ln_f"], "head": rg_pp["head"]},
        {"ln_f": g_ref["ln_f"], "head": g_ref["head"]})
    want_chunks = stack_blocks_interleaved(g_ref["blocks"], p, v)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        bg_pp, want_chunks)


def test_interleaved_1f1b_stash_bound():
    """The static stash allocation is v * min(m, 3P) microbatch inputs —
    O(vP), independent of M — and rejects m % p != 0 cleanly."""
    from dist_keras_tpu.parallel.pipeline import (
        interleaved_1f1b_stash_entries,
        pipeline_interleaved_1f1b,
    )

    assert interleaved_1f1b_stash_entries(4, 2, 64) == 2 * 12
    assert interleaved_1f1b_stash_entries(4, 2, 8) == 2 * 8  # m < 3p
    # bound is independent of m once m >= 3p
    assert (interleaved_1f1b_stash_entries(4, 2, 1024)
            == interleaved_1f1b_stash_entries(4, 2, 64))

    mesh = _mesh(4)

    def run(xb):
        return pipeline_interleaved_1f1b(
            lambda w, h: (h, jnp.float32(0.0)), jnp.zeros((2, 1)), xb,
            6, 2, lambda hm, mi: (jnp.float32(0.0), jnp.zeros_like(hm),
                                  {}))[0]

    with pytest.raises(ValueError, match="num_microbatches % stages"):
        jax.jit(shard_map(run, mesh=mesh, in_specs=(P(),),
                          out_specs=P()))(jnp.zeros((12, 4)))


def test_pp_train_step_interleaved_matches_oracle_sgd_step():
    """make_pp_train_step(virtual=2): loss and post-sgd params equal the
    single-device oracle's — the interleaved engine behind the same
    user-facing trainer surface as flat 1F1B."""
    import optax

    from dist_keras_tpu.parallel.pipeline import (
        make_pp_mesh,
        make_pp_train_step,
        stack_blocks_interleaved,
    )

    p, v, m = 4, 2, 8
    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=p * v, n_classes=3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, 8, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, m), jnp.int32)

    mesh = make_pp_mesh(stages=p)
    factory, init_fn = make_pp_train_step(
        mesh, cfg, num_microbatches=m, optimizer=optax.sgd(0.1),
        causal=True, virtual=v)
    rest, blocks, opt_r, opt_b = init_fn(0)
    assert jax.tree.leaves(blocks)[0].shape[:2] == (p, v)
    fn = factory(rest, blocks, opt_r, opt_b)
    rest2, blocks2, _, _, loss, aux = fn(rest, blocks, opt_r, opt_b, x, y)

    params = init_transformer_params(jax.random.PRNGKey(0), cfg)

    def ref_loss(full):
        logits = transformer_apply(full, x, cfg, causal=True)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    np.testing.assert_allclose(float(loss), float(ref_loss(params)),
                               atol=1e-5, rtol=1e-5)
    g = jax.grad(ref_loss)(params)
    want_rest = {k: jax.tree.map(lambda p_, g_: p_ - 0.1 * g_,
                                 params[k], g[k])
                 for k in ("proj", "pos", "ln_f", "head")}
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        {k: rest2[k] for k in want_rest}, want_rest)
    want_blocks = jax.tree.map(
        lambda p_, g_: p_ - 0.1 * g_,
        stack_blocks_interleaved(params["blocks"], p, v),
        stack_blocks_interleaved(g["blocks"], p, v))
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3),
        blocks2, want_blocks)


def test_interleaved_1f1b_dp_composition_matches_pure():
    """Interleaved 1F1B on a (workers=2, stages=4) grid == pure
    interleaved PP: the skip-branch lax.conds must type-match under the
    composed mesh's wider varying-axes sets (caught live in round 5)."""
    import optax

    from dist_keras_tpu.parallel.pipeline import (
        make_pp_mesh,
        train_pp_transformer,
    )

    cfg = transformer_config(input_dim=6, seq_len=8, d_model=16,
                             n_heads=2, n_layers=8, n_classes=3)
    rng = np.random.default_rng(1)
    x = np.asarray(rng.normal(size=(16, 8, 6)), np.float32)
    y = rng.integers(0, 3, 16).astype(np.int32)

    (rest_a, blocks_a), losses_a = train_pp_transformer(
        make_pp_mesh(stages=4), cfg, x, y, num_microbatches=8, steps=3,
        optimizer=optax.adam(1e-2), causal=True, virtual=2)
    (rest_b, blocks_b), losses_b = train_pp_transformer(
        make_pp_mesh(stages=4, dp=2), cfg, x, y, num_microbatches=8,
        steps=3, optimizer=optax.adam(1e-2), causal=True, virtual=2)
    np.testing.assert_allclose(losses_a, losses_b, atol=1e-5, rtol=1e-5)
    jax.tree.map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3),
        (rest_a, blocks_a), (rest_b, blocks_b))
