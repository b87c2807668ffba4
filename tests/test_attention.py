"""Ring attention == single-device attention, on a virtual seq mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dist_keras_tpu.ops.attention import attention, ring_attention
from dist_keras_tpu.parallel.mesh import SEQ_AXIS


def _qkv(b=2, t=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


def _ring(q, k, v, n, causal):
    mesh = Mesh(np.array(jax.devices()[:n]), (SEQ_AXIS,))
    fn = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=causal),
        mesh=mesh,
        in_specs=(P(None, SEQ_AXIS), P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
        out_specs=P(None, SEQ_AXIS),
    ))
    return fn(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n", [2, 4])
def test_ring_attention_matches_reference(causal, n):
    q, k, v = _qkv()
    want = attention(q, k, v, causal=causal)
    got = _ring(q, k, v, n, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_attention_shapes_and_mask():
    q, k, v = _qkv(b=1, t=8, h=2, d=4)
    out = attention(q, k, v, causal=True)
    assert out.shape == (1, 8, 2, 4)
    # first position can only attend to itself: output == v[0]
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               atol=1e-5)


def test_ring_attention_grads_flow():
    q, k, v = _qkv(t=16)
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), (SEQ_AXIS,))
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True),
        mesh=mesh,
        in_specs=(P(None, SEQ_AXIS),) * 3,
        out_specs=P(None, SEQ_AXIS),
    )

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g = jax.jit(jax.grad(loss))(q, k, v)
    ref = jax.grad(lambda q, k, v: jnp.sum(
        attention(q, k, v, causal=True) ** 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)
