"""Pallas flash-attention kernels vs the jnp reference (interpret mode).

Forward (K-block online softmax), the logsumexp output, the Pallas
backward kernels (dq / dk+dv), causal offsets, and the ragged-tail
fallback are all checked against ``ops.attention`` on CPU; the same
kernels run un-interpreted on TPU (`attention_auto` dispatch), where
``chip_smoke.py`` checks them (tests/test_chip_smoke.py lowers them).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dist_keras_tpu.ops.attention import attention, attention_with_lse
from dist_keras_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
)


def _qkv(b=2, t=32, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (8, 32),
                                             (32, 32)])
def test_kernel_matches_reference(causal, block_q, block_k):
    q, k, v = _qkv()
    want = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_lse_matches_reference(causal):
    q, k, v = _qkv()
    _, want = attention_with_lse(q, k, v, causal=causal)
    _, got = flash_attention_with_lse(q, k, v, causal=causal, block_q=8,
                                      block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_causal_offsets_match_global_slice():
    """Kernel blocks with q_offset/kv_offset mask like the equivalent
    slice of one big causal attention (the ring-attention contract)."""
    q, k, v = _qkv(t=32)
    # global: rows 16..31 attending to keys 0..15 under causal = fully
    # visible; rows 0..15 vs keys 16..31 = fully masked
    out_lo, lse_lo = flash_attention_with_lse(
        q[:, 16:], k[:, :16], v[:, :16], causal=True, q_offset=16,
        kv_offset=0, block_q=8, block_k=8, interpret=True)
    ref_lo, ref_lse = attention_with_lse(
        q[:, 16:], k[:, :16], v[:, :16], causal=True, q_offset=16,
        kv_offset=0)
    np.testing.assert_allclose(np.asarray(out_lo), np.asarray(ref_lo),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse_lo), np.asarray(ref_lse),
                               atol=2e-5, rtol=1e-4)
    # fully-masked direction: zero rows, lse == -1e30
    out_hi, lse_hi = flash_attention_with_lse(
        q[:, :16], k[:, 16:], v[:, 16:], causal=True, q_offset=0,
        kv_offset=16, block_q=8, block_k=8, interpret=True)
    assert np.abs(np.asarray(out_hi)).max() == 0.0
    assert np.all(np.asarray(lse_hi) <= -1e29)


def test_uneven_block_fallback():
    q, k, v = _qkv(t=24)  # 24 % 16 != 0 -> reference fallback path
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_reference_grads(causal):
    q, k, v = _qkv(t=16)

    def loss_pallas(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=8,
                                       block_k=8, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_lse_cotangent_flows():
    """Ring attention's block merge differentiates through the lse output;
    the kernel VJP must propagate that cotangent (g_lse -> dS)."""
    q, k, v = _qkv(t=16)

    def f_pallas(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, block_q=8, block_k=8,
                                            interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def f_ref(q, k, v):
        out, lse = attention_with_lse(q, k, v)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    g1 = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_dead_rows_inside_visible_tile():
    """Fully-masked causal rows sharing a tile with visible rows must
    produce zero output/grads, not mean-of-V (regression: p = exp(-1e30
    - (-1e30)) = 1 without the safe-shift guard)."""
    q, k, v = _qkv(t=8)
    # kv_offset=4: global key positions 4..11 vs query positions 0..7 —
    # query rows 0..3 see no keys but share the single 8x8 tile
    out, lse = flash_attention_with_lse(q, k, v, causal=True, q_offset=0,
                                        kv_offset=4, block_q=8, block_k=8,
                                        interpret=True)
    ref, ref_lse = attention_with_lse(q, k, v, causal=True, q_offset=0,
                                      kv_offset=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    assert np.abs(np.asarray(out)[:, :4]).max() == 0.0
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=1e-4)

    # gradients: dead rows contribute nothing to dq/dk/dv
    def f(q, k, v):
        o, _ = flash_attention_with_lse(q, k, v, causal=True, q_offset=0,
                                        kv_offset=4, block_q=8, block_k=8,
                                        interpret=True)
        return jnp.sum(o ** 2)

    def f_ref(q, k, v):
        o, _ = attention_with_lse(q, k, v, causal=True, q_offset=0,
                                  kv_offset=4)
        return jnp.sum(o ** 2)

    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)
    assert np.abs(np.asarray(g1[0])[:, :4]).max() == 0.0
