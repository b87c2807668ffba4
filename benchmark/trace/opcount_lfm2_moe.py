"""Operations and bytes the gated short-convolution, grouped-query,
sparse-expert family's kernels and steps require, from shapes alone
(``opcount``'s rules: required work, one multiply-add two operations)."""

from __future__ import annotations

from benchmark.trace.opcount import causal_pairs

F32 = 4


def flash_fwd_grouped(bh, bh_kv, tq, tk, d, causal, itemsize):
    """One flash-attention forward in which ``bh`` (batch x heads) query
    problems share ``bh_kv`` key/value problems (grouped-query attention:
    32 and 8 heads of 64) -> (operations, bytes).  Two ``d``-wide
    products for each kept pair of every QUERY head; q read and the
    output written once a query head, k and v read once a K/V head (a
    group's query heads follow one another, so a K/V block fetched for
    one is the next one's too), and the float32 log-sum-exp row."""
    pairs = causal_pairs(tq, tk) if causal else tq * tk
    ops = 2 * 2 * bh * pairs * d
    moved = itemsize * d * (2 * bh * tq + 2 * bh_kv * tk) + 4 * bh * tq
    return ops, moved


def decode_step_bytes(cfg, slots):
    """Bytes one decode step of ``slots`` slots MUST read, by what they
    depend on -> ``fixed`` (every layer's weights outside its routed
    experts, the tied embedding once as the head, the final norm, one
    embedding row and one convolution-state row of every convolution
    layer a slot), ``per_expert_cell`` (one expert's three matrices: read
    when a token reached it in that layer) and ``per_live_position`` (a
    cached position's keys and values, ``2 x kv_heads x head_dim``
    values, in every ATTENTION layer).  float32 storage."""
    d, h, hk = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = d // h
    n_conv = cfg["layer_types"].count("conv")
    n_attn = cfg["n_layers"] - n_conv
    taps = cfg["conv_l_cache"]
    conv = d * 3 * d + d * d + d * taps
    attention = 2 * d * h * hd + 2 * d * hk * hd + 2 * hd
    dense = 3 * d * cfg["d_ff"]
    expert = 3 * d * cfg["moe_d_ff"]
    n_dense = cfg["num_dense_layers"]
    router = d * cfg["n_routed_experts"] + cfg["n_routed_experts"]
    fixed = (n_conv * conv + n_attn * attention + 2 * d * cfg["n_layers"]
             + n_dense * dense + (cfg["n_layers"] - n_dense) * router
             + d * cfg["vocab_size"] + d
             + slots * (d + n_conv * (taps - 1) * d))
    return {"fixed": F32 * fixed, "per_expert_cell": F32 * expert,
            "per_live_position": F32 * n_attn * 2 * hk * hd}
