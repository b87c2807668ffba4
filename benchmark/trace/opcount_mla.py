"""Operations and bytes the latent-attention, sparse-expert family's
kernels and steps require, from shapes alone (``opcount``'s rules:
required work, one multiply-add two operations)."""

from __future__ import annotations

from benchmark.trace.opcount import causal_pairs

F32 = 4


def flash_fwd_mixed(bh, tq, tk, d_qk, d_v, causal, itemsize):
    """One flash-attention forward whose values are of another width than
    its queries and keys (latent attention's prefill: 192 and 128) ->
    (operations, bytes).  ``d_qk``-wide scores and ``d_v``-wide values
    for each kept pair; q, k, v read once, the output written once and
    the float32 log-sum-exp row."""
    pairs = causal_pairs(tq, tk) if causal else tq * tk
    ops = 2 * bh * pairs * (d_qk + d_v)
    moved = itemsize * bh * (tq * d_qk + tk * d_qk + tk * d_v + tq * d_v) \
        + 4 * bh * tq
    return ops, moved


def decode_step_bytes(cfg, slots):
    """Bytes one decode step of ``slots`` slots MUST read, by what they
    depend on -> ``fixed`` (every layer's weights outside its routed
    experts, the head, the final norm, one embedding row a slot),
    ``per_expert_cell`` (one held expert's three matrices: read when a
    token reached it in that layer) and ``per_live_position`` (a cached
    position's ``latent + rope`` values in every layer; the lanes the
    pool pads them with are not required).  float32 storage."""
    d, h = cfg["d_model"], cfg["n_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    attention = (d * h * (nope + rope) + d * (rank + rope)
                 + rank * h * (nope + dv) + h * dv * d + 2 * d + rank)
    dense = 3 * d * cfg["d_ff"]
    expert = 3 * d * cfg["moe_d_ff"]
    outside = (d * cfg["n_routed_experts"] + cfg["n_routed_experts"]
               + cfg["n_shared_experts"] * expert)
    n_dense = cfg["first_k_dense"]
    n_moe = cfg["n_layers"] - n_dense
    fixed = (cfg["n_layers"] * attention + n_dense * dense
             + n_moe * outside + d * cfg["vocab_size"] + d + slots * d)
    return {"fixed": F32 * fixed, "per_expert_cell": F32 * expert,
            "per_live_position": F32 * cfg["n_layers"] * (rank + rope)}
