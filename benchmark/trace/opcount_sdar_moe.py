"""Operations and bytes the block-diffusion, grouped-query, sparse-expert
family's kernels and passes require, from shapes alone (``opcount``'s
rules: required work, one multiply-add two operations)."""

from __future__ import annotations

F32 = 4


def block_causal_pairs(tq, tk, block):
    """Query/key pairs the BLOCK-causal mask keeps when the queries are
    the last ``tq`` of ``tk`` positions (all three whole blocks): a query
    sees its own block of ``block`` positions whole and every earlier
    one."""
    first = tk - tq
    blocks = tq // block
    # the queries of block i (counted from the first query's) see the
    # ``first`` positions before them and i + 1 blocks
    return block * (blocks * first + block * blocks * (blocks + 1) // 2)


def flash_fwd_block_causal(bh, bh_kv, tq, tk, d, block, itemsize):
    """One flash-attention forward under the block-causal mask in which
    ``bh`` (batch x heads) query problems share ``bh_kv`` key/value
    problems -> (operations, bytes): ``opcount_lfm2_moe.flash_fwd_grouped``
    with the pairs :func:`block_causal_pairs` keeps."""
    ops = 2 * 2 * bh * block_causal_pairs(tq, tk, block) * d
    moved = itemsize * d * (2 * bh * tq + 2 * bh_kv * tk) + 4 * bh * tq
    return ops, moved


def decode_step_bytes(cfg, slots):
    """Bytes one PASS of ``slots`` slots MUST read, by what they depend
    on -> ``fixed`` (every layer's weights outside its routed experts,
    the untied head and the final norm: everything outside the embedding
    table, of which a slot reads a block's rows), ``per_expert_cell`` (one
    held expert's three matrices: read when a row reached it in that
    layer) and ``per_live_position`` (a position's keys and values, ``2 x
    kv_heads x head_dim`` values, in every layer: the committed history
    and the open block's provisional rows alike, which the pass wrote
    itself and reads back).  float32 storage."""
    d, h, hk, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    attention = 2 * d * h * hd + 2 * d * hk * hd + 2 * hd + 2 * d
    router = d * cfg["n_routed_experts"]
    fixed = (cfg["n_layers"] * (attention + router)
             + d * cfg["vocab_size"] + d
             + slots * cfg["block_length"] * d)
    return {"fixed": F32 * fixed,
            "per_expert_cell": F32 * 3 * d * cfg["moe_d_ff"],
            "per_live_position": F32 * cfg["n_layers"] * 2 * hk * hd}


def kernel_unit_bytes(cfg):
    """Bytes ONE call of the pass's read kernel must move for one unit of
    the histogram that says how many units a pass had -> {the kernel's
    name in a trace: (histogram, bytes a unit)}: the K/V read of one
    layer, keys and values of a position a slot reads."""
    return {"latent_decode": (
        "decode.kv.live_positions",
        F32 * 2 * cfg["n_kv_heads"] * cfg["head_dim"])}
