"""Operations and bytes a call requires, from its shapes alone.

Required work, not executed work: recomputation (remat, a backward that
runs the forward again) is never counted, and a causal attention is
charged the lower triangle with its diagonal only.  One multiply-add
counts as two operations.
"""

from __future__ import annotations


def causal_pairs(tq, tk):
    """Query/key pairs a causal mask keeps when the last query sees every
    key (queries are the last ``tq`` of ``tk`` positions)."""
    return tq * (tk - tq) + tq * (tq + 1) // 2


def flash_fwd(bh, tq, tk, d, causal, itemsize):
    """One flash-attention forward over ``bh`` (batch x heads) problems ->
    (operations, bytes).  Two matrix products per kept pair (QK^T and PV);
    bytes are q, k, v read once, the output written once and the float32
    log-sum-exp row the kernel keeps for the backward."""
    pairs = causal_pairs(tq, tk) if causal else tq * tk
    ops = 2 * 2 * bh * pairs * d
    moved = itemsize * bh * d * (2 * tq + 2 * tk) + 4 * bh * tq
    return ops, moved


def block_forward(tokens_per_seq, d_model, d_ff, causal=True):
    """Operations one pre-LN block's forward requires per token: the four
    attention projections, the two MLP products and the attention itself
    over a sequence of ``tokens_per_seq``.  Layer norms, biases, gelu and
    residual adds are left out (under 0.1% at these widths)."""
    proj = 2 * 4 * d_model * d_model
    mlp = 2 * 2 * d_model * d_ff
    t = tokens_per_seq
    pairs = causal_pairs(t, t) if causal else t * t
    attn = 2 * 2 * pairs * d_model // t
    return proj + mlp + attn


def train_step_per_sample(seq_len, n_layers, d_model, d_ff, input_dim,
                          causal=True):
    """Operations one training sample (a sequence) requires: forward plus
    backward (twice the forward) of the blocks and the input projection.
    The 2-class pooled head is under 1e-6 of the total and left out."""
    per_token = (n_layers * block_forward(seq_len, d_model, d_ff, causal)
                 + 2 * input_dim * d_model)
    return 3 * seq_len * per_token


def roofline_seconds(ops, moved, peak_flops, peak_bytes):
    """Least time the chip could take -> (seconds, which bound)."""
    t_ops, t_mem = ops / peak_flops, moved / peak_bytes
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
