"""The plain reference of the block-diffusion, grouped-query, sparse-expert
decoder (SDAR-30B-A3B-Chat's block, ``model_type`` ``sdar_moe``), written
from the block's equations in straightforward ``jax.numpy``.

    y = RMSNorm(h)                                   (input_layernorm)
    q = y Wq (heads x hd);  k, v = y Wk, y Wv        (kv heads x hd)
    q, k = RMSNorm over each head's hd               (q_norm, k_norm)
    q, k = rotary(q), rotary(k)                      (by halves, theta)
    p = softmax(M + q_h . k_{h // g} / sqrt(hd))     M_ts = 0 iff s // B <= t // B
    h = h + concat_h(p v_{h // g}) Wo
    y = RMSNorm(h)                                   (post_attention_layernorm)
    r = softmax(y Wr) over all the experts; the k largest chosen;
    w = the chosen r / their sum                     (norm_topk_prob)
    h = h + sum_{i chosen and held} w_i W_down_i(silu(W_gate_i y) * W_up_i y)
    logits = RMSNorm(h) W_head                       (untied)

The mask is BLOCK-causal (``B = block_length``): a block's positions see
one another and every earlier block.  The logits AT a position are of the
token OF that position.  No kernels, no cache, no batching: one sequence
at a time, ``q_block`` rows of queries at a time against every key (the
same numbers as in one piece).  It imports nothing of the program and is
given nothing the program has made.

*The share.*  The expert layer is told which experts are ``held``
(``moe["experts"]`` holds their weights in that order): it routes over all
of them, normalises over all ``k`` chosen and adds the held ones' terms
alone.  What the absent experts would add is left out, as in the program.

*Generation* (:func:`generate`): greedy diffusion over blocks, the whole
sequence forwarded anew for every pass.  The first ``B * (P // B)`` prompt
positions are history; the open block holds the prompt's last ``P % B``
tokens and the mask id elsewhere.  A denoising pass takes at each masked
position the most likely token other than the mask id and its probability
under the softmax over the whole vocabulary, and fixes the ``B / T``
masked positions of largest probability (``low_confidence_static``; of
equals the first).  A block with no mask left joins the history (the
program spends one more pass there, to keep its K/V; the reference keeps
nothing) and the next block opens as ``B`` masks.

Departures from the published model, shared with the program and listed in
the configuration file: seeded weights; the mask id excluded from the
choice.

``Precision`` is ``transformer_ref``'s: the reference itself is float32
with every product at "highest"; the fp8 control rounds both operands of
every matrix product to float8_e4m3.  The router's product is float32 at
"highest" under every precision, as in the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.lfm2_moe_ref import (
    kv_for_query_heads,
    rms_norm,
    rotary,
    swiglu,
)
from benchmark.reference.transformer_ref import FLOAT32


def block_causal(q_positions, k_positions, block):
    """-> bool ``(Tq, Tk)``: query position ``t`` sees key position ``s``
    iff ``s // block <= t // block``."""
    return (k_positions[None, :] // block) <= (q_positions[:, None] // block)


def attention(attn, y, positions, mask, conf, prec=FLOAT32, q_block=None):
    """Grouped-query attention of one sequence under ``mask (T, T)``:
    y (T, d) at ``positions (T,)`` -> (T, d)."""
    t = y.shape[0]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    q = prec.dot("td,dhk->thk", y, attn["wq"])
    k = prec.dot("td,dhk->thk", y, attn["wk"])
    v = prec.dot("td,dhk->thk", y, attn["wv"])
    q = rotary(rms_norm(attn["q_norm"], q, eps), positions, theta)
    k = rotary(rms_norm(attn["k_norm"], k, eps), positions, theta)
    group, hd = q.shape[1] // k.shape[1], q.shape[2]
    k = kv_for_query_heads(k, group)
    v = kv_for_query_heads(v, group)
    step = t if q_block is None else q_block
    outs = []
    for lo in range(0, t, step):
        hi = min(t, lo + step)
        s = prec.dot("qhk,thk->hqt", q[lo:hi], k).astype(jnp.float32)
        s = jnp.where(mask[None, lo:hi], s * hd ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(prec.act)
        outs.append(prec.dot("hqt,thk->qhk", p, v))
    return prec.dot("qhk,hkd->qd", jnp.concatenate(outs, 0), attn["wo"])


def routing(moe, y, conf):
    """-> (chosen expert ids (T, k), their weights (T, k) float32)."""
    r = jax.nn.softmax(jnp.einsum(
        "td,de->te", y.astype(jnp.float32),
        moe["router"].astype(jnp.float32), precision="highest"), axis=-1)
    k = conf["num_experts_per_tok"]
    # the k largest, the first of equals first
    chosen = jnp.argsort(-r, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(r, chosen, axis=-1)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True)


def expert_layer(moe, y, conf, held, prec=FLOAT32):
    """The held experts' part of the routed sum: y (T, d) -> (T, d)."""
    chosen, w = routing(moe, y, conf)
    out = jnp.zeros(y.shape, prec.act)
    for j, expert in enumerate(held):
        weight = jnp.sum(jnp.where(chosen == expert, w, 0.0), axis=-1)
        one = jax.tree.map(lambda leaf: leaf[j], moe["experts"])
        out = out + weight[:, None].astype(prec.act) * swiglu(one, y, prec)
    return out


def layer(blk, h, positions, mask, conf, held, prec=FLOAT32, q_block=None):
    """One layer over one sequence: h (T, d) -> (T, d)."""
    eps = conf["rms_norm_eps"]
    h = h + attention(blk["attn"], rms_norm(blk["op_norm"], h, eps),
                      positions, mask, conf, prec, q_block)
    return h + expert_layer(blk["moe"], rms_norm(blk["ffn_norm"], h, eps),
                            conf, held, prec)


def embed(outer, tokens, prec=FLOAT32):
    """Rows of the embedding table -> (T, d)."""
    return outer["embed"][tokens].astype(prec.act)


def lm_logits(outer, h, rows, conf, prec=FLOAT32):
    """Final RMSNorm and the head at ``rows`` -> (P, vocab)."""
    hf = rms_norm(outer["norm_f"], h[rows], conf["rms_norm_eps"])
    return prec.dot("pd,dv->pv", hf, outer["head"]).astype(jnp.float32)


def forward(params, tokens, conf, held, prec=FLOAT32, q_block=None):
    """The whole model over one sequence under the block-causal mask ->
    logits (T, vocab)."""
    positions = jnp.arange(tokens.shape[0])
    mask = block_causal(positions, positions, conf["block_length"])
    h = embed(params, tokens, prec)
    for blk in params["blocks"]:
        h = layer(blk, h, positions, mask, conf, held, prec, q_block)
    return lm_logits(params, h, positions, conf, prec)


def confidences(logits, mask_id):
    """One pass's readings from ``logits (B, vocab)`` (numpy) -> (the most
    likely token other than ``mask_id`` at each position, the logarithm
    of its probability under the softmax over the whole vocabulary)."""
    z = np.asarray(logits, np.float32)
    top = z.max(axis=1, keepdims=True)
    lse = top[:, 0] + np.log(np.exp(z - top).sum(axis=1))
    z = z.copy()
    z[:, mask_id] = -np.inf
    return z.argmax(axis=1), z.max(axis=1) - lse


def most_confident(conf, masked, count):
    """The ``count`` masked positions of largest confidence, of equals
    the first -> sorted list."""
    order = sorted((b for b in range(len(conf)) if masked[b]),
                   key=lambda b: (-conf[b], b))
    return sorted(order[:count])


def generate(params, prompt, n, conf, held):
    """Greedy diffusion over blocks -> (the first ``n`` generated tokens,
    for each the pass of its block that fixed it, counted from 0)."""
    size, steps = conf["block_length"], conf["denoising_steps"]
    mask_id = conf["mask_token_id"]
    prompt = [int(t) for t in prompt]
    history = prompt[:size * (len(prompt) // size)]
    block = prompt[len(history):]
    given = len(block)
    block = block + [mask_id] * (size - given)
    tokens, passes = [], []
    while len(tokens) < n:
        fixed_at = [None] * size
        this = 0
        while mask_id in block:
            logits = forward(params, jnp.asarray(history + block), conf,
                             held)[-size:]
            best, c = confidences(logits, mask_id)
            masked = [t == mask_id for t in block]
            for b in most_confident(c, masked, size // steps):
                block[b], fixed_at[b] = int(best[b]), this
            this += 1
        tokens += block[given:]
        passes += fixed_at[given:]
        history, block, given = history + block, [mask_id] * size, 0
    return tokens[:n], passes[:n]
