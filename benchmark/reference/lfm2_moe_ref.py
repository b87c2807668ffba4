"""The plain reference of the gated short-convolution, grouped-query,
sparse-expert decoder (LFM2-8B-A1B's block, ``model_type`` ``lfm2_moe``),
written from the block's equations in straightforward ``jax.numpy``.

    y = RMSNorm(h)                                   (operator_norm)
    conv layer:       [B | C | X] = y W_in           (three thirds of 3 d)
                      u = B * X
                      v_t = sum_{j < L} k[:, j] u_{t - (L-1) + j}
                                                     (u = 0 before position 0)
                      h = h + (C * v) W_out
    attention layer:  q = y Wq (heads x hd);  k, v = y Wk, y Wv (kv heads x hd)
                      q, k = RMSNorm over each head's hd (q_layernorm, k_layernorm)
                      q, k = rotary(q), rotary(k)    (by halves, theta)
                      p = softmax(mask(q_h . k_{h // g} / sqrt(hd)))
                      h = h + concat_h(p v_{h // g}) Wo
    y = RMSNorm(h)                                   (ffn_norm)
    dense layer:      h = h + W_down(silu(W_gate y) * W_up y)
    expert layer:     s = sigmoid(y Wg); the top k of s + b chosen;
                      w = the chosen s / (their sum + 1e-6) * routed_scaling_factor
                      h = h + sum_{i chosen} w_i E_i(y)
    logits = RMSNorm(h) E^T                          (embedding_norm; E tied)

No kernels, no cache, no batching; one sequence at a time.  ``q_block``
rows of queries attend at a time (against every key): the same numbers as
in one piece.  The convolution is computed over the whole sequence from
shifted copies of ``u``: there is no state here, which is what holds the
program's per-sequence state to it.

It imports nothing of the program and is given nothing the program has
made.  Every routed expert is held (``moe["experts"]`` holds all of them
in order) and there is no shared expert.

Departures from the published model, shared with the program and listed
in the configuration file: the head is the token embedding (tied); seeded
weights and selection bias.

``Precision`` is ``transformer_ref``'s: the reference itself is float32
with every product at "highest"; the fp8 control rounds both operands of
every matrix product to float8_e4m3.  The router's product is float32 at
"highest" under every precision, as in the program; the convolution's
three taps are elementwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_ref import FLOAT32


def rms_norm(w, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 ** 2, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def rotary(x, positions, theta):
    """x (T, heads, d) at ``positions (T,)``: element i pairs with element
    i + d / 2 (rotated by halves)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                           b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.astype(x.dtype)


def kv_for_query_heads(x, group):
    """x (T, kv_heads, d) -> (T, kv_heads * group, d): K/V head i serves
    query heads group * i .. group * i + group - 1."""
    return jnp.repeat(x, group, axis=1)


def short_conv(conv, y, prec=FLOAT32):
    """The gated short convolution over one sequence: y (T, d) -> (T, d)."""
    t = y.shape[0]
    taps = conv["kernel"].shape[1]
    bcx = prec.dot("td,de->te", y, conv["w_in"])
    b, c, x = jnp.split(bcx, 3, axis=-1)
    u = b * x
    v = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j                     # tap j reads u_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[:t - back]], axis=0)
        v = v + conv["kernel"][:, j].astype(u.dtype) * shifted
    return prec.dot("td,de->te", c * v, conv["w_out"])


def attention(attn, y, conf, prec=FLOAT32, q_block=None):
    """Causal grouped-query attention of one sequence: y (T, d) -> (T, d)."""
    t = y.shape[0]
    eps, theta = conf["norm_eps"], conf["rope_theta"]
    positions = jnp.arange(t)
    q = prec.dot("td,dhk->thk", y, attn["wq"])
    k = prec.dot("td,dhk->thk", y, attn["wk"])
    v = prec.dot("td,dhk->thk", y, attn["wv"])
    q = rotary(rms_norm(attn["q_norm"], q, eps), positions, theta)
    k = rotary(rms_norm(attn["k_norm"], k, eps), positions, theta)
    heads, kv_heads, hd = q.shape[1], k.shape[1], q.shape[2]
    group = heads // kv_heads
    k = kv_for_query_heads(k, group)
    v = kv_for_query_heads(v, group)
    step = t if q_block is None else q_block
    outs = []
    for lo in range(0, t, step):
        hi = min(t, lo + step)
        s = prec.dot("qhk,thk->hqt", q[lo:hi], k).astype(jnp.float32)
        s = s * hd ** -0.5
        mask = positions[lo:hi, None] >= positions[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf),
                           axis=-1).astype(prec.act)
        outs.append(prec.dot("hqt,thk->qhk", p, v))
    return prec.dot("qhk,hkd->qd", jnp.concatenate(outs, 0), attn["wo"])


def swiglu(p, x, prec):
    g = prec.dot("td,df->tf", x, p["w_gate"])
    u = prec.dot("td,df->tf", x, p["w_up"])
    return prec.dot("tf,fd->td", jax.nn.silu(g) * u, p["w_down"])


def routing(moe, y, conf):
    """-> (chosen expert ids (T, k), their weights (T, k) float32)."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", y.astype(jnp.float32),
        moe["router"].astype(jnp.float32), precision="highest"))
    k = conf["num_experts_per_tok"]
    # the k largest of s + b, the first of equals first
    chosen = jnp.argsort(-(s + moe["router_bias"]), axis=-1,
                         stable=True)[:, :k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * conf["routed_scaling_factor"]


def expert_layer(moe, y, conf, prec=FLOAT32):
    """The routed sum over all the experts: y (T, d) -> (T, d)."""
    chosen, w = routing(moe, y, conf)
    out = jnp.zeros(y.shape, prec.act)
    for expert in range(moe["router"].shape[1]):
        weight = jnp.sum(jnp.where(chosen == expert, w, 0.0), axis=-1)
        one = jax.tree.map(lambda leaf: leaf[expert], moe["experts"])
        out = out + weight[:, None].astype(prec.act) * swiglu(one, y, prec)
    return out


def layer(blk, h, conf, prec=FLOAT32, q_block=None):
    """One layer over one sequence: h (T, d) -> (T, d)."""
    eps = conf["norm_eps"]
    y = rms_norm(blk["op_norm"], h, eps)
    if "conv" in blk:
        h = h + short_conv(blk["conv"], y, prec)
    else:
        h = h + attention(blk["attn"], y, conf, prec, q_block)
    y = rms_norm(blk["ffn_norm"], h, eps)
    if "mlp" in blk:
        return h + swiglu(blk["mlp"], y, prec)
    return h + expert_layer(blk["moe"], y, conf, prec)


def embed(outer, tokens, prec=FLOAT32):
    """Rows of the embedding table -> (T, d)."""
    return outer["embed"][tokens].astype(prec.act)


def lm_logits(outer, h, positions, conf, prec=FLOAT32):
    """Final RMSNorm and the tied head at ``positions`` -> (P, vocab)."""
    hf = rms_norm(outer["norm_f"], h[positions], conf["norm_eps"])
    return prec.dot("pd,vd->pv", hf, outer["embed"]).astype(jnp.float32)


def forward(params, tokens, conf, prec=FLOAT32, q_block=None):
    """The whole model over one sequence -> logits (T, vocab)."""
    h = embed(params, tokens, prec)
    for blk in params["blocks"]:
        h = layer(blk, h, conf, prec, q_block)
    return lm_logits(params, h, jnp.arange(tokens.shape[0]), conf, prec)
