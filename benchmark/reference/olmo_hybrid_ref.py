"""The plain reference of the gated-delta-rule, full-attention decoder
(Olmo-Hybrid-7B's block, ``model_type`` ``olmo_hybrid``), written from the
block's equations in straightforward ``jax.numpy``.  With ``RMS(w, x) = x *
rsqrt(mean(x^2) + eps) * w``, for layer ``l`` with input ``h``::

    h = h + RMS(mixer_norm, Mixer(h));  h = h + RMS(ffn_norm, SwiGLU(h))
    linear layer:     q~, k~, v~ = x W_q, x W_k, x W_v   (H dk, H dk, H dv)
                      q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                          (depthwise, causal, 4 taps, zeros before position 0)
                      by head: q = q / sqrt(sum q^2 + 1e-6) * dk^-0.5
                               k = k / sqrt(sum k^2 + 1e-6)
                      beta_t = 2 sigmoid(x_t W_b)
                      g_t = -exp(A_log) softplus(x_t W_a + dt_bias)
                      S_0 = 0;  S' = exp(g_t) S_(t-1)
                      u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T
                      o_t = S_t^T q_t
                      y_t = concat_h(RMS(o_norm, o_t) * silu(x_t W_g)) W_o
    attention layer:  q = RMS(q_norm, x W_q), k = RMS(k_norm, x W_k)
                          (over all of the projection, then heads of hd)
                      v = x W_v;  p = softmax(mask(q_h . k_h / sqrt(hd)))
                      y = concat_h(p v_h) W_o           (no rotation, no bias)
    logits = RMS(final_norm, h) W_head                  (untied)

No kernels, no cache, no batching, no chunks: **the recurrence runs
position by position** (a ``lax.scan`` over the sequence whose body is the
four lines above as written), which is what holds the program's chunked
scan, its per-sequence state and its in-place decode update to it.
``q_block`` rows of queries attend at a time (against every key): the same
numbers as in one piece.

It imports nothing of the program and is given nothing the program has
made.  The three projections in front of the convolutions arrive as the
columns of one matrix (``w_qkv``: ``H dk | H dk | H dv``) and the decay's
and beta's as the columns of another (``w_ab``: ``H | H``), cut apart here.

``Precision`` is ``transformer_ref``'s: the reference itself is float32
with every product at "highest"; the fp8 control rounds both operands of
every matrix product to float8_e4m3, the recurrence's three products (``S'^T
k``, ``k u^T``, ``S^T q``) among them.  The matrices ``S`` are carried in
float32 under every precision, as the configuration states, and so are the
decay's and beta's two projections (60 columns, at "highest": in the
program too).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_ref import FLOAT32

L2_EPS = 1e-6


def rms_norm(w, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 ** 2, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def causal_conv(kernel, x):
    """Depthwise causal convolution over one sequence: ``kernel (channels,
    L)``, ``x (T, channels)``; tap ``j`` reads ``x_{t - (L-1) + j}``, zero
    before position 0."""
    t, taps = x.shape[0], kernel.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:t - back]], axis=0)
        out = out + kernel[:, j].astype(x.dtype) * shifted
    return out


def l2_norm(x):
    x32 = x.astype(jnp.float32)
    return (x32 / jnp.sqrt(jnp.sum(x32 ** 2, axis=-1, keepdims=True)
                           + L2_EPS)).astype(x.dtype)


def delta_rule(q, k, v, g, beta, prec=FLOAT32):
    """The recurrence, position by position: ``q, k (T, H, dk)``, ``v (T,
    H, dv)``, ``g, beta (T, H)`` float32 -> ``o (T, H, dv)``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def position(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        u = beta_t[:, None] * (v_t.astype(jnp.float32) - prec.dot(
            "hij,hi->hj", s, k_t).astype(jnp.float32))
        s = s + prec.dot("hi,hj->hij", k_t, u).astype(jnp.float32)
        return s, prec.dot("hij,hi->hj", s, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def linear_attention(lin, x, conf, prec=FLOAT32):
    """The gated delta rule over one sequence: x (T, d) -> (T, d)."""
    h, dk, dv = (conf["linear_num_heads"], conf["linear_key_head_dim"],
                 conf["linear_value_head_dim"])
    t = x.shape[0]
    w_q, w_k, w_v = jnp.split(lin["w_qkv"], [h * dk, 2 * h * dk], axis=1)
    c_q, c_k, c_v = jnp.split(lin["conv"], [h * dk, 2 * h * dk], axis=0)
    q = jax.nn.silu(causal_conv(c_q, prec.dot("td,de->te", x, w_q)))
    k = jax.nn.silu(causal_conv(c_k, prec.dot("td,de->te", x, w_k)))
    v = jax.nn.silu(causal_conv(c_v, prec.dot("td,de->te", x, w_v)))
    q = l2_norm(q.reshape(t, h, dk)) * dk ** -0.5
    k = l2_norm(k.reshape(t, h, dk))
    w_a, w_b = jnp.split(lin["w_ab"].astype(jnp.float32), 2, axis=1)
    x32 = x.astype(jnp.float32)
    g = -jnp.exp(lin["a_log"]) * jax.nn.softplus(jnp.einsum(
        "td,dh->th", x32, w_a, precision="highest") + lin["dt_bias"])
    beta = jax.nn.sigmoid(jnp.einsum("td,dh->th", x32, w_b,
                                     precision="highest"))
    if conf["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = delta_rule(q, k, v.reshape(t, h, dv), g, beta, prec)
    o = rms_norm(lin["o_norm"], o, conf["rms_norm_eps"]).reshape(t, -1)
    gate = jax.nn.silu(prec.dot("td,de->te", x, lin["w_gate"]))
    return prec.dot("te,ed->td", o * gate, lin["w_out"])


def attention(attn, x, conf, prec=FLOAT32, q_block=None):
    """Causal multi-head attention of one sequence: x (T, d) -> (T, d)."""
    t = x.shape[0]
    eps, heads = conf["rms_norm_eps"], conf["num_attention_heads"]
    q = prec.dot("td,dhk->thk", x, attn["wq"])
    k = prec.dot("td,dhk->thk", x, attn["wk"])
    v = prec.dot("td,dhk->thk", x, attn["wv"])
    q = rms_norm(attn["q_norm"], q.reshape(t, -1), eps).reshape(t, heads, -1)
    k = rms_norm(attn["k_norm"], k.reshape(t, -1), eps).reshape(t, heads, -1)
    hd = q.shape[2]
    positions = jnp.arange(t)
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


def layer(blk, h, conf, prec=FLOAT32, q_block=None):
    """One layer over one sequence: h (T, d) -> (T, d)."""
    eps = conf["rms_norm_eps"]
    if "linear" in blk:
        mixed = linear_attention(blk["linear"], h, conf, prec)
    else:
        mixed = attention(blk["attn"], h, conf, prec, q_block)
    h = h + rms_norm(blk["mixer_norm"], mixed, eps)
    return h + rms_norm(blk["ffn_norm"], swiglu(blk["mlp"], h, prec), eps)


def embed(outer, tokens, prec=FLOAT32):
    """Rows of the embedding table -> (T, d)."""
    return outer["embed"][tokens].astype(prec.act)


def lm_logits(outer, h, positions, conf, prec=FLOAT32):
    """Final RMSNorm and the untied head at ``positions`` -> (P, vocab)."""
    hf = rms_norm(outer["norm_f"], h[positions], conf["rms_norm_eps"])
    return prec.dot("pd,dv->pv", hf, outer["head"]).astype(jnp.float32)


def forward(params, tokens, conf, prec=FLOAT32, q_block=None):
    """The whole model over one sequence -> logits (T, vocab)."""
    h = embed(params, tokens, prec)
    for blk in params["blocks"]:
        h = layer(blk, h, conf, prec, q_block)
    return lm_logits(params, h, jnp.arange(tokens.shape[0]), conf, prec)
