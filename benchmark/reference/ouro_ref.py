"""The plain reference of the looped decoder (Ouro-2.6B's block,
``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741), written from its equations in straightforward
``jax.numpy``.  With ``RMS(w, x) = x * rsqrt(mean(x^2) + eps) * w``, one
stack of layers is run ``total_ut_steps`` times on the SAME weights; layer
``l`` in pass ``t``, on its input ``x``::

    a = RMS(attn_norm, x)
    q, k, v = a W_q, a W_k, a W_v          (heads of head_dim; no bias, no
                                            norm on q or k)
    q, k rotated by halves over the whole head, theta = rope_theta
    p = softmax(mask(q_h . k_h / sqrt(head_dim)));  o = concat_h(p v_h) W_o
    x = x + RMS(attn_out_norm, o)          (the norm on the OUTPUT)
    m = RMS(mlp_norm, x)
    x = x + RMS(mlp_out_norm, (silu(m W_g) * (m W_u)) W_d)

    after the last layer:  h_t = RMS(norm_f, x);  pass t + 1 starts from h_t
    g_t = h_t . w_g + b_g;  lambda_t = sigmoid(g_t)
    p_t = lambda_t prod_{s<t}(1 - lambda_s)   (the last pass: the product alone)
    C_t = sum_{s<=t} p_s;  e = the first t with C_t >= early_exit_threshold,
                               the last pass where there is none
    logits = h_e W_head                                          (untied)

No kernels, no cache, no batching, **no loop primitive**: the passes are
nested Python passes over the same list of layers (:func:`forward`), or
the caller's own (``ouro_check`` applies :func:`layer` pass by pass, a
layer's weights made once and used in every pass).  Keys and values are
never kept: a pass attends over what it computes itself, which is what
holds the program's cache of ``(pass, layer)`` entries to it.  ``q_block``
rows of queries attend at a time (against every key): the same numbers as
in one piece.

It imports nothing of the program and is given nothing the program has
made.  Departures from the published description, shared with the program
and listed in the configuration's file: every pass runs for every
position (the published forward does the same; its adaptive exit in
generation is not run), greedy decoding, seeded weights.

``Precision`` is ``transformer_ref``'s: the reference itself is float32
with every product at "highest"; a control carries activations in a
lower precision and rounds every matrix product's operands.  The gate's
product, its sigmoid and the exit rule are float32 at "highest" under
every precision, as the configuration states (in the program too).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_ref import FLOAT32


def rms_norm(w, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 ** 2, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def rotate_half(x, positions, theta):
    """Rotary positions on ``x (T, heads, d)``: element ``i`` pairs with
    element ``i + d / 2`` (``rotate_half``), no scaling."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    angle = jnp.concatenate([angle, angle], axis=-1)
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * jnp.cos(angle) + turned * jnp.sin(angle)).astype(x.dtype)


def attention(attn, a, conf, prec=FLOAT32, q_block=None):
    """Causal multi-head attention of one sequence over its own keys and
    values: a (T, d) -> (T, d)."""
    t = a.shape[0]
    positions = jnp.arange(t)
    q = rotate_half(prec.dot("td,dhk->thk", a, attn["wq"]), positions,
                    conf["rope_theta"])
    k = rotate_half(prec.dot("td,dhk->thk", a, attn["wk"]), positions,
                    conf["rope_theta"])
    v = prec.dot("td,dhk->thk", a, attn["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    hd = q.shape[2]
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


def layer(blk, x, conf, prec=FLOAT32, q_block=None):
    """One layer over one sequence, in any pass: x (T, d) -> (T, d)."""
    eps = conf["rms_norm_eps"]
    o = attention(blk["attn"], rms_norm(blk["attn_norm"], x, eps), conf,
                  prec, q_block)
    x = x + rms_norm(blk["attn_out_norm"], o, eps)
    f = swiglu(blk["mlp"], rms_norm(blk["mlp_norm"], x, eps), prec)
    return x + rms_norm(blk["mlp_out_norm"], f, eps)


def embed(outer, tokens, prec=FLOAT32):
    """Rows of the embedding table -> (T, d)."""
    return outer["embed"][tokens].astype(prec.act)


def end_of_pass(outer, x, conf):
    """What stands behind a pass's last layer -> (``h_t`` (T, d), the
    gate ``g_t`` (T,) float32)."""
    h = rms_norm(outer["norm_f"], x, conf["rms_norm_eps"])
    g = jnp.einsum("td,d->t", h.astype(jnp.float32),
                   outer["gate"]["w"].astype(jnp.float32),
                   precision="highest") + outer["gate"]["b"]
    return h, g


def exit_pass(gates, threshold):
    """The exit pass of each position from the passes' gates, a list of
    (T,) arrays: the first ``t`` with ``C_t >= threshold``, the last pass
    where there is none."""
    last = len(gates) - 1
    lam = [jax.nn.sigmoid(g.astype(jnp.float32)) for g in gates]
    exits = jnp.full(gates[0].shape, last, jnp.int32)
    found = jnp.zeros(gates[0].shape, bool)
    total = jnp.zeros(gates[0].shape, jnp.float32)
    left = jnp.ones(gates[0].shape, jnp.float32)
    for t in range(last + 1):
        p = lam[t] * left if t < last else left
        total = total + p
        left = left * (1.0 - lam[t])
        here = (total >= threshold) & ~found
        exits = jnp.where(here, t, exits)
        found = found | here
    return exits


def lm_logits(outer, states, gates, positions, conf, prec=FLOAT32):
    """The head over each position's exit pass: ``states`` and ``gates``
    are the passes' ``h_t (T, d)`` and ``g_t (T,)`` -> (logits (P, vocab)
    float32, the exit passes (P,))."""
    exits = exit_pass([g[positions] for g in gates],
                      conf["early_exit_threshold"])
    chosen = jnp.stack([h[positions] for h in states], 0)
    chosen = jnp.take_along_axis(chosen, exits[None, :, None], 0)[0]
    return prec.dot("pd,dv->pv", chosen, outer["head"]).astype(
        jnp.float32), exits


def forward(params, tokens, conf, prec=FLOAT32, q_block=None,
            with_gates=False):
    """The whole model over one sequence -> logits (T, vocab);
    ``with_gates``: -> (logits, the passes' gates (passes, T), the exit
    passes (T,))."""
    x = embed(params, tokens, prec)
    states, gates = [], []
    for _ in range(conf["total_ut_steps"]):
        for blk in params["blocks"]:
            x = layer(blk, x, conf, prec, q_block)
        x, g = end_of_pass(params, x, conf)
        states.append(x)
        gates.append(g)
    logits, exits = lm_logits(params, states, gates,
                              jnp.arange(tokens.shape[0]), conf, prec)
    return (logits, jnp.stack(gates, 0), exits) if with_gates else logits
