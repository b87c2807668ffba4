"""The plain reference of the latent-attention, sparse-expert decoder (the
DeepseekV3 block as Kimi-VL-A3B's ``text_config`` sets it), written from
the block's equations in straightforward ``jax.numpy``.

    y = RMSNorm(h);  q = y Wq  (heads x (nope | rope))
    a = y Wkv_a = c_raw (rank) | k_pe_raw (rope);  c = RMSNorm(c_raw)
    q_pe, k_pe = rotary(q_pe), rotary(k_pe_raw)      (k_pe: one row, all heads)
    k_nope = c W_uk;  v = c W_uv                     (kv_b_proj's two halves)
    p = softmax(mask((q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)))
    h = h + (p v) Wo
    y = RMSNorm(h)
    dense layer:   h = h + W_down(silu(W_gate y) * W_up y)
    expert layer:  s = sigmoid(y Wg); the top k of s + b chosen;
                   w = the chosen s / (their sum + 1e-20) * routed_scaling_factor
                   h = h + sum_{i chosen and held} w_i E_i(y) + S(y)

No kernels, no cache, no batching; one sequence at a time.  ``q_block``
rows of queries attend at a time (against every key), so that a sequence
of thousands of positions fits: the same numbers as in one piece.  The
cache entry ``c | k_pe`` appears only in :func:`attention`'s ``absorbed``
form, which scores the query against the latent itself (``q_nope W_uk^T .
c``) and multiplies by ``W_uv`` after the sum: the form the program
decodes in, here to show that the two agree.

It imports nothing of the program and is given nothing the program has
made.  ``held`` are the ids of the experts whose weights ``experts``
holds (a chip's share: what absent experts would add is left out, as in
the program); the uncut layer is ``held = range(all)``.

Departures from the published model, shared with the program and listed
in the configuration file: no vision tower or projector; ``kv_b_proj``
given as its halves ``w_uk`` / ``w_uv``; the rotary slice de-interleaved
(even elements, then odd) and rotated by halves as ``modeling_deepseek.py``
does; seeded weights and selection bias.

``Precision`` is ``transformer_ref``'s: the reference itself is float32
with every product at "highest"; the fp8 control rounds both operands of
every matrix product to float8_e4m3.  The router's product is float32 at
"highest" under every precision, as in the published code and the program.
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
    """x (T, heads, d) at ``positions (T,)``: pair i is elements (2i,
    2i + 1); the result holds the rotated first members, then the second."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    out = jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                           b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.astype(x.dtype)


def attention(blk, y, conf, prec=FLOAT32, q_block=None, absorbed=False):
    """Causal latent attention of one sequence: y (T, d) -> (T, d)."""
    t = y.shape[0]
    nope, rank = conf["qk_nope_head_dim"], conf["kv_lora_rank"]
    scale = (nope + conf["qk_rope_head_dim"]) ** -0.5
    positions = jnp.arange(t)
    q = prec.dot("td,dhk->thk", y, blk["wq"])
    q_nope = q[..., :nope]
    q_pe = rotary(q[..., nope:], positions, conf["rope_theta"])
    a = prec.dot("td,dr->tr", y, blk["wkv_a"])
    c = rms_norm(blk["kv_norm"], a[:, :rank], conf["rms_norm_eps"])
    k_pe = rotary(a[:, None, rank:], positions, conf["rope_theta"])[:, 0]
    if absorbed:
        q_lat = prec.dot("thn,chn->thc", q_nope, blk["w_uk"])
    else:
        k_nope = prec.dot("tc,chn->thn", c, blk["w_uk"])
        v = prec.dot("tc,chv->thv", c, blk["w_uv"])
    step = t if q_block is None else q_block
    outs = []
    for lo in range(0, t, step):
        hi = min(t, lo + step)
        if absorbed:
            s = prec.dot("qhc,tc->hqt", q_lat[lo:hi], c)
        else:
            s = prec.dot("qhn,thn->hqt", q_nope[lo:hi], k_nope)
        s = s + prec.dot("qhr,tr->hqt", q_pe[lo:hi], k_pe)
        s = s.astype(jnp.float32) * scale
        mask = positions[lo:hi, None] >= positions[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf),
                           axis=-1).astype(prec.act)
        if absorbed:
            o = prec.dot("hqt,tc->qhc", p, c)
            outs.append(prec.dot("qhc,chv->qhv", o, blk["w_uv"]))
        else:
            outs.append(prec.dot("hqt,thv->qhv", p, v))
    return prec.dot("qhv,hvd->qd", jnp.concatenate(outs, 0), blk["wo"])


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
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * conf["routed_scaling_factor"]


def expert_layer(moe, y, conf, held, prec=FLOAT32, shared=True):
    """The routed sum over the experts in ``held`` (``moe["experts"]``
    holds their weights in that order), plus the shared expert unless
    ``shared`` is false: y (T, d) -> (T, d)."""
    chosen, w = routing(moe, y, conf)
    out = jnp.zeros(y.shape, prec.act)
    for slot, expert in enumerate(held):
        weight = jnp.sum(jnp.where(chosen == expert, w, 0.0), axis=-1)
        one = jax.tree.map(lambda leaf: leaf[slot], moe["experts"])
        out = out + weight[:, None].astype(prec.act) * swiglu(one, y, prec)
    if shared:
        out = out + swiglu(moe["shared"], y, prec)
    return out


def layer(blk, h, conf, held, prec=FLOAT32, q_block=None):
    """One layer over one sequence: h (T, d) -> (T, d)."""
    eps = conf["rms_norm_eps"]
    h = h + attention(blk, rms_norm(blk["attn_norm"], h, eps), conf, prec,
                      q_block)
    y = rms_norm(blk["ffn_norm"], h, eps)
    if "mlp" in blk:
        return h + swiglu(blk["mlp"], y, prec)
    return h + expert_layer(blk["moe"], y, conf, held, prec)


def embed(outer, tokens, prec=FLOAT32):
    """Rows of the embedding table -> (T, d)."""
    return outer["embed"][tokens].astype(prec.act)


def lm_logits(outer, h, positions, conf, prec=FLOAT32):
    """Final RMSNorm and the untied head at ``positions`` -> (P, vocab)."""
    hf = rms_norm(outer["norm_f"], h[positions], conf["rms_norm_eps"])
    return prec.dot("pd,dv->pv", hf, outer["head"]).astype(jnp.float32)


def forward(params, tokens, conf, held, prec=FLOAT32, q_block=None):
    """The whole model over one sequence -> logits (T, vocab)."""
    h = embed(params, tokens, prec)
    for blk in params["blocks"]:
        h = layer(blk, h, conf, held, prec, q_block)
    return lm_logits(params, h, jnp.arange(tokens.shape[0]), conf, prec)
