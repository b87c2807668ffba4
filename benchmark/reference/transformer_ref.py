"""The plain reference: a pre-LN transformer block stack in straightforward
``jax.numpy``, written from the block's equations.

    y = LN1(h);  q,k,v = y Wq, y Wk, y Wv   (per head)
    a = softmax(mask(q k^T / sqrt(dh))) v;   h = h + a Wo
    y = LN2(h);  h = h + gelu(y W1 + b1) W2 + b2

No kernels, no cache, no batching tricks; one sequence at a time, the
whole T x T attention.  It imports nothing of the program and is given
nothing the program has made.  Departures from the published Galactica
block, shared with the program and listed in the configuration file:
tanh-approximate gelu, biases present (zero), no position offset.

``Precision`` says how the arithmetic is carried: the reference itself is
float32 with every matrix product at "highest"; the controls carry
activations in bfloat16; ``fp8`` also rounds both operands of every
matrix product to float8_e4m3 with one scale per tensor, and
``bfloat16_state`` keeps weights and optimizer state in bfloat16 too.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str
    act: object          # dtype activations and weights are carried in
    fp8_operands: bool   # round matrix-product operands to float8_e4m3
    state: object = jnp.float32   # dtype of weights and optimizer state

    def operand(self, x):
        x = x.astype(self.act)
        if not self.fp8_operands:
            return x
        # rounding is applied to values only: the gradient passes straight
        # through, as in a framework that quantizes operands on the fly
        x32 = jax.lax.stop_gradient(x).astype(jnp.float32)
        scale = jnp.max(jnp.abs(x32)) / 448.0 + 1e-30
        q = (x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x + jax.lax.stop_gradient((q * scale).astype(self.act) - x)

    def dot(self, spec, a, b):
        out = jnp.einsum(spec, self.operand(a), self.operand(b),
                         precision="highest",
                         preferred_element_type=jnp.float32)
        return out.astype(self.act)


FLOAT32 = Precision("float32", jnp.float32, False)
FP8 = Precision("fp8", jnp.bfloat16, True)
# training with no float32 masters: weights, moments and the update itself
# in bfloat16, where a step of 1e-5 on a weight of 1e-2 rounds away
BFLOAT16_STATE = Precision("bfloat16_state", jnp.bfloat16, False,
                           jnp.bfloat16)


def layer_norm(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    out = (x32 - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


def block(blk, h, prec=FLOAT32):
    """One block over one sequence: h (T, d) -> (T, d), causal."""
    t = h.shape[0]
    dh = blk["wq"].shape[-1]
    y = layer_norm(blk["ln1"], h)
    q = prec.dot("td,dhk->thk", y, blk["wq"])
    k = prec.dot("td,dhk->thk", y, blk["wk"])
    v = prec.dot("td,dhk->thk", y, blk["wv"])
    s = prec.dot("qhk,thk->hqt", q, k).astype(jnp.float32) * dh ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(prec.act)
    a = prec.dot("hqt,thk->qhk", p, v)
    h = h + prec.dot("qhk,hkd->qd", a, blk["wo"])
    y = layer_norm(blk["ln2"], h)
    u = jax.nn.gelu(prec.dot("td,df->tf", y, blk["w1"])
                    + blk["b1"].astype(prec.act))
    return h + prec.dot("tf,fd->td", u, blk["w2"]) + blk["b2"].astype(
        prec.act)


def classifier_loss(params, x, label, prec=FLOAT32):
    """The training objective of one sequence: dense input projection,
    blocks, final layer norm, mean over positions, linear head, softmax
    cross-entropy -> scalar float32."""
    h = prec.dot("ti,id->td", x, params["proj"]) + params["pos"][
        :x.shape[0]].astype(prec.act)
    for blk in params["blocks"]:
        h = block(blk, h, prec)
    pooled = jnp.mean(layer_norm(params["ln_f"], h).astype(jnp.float32),
                      axis=0)
    logits = prec.dot("d,dc->c", pooled, params["head"]["kernel"]).astype(
        jnp.float32) + params["head"]["bias"]
    return -jax.nn.log_softmax(logits)[label]


def batch_loss_and_grad(params, xs, labels, prec=FLOAT32):
    """Mean loss over a batch and its gradient, one sequence at a time so
    that only one sequence's attention is alive."""
    def one(carry, row):
        x, label = row
        loss, grad = jax.value_and_grad(classifier_loss)(
            params, x, label, prec)
        return jax.tree.map(jnp.add, carry, (loss, grad)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (loss, grad), _ = jax.lax.scan(one, zero, (xs, labels))
    n = xs.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grad)


def adam_update(params, grad, mu, nu, step, lr, b1=0.9, b2=0.999,
                eps=1e-8):
    """Adam as published (bias-corrected moments, eps outside the root)
    -> (params, mu, nu); ``step`` counts from 1."""
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grad)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grad)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu


def embed(outer, tokens, prec=FLOAT32):
    """Token embedding plus learned positions: rows of the projection
    (what ``one_hot(tokens) @ proj`` selects) -> (T, d)."""
    return (outer["proj"][tokens].astype(prec.act)
            + outer["pos"][:tokens.shape[0]].astype(prec.act))


def lm_logits(outer, h, positions, prec=FLOAT32):
    """Final layer norm and output head at ``positions`` -> (P, vocab)."""
    hf = layer_norm(outer["ln_f"], h[positions])
    return prec.dot("pd,dv->pv", hf, outer["head"]["kernel"]).astype(
        jnp.float32) + outer["head"]["bias"]
