"""Gated-delta-rule linear attention among full attention, dense SwiGLU
(the ``olmo_hybrid`` block family: Olmo-Hybrid-7B's).

A fourth block family beside ``models/transformer.py``,
``models/mla_moe.py`` and ``models/lfm2_moe.py``, entered three ways over
the same functions: :func:`forward` (a whole sequence, no cache),
:func:`prefill_step` (one padded prompt: writes the K/V pool and the
sequence's two state rows) and :func:`decode_step` (one token a slot:
reads and writes all of them).  ``DecodeEngine`` takes the steps and
:func:`cache_pools` from here when the model's ``cfg["family"]`` says
``"olmo_hybrid"``.

Per layer, the norm on a sub-block's OUTPUT (Olmo 2's order): ``h <- h +
RMSNorm(Mixer(h))``, ``h <- h + RMSNorm(SwiGLU(h))``; then the final
RMSNorm and an untied head.  ``Mixer`` is one of two, by
``cfg["layer_types"]``:

*Gated delta rule* (``"linear_attention"``).  ``q~ | k~ | v~ = x W_qkv``
(``H dk``, ``H dk``, ``H dv`` columns); each channel through its own
causal convolution of ``conv_kernel`` taps (no bias, zeros before the
sequence's start), then SiLU; ``q`` and ``k`` L2-normalised a head (``x /
sqrt(sum x^2 + 1e-6)``), ``q`` scaled by ``dk^-0.5``; ``beta = 2
sigmoid(x W_b)`` (the 2 is ``allow_neg_eigval``), ``g = -exp(A_log)
softplus(x W_a + dt_bias)``; the recurrence of ``ops/gated_delta.py`` a
head; ``y = concat_h(RMSNorm(o_h) * silu(x W_g)_h) W_o`` (the norm 's
weight ``dv`` wide, shared by the heads).  **Its cache is two rows a
SEQUENCE**, in two pools over the linear layers only: ``q~ | k~ | v~`` at
the sequence's last ``conv_kernel - 1`` positions (what the convolution
needs of the past), and the recurrent matrices ``S (H, dk, dv)`` after its
last position (2.2 MB a layer at the published widths, against 96 KB for
``lfm2_moe``'s whole row: the count of rows is part of sizing a replica,
``DecodeEngine(state_rows=...)``).  A prefill scans the prompt in chunks
(:func:`~dist_keras_tpu.ops.gated_delta.gated_delta_chunked`), told the
prompt's TRUE length, so the padding behind it moves neither row; both are
written whole, so a row's previous owner is gone before anything reads it.
A decode step updates the slots' matrices where they lie
(:func:`~dist_keras_tpu.ops.pallas.gated_delta.state_step_auto`).

*Full attention* (``"full_attention"``).  ``heads`` heads of ``d_model /
heads``; RMSNorm over the WHOLE query and key projections before the split
into heads; no rotation (the published ``rope_theta`` is null and is taken
at its word: the linear layers carry the order); no biases.  **The cache
entry is ``v | k``**, every head's values then every head's keys, ``2 x
d_model`` values a position in ONE paged pool over the attention layers
only (7,680 lanes at the published widths).  Both reads are the ones the
families share (``models/blocks.py``), with as many K/V heads as query
heads: prefill attends with the flash forward, decoding reads the pool
with the latent family's read (``blocks.attend_rows``: on a TPU the kernel
that walks a slot's live pages in place, here in blocks of
``KV_BLOCK_PAGES`` pages).  One chip run chose it
(32 slots, 28 k live positions, a layer's read: 1.39 ms against 4.18 for a
kernel of a page a grid step over a K and a V pool, since deleted, and
5.21 for the ``jnp`` gather of whole tables; PERF.md, PR 36).

The two tiny projections behind ``g`` and ``beta`` run in float32 at
"highest" precision (``g`` is summed over a sequence inside an
exponential; they are 60 columns); every other product at the ambient
precision.

Both steps return, behind their tokens, what :func:`observe_step` counts:
a prefill its true and its padded length (``prefill.scan_positions`` /
``prefill.scan_padded_positions``); a decode step nothing of the device's
(its ``decode.kv.live_positions`` and ``decode.state.live_rows`` are host
arithmetic on the lengths).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.blocks import (
    FamilyDecoder,
    attend_entries,
    attend_rows,
    causal_taps,
    causal_taps_token,
    logits,
    pool_layer,
    rms_norm,
    swiglu,
    swiglu_params,
)
from dist_keras_tpu.models.layers import glorot_uniform
from dist_keras_tpu.ops.gated_delta import gated_delta_chunked
from dist_keras_tpu.ops.pallas.gated_delta import state_step_auto

FAMILY = "olmo_hybrid"
LINEAR, ATTENTION = "linear_attention", "full_attention"
L2_EPS = 1e-6
# pages of ``v | k`` rows the read's kernel fetches a grid step: 4 pages of
# 16 positions of 7,680 lanes are 2 MB (the kernel's own 32 would be two
# buffers of 16 MB); 2, 4 and 8 read alike on the chip (8.66 / 8.31 / 8.38
# ms six reads, PERF.md, PR 36)
KV_BLOCK_PAGES = 4


def olmo_hybrid_config(vocab_size, seq_len, d_model, n_heads, d_ff,
                       layer_types, linear_heads, linear_key_dim,
                       linear_value_dim, conv_kernel=4,
                       allow_neg_eigval=True, norm_eps=1e-6):
    """``seq_len`` is how many positions one sequence may hold (a slot's
    page table in the engine): nothing here needs a table of positions.
    ``layer_types`` names each layer's mixer, ``"linear_attention"`` or
    ``"full_attention"``."""
    layer_types = [str(t) for t in layer_types]
    if not layer_types or set(layer_types) - {LINEAR, ATTENTION}:
        raise ValueError(
            f"layer_types={layer_types!r} must name '{LINEAR}' or "
            f"'{ATTENTION}' for every layer")
    if d_model % n_heads:
        raise ValueError(
            f"d_model={d_model} must divide into n_heads={n_heads} heads")
    if conv_kernel < 2:
        raise ValueError(f"conv_kernel={conv_kernel} must be >= 2")
    return {
        "family": FAMILY,
        "vocab_size": int(vocab_size),
        "seq_len": int(seq_len),
        "d_model": int(d_model),
        "n_heads": int(n_heads),
        "d_ff": int(d_ff),
        "layer_types": layer_types,
        "n_layers": len(layer_types),
        "linear_heads": int(linear_heads),
        "linear_key_dim": int(linear_key_dim),
        "linear_value_dim": int(linear_value_dim),
        "conv_kernel": int(conv_kernel),
        "allow_neg_eigval": bool(allow_neg_eigval),
        "rms_norm_eps": float(norm_eps),
    }


def vocab(cfg):
    """The vocabulary a decoder of ``cfg`` reads and writes."""
    return int(cfg["vocab_size"])


def _widths(cfg):
    """-> (heads, dk, dv, channels of ``q~ | k~ | v~``)."""
    h, dk, dv = (cfg["linear_heads"], cfg["linear_key_dim"],
                 cfg["linear_value_dim"])
    return h, dk, dv, h * (2 * dk + dv)


def step_width(cfg):
    """Positions a slot a step: one token."""
    return 1


def cache_pools(cfg):
    """What the engine allocates, ``(layers spanned, "page" or
    "sequence", entry)`` a pool: the ``v | k`` pool, paged, over the
    attention layers; the convolutions' last inputs and the recurrent
    matrices, a row a sequence each, over the linear layers.  A kind of
    layer the model lacks still gets its pools, of one layer, so that the
    steps' signature does not depend on the pattern."""
    h, dk, dv, channels = _widths(cfg)
    kinds = cfg["layer_types"]
    n_lin = max(1, kinds.count(LINEAR))
    return ((max(1, kinds.count(ATTENTION)), "page", (2 * cfg["d_model"],)),
            (n_lin, "sequence", (cfg["conv_kernel"] - 1, channels)),
            (n_lin, "sequence", (h, dk, dv)))


def init_layer_params(key, cfg, layer):
    """One layer's leaves, a function of (key, layer) alone."""
    d, heads = cfg["d_model"], cfg["n_heads"]
    hd = d // heads
    h, dk, dv, channels = _widths(cfg)
    kq, kk, kv, kg, ko, kab, kc, ka, kt, kf = jax.random.split(
        jax.random.fold_in(key, 1 + layer), 10)
    blk = {"mixer_norm": jnp.ones((d,)), "ffn_norm": jnp.ones((d,)),
           "mlp": swiglu_params(kf, d, cfg["d_ff"])}
    if cfg["layer_types"][layer] == LINEAR:
        blk["linear"] = {
            "w_qkv": jnp.concatenate(
                [glorot_uniform(kq, (d, h * dk)),
                 glorot_uniform(kk, (d, h * dk)),
                 glorot_uniform(kv, (d, h * dv))], 1),
            "conv": glorot_uniform(kc, (channels, cfg["conv_kernel"])),
            "w_gate": glorot_uniform(kg, (d, h * dv)),
            # the decay's and beta's projections, side by side
            "w_ab": glorot_uniform(kab, (d, 2 * h)),
            # as the delta-rule layers' reference code seeds them
            "a_log": jnp.log(jax.random.uniform(ka, (h,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": _softplus_inverse(jax.random.uniform(
                kt, (h,), jnp.float32, 0.001, 0.1)),
            "o_norm": jnp.ones((dv,)),
            "w_out": glorot_uniform(ko, (h * dv, d)),
        }
    else:
        blk["attn"] = {
            "wq": glorot_uniform(kq, (d, heads, hd)),
            "wk": glorot_uniform(kk, (d, heads, hd)),
            "wv": glorot_uniform(kv, (d, heads, hd)),
            "q_norm": jnp.ones((d,)),
            "k_norm": jnp.ones((d,)),
            "wo": glorot_uniform(ko, (heads, hd, d)),
        }
    return blk


def _softplus_inverse(y):
    return y + jnp.log(-jnp.expm1(-y))


def init_outer_params(key, cfg):
    ke, kh = jax.random.split(jax.random.fold_in(key, 0))
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ke, (v, d), jnp.float32),
            "norm_f": jnp.ones((d,)),
            "head": glorot_uniform(kh, (d, v))}


def init_params(key, cfg):
    """Seeded weights -> the family's parameter tree."""
    tree = init_outer_params(key, cfg)
    tree["blocks"] = [init_layer_params(key, cfg, i)
                      for i in range(cfg["n_layers"])]
    return tree


# -- the pieces ---------------------------------------------------------
def _gdn_in(lin, x, cfg):
    """-> (``q~ | k~ | v~`` (T, channels), the output gate (T, H dv), ``g``
    and ``beta`` (T, H) each)."""
    h = cfg["linear_heads"]
    with jax.named_scope("gdn_in"):
        qkv = x @ lin["w_qkv"]
        gate = x @ lin["w_gate"]
        ab = jnp.dot(x.astype(jnp.float32), lin["w_ab"],
                     precision="highest")
        g = -jnp.exp(lin["a_log"]) * jax.nn.softplus(
            ab[:, :h] + lin["dt_bias"])
        beta = jax.nn.sigmoid(ab[:, h:])
        if cfg["allow_neg_eigval"]:
            beta = 2.0 * beta
    return qkv, gate, g, beta


def _gdn_heads(mixed, cfg):
    """The convolved channels -> (q (T, H, dk) normalised and scaled, k
    (T, H, dk) normalised, v (T, H, dv))."""
    h, dk, dv, _ = _widths(cfg)
    t = mixed.shape[0]
    mixed = jax.nn.silu(mixed)
    q = mixed[:, :h * dk].reshape(t, h, dk)
    k = mixed[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = mixed[:, 2 * h * dk:].reshape(t, h, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    return q * dk ** -0.5, k, v


def _gdn_out(lin, o, gate, cfg):
    """``o (T, H, dv)`` normalised a head, gated, projected -> (T, d)."""
    with jax.named_scope("gdn_out"):
        t = o.shape[0]
        o = rms_norm(lin["o_norm"], o, cfg["rms_norm_eps"])
        return (o.reshape(t, -1) * jax.nn.silu(gate)) @ lin["w_out"]


def _qkv(attn, x, cfg):
    """-> (q (T, heads, hd), the cache entry ``v | k`` (T, 2 d)), q and k
    normalised over the whole projection."""
    eps = cfg["rms_norm_eps"]
    t, heads = x.shape[0], cfg["n_heads"]
    with jax.named_scope("qkv"):
        q = jnp.einsum("td,dhk->thk", x, attn["wq"])
        k = jnp.einsum("td,dhk->thk", x, attn["wk"])
        v = jnp.einsum("td,dhk->thk", x, attn["wv"])
    with jax.named_scope("qk_norm"):
        q = rms_norm(attn["q_norm"], q.reshape(t, -1), eps)
        k = rms_norm(attn["k_norm"], k.reshape(t, -1), eps)
    return (q.reshape(t, heads, -1),
            jnp.concatenate([v.reshape(t, -1), k], -1))


def _ffn(blk, hs, cfg):
    with jax.named_scope("mlp"):
        return hs + rms_norm(blk["ffn_norm"], swiglu(blk["mlp"], hs),
                             cfg["rms_norm_eps"])


def _sequence_layers(params, tokens, length, cfg, write_kv, write_state):
    """The layers over one whole sequence -> hidden (T, d);
    ``write_kv(pool layer, entry)`` / ``write_state(pool layer, the
    convolutions' state, the recurrent matrices)`` take each layer's
    cache."""
    eps = cfg["rms_norm_eps"]
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    for li, blk in enumerate(params["blocks"]):
        at = pool_layer(cfg, li)
        if "linear" in blk:
            lin = blk["linear"]
            qkv, gate, g, beta = _gdn_in(lin, hs, cfg)
            with jax.named_scope("gdn_conv"):
                mixed, taps = causal_taps(lin["conv"], qkv, length)
                q, k, v = _gdn_heads(mixed, cfg)
            with jax.named_scope("gdn_scan"):
                o, state = gated_delta_chunked(q, k, v, g, beta, length)
            write_state(at, taps, state)
            mixed = _gdn_out(lin, o, gate, cfg)
        else:
            q, entry = _qkv(blk["attn"], hs, cfg)
            write_kv(at, entry)
            with jax.named_scope("attend"):
                a = attend_entries(q, entry, cfg["n_heads"])
            with jax.named_scope("attn_out"):
                mixed = jnp.einsum("thk,hkd->td", a, blk["attn"]["wo"])
        hs = _ffn(blk, hs + rms_norm(blk["mixer_norm"], mixed, eps), cfg)
    return hs


# -- the three entry points ---------------------------------------------
def forward(params, tokens, cfg):
    """One whole sequence ``tokens (T,)``, no cache -> logits (T, vocab)."""
    hs = _sequence_layers(params, tokens, tokens.shape[0], cfg,
                          lambda at, entry: None,
                          lambda at, taps, state: None)
    return logits(params, hs, cfg)


def prefill_step(cfg, params, kv, taps, states, tokens, length, page_idx,
                 page_off, row):
    """One padded prompt -> (``[first token, length, rung]`` int32, the
    updated pools).  Positions past ``length`` write their ``v | k`` to the
    scratch page (``page_idx`` routes them there), never influence
    position ``length - 1`` (attention, convolution and scan are causal)
    and never reach either state: the convolutions' is cut at the true
    last positions, the scan leaves the matrices alone behind ``length``;
    both are written whole into ``row``."""
    pools = [kv, taps, states]

    def write_kv(at, entry):
        # the scattered dimensions are the pool's major ones: in place on
        # the donated pool
        with jax.named_scope("kv_write"):
            pools[0] = pools[0].at[at, page_idx, page_off].set(entry)

    def write_state(at, new_taps, new_state):
        with jax.named_scope("state_write"):
            pools[1] = pools[1].at[at, row].set(new_taps)
            pools[2] = pools[2].at[at, row].set(new_state)

    hs = _sequence_layers(params, tokens, length, cfg, write_kv,
                          write_state)
    first = jnp.argmax(logits(params, hs[length - 1], cfg))
    out = jnp.stack([first.astype(jnp.int32), length.astype(jnp.int32),
                     jnp.int32(tokens.shape[0])])
    return (out, *pools)


def decode_step(cfg, params, kv, taps, states, tokens, positions,
                page_tables, write_page, write_off, lengths, rows):
    """One token step for a padded slot set -> (next tokens int32, the
    updated pools).  Padding slots carry ``length == 0``, write to the
    scratch page and the scratch row, and the read's dead-row guard
    zeroes their attention."""
    del positions                      # no layer of this family takes them
    eps = cfg["rms_norm_eps"]
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    for li, blk in enumerate(params["blocks"]):
        at = pool_layer(cfg, li)
        if "linear" in blk:
            lin = blk["linear"]
            qkv, gate, g, beta = _gdn_in(lin, hs, cfg)
            with jax.named_scope("state_read"):
                old = taps[at, rows]
            with jax.named_scope("gdn_conv"):
                mixed, window = causal_taps_token(lin["conv"], qkv, old)
                q, k, v = _gdn_heads(mixed, cfg)
            with jax.named_scope("state_write"):
                taps = taps.at[at, rows].set(window[:, 1:])
            with jax.named_scope("gdn_step"):
                o, states = state_step_auto(states, at, rows, q, k, v, g,
                                            beta)
            mixed = _gdn_out(lin, o, gate, cfg)
        else:
            q, entry = _qkv(blk["attn"], hs, cfg)
            with jax.named_scope("kv_write"):
                kv = kv.at[at, write_page, write_off].set(entry)
            with jax.named_scope("attend_pool"):
                # the whole pool viewed flat over (layer, page), the page
                # ids offset to this layer's: ``kv[at]`` would copy it
                a = attend_rows(q, kv.reshape(-1, *kv.shape[2:]),
                                page_tables + at * kv.shape[1], lengths,
                                cfg["n_heads"], KV_BLOCK_PAGES)
            with jax.named_scope("attn_out"):
                mixed = jnp.einsum("shk,hkd->sd", a, blk["attn"]["wo"])
        hs = _ffn(blk, hs + rms_norm(blk["mixer_norm"], mixed, eps), cfg)
    nxt = jnp.argmax(logits(params, hs, cfg), -1).astype(jnp.int32)
    return nxt, kv, taps, states


def observe_step(counts, at, lengths=None, page_size=None):
    """What rides behind a step's tokens -> the registry.  A prefill sends
    its true and its padded length (the scan's positions, and what the
    rung made of them), a sample each of ``prefill.scan_positions`` /
    ``prefill.scan_padded_positions``, stamped like ``decode.prefill_s``
    so that a window can be cut out of them.  A decode step passes its slots' ``lengths`` (host
    values, zeros for padding) and stamps their sum, the live positions
    its K/V read covers in each attention layer, on
    ``decode.kv.live_positions``, and the live slots, each of which had
    its state rows read and written in every linear layer, on
    ``decode.state.live_rows``."""
    from dist_keras_tpu.observability import metrics

    if lengths is None:
        true, padded = (int(c) for c in counts)
        metrics.histogram("prefill.scan_positions").observe(true, at=at)
        metrics.histogram("prefill.scan_padded_positions").observe(
            padded, at=at)
        return
    metrics.histogram("decode.kv.live_positions").observe(
        int(lengths.sum()), at=at)
    metrics.histogram("decode.state.live_rows").observe(
        int(np.count_nonzero(lengths)), at=at)


class OlmoHybridDecoder(FamilyDecoder):
    family = sys.modules[__name__]
    config = staticmethod(olmo_hybrid_config)
    name = "olmo_hybrid_decoder"
