"""Gated short-convolution, grouped-query, sparse-expert decoder (the
``lfm2_moe`` block family: LFM2-8B-A1B's).

A third block family beside ``models/transformer.py`` and
``models/mla_moe.py``, entered three ways over the same functions:
:func:`forward` (a whole sequence, no cache), :func:`prefill_step` (one
padded prompt: writes the K/V pool and the sequence's convolution state)
and :func:`decode_step` (one token a slot: reads both).  ``DecodeEngine``
takes the steps and :func:`cache_pools` from here when the model's
``cfg["family"]`` says ``"lfm2_moe"``.

Per layer ``h <- h + Op(RMSNorm(h))``, ``h <- h + FFN(RMSNorm(h))``; the
final RMSNorm and the head, which is the token embedding (tied).  ``Op``
is one of two, by ``cfg["layer_types"]``:

*Gated short convolution* (``"conv"``).  ``[B | C | X] = y W_in`` (three
thirds of ``3 d`` columns, in that order), ``u = B * X``, ``v_t = sum_j
k[:, j] u_{t - (L-1) + j}`` (depthwise, causal, ``L = conv_l_cache``
taps, ``u`` zero before the sequence's start), ``o = (C * v) W_out``.
**Its cache is per SEQUENCE, not per position:** ``u`` at the sequence's
last ``L - 1`` positions, one row of ``(L - 1, d)`` a sequence a
convolution layer, in a pool addressed by the row the allocator reserved
with the sequence's pages.  A prefill writes the row from the prompt's
TRUE last positions (the padding behind them never reaches it; a prompt
shorter than ``L - 1`` leaves zeros on the left), so a row's previous
owner is overwritten whole before anything reads it.

*Grouped-query attention* (``"full_attention"``).  ``q`` is ``heads x
head_dim``, ``k`` and ``v`` ``kv_heads x head_dim``; RMSNorm over each
head's width on ``q`` and ``k``, then rotary positions rotated by halves
(no de-interleaving: ``blocks.rope_halves``); K/V head ``i`` serves
query heads ``g i .. g i + g - 1``.  **The cache entry is ``v | k``**,
every K/V head's values then every K/V head's keys, ``2 x kv_heads x
head_dim`` values a position in ONE pool that spans the attention layers
only: rows of whole lanes (1,024 at the published widths; a 64-wide minor
dimension would be padded to 128 lanes by the chip's tiling).  Prefill
attends with the flash forward, four query heads fetching one K/V head
through its index map.  Decoding reads the pool with the latent family's
read (:func:`~dist_keras_tpu.ops.pallas.decode_attention.
latent_attention_auto`: on a TPU the kernel that walks a slot's live
pages in place): head ``h``'s query is laid into the lanes of ITS K/V
head's keys, zeros elsewhere, so one row-wide product gives ``q_h .
k_{h // g}``; the values are the row's leading half, and head ``h`` keeps
its own K/V head's lanes of the sum (PERF.md, PR 32, has the chip run
that chose it over a gather of whole page tables and over the
page-a-grid-step kernel).

*Feed-forward.*  The first ``num_dense_layers`` layers are one SwiGLU;
the others are ``blocks.moe_layer`` as it is: sigmoid scores in float32
at "highest" precision, the top ``k`` of ``s + b`` chosen, weights the
chosen ``s`` over their sum plus ``route_norm_eps`` (1e-6 here), times
``routed_scaling_factor``; every routed expert is held (``held_experts``
is all of them) and computed as that module's masked dense pass; there is
no shared expert.  No capacity, no dropped token.

Both steps return, behind their tokens, the expert layer's routing counts;
:func:`observe_step` turns them into the ``decode.moe.*`` instruments and
stamps ``decode.kv.live_positions`` a decode step.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from dist_keras_tpu.models.blocks import (
    FamilyDecoder,
    attend_entries,
    attend_rows,
    causal_taps,
    causal_taps_token,
    ffn,
    logits,
    observe_routing,
    pool_layer,
    qkv_normed_rotated,
    rms_norm,
    swiglu_params,
    zero_counts,
)
from dist_keras_tpu.models.layers import glorot_uniform

FAMILY = "lfm2_moe"
CONV, ATTENTION = "conv", "full_attention"


def lfm2_moe_config(vocab_size, seq_len, d_model, n_heads, n_kv_heads,
                    d_ff, moe_d_ff, n_routed_experts, top_k, layer_types,
                    num_dense_layers=2, conv_l_cache=3,
                    routed_scaling_factor=1.0, rope_theta=1000000.0,
                    norm_eps=1e-5):
    """``seq_len`` is how many positions one sequence may hold (a slot's
    page table in the engine): rotary positions need no table.
    ``layer_types`` names each layer's operator, ``"conv"`` or
    ``"full_attention"``."""
    layer_types = [str(t) for t in layer_types]
    if not layer_types or set(layer_types) - {CONV, ATTENTION}:
        raise ValueError(
            f"layer_types={layer_types!r} must name '{CONV}' or "
            f"'{ATTENTION}' for every layer")
    if d_model % n_heads or n_heads % n_kv_heads or (
            d_model // n_heads) % 2:
        raise ValueError(
            f"d_model={d_model} must divide into n_heads={n_heads} heads "
            f"of even width, n_kv_heads={n_kv_heads} a divisor of them")
    if top_k > n_routed_experts:
        raise ValueError(f"top_k={top_k} > {n_routed_experts} experts")
    if conv_l_cache < 2:
        raise ValueError(f"conv_l_cache={conv_l_cache} must be >= 2")
    return {
        "family": FAMILY,
        "vocab_size": int(vocab_size),
        "seq_len": int(seq_len),
        "d_model": int(d_model),
        "n_heads": int(n_heads),
        "n_kv_heads": int(n_kv_heads),
        "d_ff": int(d_ff),
        "moe_d_ff": int(moe_d_ff),
        "n_routed_experts": int(n_routed_experts),
        "top_k": int(top_k),
        "layer_types": layer_types,
        "n_layers": len(layer_types),
        "num_dense_layers": int(num_dense_layers),
        "conv_l_cache": int(conv_l_cache),
        "routed_scaling_factor": float(routed_scaling_factor),
        "rope_theta": float(rope_theta),
        # what ``blocks.ffn`` / ``route_sigmoid`` / ``moe_layer`` read: every
        # routed expert is held here, and the chosen scores are divided
        # by their sum plus 1e-6 (the published code's)
        "rms_norm_eps": float(norm_eps),
        "held_experts": list(range(int(n_routed_experts))),
        "route_norm_eps": 1e-6,
    }


def vocab(cfg):
    """The vocabulary a decoder of ``cfg`` reads and writes."""
    return int(cfg["vocab_size"])


def step_width(cfg):
    """Positions a slot a step: one token."""
    return 1


def cache_pools(cfg):
    """What the engine allocates, ``(layers spanned, "page" or
    "sequence", entry)`` a pool: the ``v | k`` pool, paged, over the
    attention layers; the convolution state, a row a sequence, over the
    convolution layers.  A kind of layer the model lacks still gets its
    pool, of one layer, so that the steps' signature does not depend on
    the pattern."""
    kv = 2 * cfg["n_kv_heads"] * (cfg["d_model"] // cfg["n_heads"])
    kinds = cfg["layer_types"]
    return (
        (max(1, kinds.count(ATTENTION)), "page", (kv,)),
        (max(1, kinds.count(CONV)), "sequence",
         (cfg["conv_l_cache"] - 1, cfg["d_model"])),
    )


def init_layer_params(key, cfg, layer):
    """One layer's leaves, a function of (key, layer) alone."""
    d, h, hk = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = d // h
    ki, kc, ko, kq, kk, kv, kf, kr, kb, ke = jax.random.split(
        jax.random.fold_in(key, 1 + layer), 10)
    blk = {"op_norm": jnp.ones((d,)), "ffn_norm": jnp.ones((d,))}
    if cfg["layer_types"][layer] == CONV:
        blk["conv"] = {
            "w_in": glorot_uniform(ki, (d, 3 * d)),
            "kernel": glorot_uniform(kc, (d, cfg["conv_l_cache"])),
            "w_out": glorot_uniform(ko, (d, d)),
        }
    else:
        blk["attn"] = {
            "wq": glorot_uniform(kq, (d, h, hd)),
            "wk": glorot_uniform(kk, (d, hk, hd)),
            "wv": glorot_uniform(kv, (d, hk, hd)),
            "q_norm": jnp.ones((hd,)),
            "k_norm": jnp.ones((hd,)),
            "wo": glorot_uniform(ko, (h, hd, d)),
        }
    if layer < cfg["num_dense_layers"]:
        blk["mlp"] = swiglu_params(kf, d, cfg["d_ff"])
        return blk
    n = cfg["n_routed_experts"]
    blk["moe"] = {
        "router": glorot_uniform(kr, (d, n)),
        # small and not zero, so that selection (s + b) and weighting (s)
        # differ
        "router_bias": jax.random.uniform(kb, (n,), jnp.float32,
                                          -0.02, 0.02),
        "experts": swiglu_params(ke, d, cfg["moe_d_ff"], (n,)),
    }
    return blk


def init_params(key, cfg):
    """Seeded weights -> the family's parameter tree."""
    ke = jax.random.fold_in(key, 0)
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ke, (v, d), jnp.float32),
            "norm_f": jnp.ones((d,)),
            "blocks": [init_layer_params(key, cfg, i)
                       for i in range(cfg["n_layers"])]}


# -- the pieces ---------------------------------------------------------
def _conv_gates(conv, y):
    """-> (``u = B * X``, ``C``), each ``(T, d)``."""
    with jax.named_scope("conv_in"):
        b, c, x = jnp.split(y @ conv["w_in"], 3, axis=-1)
        return b * x, c


def _conv_out(conv, c, v):
    with jax.named_scope("conv_out"):
        return (c * v) @ conv["w_out"]


def _conv_sequence(conv, y, length):
    """The operator over one whole sequence ``y (T, d)`` -> (``o (T,
    d)``, the state after position ``length - 1``: ``u`` at the last
    ``L - 1`` positions, zeros before the sequence's start)."""
    u, c = _conv_gates(conv, y)
    with jax.named_scope("conv_mix"):
        v, state = causal_taps(conv["kernel"], u, length)
    return _conv_out(conv, c, v), state


def _conv_token(conv, y, state):
    """One token a slot: ``y (S, d)``, ``state (S, L - 1, d)`` -> (``o
    (S, d)``, the state one position on)."""
    u, c = _conv_gates(conv, y)
    with jax.named_scope("conv_mix"):
        v, window = causal_taps_token(conv["kernel"], u, state)
    return _conv_out(conv, c, v), window[:, 1:]


def _sequence_layers(params, tokens, length, cfg, write_kv, write_state):
    """The layers over one whole sequence -> (hidden (T, d), counts);
    ``write_kv(pool layer, entry)`` / ``write_state(pool layer, state)``
    take each layer's cache."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    valid = positions < length
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    counts = zero_counts(cfg, tokens.shape[0])
    for li, blk in enumerate(params["blocks"]):
        y = rms_norm(blk["op_norm"], hs, cfg["rms_norm_eps"])
        if "conv" in blk:
            o, state = _conv_sequence(blk["conv"], y, length)
            write_state(pool_layer(cfg, li), state)
        else:
            q, entry = qkv_normed_rotated(blk["attn"], y, positions, cfg)
            write_kv(pool_layer(cfg, li), entry)
            with jax.named_scope("attend"):
                a = attend_entries(q, entry, cfg["n_kv_heads"])
            with jax.named_scope("attn_out"):
                o = jnp.einsum("thk,hkd->td", a, blk["attn"]["wo"])
        hs, counts = ffn(blk, hs + o, cfg, valid, counts)
    return hs, counts


# -- the three entry points ---------------------------------------------
def forward(params, tokens, cfg):
    """One whole sequence ``tokens (T,)``, no cache -> logits (T, vocab)."""
    hs, _ = _sequence_layers(params, tokens, tokens.shape[0], cfg,
                             lambda li, entry: None,
                             lambda li, state: None)
    return logits(params, hs, cfg)


def prefill_step(cfg, params, kv, state, tokens, length, page_idx,
                 page_off, row):
    """One padded prompt -> (``[first token, counts...]`` int32, the
    updated pools).  Positions past ``length`` write their ``v | k`` to
    the scratch page (``page_idx`` routes them there), reach no expert,
    never influence position ``length - 1`` (the attention and the
    convolution are causal) and never reach the state, which is ``u`` at
    positions ``length - L + 1 .. length - 1`` written whole into
    ``row``."""
    pools = [kv, state]

    def write_kv(li, entry):
        # the scattered dimensions are the pool's major ones: in place on
        # the donated pool
        with jax.named_scope("kv_write"):
            pools[0] = pools[0].at[li, page_idx, page_off].set(entry)

    def write_state(li, new):
        with jax.named_scope("state_write"):
            pools[1] = pools[1].at[li, row].set(new)

    hs, counts = _sequence_layers(params, tokens, length, cfg, write_kv,
                                  write_state)
    first = jnp.argmax(logits(params, hs[length - 1], cfg))
    return (jnp.concatenate([first.astype(jnp.int32)[None], counts]),
            *pools)


def _decode_layers(cfg, params, kv, state, tokens, positions, page_tables,
                   write_page, write_off, lengths, rows):
    """The layers of one token step -> (hidden (S, d), counts, the
    updated pools)."""
    eps = cfg["rms_norm_eps"]
    valid = lengths > 0
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    counts = zero_counts(cfg)
    for li, blk in enumerate(params["blocks"]):
        y = rms_norm(blk["op_norm"], hs, eps)
        at = pool_layer(cfg, li)
        if "conv" in blk:
            with jax.named_scope("state_read"):
                old = state[at, rows]
            o, new = _conv_token(blk["conv"], y, old)
            with jax.named_scope("state_write"):
                state = state.at[at, rows].set(new)
        else:
            q, entry = qkv_normed_rotated(blk["attn"], y, positions, cfg)
            with jax.named_scope("kv_write"):
                kv = kv.at[at, write_page, write_off].set(entry)
            with jax.named_scope("attend_pool"):
                # the whole pool viewed flat over (layer, page), the page
                # ids offset to this layer's: ``kv[at]`` would copy it
                a = attend_rows(q, kv.reshape(-1, *kv.shape[2:]),
                                page_tables + at * kv.shape[1], lengths,
                                cfg["n_kv_heads"])
            with jax.named_scope("attn_out"):
                o = jnp.einsum("shk,hkd->sd", a, blk["attn"]["wo"])
        hs, counts = ffn(blk, hs + o, cfg, valid, counts)
    return hs, counts, kv, state


def decode_step(cfg, params, kv, state, tokens, positions, page_tables,
                write_page, write_off, lengths, rows):
    """One token step for a padded slot set -> (``[next tokens...,
    counts...]`` int32, the updated pools).  Padding slots carry ``length
    == 0``, write to the scratch page and the scratch row, reach no
    expert, and the read's dead-row guard zeroes their attention."""
    hs, counts, kv, state = _decode_layers(
        cfg, params, kv, state, tokens, positions, page_tables, write_page,
        write_off, lengths, rows)
    nxt = jnp.argmax(logits(params, hs, cfg), -1).astype(jnp.int32)
    return jnp.concatenate([nxt, counts]), kv, state


def observe_step(counts, at, lengths=None, page_size=None):
    """The counts behind a step's tokens -> the registry
    (``blocks.observe_routing``).  A decode step passes its slots'
    ``lengths`` (host values, zeros for padding) and stamps their sum,
    the live positions its K/V read covers in each attention layer, on
    ``decode.kv.live_positions``."""
    from dist_keras_tpu.observability import metrics

    observe_routing(counts, at, decode=lengths is not None)
    if lengths is not None:
        metrics.histogram("decode.kv.live_positions").observe(
            int(lengths.sum()), at=at)


class Lfm2MoeDecoder(FamilyDecoder):
    family = sys.modules[__name__]
    config = staticmethod(lfm2_moe_config)
    name = "lfm2_moe_decoder"
