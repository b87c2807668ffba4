"""Latent-attention, sparse-expert decoder (the DeepseekV3 block family).

A second block family beside ``models/transformer.py``, defined once and
entered three ways over the same functions: :func:`forward` (a whole
sequence, no cache: parity tests, a later training step),
:func:`prefill_step` (one padded prompt, writes the latent pool) and
:func:`decode_step` (one token a slot, reads it).  ``DecodeEngine`` takes
the two steps and :func:`cache_pools` from here when the model's
``cfg["family"]`` says ``"mla_moe"``.

Per layer ``h <- h + Attn(RMSNorm(h))``, ``h <- h + FFN(RMSNorm(h))``;
final RMSNorm, untied head; the input is a row of the embedding table.

*Latent attention (MLA, no low-rank query step).*  ``q = x Wq`` is
``heads x (nope | rope)``; ``x Wkv_a`` is ``c_raw (rank) | k_pe_raw
(rope)``; ``c = RMSNorm(c_raw)``; rotary positions turn ``q_pe`` (every
head) and ``k_pe_raw`` (one row shared by all heads).  **The cache entry
is ``c | k_pe``**, ``rank + rope`` values a token a layer, in ONE pool
whose rows are padded with zeros to whole lanes (:data:`LANES`: 576 ->
640).  The chip's tiling pads such a row to 640 lanes wherever it is the
minor dimension, and left at 576 the runtime's default layout makes the
page dimension minor instead, so that every step converts the whole pool
on the way in and on the way out (two 4.4 GB copies a step, AOT for a
v5e, PR 27).
``kv_b_proj`` is kept as its two halves ``w_uk`` / ``w_uv`` ``(rank,
heads, 128)``.  Prefill rebuilds ``k_nope = c w_uk`` and ``v = c w_uv``
for its own positions and attends with query/key width ``nope + rope``
and value width ``v``; decoding runs the absorbed form over the cache
(``q~ = q_nope w_uk^T``, scores ``(q~ . c + q_pe . k_pe) / sqrt(nope +
rope)``, ``o = (sum p c) w_uv``) and never rebuilds keys or values of
cached positions.  The rotary slice is de-interleaved (even elements,
then odd) and rotated by halves, as the published ``modeling_deepseek.py``
does.

*Feed-forward.*  The first ``first_k_dense`` layers are one SwiGLU; the
others route: ``s = sigmoid(x Wg)`` in float32 at "highest" precision (a
near-tie is not decided by bfloat16 rounding), the top ``k`` of ``s + b``
are chosen (``b``: the selection bias, a parameter), their weights are
the chosen ``s`` WITHOUT ``b``, divided by their sum, times
``routed_scaling_factor``.  No capacity and no dropped token.

*The share.*  The layer is told which experts it ``held`` (consecutive
ids of the published count): it routes over all of them, normalises over
all ``k`` chosen and adds only the held experts' terms plus the shared
expert.  What absent experts would add is left out; nothing stands in
for the other chips.  The held experts run as a masked dense pass over
every token in a call of up to ``GROUPED_OVER`` tokens, and over the
chosen pairs sorted by expert in a longer one (the expert layer is
``models/blocks.py``'s, which two more families run: ``held_experts``
there says why).

Both steps return, behind their tokens, a few routing counts in the same
small int32 array (``blocks.N_COUNTS`` past the per-expert ones; a prefill
of the grouped form one more, ``blocks.zero_counts``), which
:func:`observe_step` turns into the ``decode.moe.*`` / ``decode.latent.*``
instruments.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

from dist_keras_tpu.models import blocks
from dist_keras_tpu.models.blocks import (
    FamilyDecoder,
    ffn,
    logits,
    observe_routing,
    rms_norm,
    rope,
    swiglu_params,
    zero_counts,
)
from dist_keras_tpu.models.layers import glorot_uniform
from dist_keras_tpu.ops.pallas.decode_attention import (
    latent_attention_auto,
    latent_walked_positions,
)
from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

FAMILY = "mla_moe"
LANES = 128


def mla_moe_config(vocab_size, seq_len, d_model, n_heads, qk_nope_head_dim,
                   qk_rope_head_dim, v_head_dim, kv_lora_rank, d_ff,
                   moe_d_ff, n_routed_experts, n_shared_experts, top_k,
                   n_layers, first_k_dense=1, held_experts=None,
                   routed_scaling_factor=1.0, rope_theta=10000.0,
                   rms_norm_eps=1e-5):
    """``seq_len`` is how many positions one sequence may hold (a slot's
    page table in the engine): rotary positions need no table.
    ``held_experts``: consecutive ids of the routed experts computed
    here (default: all of them)."""
    held = (list(range(n_routed_experts)) if held_experts is None
            else [int(e) for e in held_experts])
    if not held or held != list(range(held[0], held[0] + len(held))) \
            or held[0] < 0 or held[-1] >= n_routed_experts:
        raise ValueError(
            f"held_experts={held!r} must be consecutive ids in "
            f"[0, {n_routed_experts})")
    if top_k > n_routed_experts:
        raise ValueError(f"top_k={top_k} > {n_routed_experts} experts")
    return {
        "family": FAMILY,
        "vocab_size": int(vocab_size),
        "seq_len": int(seq_len),
        "d_model": int(d_model),
        "n_heads": int(n_heads),
        "qk_nope_head_dim": int(qk_nope_head_dim),
        "qk_rope_head_dim": int(qk_rope_head_dim),
        "v_head_dim": int(v_head_dim),
        "kv_lora_rank": int(kv_lora_rank),
        "d_ff": int(d_ff),
        "moe_d_ff": int(moe_d_ff),
        "n_routed_experts": int(n_routed_experts),
        "n_shared_experts": int(n_shared_experts),
        "top_k": int(top_k),
        "n_layers": int(n_layers),
        "first_k_dense": int(first_k_dense),
        "held_experts": held,
        "routed_scaling_factor": float(routed_scaling_factor),
        "rope_theta": float(rope_theta),
        "rms_norm_eps": float(rms_norm_eps),
    }


def vocab(cfg):
    """The vocabulary a decoder of ``cfg`` reads and writes."""
    return int(cfg["vocab_size"])


def step_width(cfg):
    """Positions a slot a step: one token."""
    return 1


def cache_pools(cfg):
    """What the engine allocates: the one latent pool, paged, a row a
    cached position in every layer: the normalised latent and the rotated
    shared key, in a row of whole lanes."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return ((cfg["n_layers"], "page", (-(-width // LANES) * LANES,)),)


def _pad_lanes(x, cfg):
    """``(..., rank + rope)`` -> the pool's row width, zeros behind."""
    pad = cache_pools(cfg)[0][2][0] - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def init_layer_params(key, cfg, layer):
    """One layer's leaves, a function of (key, layer) alone."""
    d, h = cfg["d_model"], cfg["n_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    kq, ka, kuk, kuv, ko, kf, kr, kb, ke, ks = jax.random.split(
        jax.random.fold_in(key, 1 + layer), 10)
    blk = {
        "attn_norm": jnp.ones((d,)),
        "wq": glorot_uniform(kq, (d, h, nope + rope)),
        "wkv_a": glorot_uniform(ka, (d, rank + rope)),
        "kv_norm": jnp.ones((rank,)),
        "w_uk": glorot_uniform(kuk, (rank, h, nope)),
        "w_uv": glorot_uniform(kuv, (rank, h, dv)),
        "wo": glorot_uniform(ko, (h, dv, d)),
        "ffn_norm": jnp.ones((d,)),
    }
    if layer < cfg["first_k_dense"]:
        blk["mlp"] = swiglu_params(kf, d, cfg["d_ff"])
        return blk
    n_all, n_held = cfg["n_routed_experts"], len(cfg["held_experts"])
    blk["moe"] = {
        "router": glorot_uniform(kr, (d, n_all)),
        # small and not zero, so that selection (s + b) and weighting (s)
        # differ
        "router_bias": jax.random.uniform(kb, (n_all,), jnp.float32,
                                          -0.02, 0.02),
        "experts": swiglu_params(ke, d, cfg["moe_d_ff"], (n_held,)),
        "shared": swiglu_params(
            ks, d, cfg["n_shared_experts"] * cfg["moe_d_ff"]),
    }
    return blk


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
def _query_and_entry(blk, y, positions, cfg):
    """-> (q_nope (T, H, nope), q_pe rotated (T, H, rope), the cache
    entry ``c | k_pe`` (T, rank + rope))."""
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    with jax.named_scope("mla_q"):
        q = jnp.einsum("td,dhk->thk", y, blk["wq"])
        q_pe = rope(q[..., nope:], positions, cfg["rope_theta"])
    with jax.named_scope("latent_write"):
        a = y @ blk["wkv_a"]
        c = rms_norm(blk["kv_norm"], a[:, :rank], cfg["rms_norm_eps"])
        k_pe = rope(a[:, None, rank:], positions, cfg["rope_theta"])[:, 0]
        entry = jnp.concatenate([c, k_pe], -1)
    return q[..., :nope], q_pe, entry


def _attend_sequence(blk, q_nope, q_pe, entry, cfg):
    """Causal attention of one whole sequence over its own positions,
    keys and values rebuilt from the latent -> (T, H, v)."""
    rank = cfg["kv_lora_rank"]
    c, k_pe = entry[:, :rank], entry[:, rank:]
    k_nope = jnp.einsum("tc,chn->thn", c, blk["w_uk"])
    v = jnp.einsum("tc,chv->thv", c, blk["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], q_pe.shape)], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    return attention_auto(q[None], k[None], v[None], causal=True,
                          scale=q.shape[-1] ** -0.5)[0]


def _sequence_layer(blk, hs, valid, counts, positions, cfg, write):
    """One layer over a whole sequence -> (hidden (T, d), counts);
    ``write(entry)`` takes the layer's cache entries."""
    y = rms_norm(blk["attn_norm"], hs, cfg["rms_norm_eps"])
    q_nope, q_pe, entry = _query_and_entry(blk, y, positions, cfg)
    write(entry)
    with jax.named_scope("attend"):
        a = _attend_sequence(blk, q_nope, q_pe, entry, cfg)
    with jax.named_scope("attn_out"):
        hs = hs + jnp.einsum("thv,hvd->td", a, blk["wo"])
    return ffn(blk, hs, cfg, valid, counts)


def _sequence_layers(params, tokens, valid, cfg, write):
    """The layers over one whole sequence -> (hidden (T, d), counts);
    ``write(layer, entry)`` takes each layer's cache entries."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    counts = zero_counts(cfg, tokens.shape[0])

    # read where it is stated, not copied: the expert layer and the counts
    # go by the same value
    if tokens.shape[0] <= blocks.GROUPED_OVER:
        for li, blk in enumerate(params["blocks"]):
            hs, counts = _sequence_layer(blk, hs, valid, counts, positions,
                                         cfg, functools.partial(write, li))
        return hs, counts

    # the grouped form takes longer to trace and to lower than the dense
    # pass and its program longer to load from the compile cache (a warm
    # set-up of ``kimivl_serve_longgen`` 8.1 s over the parent's 63.2 with
    # this loop unrolled, PERF.md, PR 47): layers of one structure are
    # traced and lowered once, as a step does its latent read
    # (``latent_attention_kernel``).  The shorter programs stay as they
    # were, text and all
    @jax.jit
    def layer(blk, hs, counts):
        entries = []
        hs, counts = _sequence_layer(blk, hs, valid, counts, positions, cfg,
                                     entries.append)
        return hs, counts, entries[0]

    for li, blk in enumerate(params["blocks"]):
        hs, counts, entry = layer(blk, hs, counts)
        write(li, entry)
    return hs, counts


# -- the three entry points ---------------------------------------------
def forward(params, tokens, cfg):
    """One whole sequence ``tokens (T,)``, no cache -> logits (T, vocab)."""
    hs, _ = _sequence_layers(params, tokens,
                             jnp.ones(tokens.shape, bool), cfg,
                             lambda li, entry: None)
    return logits(params, hs, cfg)


def prefill_step(cfg, params, pool, tokens, length, page_idx, page_off):
    """One padded prompt -> (``[first token, counts...]`` int32, the
    updated pool).  Positions past ``length`` write to the scratch page
    (``page_idx`` routes them there), reach no held expert and never
    influence position ``length - 1`` under the causal mask."""
    pools = [pool]

    def write(li, entry):
        # the scattered dimensions are the pool's major ones: in place on
        # the donated pool
        pools[0] = pools[0].at[li, page_idx, page_off].set(
            _pad_lanes(entry, cfg))

    valid = jnp.arange(tokens.shape[0]) < length
    hs, counts = _sequence_layers(params, tokens, valid, cfg, write)
    first = jnp.argmax(logits(params, hs[length - 1], cfg))
    return jnp.concatenate([first.astype(jnp.int32)[None], counts]), pools[0]


def _decode_layers(cfg, params, pool, tokens, positions, page_tables,
                   write_page, write_off, lengths):
    """The layers of one token step over the paged latent pool ->
    (hidden (S, d), counts, the updated pool)."""
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    valid = lengths > 0
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    counts = zero_counts(cfg)
    for li, blk in enumerate(params["blocks"]):
        y = rms_norm(blk["attn_norm"], hs, eps)
        q_nope, q_pe, entry = _query_and_entry(blk, y, positions, cfg)
        with jax.named_scope("mla_q"):
            # absorbed: the query meets the latent itself, so cached keys
            # are never rebuilt
            q = _pad_lanes(jnp.concatenate(
                [jnp.einsum("shn,chn->shc", q_nope, blk["w_uk"]), q_pe],
                -1), cfg)
        with jax.named_scope("latent_write"):
            pool = pool.at[li, write_page, write_off].set(
                _pad_lanes(entry, cfg))
        with jax.named_scope("attend_latent"):
            # the whole pool viewed flat over (layer, page), the page ids
            # offset to this layer's: ``pool[li]`` would copy the layer
            o = latent_attention_auto(
                q, pool.reshape(-1, *pool.shape[2:]),
                page_tables + li * pool.shape[1], lengths, rank=rank,
                scale=scale)
        with jax.named_scope("attn_out"):
            a = jnp.einsum("shc,chv->shv", o, blk["w_uv"])
            hs = hs + jnp.einsum("shv,hvd->sd", a, blk["wo"])
        hs, counts = ffn(blk, hs, cfg, valid, counts)
    return hs, counts, pool


def decode_step(cfg, params, pool, tokens, positions, page_tables,
                write_page, write_off, lengths):
    """One token step for a padded slot set -> (``[next tokens...,
    counts...]`` int32, the updated pool).  Padding slots carry
    ``length == 0``, write to the scratch page, reach no held expert, and
    the latent read's dead-row guard zeroes their output."""
    hs, counts, pool = _decode_layers(
        cfg, params, pool, tokens, positions, page_tables, write_page,
        write_off, lengths)
    nxt = jnp.argmax(logits(params, hs, cfg), -1).astype(jnp.int32)
    return jnp.concatenate([nxt, counts]), pool


def observe_step(counts, at, lengths=None, page_size=None):
    """The counts behind a step's tokens -> the registry.  A decode step
    passes its slots' ``lengths`` (host values, zeros for padding) and
    the ``page_size`` and adds one sample to each per-step histogram,
    stamped ``at`` like ``decode.step_s``: the live cached positions,
    and the positions the read's blocks of pages fetch for them; a
    prefill adds its pairs and the form its expert layers took."""
    from dist_keras_tpu.observability import metrics

    observe_routing(counts, at, decode=lengths is not None)
    if lengths is None:
        return
    metrics.histogram("decode.latent.live_positions").observe(
        int(lengths.sum()), at=at)
    metrics.histogram("decode.latent.walked_positions").observe(
        latent_walked_positions(lengths, page_size), at=at)


class LatentMoEDecoder(FamilyDecoder):
    family = sys.modules[__name__]
    config = staticmethod(mla_moe_config)
    name = "latent_moe_decoder"
