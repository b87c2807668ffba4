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
every token (:func:`held_experts` says why).

Both steps return, behind their tokens, a few routing counts in the same
small int32 array (:data:`N_COUNTS` past the per-expert ones), which
:func:`observe_step` turns into the ``decode.moe.*`` / ``decode.latent.*``
instruments.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.layers import glorot_uniform, select_top_k
from dist_keras_tpu.ops.pallas.decode_attention import (
    latent_attention_auto,
    latent_walked_positions,
)
from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

FAMILY = "mla_moe"
LANES = 128
# behind a step's tokens: pairs on each held expert, then expert layers'
# (layer, held expert) cells that received a token, then all chosen pairs
N_COUNTS = 2


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


def cache_entry_shapes(cfg):
    """The trailing shape of each pool a replica holds: one pool whose
    entry is the normalised latent and the rotated shared key, in a row
    of whole lanes."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return ((-(-width // LANES) * LANES,),)


def step_width(cfg):
    """Positions a slot a step: one token."""
    return 1


def cache_pools(cfg):
    """What the engine allocates: the one latent pool, paged, a row a
    cached position in every layer."""
    return tuple((cfg["n_layers"], "page", entry)
                 for entry in cache_entry_shapes(cfg))


def _pad_lanes(x, cfg):
    """``(..., rank + rope)`` -> the pool's row width, zeros behind."""
    pad = cache_entry_shapes(cfg)[0][0] - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _swiglu_params(key, d, f, lead=()):
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": glorot_uniform(kg, lead + (d, f)),
            "w_up": glorot_uniform(ku, lead + (d, f)),
            "w_down": glorot_uniform(kd, lead + (f, d))}


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
        blk["mlp"] = _swiglu_params(kf, d, cfg["d_ff"])
        return blk
    n_all, n_held = cfg["n_routed_experts"], len(cfg["held_experts"])
    blk["moe"] = {
        "router": glorot_uniform(kr, (d, n_all)),
        # small and not zero, so that selection (s + b) and weighting (s)
        # differ
        "router_bias": jax.random.uniform(kb, (n_all,), jnp.float32,
                                          -0.02, 0.02),
        "experts": _swiglu_params(ke, d, cfg["moe_d_ff"], (n_held,)),
        "shared": _swiglu_params(
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
def rms_norm(w, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def rope(x, positions, theta):
    """Rotary positions on ``x (T, heads, d)`` at ``positions (T,)``:
    the slice de-interleaved (even elements, then odd), then rotated by
    halves."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def route(moe, x, cfg):
    """-> (expert ids (N, k), weights (N, k) float32) over ALL the routed
    experts, held here or not.  The chosen scores are divided by their
    sum plus ``cfg["route_norm_eps"]`` (this family's published code:
    1e-20, the default; ``models/lfm2_moe.py`` states its own 1e-6)."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), moe["router"].astype(jnp.float32),
        precision="highest"))
    idx, w = select_top_k(s, moe["router_bias"], cfg["top_k"])
    w = w / (jnp.sum(w, -1, keepdims=True)
             + cfg.get("route_norm_eps", 1e-20))
    return idx, w * cfg["routed_scaling_factor"]


def held_experts(experts, x, idx, w, first_held, valid):
    """The held experts' part of the routed sum for tokens ``x (N, d)``
    -> (``(N, d)``, pairs on each held expert ``(n_held,)`` int32).

    A masked dense pass: every held expert over every token, each
    token's result weighted by its routing weight for that expert, zero
    where the expert was not chosen (or the token is padding, ``valid``
    false).  So every chosen pair whose expert is held is computed, none
    can be dropped, and a step's time does not depend on where the router
    sent its tokens.  On a v5e it is also the faster form at both ends
    (PERF.md, PR 27): a decode step reads the held experts' weights
    either way, and a 4,096-token prefill spends 4.8 ms a layer here
    against 6.5 ms for pairs sorted by expert into ``jax.lax.ragged_dot``
    groups, whose gathers of all 6 pairs a token cost as much as the
    products they save."""
    n_held = experts["w_gate"].shape[0]
    local = idx - first_held
    here = (local >= 0) & (local < n_held) & valid[:, None]
    chosen = here[..., None] & (local[..., None] == jnp.arange(n_held))
    gate = jnp.sum(jnp.where(chosen, w[..., None], 0.0), 1)   # (N, held)
    hidden = (jax.nn.silu(jnp.einsum("nd,edf->enf", x, experts["w_gate"]))
              * jnp.einsum("nd,edf->enf", x, experts["w_up"]))
    ys = jnp.einsum("enf,efd->end", hidden, experts["w_down"])
    return (jnp.einsum("end,ne->nd", ys, gate.astype(ys.dtype)),
            jnp.sum(chosen, (0, 1), dtype=jnp.int32))


def moe_layer(moe, x, cfg, valid, router=route):
    """-> (the layer's output for ``x (N, d)``, routing counts).  A
    layer whose ``moe`` holds no ``"shared"`` has no shared expert;
    ``router`` is the family's (``models/sdar_moe.py`` routes by a
    softmax)."""
    with jax.named_scope("moe_route"):
        idx, w = router(moe, x, cfg)
    with jax.named_scope("moe_experts"):
        y, sizes = held_experts(moe["experts"], x, idx, w,
                                cfg["held_experts"][0], valid)
    if "shared" in moe:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(moe["shared"], x)
    total = jnp.sum(valid, dtype=jnp.int32) * cfg["top_k"]
    return y, jnp.concatenate([
        sizes, jnp.stack([jnp.sum(sizes > 0, dtype=jnp.int32), total])])


def ffn(blk, x, cfg, valid, counts):
    y = rms_norm(blk["ffn_norm"], x, cfg["rms_norm_eps"])
    if "mlp" in blk:
        with jax.named_scope("mlp"):
            return x + swiglu(blk["mlp"], y), counts
    out, c = moe_layer(blk["moe"], y, cfg, valid)
    return x + out, counts + c


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


def _zero_counts(cfg):
    return jnp.zeros((len(cfg["held_experts"]) + N_COUNTS,), jnp.int32)


def _sequence_layers(params, tokens, valid, cfg, write):
    """The layers over one whole sequence -> (hidden (T, d), counts);
    ``write(layer, entry)`` takes each layer's cache entries."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    counts = _zero_counts(cfg)
    for li, blk in enumerate(params["blocks"]):
        y = rms_norm(blk["attn_norm"], hs, cfg["rms_norm_eps"])
        q_nope, q_pe, entry = _query_and_entry(blk, y, positions, cfg)
        write(li, entry)
        with jax.named_scope("attend"):
            a = _attend_sequence(blk, q_nope, q_pe, entry, cfg)
        with jax.named_scope("attn_out"):
            hs = hs + jnp.einsum("thv,hvd->td", a, blk["wo"])
        hs, counts = ffn(blk, hs, cfg, valid, counts)
    return hs, counts


def _logits(params, hs, cfg):
    with jax.named_scope("head"):
        # behind a barrier: for a few rows the compiler otherwise folds
        # the norm's weight into the head and scales all of the head's
        # vocabulary x width every step (1.3 GB written and read again)
        return jax.lax.optimization_barrier(rms_norm(
            params["norm_f"], hs, cfg["rms_norm_eps"])) @ params["head"]


# -- the three entry points ---------------------------------------------
def forward(params, tokens, cfg):
    """One whole sequence ``tokens (T,)``, no cache -> logits (T, vocab)."""
    hs, _ = _sequence_layers(params, tokens,
                             jnp.ones(tokens.shape, bool), cfg,
                             lambda li, entry: None)
    return _logits(params, hs, cfg)


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
    first = jnp.argmax(_logits(params, hs[length - 1], cfg))
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
    counts = _zero_counts(cfg)
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
    nxt = jnp.argmax(_logits(params, hs, cfg), -1).astype(jnp.int32)
    return jnp.concatenate([nxt, counts]), pool


def observe_routing(counts, at, decode):
    """The routing counts behind a step's tokens -> the ``decode.moe.*``
    instruments: the pairs of every step and prefill, and for a
    ``decode`` step one sample of each per-step histogram, stamped ``at``
    like ``decode.step_s``."""
    from dist_keras_tpu.observability import metrics

    counts = np.asarray(counts)
    held, hit, total = counts[:-N_COUNTS], counts[-2], counts[-1]
    metrics.counter("decode.moe.pairs_total").inc(int(total))
    metrics.counter("decode.moe.pairs_held").inc(int(held.sum()))
    if not decode:
        return
    metrics.histogram("decode.moe.experts_hit").observe(int(hit), at=at)
    if held.sum() > 0:
        metrics.histogram("decode.moe.load_max_over_mean").observe(
            held.max() / held.mean(), at=at)


def observe_step(counts, at, lengths=None, page_size=None):
    """The counts behind a step's tokens -> the registry.  A decode step
    passes its slots' ``lengths`` (host values, zeros for padding) and
    the ``page_size`` and adds one sample to each per-step histogram,
    stamped ``at`` like ``decode.step_s``: the live cached positions,
    and the positions the read's blocks of pages fetch for them; a
    prefill only adds its pairs."""
    from dist_keras_tpu.observability import metrics

    observe_routing(counts, at, decode=lengths is not None)
    if lengths is None:
        return
    metrics.histogram("decode.latent.live_positions").observe(
        int(lengths.sum()), at=at)
    metrics.histogram("decode.latent.walked_positions").observe(
        latent_walked_positions(lengths, page_size), at=at)


class LatentMoEDecoder:
    """Model-contract wrapper (cfg + params + weights round-trip) that the
    serialization layer and ``DecodeEngine`` take.  Weights are made from
    ``seed`` on first use, so a deserialized copy that is handed its
    weights never holds a second, random set."""

    def __init__(self, cfg=None, seed=0, **cfg_kw):
        self.cfg = cfg or mla_moe_config(**cfg_kw)
        self.name = "latent_moe_decoder"
        self._seed = seed
        self._params = None

    @property
    def params(self):
        if self._params is None:
            self._params = init_params(jax.random.PRNGKey(self._seed),
                                       self.cfg)
        return self._params

    def apply(self, params, tokens, *, training=False, rng=None):
        return forward(params, tokens, self.cfg)

    def __call__(self, tokens, *, training=False, rng=None):
        return self.apply(self.params, jnp.asarray(tokens))

    def set_params(self, params):
        self._params = jax.tree.map(jnp.asarray, params)

    def get_weights(self):
        return [np.asarray(leaf) for leaf in jax.tree.leaves(self.params)]

    def set_weights(self, weights):
        shapes = jax.eval_shape(
            functools.partial(init_params, cfg=self.cfg),
            jax.random.PRNGKey(0))
        self._params = jax.tree.unflatten(
            jax.tree.structure(shapes), [jnp.asarray(w) for w in weights])

    def to_json(self):
        return json.dumps({"class_name": "LatentMoEDecoder",
                           "config": self.cfg})
