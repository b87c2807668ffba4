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
every token in a call of up to :data:`GROUPED_OVER` tokens, and over the
chosen pairs sorted by expert in a longer one (:func:`held_experts` says
why).

Both steps return, behind their tokens, a few routing counts in the same
small int32 array (:data:`N_COUNTS` past the per-expert ones; a prefill
of the grouped form one more, :func:`_zero_counts`), which
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
    operand_dtype,
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


# A call of more than this many tokens runs its held experts over the
# chosen pairs sorted by expert, a shorter one over every token.  One bare
# layer on a v5e, dense | grouped, ms (builders' chip runs, PR 45 and 46,
# the grouped side through ``megablox.gmm``, which the plain loops below
# trail by a quarter of its gain):
#   tokens   8 held of 64, top 6,   32 held of 32, top 4,   16 held of 128,
#            d 2048 f 1408          d 2048 f 1792           top 8, f 768
#   1,024    1.24 | 1.26            5.68 | 6.17             1.42 | 1.52
#   2,560    2.89 | 1.50            14.84 | 8.27            3.77 | 1.68
#   4,096    5.17 | 2.04
# The dense pass leads or ties at 1,024 at all three shapes and trails at
# 2,560, so one rule serves them: every decode step and every prefill rung
# of 1,024 or fewer stays the dense program.  Not a knob: nothing the
# benchmark runs lies between 1,024 and 2,560.
GROUPED_OVER = 1024

# Rows of one expert's sorted pairs a pass takes through its products.  A
# whole nine-layer prefill of ``kimivl_serve_longgen`` at 256 rows a pass,
# dense | grouped, ms (builder's chip run, PR 46): 44.2 | 41.65 at the
# 2,560 rung, 81.6 | 65.42 at 4,096, 125.7 | 102.50 at 6,144.  A prefill
# of 512 rows a pass never came back on the chip (the same session).
GROUP_TILE_ROWS = 256


def _held_dense(experts, x, w, chosen):
    """Every held expert over every token, each token's result weighted
    by its routing weight for that expert, zero where ``chosen (N, k,
    held)`` says the expert was not."""
    gate = jnp.sum(jnp.where(chosen, w[..., None], 0.0), 1)   # (N, held)
    hidden = (jax.nn.silu(jnp.einsum("nd,edf->enf", x, experts["w_gate"]))
              * jnp.einsum("nd,edf->enf", x, experts["w_up"]))
    ys = jnp.einsum("enf,efd->end", hidden, experts["w_down"])
    return jnp.einsum("end,ne->nd", ys, gate.astype(ys.dtype))


def _held_grouped(experts, x, w, group, sizes):
    """The pairs whose ``group (N, k)`` is a held expert (the others carry
    ``len(sizes)``), sorted by it; then expert by expert, and within one
    :data:`GROUP_TILE_ROWS` sorted pairs at a time: the tokens' rows
    gathered, the expert's three products, each pair's result times its
    routing weight added to its token's row.  The work is the
    ``sum(sizes)`` counted pairs (in whole passes), whatever the static
    ``N x k``; two loops of plain products and no kernel call, so that
    the program stays the size of the dense one and loads from the
    compile cache as fast (PERF.md, PR 45-47)."""
    (n, k), rows_a_pass = group.shape, GROUP_TILE_ROWS
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # a counting sort: a pair's place is its group's first row plus the
    # pairs of its group ahead of it, so a group's tokens stay in order.
    # The last group (experts not held, padding tokens) lies behind the
    # held pairs and nothing visits it.  One pass of rows more than the
    # pairs, so that no slice below is moved back from the end; a row past
    # the pairs reads pair 0
    mine = group.reshape(-1) == jnp.arange(sizes.shape[0] + 1)[:, None]
    ahead = jnp.cumsum(mine, 1, dtype=jnp.int32) - 1
    first = jnp.concatenate([starts, ends[-1:]])[:, None]
    place = jnp.sum(jnp.where(mine, first + ahead, 0), 0)
    order = jnp.zeros((n * k + rows_a_pass,), jnp.int32).at[place].set(
        jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
    token, weight = order // k, w.reshape(-1)[order]
    # the rounding the dense product's one MXU pass gives the same values
    # (float32 when a caller asked for "highest").  Behind a barrier with
    # the layer's own input, or the compiler converts all the held experts
    # in front of the loop
    operand = operand_dtype()
    experts, x = jax.lax.optimization_barrier((experts, x))
    x = x.astype(operand)

    def one_expert(e, out):
        gate, up, down = (
            jax.lax.dynamic_index_in_dim(experts[name], e, keepdims=False)
            .astype(operand) for name in ("w_gate", "w_up", "w_down"))

        def one_pass(carry):
            i, out = carry
            lo = starts[e] + i * rows_a_pass
            rows = jax.lax.dynamic_slice_in_dim(token, lo, rows_a_pass)
            xs = x[rows]
            hidden = (jax.nn.silu(jnp.dot(
                xs, gate, preferred_element_type=jnp.float32)) * jnp.dot(
                    xs, up, preferred_element_type=jnp.float32))
            ys = jnp.dot(hidden.astype(operand), down,
                         preferred_element_type=jnp.float32)
            ys = ys * jax.lax.dynamic_slice_in_dim(
                weight, lo, rows_a_pass)[:, None]
            # the rows behind the expert's last pair are another expert's
            # or nobody's (a padding token's, whose content is anything):
            # selected away, not multiplied by a zero weight (0 x NaN)
            live = i * rows_a_pass + jnp.arange(rows_a_pass) < sizes[e]
            return i + 1, out.at[rows].add(jnp.where(live[:, None], ys, 0.0))

        return jax.lax.while_loop(
            lambda carry: carry[0] * rows_a_pass < sizes[e], one_pass,
            (jnp.int32(0), out))[1]

    return jax.lax.fori_loop(
        0, sizes.shape[0], one_expert,
        jnp.zeros((n, experts["w_down"].shape[2]), jnp.float32))


def held_experts(experts, x, idx, w, first_held, valid):
    """The held experts' part of the routed sum for tokens ``x (N, d)``
    -> (``(N, d)``, pairs on each held expert ``(n_held,)`` int32).

    Every chosen pair whose expert is held is computed and none can be
    dropped (no capacity; a padding token, ``valid`` false, has no pair),
    in one of two forms of the same sum, picked by the call's static
    ``N``:

    *Up to* :data:`GROUPED_OVER` *tokens, a masked dense pass*
    (:func:`_held_dense`): a decode step reads the held experts' weights
    either way, a short prefill's products are too small for the sort and
    the gathers to pay, and a step's time does not depend on where the
    router sent its tokens.

    *Over it, the pairs sorted by expert* (:func:`_held_grouped`): an
    expert at a time, its pairs :data:`GROUP_TILE_ROWS` at a time through
    plain products, the gathered rows and the expert in the type the dense
    product's one MXU pass rounds them to, float32 accumulation, each
    pair's result times its routing weight added to its token's row.  The
    router sends a token to ``top_k`` of all the experts and few are held
    (0.75 pairs a token where the dense pass computes 8,
    ``kimivl_serve_longgen``): the work is the counted pairs, and no
    buffer is sized by a capacity.  The result differs from the dense
    form's by the order of a token's at most ``top_k`` additions (and by
    the dense combine's own bfloat16 pass).  The tables beside
    :data:`GROUPED_OVER` and :data:`GROUP_TILE_ROWS` are why the rule is
    what it is.  jax's own grouped products are not on this path:
    ``jax.lax.ragged_dot`` lost to the dense pass at every size (PERF.md,
    PR 27), and ``megablox.gmm`` ran these pairs a quarter faster than the
    loops do in a program half as large again, which took twice as long
    to load from the compile cache (PERF.md, PR 45 and 46)."""
    n_held = experts["w_gate"].shape[0]
    local = idx - first_held
    here = (local >= 0) & (local < n_held) & valid[:, None]
    chosen = here[..., None] & (local[..., None] == jnp.arange(n_held))
    if x.shape[0] > GROUPED_OVER:
        sizes = jnp.sum(chosen, (0, 1), dtype=jnp.int32)
        # a pair not computed here joins a last group that nothing visits
        return _held_grouped(experts, x, w, jnp.where(here, local, n_held),
                             sizes), sizes
    # the sum, then the counts: the order the pinned programs were lowered
    # in (tests/test_lowered_text.py)
    return (_held_dense(experts, x, w, chosen),
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


def add_counts(counts, c):
    """A layer's routing counts ``c`` onto its pass's ``counts``.  A
    prefill of the grouped form keeps one slot more
    (:func:`_zero_counts`): the rows its layers' passes covered (whole
    passes of :data:`GROUP_TILE_ROWS`, an expert's last one as full as
    its pairs make it), reckoned here from the layer's pairs on each held
    expert and taken off a slot that starts at -1."""
    if counts.shape[0] > c.shape[0]:
        passes = jnp.sum(-(-c[:-N_COUNTS] // GROUP_TILE_ROWS))
        c = jnp.concatenate([c, -GROUP_TILE_ROWS * passes[None]])
    return counts + c


def ffn(blk, x, cfg, valid, counts):
    y = rms_norm(blk["ffn_norm"], x, cfg["rms_norm_eps"])
    if "mlp" in blk:
        with jax.named_scope("mlp"):
            return x + swiglu(blk["mlp"], y), counts
    out, c = moe_layer(blk["moe"], y, cfg, valid)
    return x + out, add_counts(counts, c)


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


def _zero_counts(cfg, sequence=0):
    """What a pass's routing counts start from.  A whole-sequence pass
    (a prefill) says how many tokens its ``sequence`` holds: over
    :data:`GROUPED_OVER` its expert layers take the grouped form and one
    slot more rides behind the others, ``-1 - rows covered``
    (:func:`add_counts`): below zero, as no count is, so that the host
    tells the form from the counts alone (:func:`observe_routing`).  A
    decode step's counts never grow, whatever its rung (the engine
    carries one width)."""
    zeros = jnp.zeros((len(cfg["held_experts"]) + N_COUNTS,), jnp.int32)
    if sequence > GROUPED_OVER:
        return jnp.concatenate([zeros, jnp.full((1,), -1, jnp.int32)])
    return zeros


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
    counts = _zero_counts(cfg, tokens.shape[0])

    if tokens.shape[0] <= GROUPED_OVER:
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
    instruments: the pairs of every step and prefill; for a ``decode``
    step one sample of each per-step histogram, stamped ``at`` like
    ``decode.step_s``; for a prefill a sample of
    ``decode.moe.prefill_grouped``, stamped like ``decode.prefill_s``,
    and where its program is the grouped form (it says so itself: one
    slot more behind the counts, below zero, :func:`_zero_counts`) one of
    ``decode.moe.tile_fill_pct``."""
    from dist_keras_tpu.observability import metrics

    counts = np.asarray(counts)
    grouped = not decode and counts[-1] < 0
    if grouped:
        counts, covered = counts[:-1], -1 - int(counts[-1])
    held, hit, total = counts[:-N_COUNTS], counts[-2], counts[-1]
    metrics.counter("decode.moe.pairs_total").inc(int(total))
    metrics.counter("decode.moe.pairs_held").inc(int(held.sum()))
    if not decode:
        metrics.histogram("decode.moe.prefill_grouped").observe(
            100.0 * grouped, at=at)
        if grouped and covered:
            metrics.histogram("decode.moe.tile_fill_pct").observe(
                100.0 * int(held.sum()) / covered, at=at)
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
    prefill adds its pairs and the form its expert layers took."""
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
