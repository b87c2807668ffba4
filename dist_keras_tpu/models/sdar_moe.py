"""Block-diffusion, grouped-query, sparse-expert decoder (the ``sdar_moe``
block family: SDAR-30B-A3B-Chat's).

A fifth block family, and the first whose step does not yield one token a
sequence: it generates in BLOCKS of ``B = cfg["block_length"]`` positions.
Entered three ways over the same functions: :func:`forward` (a whole
sequence, no cache), :func:`prefill_step` (one padded prompt: commits the
K/V of its whole blocks) and :func:`decode_step` (one PASS over every
slot's open block).  ``DecodeEngine`` takes the steps, :func:`cache_pools`
and :func:`step_width` from here when the model's ``cfg["family"]`` says
``"sdar_moe"``.

*The layer.*  ``h <- h + Attn(RMSNorm(h))``, ``h <- h + MoE(RMSNorm(h))``;
the final RMSNorm and an untied head.  Attention is grouped-query (``q`` is
``heads x head_dim``, ``k`` and ``v`` ``kv_heads x head_dim``, K/V head
``i`` serving query heads ``g i .. g i + g - 1``; ``heads x head_dim`` need
not be ``d_model``), RMSNorm over each head's width on ``q`` and ``k``,
then rotary positions by halves (``blocks.qkv_normed_rotated``, which
``lfm2_moe`` shares).  **The
mask is BLOCK-causal:** position ``t`` sees ``s`` iff ``s // B <= t //
B``, so a block's positions all see one another.  Every layer routes:
``p = softmax(m W_r)`` over ALL the routed experts in float32 at
"highest" precision, the ``k`` largest taken and divided by their sum; no
selection bias, no scaling factor, no shared expert.  The chip holds
``held_experts`` (consecutive ids) and adds their part of the routed sum
alone, as ``blocks.held_experts`` computes it; nothing stands in for the
absent ones (``blocks.moe_layer`` with this family's :func:`route`).

*The cache.*  One paged pool of ``v | k`` rows (``2 x kv_heads x
head_dim`` values a position: 1,024 lanes at the published widths) over
every layer.  **A position's row is not written once.**  An open block's
``B`` rows are PROVISIONAL: every pass writes them anew from the block's
current tokens (mask ids among them) and reads them back with the
committed history; they become history at the block's COMMIT, the pass
over its final tokens.  ``B`` divides the page size, so a block never
straddles a page.

*A pass* (:func:`decode_step`).  For each ENTRY (a block of a sequence;
"slot" below): the open block's ``B``
tokens (fixed ones, and ``mask_token_id`` where none is fixed yet), where
the block starts, and how many masked positions this pass FIXES (0: the
pass commits).  It writes the ``B`` provisional rows, reads the slot's
pages once for its ``B x heads`` queries over ``start + B`` rows with
nothing masked inside the block (``blocks.attend_rows``, the query rows
head-major so that a K/V head's queries stay together), runs the held
experts over ``slots x B`` rows and the head at every block position, and
chooses ON THE DEVICE (:func:`unmask`): at each masked position the most
likely token other than the mask id and its probability, the ``fix``
most confident positions taking theirs.  Out come the block's tokens
after the pass and the expert layer's routing counts; a pass can therefore be
launched on its predecessor's output without the host seeing a token.

Generation (the engine's part, ``serving/decode.py``): a prefill commits
the prompt's whole blocks and yields no token; the open block holds the
prompt's tail and masks; ``cfg["denoising_steps"]`` passes of ``B /
steps`` positions each fix a block of ``B`` masks (fewer where the block
opened with fewer).  The block's COMMIT, a pass over its final tokens
that fixes nothing, needs no pass of its own: it rides as one entry of
the pass whose next entry is the sequence's NEXT block's first denoising
pass (same pages, ``start + B``, ``lengths = start + 2 B``).  Every
layer writes all entries' rows before its read, each entry reads its own
``lengths`` and the mask lets a block see every earlier one, so the new
block reads exactly the committed rows it would a pass later and the
commit never sees the new block's.  Only a sequence's last block, and a
commit whose pass has no entry to spare, take a pass alone.  Greedy
throughout.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.blocks import (
    FamilyDecoder,
    add_counts,
    attend_rows,
    logits,
    moe_layer,
    observe_routing,
    qkv_normed_rotated,
    rms_norm,
    swiglu_params,
    zero_counts,
)
from dist_keras_tpu.models.layers import glorot_uniform, select_top_k
from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

FAMILY = "sdar_moe"


def sdar_moe_config(vocab_size, seq_len, d_model, n_heads, n_kv_heads,
                    head_dim, moe_d_ff, n_routed_experts, top_k, n_layers,
                    held_experts=None, block_length=4, denoising_steps=4,
                    mask_token_id=None, rope_theta=1000000.0,
                    rms_norm_eps=1e-6):
    """``seq_len`` is how many positions one sequence may hold (a slot's
    page table in the engine), a whole number of blocks.
    ``held_experts``: consecutive ids of the routed experts computed here
    (default: all of them).  ``mask_token_id`` (default: the
    vocabulary's last id) stands at a block position no pass has fixed
    yet and is never chosen; ``denoising_steps`` divides
    ``block_length``: a pass fixes ``block_length / denoising_steps``
    positions."""
    held = (list(range(n_routed_experts)) if held_experts is None
            else [int(e) for e in held_experts])
    if not held or held != list(range(held[0], held[0] + len(held))) \
            or held[0] < 0 or held[-1] >= n_routed_experts:
        raise ValueError(
            f"held_experts={held!r} must be consecutive ids in "
            f"[0, {n_routed_experts})")
    if top_k > n_routed_experts:
        raise ValueError(f"top_k={top_k} > {n_routed_experts} experts")
    if n_heads % n_kv_heads or head_dim % 2:
        raise ValueError(
            f"n_kv_heads={n_kv_heads} must divide n_heads={n_heads}, and "
            f"head_dim={head_dim} be even")
    if block_length < 1 or denoising_steps < 1 \
            or block_length % denoising_steps or seq_len % block_length:
        raise ValueError(
            f"denoising_steps={denoising_steps} must divide "
            f"block_length={block_length}, and that seq_len={seq_len}")
    mask = vocab_size - 1 if mask_token_id is None else int(mask_token_id)
    if not 0 <= mask < vocab_size:
        raise ValueError(f"mask_token_id={mask} is outside the vocabulary")
    return {
        "family": FAMILY,
        "vocab_size": int(vocab_size),
        "seq_len": int(seq_len),
        "d_model": int(d_model),
        "n_heads": int(n_heads),
        "n_kv_heads": int(n_kv_heads),
        "head_dim": int(head_dim),
        "moe_d_ff": int(moe_d_ff),
        "n_routed_experts": int(n_routed_experts),
        "top_k": int(top_k),
        "n_layers": int(n_layers),
        "held_experts": held,
        "block_length": int(block_length),
        "denoising_steps": int(denoising_steps),
        "mask_token_id": mask,
        "rope_theta": float(rope_theta),
        "rms_norm_eps": float(rms_norm_eps),
    }


def vocab(cfg):
    """The vocabulary a decoder of ``cfg`` reads and writes."""
    return int(cfg["vocab_size"])


def step_width(cfg):
    """Positions a slot a step: a pass computes a whole block."""
    return int(cfg["block_length"])


def step_fixes(cfg):
    """-> (the id that stands at a block position no pass has fixed yet,
    the masked positions a denoising pass fixes)."""
    return (int(cfg["mask_token_id"]),
            cfg["block_length"] // cfg["denoising_steps"])


def cache_pools(cfg):
    """What the engine allocates: the one ``v | k`` pool, paged, a row a
    position in every layer."""
    return ((cfg["n_layers"], "page",
             (2 * cfg["n_kv_heads"] * cfg["head_dim"],)),)


def init_layer_params(key, cfg, layer):
    """One layer's leaves, a function of (key, layer) alone."""
    d, h, hk, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    kq, kk, kv, ko, kr, ke = jax.random.split(
        jax.random.fold_in(key, 1 + layer), 6)
    n_all, n_held = cfg["n_routed_experts"], len(cfg["held_experts"])
    return {
        "op_norm": jnp.ones((d,)),
        "attn": {
            "wq": glorot_uniform(kq, (d, h, hd)),
            "wk": glorot_uniform(kk, (d, hk, hd)),
            "wv": glorot_uniform(kv, (d, hk, hd)),
            "q_norm": jnp.ones((hd,)),
            "k_norm": jnp.ones((hd,)),
            "wo": glorot_uniform(ko, (h, hd, d)),
        },
        "ffn_norm": jnp.ones((d,)),
        "moe": {
            "router": glorot_uniform(kr, (d, n_all)),
            "experts": swiglu_params(ke, d, cfg["moe_d_ff"], (n_held,)),
        },
    }


def init_params(key, cfg):
    """Seeded weights -> the family's parameter tree."""
    ke, kh = jax.random.split(jax.random.fold_in(key, 0))
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ke, (v, d), jnp.float32),
            "norm_f": jnp.ones((d,)),
            "head": glorot_uniform(kh, (d, v)),
            "blocks": [init_layer_params(key, cfg, i)
                       for i in range(cfg["n_layers"])]}


# -- the pieces ---------------------------------------------------------
def route(moe, x, cfg):
    """-> (expert ids (N, k), weights (N, k) float32) over ALL the routed
    experts, held here or not: the softmax of the router's scores, its
    ``k`` largest divided by their sum (``norm_topk_prob``)."""
    p = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), moe["router"].astype(jnp.float32),
        precision="highest"), -1)
    idx, w = select_top_k(p, None, cfg["top_k"])
    return idx, w / jnp.sum(w, -1, keepdims=True)


def _ffn(blk, x, cfg, valid, counts):
    """The shared expert layer (the held experts' part, its routing
    counts) under this family's router; no shared expert."""
    out, c = moe_layer(blk["moe"],
                       rms_norm(blk["ffn_norm"], x, cfg["rms_norm_eps"]),
                       cfg, valid, router=route)
    return x + out, add_counts(counts, c)


def unmask(logits, tokens, fix, mask_id):
    """One pass's choice, a slot a row: ``logits (S, B, vocab)`` at the
    block's positions, its ``tokens (S, B)`` (``mask_id`` where nothing
    is fixed yet), ``fix (S,)`` positions to fix -> the block's tokens
    after the pass.  At each masked position the most likely token other
    than ``mask_id`` and its probability under the softmax over the whole
    vocabulary (compared as logarithms: the same order); the ``fix`` most
    confident masked positions take theirs, of equals the first."""
    masked = tokens == mask_id
    z = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf, logits)
    best = jnp.argmax(z, -1).astype(jnp.int32)
    conf = jnp.where(masked, jnp.max(z, -1)
                     - jax.nn.logsumexp(logits, -1), -jnp.inf)
    at = jnp.arange(tokens.shape[1])
    # how many positions of the block come before this one by confidence
    ahead = jnp.sum((conf[:, :, None] > conf[:, None, :])
                    | ((conf[:, :, None] == conf[:, None, :])
                       & (at[:, None] < at[None, :])), 1)
    return jnp.where(masked & (ahead < fix[:, None]), best, tokens)


def _sequence_layers(params, tokens, valid, cfg, write):
    """The layers over one whole sequence under the block-causal mask ->
    (hidden (T, d), counts); ``write(layer, entry)`` takes each layer's
    ``v | k`` rows."""
    t, hk = tokens.shape[0], cfg["n_kv_heads"]
    positions = jnp.arange(t, dtype=jnp.int32)
    with jax.named_scope("embed"):
        hs = params["embed"][tokens]
    counts = zero_counts(cfg, t)
    for li, blk in enumerate(params["blocks"]):
        y = rms_norm(blk["op_norm"], hs, cfg["rms_norm_eps"])
        q, entry = qkv_normed_rotated(blk["attn"], y, positions, cfg)
        write(li, entry)
        with jax.named_scope("attend"):
            v, k = jnp.split(entry, 2, axis=-1)
            a = attention_auto(q[None], k.reshape(1, t, hk, -1),
                               v.reshape(1, t, hk, -1), causal=True,
                               mask_block=cfg["block_length"])[0]
        with jax.named_scope("attn_out"):
            hs = hs + jnp.einsum("thk,hkd->td", a, blk["attn"]["wo"])
        hs, counts = _ffn(blk, hs, cfg, valid, counts)
    return hs, counts


# -- the three entry points ---------------------------------------------
def forward(params, tokens, cfg):
    """One whole sequence ``tokens (T,)`` under the block-causal mask, no
    cache -> logits (T, vocab); the logits AT a position are of the token
    OF that position."""
    hs, _ = _sequence_layers(params, tokens, jnp.ones(tokens.shape, bool),
                             cfg, lambda li, entry: None)
    return logits(params, hs, cfg)


def prefill_step(cfg, params, kv, tokens, length, page_idx, page_off):
    """One padded prompt -> (the routing counts, int32: a prefill yields
    NO token, the updated pool).  ``length`` is what the prefill commits,
    the prompt's whole blocks; positions past it write to the scratch
    page (``page_idx`` routes them there), reach no expert and, under the
    block-causal mask, are seen by no committed position."""
    pools = [kv]

    def write(li, entry):
        # the scattered dimensions are the pool's major ones: in place on
        # the donated pool
        with jax.named_scope("kv_write"):
            pools[0] = pools[0].at[li, page_idx, page_off].set(entry)

    valid = jnp.arange(tokens.shape[0]) < length
    _, counts = _sequence_layers(params, tokens, valid, cfg, write)
    return counts, pools[0]


def decode_step(cfg, params, kv, tokens, positions, page_tables,
                write_page, write_off, lengths, fix):
    """One pass for a padded slot set -> (``[the blocks' tokens after the
    pass (slots x B)..., counts...]`` int32, the updated pool).
    ``tokens (S, B)`` are the open blocks, ``positions (S,)`` where each
    starts (its row ``write_off`` of page ``write_page``), ``lengths (S,)``
    the positions a slot reads, ``start + B``, and ``fix (S,)`` how many
    masked positions the pass fixes (0: it commits; the rows it writes
    are then the block's final ones).  The rows of ``tokens`` are ENTRIES:
    two of them may be one sequence's (same ``page_tables`` row), a
    committing block at ``start`` and the next block at ``start + B``
    with ``lengths = start + 2 B``: each layer's write below puts both
    entries' rows into the pool before its read, so the second reads the
    first's final rows and the first, whose length stops before them,
    none of the second's.  Padding slots carry ``length == 0``, write to
    the scratch page, reach no expert, and the read's dead-row guard
    zeroes their attention."""
    s, b = tokens.shape
    h, hk, eps = cfg["n_heads"], cfg["n_kv_heads"], cfg["rms_norm_eps"]
    within = jnp.arange(b, dtype=jnp.int32)
    at = (positions[:, None] + within).reshape(-1)              # (S B,)
    valid = jnp.repeat(lengths > 0, b)
    with jax.named_scope("embed"):
        hs = params["embed"][tokens.reshape(-1)]
    counts = zero_counts(cfg)
    for li, blk in enumerate(params["blocks"]):
        y = rms_norm(blk["op_norm"], hs, eps)
        q, entry = qkv_normed_rotated(blk["attn"], y, at, cfg)
        with jax.named_scope("kv_write"):
            kv = kv.at[li, write_page[:, None],
                       write_off[:, None] + within].set(
                entry.reshape(s, b, -1))
        with jax.named_scope("attend_pool"):
            # a slot's B x H queries head-major, so that a K/V head's
            # queries are consecutive rows; the whole pool viewed flat
            # over (layer, page), the page ids offset to this layer's
            rows = q.reshape(s, b, h, -1).transpose(0, 2, 1, 3)
            a = attend_rows(rows.reshape(s, h * b, -1),
                            kv.reshape(-1, *kv.shape[2:]),
                            page_tables + li * kv.shape[1], lengths, hk)
            a = a.reshape(s, h, b, -1).transpose(0, 2, 1, 3)
        with jax.named_scope("attn_out"):
            hs = hs + jnp.einsum("shk,hkd->sd", a.reshape(s * b, h, -1),
                                 blk["attn"]["wo"])
        hs, counts = _ffn(blk, hs, cfg, valid, counts)
    at_block = logits(params, hs, cfg).reshape(s, b, -1)
    with jax.named_scope("unmask"):
        after = unmask(at_block, tokens, fix, cfg["mask_token_id"])
    return jnp.concatenate([after.reshape(-1), counts]), kv


def observe_step(counts, at, lengths=None, page_size=None, fix=None,
                 folded=0):
    """The counts behind a step's tokens -> the registry
    (``blocks.observe_routing``).  A pass hands in its entries'
    ``lengths`` (host values, zeros for padding: the rows its read covers
    in each layer, the open blocks' among them), what each entry was to
    ``fix`` and how many of its commits were ``folded`` (their sequence's
    next block an entry of the same pass), and stamps
    ``decode.kv.live_positions`` and the pass's ``decode.block.*``
    samples: the SEQUENCES in it (a folded commit's two entries are one),
    the positions fixed, the share of its live entries that committed
    and, where any did, the share of those commits that were folded."""
    from dist_keras_tpu.observability import metrics

    observe_routing(counts, at, decode=lengths is not None)
    if lengths is None:
        return
    live = np.asarray(lengths) > 0
    fix = np.asarray(fix)[live]
    commits = int(np.sum(fix == 0))
    metrics.histogram("decode.kv.live_positions").observe(
        int(np.asarray(lengths).sum()), at=at)
    metrics.histogram("decode.block.slots").observe(
        int(live.sum()) - folded, at=at)
    metrics.histogram("decode.block.tokens_fixed").observe(
        int(fix.sum()), at=at)
    if fix.size:
        metrics.histogram("decode.block.commit_share").observe(
            100.0 * commits / fix.size, at=at)
    if commits:
        metrics.histogram("decode.block.commit_folded").observe(
            100.0 * folded / commits, at=at)


class SdarMoeDecoder(FamilyDecoder):
    family = sys.modules[__name__]
    config = staticmethod(sdar_moe_config)
    name = "sdar_moe_decoder"
