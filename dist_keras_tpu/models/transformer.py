"""Transformer models (single-device reference implementation).

New capability surface — the reference has no attention or sequence models
(SURVEY.md §2.3).  This is the flagship architecture for the framework's
long-context path: the same parameter pytree layout is consumed by the
sharded dp x tp x sp training step in ``parallel/transformer_tp.py``, and
this implementation is the correctness oracle its tests compare against.

Layout notes (TPU-first):
- attention projections keep an explicit head axis: wq/wk/wv are
  (d_model, heads, head_dim) and wo is (heads, head_dim, d_model) so the
  head axis can be sharded over the ``model`` mesh axis without reshapes;
- MLP is d -> ff (gelu) -> d, column/row-shardable;
- pre-LN residual blocks; mean-pool + linear head for classification.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.blocks import attend_rows
from dist_keras_tpu.models.layers import glorot_uniform
from dist_keras_tpu.ops.attention import attention  # noqa: F401 (oracle)
from dist_keras_tpu.ops.pallas.decode_attention import latent_walked_positions
from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

FAMILY = "transformer"
# cached positions a grid step of the decode read fetches, whatever the
# page size: 8 pages of 8 positions of 8,192 lanes are 2 MB a buffer, two
# of them in VMEM.  At 8 slots of 1,024-2,048 positions, 32 / 64 / 128
# read 3.15 / 2.98 / 3.07 ms six layers and made a whole decode step of
# 12.35 / 12.22 / 12.32 ms on a v5e; 256 does not fit VMEM (PERF.md, PR 37)
KV_BLOCK_POSITIONS = 64


def transformer_config(input_dim, seq_len, d_model=64, n_heads=4,
                       n_layers=2, d_ff=None, n_classes=2,
                       moe_experts=0, moe_capacity_factor=1.25):
    """``moe_experts > 0`` replaces every block's dense FFN with a
    Switch-MoE FFN of that many experts (parallel/moe.py) — use
    ``transformer_apply_with_aux`` / ``make_moe_train_step`` so the
    router's load-balancing aux loss reaches the objective."""
    return {
        "input_dim": int(input_dim),
        "seq_len": int(seq_len),
        "d_model": int(d_model),
        "n_heads": int(n_heads),
        "n_layers": int(n_layers),
        "d_ff": int(d_ff if d_ff is not None else 4 * d_model),
        "n_classes": int(n_classes),
        "moe_experts": int(moe_experts),
        "moe_capacity_factor": float(moe_capacity_factor),
    }


def init_transformer_params(key, cfg):
    """-> params pytree (dict), replicated layout shared with the TP step."""
    d, h = cfg["d_model"], cfg["n_heads"]
    dh = d // h
    ff = cfg["d_ff"]
    keys = iter(jax.random.split(key, 6 + 8 * cfg["n_layers"]))

    def dense(shape):
        return glorot_uniform(next(keys), shape)

    params = {
        "proj": dense((cfg["input_dim"], d)),
        "pos": 0.02 * jax.random.normal(next(keys),
                                        (cfg["seq_len"], d)),
        "blocks": [],
        "ln_f": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "head": {"kernel": dense((d, cfg["n_classes"])),
                 "bias": jnp.zeros((cfg["n_classes"],))},
    }
    moe = cfg.get("moe_experts", 0)
    for _ in range(cfg["n_layers"]):
        blk = {
            "ln1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            "wq": dense((d, h, dh)),
            "wk": dense((d, h, dh)),
            "wv": dense((d, h, dh)),
            "wo": dense((h, dh, d)),
            "ln2": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        }
        if moe:
            from dist_keras_tpu.parallel.moe import init_moe_params

            blk["moe"] = init_moe_params(next(keys), d, ff, moe)
        else:
            blk.update({
                "w1": dense((d, ff)),
                "b1": jnp.zeros((ff,)),
                "w2": dense((ff, d)),
                "b2": jnp.zeros((d,)),
            })
        params["blocks"].append(blk)
    return params


def layer_norm(p, x, eps=1e-5):
    """Shared by the single-device oracle and the sharded TP step — keep
    one definition so they can never silently diverge."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


_ln = layer_norm


def apply_block_aux(blk, h, attn_fn, causal, capacity_factor=1.25,
                    moe_fn=None):
    """One pre-LN attention+FFN residual block -> (h, aux).

    The single definition shared by the oracle forward, the TP step, the
    pipelined forward AND the expert-parallel step, so their math can
    never silently diverge.  Dense blocks return aux = 0.0; MoE blocks
    (``"moe"`` in blk) return the Switch router's load-balancing loss.
    ``moe_fn(moe_params, tokens_2d) -> (out_2d, aux)`` is injectable —
    the EP step swaps in ``switch_moe_ep``; default is the dense
    single-device mixture."""
    with jax.named_scope("attention"):
        y = _ln(blk["ln1"], h)
        q = jnp.einsum("btd,dhk->bthk", y, blk["wq"])
        k = jnp.einsum("btd,dhk->bthk", y, blk["wk"])
        v = jnp.einsum("btd,dhk->bthk", y, blk["wv"])
        a = attn_fn(q, k, v, causal=causal)
        h = h + jnp.einsum("bthk,hkd->btd", a, blk["wo"])
    with jax.named_scope("mlp"):
        y = _ln(blk["ln2"], h)
        if "moe" in blk:
            if moe_fn is None:
                from dist_keras_tpu.parallel.moe import switch_moe_dense

                moe_fn = functools.partial(
                    switch_moe_dense, capacity_factor=capacity_factor)
            b, t, d = y.shape
            u, aux = moe_fn(blk["moe"], y.reshape(b * t, d))
            return h + u.reshape(b, t, d), aux
        u = jax.nn.gelu(y @ blk["w1"] + blk["b1"])
        return h + u @ blk["w2"] + blk["b2"], jnp.float32(0.0)


def apply_block(blk, h, attn_fn, causal):
    """Dense-FFN block (aux discarded — MoE blocks must go through
    ``apply_block_aux`` so the router loss reaches the objective)."""
    h, _ = apply_block_aux(blk, h, attn_fn, causal)
    return h


def transformer_apply_with_aux(params, x, cfg, *, causal=False,
                               attn_fn=None, remat=False):
    """Forward returning (logits, total_aux_loss) — required for MoE
    configs; identical to ``transformer_apply`` for dense ones.

    ``remat=True`` wraps each block in ``jax.checkpoint``: activations
    inside a block are recomputed during the backward instead of stored,
    trading ~1 extra forward of FLOPs for O(layers) less HBM — the
    standard long-context/deep-model memory lever.
    """
    if attn_fn is None:
        from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

        attn_fn = attention_auto
    cf = cfg.get("moe_capacity_factor", 1.25)
    block = functools.partial(apply_block_aux, attn_fn=attn_fn,
                              causal=causal, capacity_factor=cf)
    if remat:
        block = jax.checkpoint(block)
    h = x @ params["proj"] + params["pos"][None, :x.shape[1]]
    aux = jnp.float32(0.0)
    for blk in params["blocks"]:
        h, a = block(blk, h)
        aux = aux + a
    pooled = jnp.mean(_ln(params["ln_f"], h), axis=1)
    logits = pooled @ params["head"]["kernel"] + params["head"]["bias"]
    return logits, aux


def transformer_apply(params, x, cfg, *, causal=False, attn_fn=None,
                      remat=False):
    """Forward pass.  x: (B, T, input_dim) -> logits (B, n_classes).

    ``attn_fn`` is injectable so the sharded step can swap in
    ``ring_attention`` while reusing every other line of this function;
    the default dispatches to the Pallas flash kernel on TPU backends and
    the jnp reference elsewhere (``attention_auto``).  Pass
    ``attn_fn=attention`` to force the jnp oracle.  ``remat=True``
    checkpoints each block (see ``transformer_apply_with_aux``).
    """
    if cfg.get("moe_experts", 0):
        raise ValueError(
            "MoE transformer configs must use transformer_apply_with_aux "
            "(or make_moe_train_step) so the router's load-balancing "
            "loss reaches the objective; for pure inference the "
            "Transformer wrapper's apply() discards aux for you")
    logits, _ = transformer_apply_with_aux(
        params, x, cfg, causal=causal, attn_fn=attn_fn, remat=remat)
    return logits


# -- what ``serving.decode.DecodeEngine`` takes from a block family -----
# (every module ``models/families.py`` lists has the same names, and the
# contract is stated there).  The cache is ONE paged
# pool over all layers whose entry is the row ``v | k`` of a cached
# position: every head's values, then every head's keys.  A prefill
# attends over its own q, k, v (the flash forward) and writes a row a
# position; a decode step writes its row and reads the slots' LIVE pages
# where they lie, through the read four other families share
# (``blocks.attend_rows``: on a TPU ``latent_attention_kernel``, blocks
# of pages double-buffered in VMEM and everything past a slot's length
# skipped; elsewhere ``latent_attention_reference``).
#
# Both steps embed a token by reading its row of ``proj``, as the other
# families read theirs: the float32 row as stored (a ``one_hot``
# product read the whole table a step and handed on the row rounded to
# the product's precision).  An id outside the table reads a row all the
# same: an indexed read clamps past the end and counts a negative id from
# the end, where the product gave zeros.  No such id reaches a live
# entry: the engine's door refuses a prompt token outside ``[0,
# vocab(cfg))`` (``DecodeEngine.submit_generate``: ``ValueError``, the
# front end's 400), a fed-back token is an ``argmax`` over ``n_classes``
# logits, and the worker pads with token 0.  A padded entry, whatever it
# reads, moves no live one (``tests/test_decode_embedding.py``, bit for
# bit): see each step's docstring.
def vocab(cfg):
    """The vocabulary a decoder of ``cfg`` reads and writes; a config
    this family cannot decode is refused here."""
    if cfg.get("moe_experts", 0):
        raise ValueError(
            "a Transformer with Switch-MoE blocks (moe_experts > 0) "
            "has no decode step: its top-1 routing drops tokens over "
            "capacity.  The engine decodes dense Transformer blocks "
            "and the models.mla_moe family (top-k experts, no drops)")
    if cfg["input_dim"] != cfg["n_classes"]:
        raise ValueError(
            "a Transformer decodes with token-in == logit-out (its "
            "embedding is the token's row of proj, and a decoded token "
            "is fed back, so proj holds a row for every logit): "
            f"input_dim={cfg['input_dim']} != "
            f"n_classes={cfg['n_classes']}.  The models.mla_moe "
            "family has a table of its own and needs no such match")
    return int(cfg["n_classes"])


def step_width(cfg):
    """Positions a slot a step: one token."""
    return 1


def cache_pools(cfg):
    """What the engine allocates, ``(layers spanned, "page" or
    "sequence", entry)`` a pool: the one ``v | k`` pool, paged, a row of
    ``2 x d_model`` values a cached position in every layer."""
    return ((cfg["n_layers"], "page", (2 * cfg["d_model"],)),)


def kv_block_pages(page_size):
    """Pages of ``page_size`` positions a grid step of the read fetches."""
    return max(1, KV_BLOCK_POSITIONS // int(page_size))


def _kv_row(k, v):
    """``k, v (T, H, dh)`` -> the cache entries ``v | k`` ``(T, 2 H dh)``."""
    t = k.shape[0]
    return jnp.concatenate([v.reshape(t, -1), k.reshape(t, -1)], -1)


def _write_prompt_rows(pool, li, page_idx, page_off, rows):
    """A prompt's rows ``(T, 2 d_model)`` into layer ``li`` of the pool, in
    place on the donated pool (the scattered dimensions are its major
    ones).  A prefill's positions are 0 .. T - 1 of one sequence: position
    t lies at offset ``t % page_size`` of the sequence's page ``t //
    page_size``, so a rung of whole pages is written a PAGE an update, 64
    contiguous (8, 128) tiles of 8,192 lanes, where a row an update is
    one sublane of each (2,048 rows: 12 ms more a six-layer prefill on a
    v5e, PERF.md, PR 37).  A page's id is its first position's; the
    padding that shares the prompt's last page lands behind its length
    there, where no read looks and the next decode steps write, the rest
    on the scratch page as ``page_idx`` says."""
    t, ps = rows.shape[0], pool.shape[2]
    if t % ps:
        return pool.at[li, page_idx, page_off].set(rows)
    return pool.at[li, page_idx[::ps]].set(rows.reshape(t // ps, ps, -1))


def prefill_step(cfg, params, pool, tokens, length, page_idx, page_off):
    """One padded prompt -> (first generated token, updated pool).

    ``tokens (T,) int32`` padded to a prefill rung; positions past
    ``length`` write their row to the scratch page (``page_idx``
    routes them there) and never influence position ``length - 1``
    under the causal mask, whatever token id they carry."""
    t = tokens.shape[0]
    with jax.named_scope("embed"):
        hs = (params["proj"][tokens] + params["pos"][:t])[None]
    for li, blk in enumerate(params["blocks"]):
        with jax.named_scope("qkv"):
            y = layer_norm(blk["ln1"], hs)
            q = jnp.einsum("btd,dhk->bthk", y, blk["wq"])
            k = jnp.einsum("btd,dhk->bthk", y, blk["wk"])
            v = jnp.einsum("btd,dhk->bthk", y, blk["wv"])
        with jax.named_scope("kv_write"):
            pool = _write_prompt_rows(pool, li, page_idx, page_off,
                                      _kv_row(k[0], v[0]))
        with jax.named_scope("attend"):
            a = attention_auto(q, k, v, causal=True)
        with jax.named_scope("attn_out"):
            hs = hs + jnp.einsum("bthk,hkd->btd", a, blk["wo"])
        with jax.named_scope("mlp"):
            y = layer_norm(blk["ln2"], hs)
            u = jax.nn.gelu(y @ blk["w1"] + blk["b1"])
            hs = hs + u @ blk["w2"] + blk["b2"]
    with jax.named_scope("head"):
        hf = layer_norm(params["ln_f"], hs)[0, length - 1]
        logits = (hf @ params["head"]["kernel"]
                  + params["head"]["bias"])
        first = jnp.argmax(logits).astype(jnp.int32)
    return first, pool


def decode_step(cfg, params, pool, tokens, positions, page_tables,
                write_page, write_off, lengths):
    """One token step for a padded slot set -> (next tokens, updated
    pool).  Padding slots carry ``length == 0`` and write to the scratch
    page; the read's dead-row guard makes their output exact zeros (then
    discarded), and no operation of the step mixes slots: whatever token
    id a padding slot carries moves no live one."""
    block_pages = kv_block_pages(pool.shape[2])
    with jax.named_scope("embed"):
        hs = params["proj"][tokens] + params["pos"][positions]
    for li, blk in enumerate(params["blocks"]):
        with jax.named_scope("qkv"):
            y = layer_norm(blk["ln1"], hs)
            q = jnp.einsum("sd,dhk->shk", y, blk["wq"])
            k = jnp.einsum("sd,dhk->shk", y, blk["wk"])
            v = jnp.einsum("sd,dhk->shk", y, blk["wv"])
        with jax.named_scope("kv_write"):
            pool = pool.at[li, write_page, write_off].set(_kv_row(k, v))
        with jax.named_scope("attend"):
            # the whole pool viewed flat over (layer, page), the page
            # ids offset to this layer's: ``pool[li]`` would copy it
            a = attend_rows(q, pool.reshape(-1, *pool.shape[2:]),
                            page_tables + li * pool.shape[1], lengths,
                            cfg["n_heads"], block_pages)
        with jax.named_scope("attn_out"):
            hs = hs + jnp.einsum("shk,hkd->sd", a, blk["wo"])
        with jax.named_scope("mlp"):
            y = layer_norm(blk["ln2"], hs)
            u = jax.nn.gelu(y @ blk["w1"] + blk["b1"])
            hs = hs + u @ blk["w2"] + blk["b2"]
    with jax.named_scope("head"):
        hf = layer_norm(params["ln_f"], hs)
        logits = (hf @ params["head"]["kernel"]
                  + params["head"]["bias"])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, pool


def observe_step(counts, at, lengths=None, page_size=None):
    """This family's steps send no counts behind their tokens.  A decode
    step passes its slots' ``lengths`` (host values, zeros for padding)
    and the ``page_size`` and adds one sample to each per-step histogram,
    stamped ``at`` like ``decode.step_s``: the live cached positions its
    read covers in each layer, and the positions the read's blocks of
    pages fetch for them."""
    if lengths is None:
        return
    from dist_keras_tpu.observability import metrics

    metrics.histogram("decode.kv.live_positions").observe(
        int(lengths.sum()), at=at)
    metrics.histogram("decode.kv.walked_positions").observe(
        latent_walked_positions(lengths, page_size,
                                kv_block_pages(page_size)), at=at)


class Transformer:
    """Model-contract wrapper (params + apply + weights round-trip) so the
    standard trainers accept a Transformer like any other model.

    MoE configs: ``apply`` DISCARDS the router aux loss — fine for
    inference/prediction; for training prefer ``make_moe_train_step``
    (the Switch objective), since standard trainers going through
    ``apply`` would optimize nll without the load-balancing term."""

    def __init__(self, cfg=None, seed=0, **cfg_kw):
        self.cfg = cfg or transformer_config(**cfg_kw)
        self.params = init_transformer_params(
            jax.random.PRNGKey(seed), self.cfg)
        self.name = "transformer"

    def apply(self, params, x, *, training=False, rng=None):
        if self.cfg.get("moe_experts", 0):
            if training:
                raise ValueError(
                    "training a MoE Transformer through the standard "
                    "model contract would silently drop the router "
                    "load-balancing loss; use "
                    "parallel.make_moe_train_step instead")
            logits, _ = transformer_apply_with_aux(params, x, self.cfg)
            return logits
        return transformer_apply(params, x, self.cfg)

    def __call__(self, x, *, training=False, rng=None):
        return self.apply(self.params, jnp.asarray(x))

    def predict(self, x, batch_size=None):
        return np.asarray(self(np.asarray(x)))

    def set_params(self, params):
        self.params = jax.tree.map(jnp.asarray, params)

    def get_weights(self):
        return [np.asarray(l) for l in jax.tree.leaves(self.params)]

    def set_weights(self, weights):
        treedef = jax.tree.structure(self.params)
        self.params = jax.tree.unflatten(
            treedef, [jnp.asarray(w) for w in weights])

    def to_json(self):
        import json

        return json.dumps({"class_name": "Transformer", "config": self.cfg})

    @property
    def count_params(self):
        return sum(int(np.prod(np.shape(w))) for w in self.get_weights())
