"""Looped dense decoder: one stack of layers run several times a token on
shared weights (the ``ouro`` block family: Ouro-2.6B's).

A sixth block family, and the first in which a layer of WEIGHTS is not a
layer of the program: the stack of ``n_layers`` layers is applied
``ut_steps`` times to every position, the same leaves each time, and what
the head reads is chosen among the passes by a learned gate.  Entered
three ways over the same functions: :func:`forward` (a whole sequence, no
cache), :func:`prefill_step` (one padded prompt) and :func:`decode_step`
(one token a slot).  ``DecodeEngine`` takes the steps and
:func:`cache_pools` from here when the model's ``cfg["family"]`` says
``"ouro"``; it knows of no pass.

*The layer*, in pass ``t`` as in every other (sandwich norms: one on a
sub-block's input AND one on its output, before the residual add)::

    a = RMS_1(x);  q, k, v = a Wq, a Wk, a Wv      (heads x head_dim, no bias,
                                                    no norm on q or k)
    q, k rotated by halves over the whole head at the position's index
    o = softmax(q k^T / sqrt(head_dim)) v   causal, over the keys and values
                                            THIS layer wrote in THIS pass
    x = x + RMS_2(o Wo)
    m = RMS_3(x);  x = x + RMS_4((silu(m Wg) * (m Wu)) Wd)

After the last layer of pass ``t``: ``h_t = RMS_f(x)`` (one final norm, the
passes share it), and ``h_t`` is what pass ``t + 1`` starts from (pass 0
from the embedding's row).  *The exit gate:* ``g_t = h_t . w_g + b_g``,
``lambda_t = sigmoid(g_t)``, ``p_t = lambda_t prod_{s<t}(1 - lambda_s)``
(the last pass takes what is left, ``prod_{s<last}(1 - lambda_s)``), ``C_t
= sum_{s<=t} p_s``; the exit pass ``e`` is the first ``t`` with ``C_t >=
early_exit_threshold``, the last pass where there is none
(:func:`exit_pass`; float32 at "highest" precision); ``logits = h_e
W_head``, untied.  **Every pass runs for every position whatever ``e``
is**, as in the published forward: the rule chooses which pass's state the
head reads and skips no work, so every (pass, layer) entry of a position's
cache is always written.

*The program.*  The passes are a LOOP of the compiled program
(``jax.lax.fori_loop`` over the pass index, its body the stack once:
:func:`_passes`), so a program holds ``n_layers`` layer bodies whatever
``ut_steps`` is.  The loop carries the hidden state, the cache and the
passes' ``h_t`` and ``g_t`` (of the rows the head may read).

*The cache.*  One paged pool of ``v | k`` rows (every head's values, then
every head's keys: ``2 x kv_heads x head_dim`` values a position, 4,096
lanes at the published widths) over ``ut_steps x n_layers`` ENTRIES:
entry ``t x n_layers + l`` holds what layer ``l`` wrote in pass ``t``, and
a pass never reads another pass's keys.  Inside the loop the entry's index
is a traced value: the write scatters into the carried, donated pool where
it lies, and the read walks the slot's live pages of the pool viewed flat
over (entry, page) with the page ids offset to the entry's
(``blocks.attend_rows``: on a TPU the ``latent_decode`` kernel), so no
pass copies the pool or an entry of it.

Behind their tokens both steps send what :func:`observe_step` counts: the
exit passes of the rows the head read (``e + 1``, summed over the live
slots), the passes the program ran and its layer applications.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.blocks import (
    FamilyDecoder,
    attend_entries,
    attend_rows,
    rms_norm,
    rope_halves,
    swiglu,
    swiglu_params,
)
from dist_keras_tpu.models.layers import glorot_uniform

FAMILY = "ouro"
# pages of ``v | k`` rows the read's kernel fetches a grid step: 4 pages of
# 16 positions of 4,096 lanes are 1 MB a buffer, two of them in VMEM.  A
# bare decode step of 16 slots at 6,880 live positions on a v5e: 22.53 ms
# at 4 pages, 22.95 at 8, 23.93 at 16 (PERF.md, PR 44): shorter blocks walk
# fewer positions past a slot's length, and their grid steps cost less
KV_BLOCK_PAGES = 4
# what a step sends behind its tokens: the exit passes summed, the passes
# the program ran, its layer applications
N_COUNTS = 3


def ouro_config(vocab_size, seq_len, d_model, n_heads, n_kv_heads, head_dim,
                d_ff, n_layers, ut_steps=4, early_exit_threshold=1.0,
                rope_theta=1000000.0, rms_norm_eps=1e-6):
    """``seq_len`` is how many positions one sequence may hold (a slot's
    page table in the engine): rotary positions need no table.
    ``n_layers`` counts the layers of WEIGHTS; a token passes ``ut_steps x
    n_layers`` layer applications."""
    if n_heads % n_kv_heads or head_dim % 2:
        raise ValueError(
            f"n_kv_heads={n_kv_heads} must divide n_heads={n_heads}, and "
            f"head_dim={head_dim} be even")
    if ut_steps < 1 or n_layers < 1:
        raise ValueError(
            f"ut_steps={ut_steps} and n_layers={n_layers} must be >= 1")
    return {
        "family": FAMILY,
        "vocab_size": int(vocab_size),
        "seq_len": int(seq_len),
        "d_model": int(d_model),
        "n_heads": int(n_heads),
        "n_kv_heads": int(n_kv_heads),
        "head_dim": int(head_dim),
        "d_ff": int(d_ff),
        "n_layers": int(n_layers),
        "ut_steps": int(ut_steps),
        "early_exit_threshold": float(early_exit_threshold),
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
    """What the engine allocates: the one ``v | k`` pool, paged, a row a
    position in every ENTRY, of which there is one a (pass, layer): the
    pool spans ``ut_steps x n_layers`` entries over ``n_layers`` layers of
    weights."""
    return ((cfg["ut_steps"] * cfg["n_layers"], "page",
             (2 * cfg["n_kv_heads"] * cfg["head_dim"],)),)


def init_layer_params(key, cfg, layer):
    """One layer's leaves, a function of (key, layer) alone."""
    d, h, hk, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    kq, kk, kv, ko, kf = jax.random.split(
        jax.random.fold_in(key, 1 + layer), 5)
    return {
        "attn_norm": jnp.ones((d,)),
        "attn": {
            "wq": glorot_uniform(kq, (d, h, hd)),
            "wk": glorot_uniform(kk, (d, hk, hd)),
            "wv": glorot_uniform(kv, (d, hk, hd)),
            "wo": glorot_uniform(ko, (h, hd, d)),
        },
        "attn_out_norm": jnp.ones((d,)),
        "mlp_norm": jnp.ones((d,)),
        "mlp": swiglu_params(kf, d, cfg["d_ff"]),
        "mlp_out_norm": jnp.ones((d,)),
    }


def init_outer_params(key, cfg):
    ke, kh, kg = jax.random.split(jax.random.fold_in(key, 0), 3)
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ke, (v, d), jnp.float32),
            "norm_f": jnp.ones((d,)),
            "gate": {"w": glorot_uniform(kg, (d, 1))[:, 0],
                     "b": jnp.zeros(())},
            "head": glorot_uniform(kh, (d, v))}


def init_params(key, cfg):
    """Seeded weights -> the family's parameter tree."""
    tree = init_outer_params(key, cfg)
    tree["blocks"] = [init_layer_params(key, cfg, i)
                      for i in range(cfg["n_layers"])]
    return tree


# -- the pieces ---------------------------------------------------------
def _qkv(attn, a, positions, cfg):
    """-> (q (T, H, hd), the cache entry ``v | k`` (T, 2 Hkv hd)), q and
    k rotated; no norm on either."""
    theta = cfg["rope_theta"]
    with jax.named_scope("qkv"):
        q = rope_halves(jnp.einsum("td,dhk->thk", a, attn["wq"]),
                        positions, theta)
        k = rope_halves(jnp.einsum("td,dhk->thk", a, attn["wk"]),
                        positions, theta)
        v = jnp.einsum("td,dhk->thk", a, attn["wv"])
    t = a.shape[0]
    return q, jnp.concatenate([v.reshape(t, -1), k.reshape(t, -1)], -1)


def _float32_reader(blk):
    """An exact zero that reads one value of each of a layer's matrices
    in float32, added to the layer's input INSIDE the loop.  Why: a
    matrix that the loop's body only ever multiplies by is, to the
    compiler's bfloat16 propagation, a value nobody needs in float32; it
    carries the product's rounding back through the loop's state to the
    program's entry and holds a bfloat16 COPY of all the layers, beside
    the float32 leaves, for as long as the program runs: 1.23 GB at the
    published widths in each of a decode step, the step in flight behind
    it and a prefill between them (compiled for a v5e: temporaries 1.270
    and 1.273 GB; 0.012 and 0.022 with this reader and the barrier in
    :func:`_passes`, neither of which does it alone, nor do the leaves as
    the loop's own state or the compiler's code-motion options:
    ``PERF.md``, PR 44).  The passes then read the float32 leaves
    themselves, as every other family's layers are read and as the
    configuration's ``precision`` states; storing them in bfloat16 is a
    statement for every family together (ROADMAP S2)."""
    return 0.0 * sum(jnp.ravel(w)[0] for w in jax.tree.leaves(blk)
                     if w.ndim > 1)


def _layer(blk, x, kv, positions, cfg, attend):
    """One layer on ``x (T, d)`` -> (x, kv); ``attend(q, entry, kv) -> (the
    attention's output (T, H, hd), kv)`` takes the layer's cache entry."""
    eps = cfg["rms_norm_eps"]
    x = x + _float32_reader(blk)
    q, entry = _qkv(blk["attn"], rms_norm(blk["attn_norm"], x, eps),
                    positions, cfg)
    o, kv = attend(q, entry, kv)
    with jax.named_scope("attn_out"):
        o = jnp.einsum("thk,hkd->td", o, blk["attn"]["wo"])
        x = x + rms_norm(blk["attn_out_norm"], o, eps)
    with jax.named_scope("mlp"):
        f = swiglu(blk["mlp"], rms_norm(blk["mlp_norm"], x, eps))
        return x + rms_norm(blk["mlp_out_norm"], f, eps), kv


def _passes(params, x, kv, positions, cfg, attend, read=lambda h: h):
    """The loop: its body is the stack of layers once, in pass ``t`` (a
    traced index), and runs ``ut_steps`` times on the same leaves;
    ``attend(index, q, entry, kv) -> (output, kv)`` is a layer's
    attention over cache entry ``index = t x n_layers + layer`` -> (kv,
    ``h_t`` of the rows ``read`` takes of a pass's normed state (passes,
    rows, d), their gates ``g_t`` (passes, rows) float32)."""
    passes, n, eps = cfg["ut_steps"], cfg["n_layers"], cfg["rms_norm_eps"]
    gate = params["gate"]

    def one_pass(t, carry):
        x, kv, hs, gs = carry
        with jax.named_scope("loop_pass"):
            # the leaves enter a pass behind a barrier shared with its
            # input: what ``_float32_reader`` reads of them is otherwise
            # computed once, in front of the loop, and reads nothing
            # inside it
            x, blocks = jax.lax.optimization_barrier((x, params["blocks"]))
            for li, blk in enumerate(blocks):
                x, kv = _layer(blk, x, kv, positions, cfg,
                               functools.partial(attend, t * n + li))
            h = rms_norm(params["norm_f"], x, eps)
        with jax.named_scope("exit_gate"):
            mine = read(h)
            g = jnp.dot(mine.astype(jnp.float32), gate["w"],
                        precision="highest") + gate["b"]
        return h, kv, hs.at[t].set(mine), gs.at[t].set(g)

    rows = read(x).shape[0]
    _, kv, hs, gs = jax.lax.fori_loop(
        0, passes, one_pass,
        (x, kv, jnp.zeros((passes, rows, x.shape[1]), x.dtype),
         jnp.zeros((passes, rows), jnp.float32)))
    return kv, hs, gs


def exit_pass(gates, threshold):
    """The published rule: gates ``g_t (passes, rows)`` -> the exit pass
    of each row, int32: the first ``t`` whose cumulated exit probability
    ``C_t`` reaches ``threshold``, the last pass where none does."""
    last = gates.shape[0] - 1
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    reached, cum, stay = [], 0.0, 1.0
    for t in range(last):
        cum = cum + lam[t] * stay
        stay = stay * (1.0 - lam[t])
        reached.append(cum >= threshold)
    # the last pass takes what is left (C_last = 1): it needs no test
    e = jnp.full(gates.shape[1:], last, jnp.int32)
    for t in reversed(range(last)):
        e = jnp.where(reached[t], t, e)
    return e


def _head(params, hs, gs, cfg):
    """The passes' states and gates of some rows -> (logits (rows, vocab)
    of each row's exit pass, the exit passes)."""
    with jax.named_scope("exit_gate"):
        e = exit_pass(gs, cfg["early_exit_threshold"])
        chosen = jnp.take_along_axis(hs, e[None, :, None], 0)[0]
    with jax.named_scope("head"):
        return chosen @ params["head"], e


def _sequence_attend(cfg, write):
    """-> ``attend`` of one whole sequence over its own entries under the
    causal mask; ``write(kv, index, entry) -> kv`` takes each (pass,
    layer) entry."""
    def attend(index, q, entry, kv):
        with jax.named_scope("kv_write"):
            kv = write(kv, index, entry)
        with jax.named_scope("attend"):
            return attend_entries(q, entry, cfg["n_kv_heads"]), kv

    return attend


# -- the three entry points ---------------------------------------------
def forward(params, tokens, cfg, with_gates=False):
    """One whole sequence ``tokens (T,)``, no cache -> logits (T, vocab);
    ``with_gates``: -> (logits, the gates ``g_t`` (passes, T), the exit
    passes (T,))."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    _, hs, gs = _passes(
        params, x, None, positions, cfg,
        _sequence_attend(cfg, lambda kv, index, entry: kv))
    logits, e = _head(params, hs, gs, cfg)
    return (logits, gs, e) if with_gates else logits


def _counts(e, live, cfg):
    """What rides behind a step's tokens, int32: the live rows' exit
    passes (``e + 1``) summed, the passes the program ran, its layer
    applications."""
    return jnp.stack([
        jnp.sum(jnp.where(live, e + 1, 0)).astype(jnp.int32),
        jnp.int32(cfg["ut_steps"]),
        jnp.int32(cfg["ut_steps"] * cfg["n_layers"])])


def prefill_step(cfg, params, kv, tokens, length, page_idx, page_off):
    """One padded prompt -> (``[first token, counts...]`` int32, the
    updated pool).  A position's row is written in all ``ut_steps x
    n_layers`` entries; positions past ``length`` write to the scratch
    page (``page_idx`` routes them there) and, the mask being causal,
    never influence position ``length - 1``, the one row whose passes the
    gate and the head read."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.named_scope("embed"):
        x = params["embed"][tokens]

    def write(kv, index, entry):
        # the scattered dimensions are the pool's major ones: in place on
        # the donated pool the loop carries
        return kv.at[index, page_idx, page_off].set(entry)

    kv, hs, gs = _passes(
        params, x, kv, positions, cfg, _sequence_attend(cfg, write),
        read=lambda h: jax.lax.dynamic_slice_in_dim(h, length - 1, 1))
    logits, e = _head(params, hs, gs, cfg)
    first = jnp.argmax(logits[0]).astype(jnp.int32)
    return jnp.concatenate([first[None], _counts(e, True, cfg)]), kv


def decode_step(cfg, params, kv, tokens, positions, page_tables,
                write_page, write_off, lengths):
    """One token step for a padded slot set -> (``[next tokens...,
    counts...]`` int32, the updated pool).  Each pass writes the step's
    row into its own ``n_layers`` entries and reads the slot's pages of
    the same entries.  Padding slots carry ``length == 0``, write to the
    scratch page, and the read's dead-row guard zeroes their attention."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]

    def attend(index, q, entry, kv):
        with jax.named_scope("kv_write"):
            kv = kv.at[index, write_page, write_off].set(entry)
        with jax.named_scope("attend_pool"):
            # the whole pool viewed flat over (entry, page), the page ids
            # offset to this entry's: ``kv[index]`` would copy it
            return attend_rows(q, kv.reshape(-1, *kv.shape[2:]),
                               page_tables + index * kv.shape[1], lengths,
                               cfg["n_kv_heads"], KV_BLOCK_PAGES), kv

    kv, hs, gs = _passes(params, x, kv, positions, cfg, attend)
    logits, e = _head(params, hs, gs, cfg)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    return jnp.concatenate([nxt, _counts(e, lengths > 0, cfg)]), kv


def observe_step(counts, at, lengths=None, page_size=None):
    """The counts behind a step's tokens -> the registry: the counter
    ``decode.loop.layer_passes`` grows by the step's layer applications.
    A decode step also hands in its slots' ``lengths`` (host values,
    zeros for padding) and stamps their sum on
    ``decode.kv.live_positions`` (positions, not entries: every entry
    holds a row for each), the passes it ran on ``decode.loop.passes``
    and the mean over its live slots of ``e + 1`` on
    ``decode.loop.exit_pass``."""
    from dist_keras_tpu.observability import metrics

    exits, passes, layer_passes = (int(c) for c in counts)
    metrics.counter("decode.loop.layer_passes").inc(layer_passes)
    if lengths is None:
        return
    metrics.histogram("decode.kv.live_positions").observe(
        int(lengths.sum()), at=at)
    metrics.histogram("decode.loop.passes").observe(passes, at=at)
    live = int(np.count_nonzero(lengths))
    if live:
        metrics.histogram("decode.loop.exit_pass").observe(
            exits / live, at=at)


class OuroDecoder(FamilyDecoder):
    family = sys.modules[__name__]
    config = staticmethod(ouro_config)
    name = "ouro_decoder"
