"""What the block families share: the one home of every function that two
or more of ``models/transformer.py``, ``mla_moe.py``, ``lfm2_moe.py``,
``olmo_hybrid.py``, ``sdar_moe.py`` and ``ouro.py`` call, and of the one
decoder class behind their five model wrappers.

A family imports from here and from no other family
(``tests/test_families.py`` walks the imports); what one family alone
computes stays in its module (a projection, a router of its own, a
convolution, a recurrence: those differ by design, and a shared one would
branch on its caller).  Who calls what, so that whoever edits a function
here knows whose programs it moves (``tests/test_lowered_text.py`` pins
them, and ``benchmark/`` runs a cell or two of each):

- the norm and SwiGLU's seeded leaves (:func:`rms_norm`,
  :func:`swiglu_params`): every family but ``transformer``;
  :func:`swiglu`: those but ``sdar_moe``; the head (:func:`logits`): those
  but ``ouro``, which reads its exit pass's state; the rotations:
  :func:`rope` ``mla_moe``, :func:`rope_halves` ``lfm2_moe``, ``sdar_moe``
  and ``ouro``;
- the ``v | k`` rows (:func:`attend_rows`: the decode read of
  ``transformer``, ``lfm2_moe``, ``olmo_hybrid``, ``sdar_moe`` and ``ouro``;
  :func:`attend_entries`: the prefill read of ``lfm2_moe``, ``olmo_hybrid``
  and ``ouro``; :func:`qkv_normed_rotated`: ``lfm2_moe`` and ``sdar_moe``);
- the depthwise taps and a layer's place in its pool (:func:`causal_taps`,
  :func:`causal_taps_token`, :func:`pool_layer`): ``lfm2_moe`` and
  ``olmo_hybrid``;
- the expert layer and its counts (:func:`route_sigmoid`,
  :func:`held_experts`, :func:`moe_layer`, :func:`ffn`, :func:`add_counts`,
  :func:`zero_counts`, :func:`observe_routing`): ``mla_moe``, ``lfm2_moe``
  and ``sdar_moe`` (the last under its own router);
- :class:`FamilyDecoder`: the model contract of every family but
  ``transformer``, whose ``Transformer`` is a trainers' model too.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.layers import glorot_uniform, select_top_k
from dist_keras_tpu.ops.pallas.decode_attention import (
    LATENT_BLOCK_PAGES,
    latent_attention_auto,
    operand_dtype,
)
from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

# behind a step's tokens: pairs on each held expert, then expert layers'
# (layer, held expert) cells that received a token, then all chosen pairs
N_COUNTS = 2


# -- norms, rotations, feed-forward, head --------------------------------
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


def rope_halves(x, positions, theta):
    """Rotary positions on ``x (T, heads, d)`` at ``positions (T,)``,
    rotated by halves: element ``i`` pairs with element ``i + d / 2`` (no
    de-interleaving, unlike :func:`rope`)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def swiglu_params(key, d, f, lead=()):
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": glorot_uniform(kg, lead + (d, f)),
            "w_up": glorot_uniform(ku, lead + (d, f)),
            "w_down": glorot_uniform(kd, lead + (f, d))}


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def logits(params, hs, cfg):
    """The final norm and the head over ``hs``: ``params["head"]``, and
    where a tree holds none the embedding table (tied)."""
    with jax.named_scope("head"):
        # behind a barrier: for a few rows the compiler otherwise folds
        # the norm's weight into the head and scales all of the head's
        # vocabulary x width every step (1.3 GB written and read again).
        # The table is named behind the norm, the order the pinned
        # programs were lowered in (tests/test_lowered_text.py)
        normed = jax.lax.optimization_barrier(
            rms_norm(params["norm_f"], hs, cfg["rms_norm_eps"]))
        return normed @ (params["head"] if "head" in params
                         else params["embed"].T)


# -- the ``v | k`` rows --------------------------------------------------
def qkv_normed_rotated(attn, y, positions, cfg):
    """-> (q (T, H, hd), the cache entry ``v | k`` (T, 2 Hkv hd)), q and
    k normalised a head and rotated by halves."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    with jax.named_scope("qkv"):
        q = jnp.einsum("td,dhk->thk", y, attn["wq"])
        k = jnp.einsum("td,dhk->thk", y, attn["wk"])
        v = jnp.einsum("td,dhk->thk", y, attn["wv"])
    with jax.named_scope("qk_norm_rope"):
        q = rope_halves(rms_norm(attn["q_norm"], q, eps), positions, theta)
        k = rope_halves(rms_norm(attn["k_norm"], k, eps), positions, theta)
    t = y.shape[0]
    return q, jnp.concatenate([v.reshape(t, -1), k.reshape(t, -1)], -1)


def attend_entries(q, entry, hk):
    """Causal attention of one whole sequence over its own ``v | k``
    entries, ``hk`` K/V heads serving the query heads -> (T, H, hd)."""
    t = q.shape[0]
    v, k = jnp.split(entry, 2, axis=-1)
    return attention_auto(q[None], k.reshape(1, t, hk, -1),
                          v.reshape(1, t, hk, -1), causal=True)[0]


def attend_rows(q, pool_rows, page_tables, lengths, hk,
                block_pages=LATENT_BLOCK_PAGES):
    """One query a slot over the paged ``v | k`` rows of ``hk`` K/V heads
    -> (S, H, hd); ``block_pages`` is the read kernel's (a family whose
    rows are wide states fewer than its own)."""
    s, h, hd = q.shape
    mine = (jnp.arange(h)[:, None] // (h // hk)
            == jnp.arange(hk)[None]).astype(q.dtype)            # (H, Hkv)
    # head h's query in the lanes of its own K/V head's keys
    wide = (q[:, :, None, :] * mine[None, :, :, None]).reshape(s, h, -1)
    wide = jnp.concatenate([jnp.zeros_like(wide), wide], -1)
    o = latent_attention_auto(wide, pool_rows, page_tables, lengths,
                              rank=hk * hd, scale=hd ** -0.5,
                              block_pages=block_pages)
    # and of the summed values' row its own K/V head's lanes
    return jnp.einsum("shkd,hk->shd", o.reshape(s, h, hk, hd), mine)


# -- depthwise taps, a layer's place in its pool -------------------------
def causal_taps(kernel, u, length):
    """A depthwise causal convolution over one whole sequence, and what it
    needs of the past afterwards: ``kernel (channels, L)``, ``u (T,
    channels)`` -> (``v_t = sum_j kernel[:, j] u_{t - (L-1) + j}`` with
    ``u`` zero before the sequence's start, ``u`` at positions ``length -
    L + 1 .. length - 1``, zeros on the left of a sequence shorter than
    that)."""
    taps = kernel.shape[1]
    t = u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    v = sum(kernel[:, j] * padded[j:j + t] for j in range(taps))
    # position p is row p + taps - 1 of ``padded``
    return v, jax.lax.dynamic_slice_in_dim(padded, length, taps - 1)


def causal_taps_token(kernel, u, state):
    """The same convolution one position on, a slot each: ``u (S,
    channels)``, ``state (S, L - 1, channels)`` -> (``v (S, channels)``,
    the window ``(S, L, channels)`` it was taken over: ``window[:, 1:]``
    is the state one position on)."""
    window = jnp.concatenate([state, u[:, None]], 1)         # (S, L, d)
    v = sum(kernel[:, j] * window[:, j] for j in range(window.shape[1]))
    return v, window


def pool_layer(cfg, layer):
    """Which layer of its pool layer ``layer`` writes: its ordinal among
    the layers of its kind."""
    kind = cfg["layer_types"][layer]
    return cfg["layer_types"][:layer].count(kind)


# -- the expert layer ----------------------------------------------------
def route_sigmoid(moe, x, cfg):
    """-> (expert ids (N, k), weights (N, k) float32) over ALL the routed
    experts, held here or not: ``s = sigmoid(x Wg)``, the top ``k`` of ``s
    + b`` chosen, their weights the chosen ``s`` WITHOUT ``b``.  The
    chosen scores are divided by their sum plus ``cfg["route_norm_eps"]``
    (``mla_moe``'s published code: 1e-20, the default;
    ``models/lfm2_moe.py`` states its own 1e-6)."""
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


def moe_layer(moe, x, cfg, valid, router=route_sigmoid):
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


def zero_counts(cfg, sequence=0):
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


def add_counts(counts, c):
    """A layer's routing counts ``c`` onto its pass's ``counts``.  A
    prefill of the grouped form keeps one slot more
    (:func:`zero_counts`): the rows its layers' passes covered (whole
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


def observe_routing(counts, at, decode):
    """The routing counts behind a step's tokens -> the ``decode.moe.*``
    instruments: the pairs of every step and prefill; for a ``decode``
    step one sample of each per-step histogram, stamped ``at`` like
    ``decode.step_s``; for a prefill a sample of
    ``decode.moe.prefill_grouped``, stamped like ``decode.prefill_s``,
    and where its program is the grouped form (it says so itself: one
    slot more behind the counts, below zero, :func:`zero_counts`) one of
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


# -- the model contract --------------------------------------------------
class FamilyDecoder:
    """Model-contract wrapper (cfg + params + weights round-trip) that the
    serialization layer and ``DecodeEngine`` take.  A family's subclass
    states ``family`` (its module: ``init_params`` and ``forward`` are
    read from it), ``config`` (its config function) and ``name``; a saved
    model carries the subclass's name.  Weights are made from ``seed`` on
    first use, so a deserialized copy that is handed its weights never
    holds a second, random set."""

    family = config = name = None

    def __init__(self, cfg=None, seed=0, **cfg_kw):
        self.cfg = cfg or self.config(**cfg_kw)
        self._seed = seed
        self._params = None

    @property
    def params(self):
        if self._params is None:
            self._params = self.family.init_params(
                jax.random.PRNGKey(self._seed), self.cfg)
        return self._params

    def apply(self, params, tokens, *, training=False, rng=None):
        return self.family.forward(params, tokens, self.cfg)

    def __call__(self, tokens, *, training=False, rng=None):
        return self.apply(self.params, jnp.asarray(tokens))

    def set_params(self, params):
        self._params = jax.tree.map(jnp.asarray, params)

    def get_weights(self):
        return [np.asarray(leaf) for leaf in jax.tree.leaves(self.params)]

    def set_weights(self, weights):
        shapes = jax.eval_shape(
            functools.partial(self.family.init_params, cfg=self.cfg),
            jax.random.PRNGKey(0))
        self._params = jax.tree.unflatten(
            jax.tree.structure(shapes), [jnp.asarray(w) for w in weights])

    def to_json(self):
        return json.dumps({"class_name": type(self).__name__,
                           "config": self.cfg})
