"""The latent-attention, sparse-expert decoder family (``models/mla_moe.py``)
at a small size on the CPU, against the plain reference the benchmark
keeps (``benchmark/reference/mla_moe_ref.py``): whole-sequence forward,
prefill then decoding through ``DecodeEngine``'s paged latent pool, the
routing case table, the two forms of the held experts' sum (a masked
dense pass, and over 1,024 tokens the pairs sorted by expert), the share
test, the pool's layout and the routing counters.  Logits are compared,
not tokens.

Tolerances.  Everything here is float32 on the CPU, the program under
``jax.default_matmul_precision("highest")`` where it is compared (the
reference sets it product by product), so program and reference differ by
the order of float32 sums only: logits of magnitude up to 3 agree to
about 3e-6, and the limit is 1e-4 (30 times the reading, five hundred
times under the 0.05 by which a bfloat16 product moves such a logit).
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import mla_moe as family
from benchmark.reference import mla_moe_ref as ref
from dist_keras_tpu.models import blocks, mla_moe, transformer
from dist_keras_tpu.models.layers import select_top_k
from dist_keras_tpu.models.transformer import Transformer, transformer_config
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.serving import DecodeEngine

TOL = 1e-4
VOCAB = 128
SIZES = dict(vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=4,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             kv_lora_rank=32, d_ff=96, moe_d_ff=48, n_routed_experts=8,
             n_shared_experts=1, top_k=3, n_layers=3, first_k_dense=1,
             routed_scaling_factor=2.446, rope_theta=800000.0)


def config(held=(2, 3, 4), **kw):
    return mla_moe.mla_moe_config(**{**SIZES, "held_experts": list(held),
                                     **kw})


def weights_for(cfg, seed=2 ** 31 + 7):
    """The benchmark's seeded weights: the ones a chip run hands to the
    program and to the reference alike."""
    from benchmark import weights

    return family.tree(weights.base_key(seed), cfg)


def reference_logits(params, tokens, cfg, **kw):
    return ref.forward(params, jnp.asarray(tokens),
                       family.reference_config(cfg),
                       tuple(cfg["held_experts"]), **kw)


def engine_for(cfg, params, **kw):
    model = mla_moe.LatentMoEDecoder(cfg=cfg)
    model.set_params(params)
    kw.setdefault("replicas", 1)
    kw.setdefault("prefill_ladder", (8, 16, 32))
    kw.setdefault("decode_ladder", (1, 4))
    kw.setdefault("page_size", 4)
    return DecodeEngine(model, **kw)


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (1) whole-sequence forward ----------------------------------------
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7])
def test_forward_equals_the_reference(highest, seed):
    cfg = config()
    params = weights_for(cfg, seed)
    tokens = np.random.default_rng(seed).integers(0, VOCAB, 40)
    got = mla_moe.forward(params, jnp.asarray(tokens), cfg)
    want = reference_logits(params, tokens, cfg)
    assert got.shape == (40, VOCAB)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_benchmark_weights_are_in_the_programs_layout():
    cfg = config()
    mine = jax.eval_shape(lambda: weights_for(cfg))
    theirs = jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(mine)] == \
        [a.shape for a in jax.tree.leaves(theirs)]


def test_serialization_round_trip_holds_no_second_set_of_weights():
    from dist_keras_tpu.utils.serialization import (
        deserialize_model,
        serialize_model,
    )

    cfg = config()
    model = mla_moe.LatentMoEDecoder(cfg=cfg, seed=3)
    back = deserialize_model(serialize_model(model))
    assert isinstance(back, mla_moe.LatentMoEDecoder) and back.cfg == cfg
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(back.params)):
        np.testing.assert_array_equal(a, b)
    fresh = mla_moe.LatentMoEDecoder(cfg=cfg)
    assert fresh._params is None          # made on first use, not before
    fresh.set_weights(model.get_weights())
    assert fresh._params is not None


# -- (2) prefill, then decoding through the paged latent pool ----------
def test_steps_over_the_pool_equal_the_reference_at_every_position(highest):
    """Teacher-forced: two sequences prefilled into scattered pages, then
    stepped together on a 4-slot rung whose other two slots are padding;
    the first crosses a page boundary (positions 6..13, pages of 4).  The
    logits of every step equal the reference's full forward."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, VOCAB, 14), rng.integers(0, VOCAB, 17)]
    prompts = [6, 9]
    ps, n_pages = 4, 12
    pool = jnp.zeros((cfg["n_layers"], n_pages + 1, ps)
                     + mla_moe.cache_pools(cfg)[0][2])
    pages = [[7, 2, 9, 4, 0], [5, 11, 1, 8, 3]]       # scratch page is 12
    for toks, n, mine in zip(seqs, prompts, pages):
        rung = 16
        padded = np.zeros((rung,), np.int32)
        padded[:n] = toks[:n]
        page_idx = np.full((rung,), n_pages, np.int32)
        page_idx[:n] = [mine[t // ps] for t in range(n)]
        out, pool = mla_moe.prefill_step(
            cfg, params, pool, jnp.asarray(padded), jnp.int32(n),
            jnp.asarray(page_idx), jnp.arange(rung, dtype=jnp.int32) % ps)
        want = reference_logits(params, toks[:n], cfg)[-1]
        assert int(out[0]) == int(jnp.argmax(want))
    wants = [reference_logits(params, toks, cfg) for toks in seqs]
    for step in range(8):
        at = [n + step for n in prompts]
        tables = np.zeros((4, 5), np.int32)
        tables[0], tables[1] = pages
        lengths = np.array([at[0] + 1, at[1] + 1, 0, 0], np.int32)
        hs, counts, pool = mla_moe._decode_layers(
            cfg, params, pool,
            jnp.asarray([seqs[0][at[0]], seqs[1][at[1]], 0, 0], jnp.int32),
            jnp.asarray(at + [0, 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray([pages[0][at[0] // ps], pages[1][at[1] // ps],
                         n_pages, n_pages], jnp.int32),
            jnp.asarray([at[0] % ps, at[1] % ps, 0, 0], jnp.int32),
            jnp.asarray(lengths))
        got = blocks.logits(params, hs, cfg)
        for slot in (0, 1):
            np.testing.assert_allclose(got[slot], wants[slot][at[slot]],
                                       atol=TOL, rtol=0)
        # padding slots reached no expert: two real tokens, two layers
        assert int(counts[-1]) == 2 * cfg["top_k"] * 2


def test_engine_tokens_are_the_references_own(highest):
    """Through ``DecodeEngine`` itself: three requests of different
    lengths on the 4-slot rung (one padding slot), replies that cross
    page boundaries; every served token's logit is the reference's best
    to within the tolerance."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(11)
    with engine_for(cfg, params) as eng:
        gens = [eng.submit_generate(rng.integers(0, VOCAB, n).tolist(),
                                    max_new_tokens=m)
                for n, m in ((7, 12), (13, 9), (3, 14))]
        docs = [g.result(timeout=600) for g in gens]
        assert ("decode", 4) in eng.stats()["shapes_dispatched"]
    for doc in docs:
        z = np.asarray(reference_logits(params, doc["tokens"][:-1], cfg))
        z = z[doc["prompt_len"] - 1:]
        gap = z.max(axis=1) - z[np.arange(len(z)), doc["generated"]]
        assert gap.max() <= TOL, gap
    eng.assert_no_leaks()


# -- (3) the two forms of the attention --------------------------------
@pytest.mark.parametrize("q_block", [None, 16])
def test_absorbed_and_unabsorbed_attention_agree_in_the_reference(q_block):
    cfg = config()
    conf = family.reference_config(cfg)
    blk = weights_for(cfg)["blocks"][1]
    y = jax.random.normal(jax.random.PRNGKey(3), (40, cfg["d_model"]))
    plain = ref.attention(blk, y, conf, q_block=q_block)
    absorbed = ref.attention(blk, y, conf, q_block=q_block, absorbed=True)
    # the same numbers in exact arithmetic; float32 sums in another order
    np.testing.assert_allclose(plain, absorbed, atol=1e-5, rtol=0)


# -- (4) routing --------------------------------------------------------
def _moe_with(router, bias, cfg):
    moe = weights_for(cfg)["blocks"][1]["moe"]
    return {**moe, "router": jnp.asarray(router, jnp.float32),
            "router_bias": jnp.asarray(bias, jnp.float32)}


def _logit(p):
    return float(np.log(p / (1 - p)))


ROUTING = {
    # scores s by expert, selection bias b -> the chosen experts
    "by_score": ([.9, .8, .7, .6, .5, .4, .3, .2], [0] * 8, [0, 1, 2]),
    "bias_lifts_a_lower_score": ([.9, .8, .7, .6, .5, .4, .3, .2],
                                 [0, 0, 0, 0, 0, 0, 0, .65], [0, 1, 7]),
    "bias_sinks_the_best": ([.9, .8, .7, .6, .5, .4, .3, .2],
                            [-.5, 0, 0, 0, 0, 0, 0, 0], [1, 2, 3]),
    "first_of_equals": ([.5] * 8, [0] * 8, [0, 1, 2]),
}


@pytest.mark.parametrize("case", sorted(ROUTING))
def test_routing_selects_by_score_plus_bias_and_weighs_by_score(case):
    scores, bias, chosen = ROUTING[case]
    cfg = config(d_model=8, n_heads=1)
    # x = e_0, router row 0 = logit(s): the scores are exactly ``scores``
    router = np.zeros((8, 8), np.float32)
    router[0] = [_logit(p) for p in scores]
    x = jnp.zeros((1, 8)).at[0, 0].set(1.0)
    idx, w = blocks.route_sigmoid(_moe_with(router, bias, cfg), x, cfg)
    assert sorted(np.asarray(idx[0]).tolist()) == chosen
    s = np.asarray(scores)[np.asarray(idx[0])]
    # weights from s alone (no bias), normalised, times the scaling factor
    np.testing.assert_allclose(
        w[0], s / s.sum() * cfg["routed_scaling_factor"], rtol=1e-5)
    want_idx, want_w = ref.routing(_moe_with(router, bias, cfg), x,
                                   family.reference_config(cfg))
    assert sorted(np.asarray(want_idx[0]).tolist()) == chosen
    np.testing.assert_allclose(np.sort(w[0]), np.sort(want_w[0]),
                               rtol=1e-6)


def test_every_token_to_one_expert_drops_none(highest):
    """No capacity: 24 tokens whose three choices are all the same three
    experts, two of them held; every pair is computed."""
    cfg = config(held=(2, 3))
    conf = family.reference_config(cfg)
    moe = weights_for(cfg)["blocks"][1]["moe"]
    moe = {**moe, "router": jnp.zeros_like(moe["router"]),
           "router_bias": jnp.zeros((8,)).at[jnp.asarray([1, 2, 3])].set(1.)}
    x = jax.random.normal(jax.random.PRNGKey(0), (24, cfg["d_model"]))
    got, counts = blocks.moe_layer(moe, x, cfg, jnp.ones((24,), bool))
    assert counts.tolist() == [24, 24, 2, 72]
    want = ref.expert_layer(moe, x, conf, (2, 3))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # padding tokens reach no expert and count nowhere
    valid = jnp.arange(24) < 10
    _, counts = blocks.moe_layer(moe, x, cfg, valid)
    assert counts.tolist() == [10, 10, 2, 30]
    # nor does the grouped form know a capacity: the same router over more
    # tokens than the crossover, every pair on two experts, five passes each
    n = N_OVER[0]
    x = jax.random.normal(jax.random.PRNGKey(0), (n, cfg["d_model"]))
    got, counts = blocks.moe_layer(moe, x, cfg, jnp.ones((n,), bool))
    assert counts.tolist() == [n, n, 2, 3 * n]
    want = ref.expert_layer(moe, x, conf, (2, 3))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# -- (4b) the two forms of the held experts' sum ---------------------------
# a call of more than blocks.GROUPED_OVER tokens runs the pairs sorted by
# expert, an expert at a time; the dense form of the same call is what the
# same function gives with the crossover out of reach
N_OVER = (1280, 2560)
# of the largest output: the order of a token's three float32 additions;
# a bfloat16 rounding of the rows and the experts (the default precision
# on the CPU leaves the dense side float32)
EXACT, ROUNDED = 1e-6, 2e-2


def _dense_form(monkeypatch, fn, *args, **kw):
    with monkeypatch.context() as m:
        m.setattr(blocks, "GROUPED_OVER", 10 ** 9)
        return fn(*args, **kw)


def _some_of(rng, experts, k, n):
    """``k`` distinct experts of ``experts`` a token, in a random order."""
    return rng.permuted(np.tile(np.asarray(experts), (n, 1)), axis=1)[:, :k]


# name -> (the experts held, the experts a token's three are drawn from)
GROUPED = {
    "every_expert_held": (range(8), range(8)),
    "pairs_on_experts_not_held": ((2, 3, 4), range(8)),
    # and expert 3 put in every token's second place
    "all_to_one_held_expert": ((2, 3, 4), (0, 1, 5, 6, 7)),
    "a_held_expert_without_a_pair": ((2, 3, 4), (0, 1, 2, 4, 5, 6, 7)),
    "no_pair_held": ((2, 3, 4), (0, 1, 5, 6, 7)),
    "padding_in_the_middle_and_at_the_end": ((2, 3, 4), range(8)),
}


def _grouped_case(case, n):
    held, drawn = GROUPED[case]
    rng = np.random.default_rng(sorted(GROUPED).index(case))
    idx, at = _some_of(rng, drawn, 3, n), np.arange(n)
    if case == "all_to_one_held_expert":
        idx[:, 1] = 3
    valid = (((at < 300) | (at >= 420)) & (at < n - 130)
             if case.startswith("padding") else np.ones(n, bool))
    experts = blocks.swiglu_params(jax.random.PRNGKey(3), 64, 48,
                                     (len(held),))
    x = jax.random.normal(jax.random.PRNGKey(4), (n, 64))
    w = jnp.asarray(rng.uniform(0.1, 1.0, idx.shape), jnp.float32)
    return (experts, x, jnp.asarray(idx, jnp.int32), w, held[0],
            jnp.asarray(valid))


@pytest.mark.parametrize("precision,bound", [("highest", EXACT),
                                             (None, ROUNDED)])
@pytest.mark.parametrize("n", N_OVER)
@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_form_equals_the_dense_form(monkeypatch, case, n, precision,
                                            bound):
    """Both forms on the same pairs: under "highest" they differ by the
    order of a token's at most ``top_k`` float32 additions; under the
    default the grouped form rounds the rows and the experts to bfloat16
    (the one MXU pass of the dense products on a TPU; on the CPU the
    dense side stays float32), a rounding's size of the largest output.
    The pairs on each held expert are the same numbers; an expert with
    pairs takes several passes of ``GROUP_TILE_ROWS``, the last not full,
    and one without takes none."""
    args = _grouped_case(case, n)
    with jax.default_matmul_precision(precision or "default"):
        want, want_sizes = _dense_form(monkeypatch, blocks.held_experts,
                                       *args)
        got, sizes = blocks.held_experts(*args)
    sizes = np.asarray(sizes)
    assert sizes.tolist() == want_sizes.tolist()
    tile = blocks.GROUP_TILE_ROWS
    assert all(size == 0 or size > tile for size in sizes)
    # (all to one: 1,280 and 2,560 pairs, whole passes and none behind)
    assert any(sizes % tile) or case in ("no_pair_held",
                                         "all_to_one_held_expert")
    if case == "no_pair_held":
        assert sizes.sum() == 0 and not got.any()
    if case == "a_held_expert_without_a_pair":
        assert sizes[1] == 0 and sizes[0] > tile < sizes[2]
    top = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(got, want, atol=bound * top, rtol=0)


def test_rows_behind_an_experts_pairs_are_selected_away(monkeypatch,
                                                        highest):
    """A pass's rows behind its expert's last pair are another expert's
    pairs or a padding token's, whose content may be anything: with NaN
    in every padding token, every other token's sum is the dense form's
    still (a zero routing weight would not do: 0 x NaN)."""
    experts, x, idx, w, first, valid = _grouped_case(
        "padding_in_the_middle_and_at_the_end", N_OVER[0])
    x = jnp.where(valid[:, None], x, jnp.nan)
    args = (experts, x, idx, w, first, valid)
    want, _ = _dense_form(monkeypatch, blocks.held_experts, *args)
    got, sizes = blocks.held_experts(*args)
    # some expert's last pass is not full: rows behind its pairs there are
    assert any(np.asarray(sizes) % blocks.GROUP_TILE_ROWS)
    real = np.asarray(valid)
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(want)[real]).all()
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=EXACT * 10, rtol=0)
    assert not np.asarray(got)[~real].any()       # no pair, nothing added


def _sdar_layer():
    from dist_keras_tpu.models import sdar_moe

    cfg = sdar_moe.sdar_moe_config(
        vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, moe_d_ff=48, n_routed_experts=8, top_k=3, n_layers=1,
        held_experts=[1, 2, 3], block_length=4, denoising_steps=4,
        mask_token_id=VOCAB - 1)
    moe = sdar_moe.init_params(jax.random.PRNGKey(5), cfg)["blocks"][0]["moe"]
    return cfg, moe, {"router": sdar_moe.route}


def _lfm2_layer():
    from dist_keras_tpu.models import lfm2_moe

    cfg = lfm2_moe.lfm2_moe_config(
        vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=96, moe_d_ff=48, n_routed_experts=8, top_k=3,
        layer_types=["conv", "full_attention", "conv"])
    blocks = lfm2_moe.init_params(jax.random.PRNGKey(6), cfg)["blocks"]
    return cfg, next(b["moe"] for b in blocks if "moe" in b), {}


def _mla_layer():
    cfg = config()
    return cfg, weights_for(cfg)["blocks"][1]["moe"], {}


@pytest.mark.parametrize("layer", [_mla_layer, _sdar_layer, _lfm2_layer],
                         ids=["mla_moe", "sdar_moe", "lfm2_moe"])
def test_each_familys_layer_takes_the_grouped_form_over_the_crossover(
        monkeypatch, highest, layer):
    """``moe_layer`` as the three families call it (``sdar_moe`` with its
    softmax router and no shared expert, ``lfm2_moe`` with every expert
    held): over the crossover the layer's output and its counts are the
    dense form's."""
    cfg, moe, kw = layer()
    n = N_OVER[0]
    x = jax.random.normal(jax.random.PRNGKey(7), (n, cfg["d_model"]))
    valid = jnp.arange(n) < n - 9
    want, want_counts = _dense_form(monkeypatch, blocks.moe_layer, moe, x,
                                    cfg, valid, **kw)
    got, counts = blocks.moe_layer(moe, x, cfg, valid, **kw)
    assert counts.tolist() == want_counts.tolist()
    assert counts[-1] == (n - 9) * cfg["top_k"]
    top = max(float(jnp.max(jnp.abs(want))), 1.0)
    np.testing.assert_allclose(got, want, atol=10 * EXACT * top, rtol=0)


def test_forward_over_the_crossover_equals_the_reference(highest):
    """A whole sequence of more than 1,024 tokens: every expert layer
    takes the grouped form, and the logits are the plain reference's."""
    n = N_OVER[0]
    cfg = config(seq_len=n + 2, n_layers=2)
    params = weights_for(cfg)
    tokens = np.random.default_rng(11).integers(0, VOCAB, n)
    got = mla_moe.forward(params, jnp.asarray(tokens), cfg)
    want = reference_logits(params, tokens, cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("tokens,functions", [(N_OVER[0], 2), (64, 0)],
                         ids=["over", "under"])
def test_a_long_sequences_layers_of_one_structure_lower_once(tokens,
                                                             functions):
    """Over the crossover the program holds one function for the dense
    layer and ONE for the three expert layers, called a layer each (a
    long prefill's set-up traces and lowers a structure once, PR 47); a
    shorter sequence's text has no such function, as before."""
    cfg = config(seq_len=N_OVER[0] + 2, n_layers=4)
    params = jax.eval_shape(lambda: weights_for(cfg))
    text = jax.jit(lambda p, t: mla_moe.forward(p, t, cfg)).lower(
        params, jax.ShapeDtypeStruct((tokens,), jnp.int32)).as_text()
    assert len(re.findall(r"func\.func private @layer", text)) == functions
    assert len(re.findall(r"call @layer", text)) == (
        cfg["n_layers"] if functions else 0)


def test_switch_router_is_the_one_selection_at_k_1():
    """``parallel/moe.py:_route`` chooses through ``select_top_k``: the
    first of equal scores, as argmax did."""
    from dist_keras_tpu.parallel.moe import _route, init_moe_params

    params = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 16))
    dispatch, combine, _ = _route(params, x, 4, 12)
    probs = jax.nn.softmax(x @ params["router"], -1)
    np.testing.assert_array_equal(dispatch.sum(-1).argmax(-1),
                                  probs.argmax(-1))
    np.testing.assert_allclose(combine.sum((-1, -2)), probs.max(-1),
                               rtol=1e-6)
    idx, s = select_top_k(jnp.ones((3, 5)), None, 1)
    assert idx.tolist() == [[0]] * 3 and s.tolist() == [[1.0]] * 3


# -- (5) the share -------------------------------------------------------
@pytest.mark.parametrize("per_share", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(highest, per_share):
    """The parts all the shares give, the shared expert counted once, add
    up to the uncut layer of the reference."""
    whole = config(held=range(8))
    conf = family.reference_config(whole)
    moe = weights_for(whole)["blocks"][2]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (20, whole["d_model"]))
    valid = jnp.ones((20,), bool)
    uncut = ref.expert_layer(moe, x, conf, tuple(range(8)))
    shared = blocks.swiglu(moe["shared"], x)
    total, pairs = jnp.zeros_like(uncut), 0
    for first in range(0, 8, per_share):
        held = tuple(range(first, first + per_share))
        mine = {**moe, "experts": jax.tree.map(
            lambda leaf: leaf[first:first + per_share], moe["experts"])}
        part, counts = blocks.moe_layer(mine, x, config(held=held), valid)
        # the program's share is the reference's share
        np.testing.assert_allclose(
            part, ref.expert_layer(mine, x, conf, held), atol=TOL, rtol=0)
        total = total + (part - shared)
        pairs += int(counts[:per_share].sum())
    np.testing.assert_allclose(total + shared, uncut, atol=TOL, rtol=0)
    assert pairs == 20 * whole["top_k"]     # every chosen pair, once


# -- (6) the pool ---------------------------------------------------------
def test_pool_shape_is_one_latent_pool_and_the_old_family_is_unchanged():
    cfg = config()
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    lanes = -(-width // mla_moe.LANES) * mla_moe.LANES
    assert mla_moe.cache_pools(cfg) == (
        (cfg["n_layers"], "page", (lanes,)),)
    with engine_for(cfg, weights_for(cfg), num_pages=20) as eng:
        assert eng.pool_shapes == ((cfg["n_layers"], 21, 4, lanes),)
        (pool,) = eng._replicas[0].pools
        assert (pool.shape,) == eng.pool_shapes
        assert pool.dtype == jnp.float32
        # a mixed run: short and long prompts, one cancelled mid-way
        rng = np.random.default_rng(2)
        gens = [eng.submit_generate(rng.integers(0, VOCAB, n).tolist(),
                                    max_new_tokens=m)
                for n, m in ((3, 4), (20, 6), (9, 10))]
        gens[2].cancel()
        for g in gens:
            g.result(timeout=600)
        for rep in eng._replicas:
            rep.cache.assert_balanced()
            assert rep.cache.used_pages() == 0
        # the rows' lanes past the entry stay zero (the steps donated the
        # pool looked at above: this is its successor)
        (pool,) = eng._replicas[0].pools
        assert float(jnp.abs(pool[..., width:]).max()) == 0.0
    old = transformer_config(input_dim=16, seq_len=32, d_model=16, n_heads=2,
                             n_layers=2, n_classes=16)
    assert transformer.cache_pools(old) == ((2, "page", (32,)),)
    with DecodeEngine(Transformer(old), replicas=1, prefill_ladder=(4,),
                      decode_ladder=(1,), page_size=4) as eng:
        assert eng.pool_shapes == ((2, eng.num_pages + 1, 4, 32),)


@pytest.mark.parametrize("cfg_kw,match", [
    (dict(moe_experts=4), "Switch-MoE"),
    (dict(n_classes=8), "token-in == logit-out"),
])
def test_what_the_engine_cannot_decode_is_refused_with_what_it_can(
        cfg_kw, match):
    kw = dict(input_dim=16, seq_len=32, d_model=16, n_heads=2, n_layers=1,
              n_classes=16)
    kw.update(cfg_kw)
    with pytest.raises(ValueError, match=match) as e:
        DecodeEngine(Transformer(transformer_config(**kw)), replicas=1)
    assert "mla_moe" in str(e.value)


# -- (8) the counters ------------------------------------------------------
HISTOGRAMS = ("decode.moe.load_max_over_mean", "decode.moe.experts_hit",
              "decode.latent.live_positions")
PAIRS = ("decode.moe.pairs_total", "decode.moe.pairs_held")


def test_routing_counters_exist_and_are_stamped():
    import time

    cfg = config(held=(6, 7), n_routed_experts=16)
    for name in PAIRS + HISTOGRAMS:
        assert name in metrics.KNOWN_METRICS
    before = [metrics.counter(n).value for n in PAIRS]
    lo = time.perf_counter()
    rng = np.random.default_rng(4)
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,)) as eng:
        gens = [eng.submit_generate(rng.integers(0, VOCAB, 30).tolist(),
                                    max_new_tokens=16) for _ in range(4)]
        docs = [g.result(timeout=600) for g in gens]
    hi = time.perf_counter()
    total, held = (metrics.counter(n).value - b
                   for n, b in zip(PAIRS, before))
    # every real token of every prefill and step, in both expert layers
    tokens = sum(d["prompt_len"] + len(d["generated"]) - 1 for d in docs)
    assert total == tokens * cfg["top_k"] * 2
    assert 0 < held < total
    # one sample a decode step, stamped with the step's start like
    # decode.step_s, so that a reader can cut a window out
    steps = metrics.histogram("decode.step_s").samples_between(lo, hi)[0]
    for name in HISTOGRAMS:
        pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
        assert not truncated and pairs, name
        assert {at for at, _ in pairs} <= {at for at, _ in steps}, name
    live = [v for _, v in metrics.histogram(
        "decode.latent.live_positions").samples_between(lo, hi)[0]]
    assert min(live) >= 31 and max(live) <= 4 * 46
    hit = [v for _, v in metrics.histogram(
        "decode.moe.experts_hit").samples_between(lo, hi)[0]]
    assert all(0 <= v <= 2 * 2 for v in hit)


def test_a_prefill_stamps_its_form_and_its_tile_fill(monkeypatch, highest):
    """``decode.moe.prefill_grouped``: a sample a prefill, stamped like
    ``decode.prefill_s``: 100 for a rung over the crossover, 0 under it;
    ``decode.moe.tile_fill_pct``: a sample a prefill of the grouped form,
    the held pairs over the rows its passes covered, which the program
    reckons layer by layer and sends behind its other counts, below zero
    (``-1 - rows``): the counts themselves say the form.  The first token
    and the pairs of such a prefill are the dense form's and the plain
    reference's."""
    import functools
    import time

    for name in ("decode.moe.prefill_grouped", "decode.moe.tile_fill_pct"):
        assert metrics.KNOWN_METRICS[name] == "histogram"
    rung, tile = N_OVER[0] + 8, blocks.GROUP_TILE_ROWS
    # ONE expert layer, so that the counts are that layer's own
    cfg = config(seq_len=rung + 8, n_layers=2)
    params = weights_for(cfg)
    rng = np.random.default_rng(5)
    long = rng.integers(0, VOCAB, rung - 30).tolist()
    lo = time.perf_counter()
    with engine_for(cfg, params, prefill_ladder=(16, rung),
                    decode_ladder=(2,)) as eng:
        docs = [eng.submit_generate(prompt, max_new_tokens=2).result(
            timeout=600) for prompt in (long, long[:10])]
        (pool_shape,) = eng.pool_shapes
    hi = time.perf_counter()

    def between(name):
        pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
        assert not truncated
        return pairs

    grouped, fill = (between("decode.moe.prefill_grouped"),
                     between("decode.moe.tile_fill_pct"))
    assert [v for _, v in grouped] == [100.0, 0.0]
    assert [at for at, _ in grouped] == [
        at for at, _ in between("decode.prefill_s")]
    assert [at for at, _ in fill] == [grouped[0][0]]
    # the arithmetic, on the counts of the same prefill run apart: one
    # count more than a dense program's rides behind them
    tokens = np.zeros((rung,), np.int32)
    tokens[:len(long)] = long
    step = jax.jit(functools.partial(mla_moe.prefill_step, cfg))
    args = (params, jnp.zeros(pool_shape), jnp.asarray(tokens), len(long),
            jnp.full((rung,), pool_shape[1] - 1),
            jnp.zeros((rung,), jnp.int32))
    out = np.asarray(step(*args)[0])
    first, counts = out[0], out[1:]
    assert counts.shape == (3 + blocks.N_COUNTS + 1,)
    sizes, covered = counts[:3].astype(np.int64), -1 - int(counts[-1])
    assert covered == tile * (-(-sizes // tile)).sum() > sizes.sum() > 0
    assert fill[0][1] == 100.0 * sizes.sum() / covered
    # the dense form of the same prefill: the same token and pairs, and
    # the parent's width; the token is the engine's and the reference's
    dense = np.asarray(_dense_form(
        monkeypatch, jax.jit(functools.partial(mla_moe.prefill_step, cfg)),
        *args)[0])
    assert dense.tolist() == out[:-1].tolist()
    want = reference_logits(params, long, cfg)
    assert first == docs[0]["generated"][0] == int(jnp.argmax(want[-1]))
    # the same counts by hand: with the last slot and without it
    at = time.perf_counter()
    mla_moe.observe_step(counts, at)
    mla_moe.observe_step(counts[:-1], at + 1e-3)
    assert metrics.histogram("decode.moe.prefill_grouped").samples_between(
        at, at + 1)[0] == [(at, 100.0), (at + 1e-3, 0.0)]
    assert metrics.histogram("decode.moe.tile_fill_pct").samples_between(
        at, at + 1)[0] == [(at, fill[0][1])]


@pytest.mark.parametrize("module", ["mla_moe", "lfm2_moe", "sdar_moe"])
def test_only_a_long_prefills_counts_grow(module):
    """The engine carries ONE output width from step to step, whatever the
    rung: a decode step over more rows than the crossover runs the grouped
    form and still sends the counts a dense one sends, as a prefill of
    1,024 tokens does; a prefill over the crossover sends one more."""
    import functools
    import importlib

    mod = importlib.import_module(f"dist_keras_tpu.models.{module}")
    rows = N_OVER[0]
    if module == "mla_moe":
        cfg, width = config(seq_len=rows + 2, n_layers=2), 1
        params = jax.eval_shape(lambda: weights_for(cfg))
    else:
        cfg = {"lfm2_moe": _lfm2_layer, "sdar_moe": _sdar_layer}[module]()[0]
        cfg = {**cfg, "seq_len": rows + 2}
        width = mod.step_width(cfg)
        params = jax.eval_shape(functools.partial(mod.init_params, cfg=cfg),
                                jax.random.PRNGKey(0))
    held = len(cfg["held_experts"])
    pools = [jax.ShapeDtypeStruct(
        (layers, 9 if kind == "page" else rows + 1)
        + ((4,) if kind == "page" else ()) + tuple(entry), jnp.float32)
        for layers, kind, entry in mod.cache_pools(cfg)]

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def prefill_width(n):
        args = (ints(n), ints(), ints(n), ints(n))
        if len(pools) > 1:
            args += (ints(),)
        out = jax.eval_shape(functools.partial(mod.prefill_step, cfg),
                             params, *pools, *args)[0]
        return out.shape[0] - (1 if width == 1 else 0)

    assert prefill_width(blocks.GROUPED_OVER) == held + blocks.N_COUNTS
    assert prefill_width(rows) == held + blocks.N_COUNTS + 1
    if module != "mla_moe":
        return
    step, _ = jax.eval_shape(
        functools.partial(mla_moe.decode_step, cfg), params, *pools,
        ints(rows), ints(rows), ints(rows, 2), ints(rows), ints(rows),
        ints(rows))
    assert step.shape == (rows + held + blocks.N_COUNTS,)


def test_walked_positions_are_stamped_once_a_step_with_the_block_arithmetic():
    """``decode.latent.walked_positions``: one sample a decode step,
    stamped like ``decode.step_s``, equal to what the latent kernel's
    blocks of pages fetch for that step's lengths (each live slot's length
    rounded up to ``LATENT_BLOCK_PAGES`` pages): host arithmetic on the
    lengths the worker already has."""
    import time

    from dist_keras_tpu.ops.pallas.decode_attention import (
        LATENT_BLOCK_PAGES,
    )

    assert metrics.KNOWN_METRICS["decode.latent.walked_positions"] == \
        "histogram"
    walked_h = metrics.histogram("decode.latent.walked_positions")
    page = 4
    block = LATENT_BLOCK_PAGES * page               # 128 positions
    # known lengths: a padding slot, one position, a block to the last
    # position, one past it, and three blocks and a bit
    lengths = np.asarray([0, 1, block, block + 1, 3 * block + 7], np.int32)
    counts = np.zeros((3 + blocks.N_COUNTS,), np.int32)
    at = time.perf_counter()
    mla_moe.observe_step(counts, at, lengths=lengths, page_size=page)
    assert walked_h.samples_between(at, at + 1e-6)[0] == [
        (at, (0 + 1 + 1 + 2 + 4) * block)]
    assert metrics.histogram("decode.latent.live_positions").samples_between(
        at, at + 1e-6)[0] == [(at, int(lengths.sum()))]
    # a prefill passes no lengths and stamps nothing
    mla_moe.observe_step(counts, at + 1e-3)
    assert walked_h.samples_between(at + 1e-3, at + 2e-3)[0] == []

    # through the engine: once a decode step, with the step's own stamp.
    # Prompts whose block counts stay 1, 2 and 3 for all 11 steps
    cfg = config(seq_len=400)
    rng = np.random.default_rng(9)
    lo = time.perf_counter()
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,),
                    prefill_ladder=(8, 256, 384), page_size=page) as eng:
        gens = [eng.submit_generate(rng.integers(0, VOCAB, n).tolist(),
                                    max_new_tokens=12)
                for n in (5, 130, 260)]
        for g in gens:
            g.result(timeout=600)
    hi = time.perf_counter()
    steps = metrics.histogram("decode.step_s").samples_between(lo, hi)[0]
    walked = walked_h.samples_between(lo, hi)[0]
    live = metrics.histogram("decode.latent.live_positions").samples_between(
        lo, hi)[0]
    assert [at for at, _ in walked] == [at for at, _ in steps]
    assert len(walked) == len(live) >= 11
    for (_, w), (_, n) in zip(walked, live):
        assert w in [k * block for k in range(1, 7)] and n <= w
    assert max(w for _, w in walked) == 6 * block     # all three at once


def test_held_share_reads_an_eighth_on_uniform_routing():
    """16 experts, 2 held, top 3: with router columns of one length, no
    bias and isotropic tokens every expert is as likely as another, and
    an eighth of the chosen pairs is held, within sampling (4096 tokens:
    a standard deviation of 0.003)."""
    cfg = config(held=(6, 7), n_routed_experts=16)
    moe = weights_for(cfg)["blocks"][1]["moe"]
    router = jax.random.normal(jax.random.PRNGKey(1), moe["router"].shape)
    moe = {**moe, "router_bias": jnp.zeros((16,)),
           "router": router / jnp.linalg.norm(router, axis=0)}
    x = jax.random.normal(jax.random.PRNGKey(2), (4096, cfg["d_model"]))
    _, counts = blocks.moe_layer(moe, x, cfg, jnp.ones((4096,), bool))
    before = [metrics.counter(n).value for n in PAIRS]
    mla_moe.observe_step(counts, at=0.0)
    total, held = (metrics.counter(n).value - b
                   for n, b in zip(PAIRS, before))
    assert total == 4096 * 3
    assert abs(held / total - 1 / 8) < 0.015, held / total


# -- scopes ------------------------------------------------------------------
SCOPES = {"decode": ("embed", "mla_q", "latent_write", "attend_latent",
                     "attn_out", "moe_route", "moe_experts", "moe_shared",
                     "mlp", "head"),
          "prefill": ("embed", "mla_q", "latent_write", "attend", "attn_out",
                      "moe_route", "moe_experts", "moe_shared", "mlp",
                      "head")}


@pytest.mark.parametrize("phase", sorted(SCOPES))
def test_steps_carry_their_names_and_scopes(phase):
    """The engine's jitted steps are ``_packed_prefill_fn`` /
    ``_packed_decode_fn`` whatever the family (a trace's programs carry
    the names, ``_decode_fn`` within them) and every part of this
    family's lies under a named scope."""
    cfg = config()
    i32 = jnp.int32
    with engine_for(cfg, weights_for(cfg)) as eng:
        rep = eng._replicas[0]
        # what the worker hands over: ONE packed int32 array a dispatch
        # (4 slots of 12 pages, 5 more values a slot; an 8-token prompt)
        if phase == "decode":
            lowered = eng._decode_jit.lower(
                rep.params, *rep.pools, rep.no_tokens,
                jnp.zeros((4 * (12 + 5),), i32))
        else:
            lowered = eng._prefill_jit.lower(
                rep.params, *rep.pools, jnp.zeros((3 * 8 + 1,), i32))
    text = lowered.as_text(debug_info=True)
    assert f"jit__packed_{phase}_fn" in text
    for scope in SCOPES[phase]:
        assert f"jit(_packed_{phase}_fn)/{scope}/" in text, scope
