"""The gated short-convolution, grouped-query, sparse-expert decoder family
(``models/lfm2_moe.py``) at a small size on the CPU (two periods of the
published pattern, 8 experts, top 2), against the plain reference the
benchmark keeps (``benchmark/reference/lfm2_moe_ref.py``): whole-sequence
forward, prefill then decoding through ``DecodeEngine``'s paged ``v | k``
pool and its per-sequence state rows, what a row's life looks like (a
padded prompt, two owners one after the other, every exit path,
recovery), the routing cases, the counters and the scopes.  Logits are
compared, not tokens.

Tolerances.  Everything here is float32 on the CPU, the program under
``jax.default_matmul_precision("highest")`` where it is compared (the
reference sets it product by product), so program and reference differ by
the order of float32 sums only: logits of magnitude up to 0.6 agree to
about 1e-6, and the limit is 1e-4.  ``test_the_comparison_sees_each_part``
shows what the limit can see: the reference with one part left out (the
convolution's memory, the q/k norms, the head mapping, the selection
bias) lies 30 to 3,000 limits away.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.families import lfm2_moe as family
from benchmark.reference import lfm2_moe_ref as ref
from dist_keras_tpu.models import blocks, lfm2_moe, mla_moe
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.resilience import faults
from dist_keras_tpu.resilience.faults import FaultInjected
from dist_keras_tpu.serving import DecodeEngine
from dist_keras_tpu.serving.engine import Overloaded
from dist_keras_tpu.serving.kv_cache import (
    PagedKVCache,
    PagesExhausted,
    StateRowsExhausted,
)

TOL = 1e-4
VOCAB = 128
PATTERN = ["conv", "conv", "full_attention", "conv"] * 2
SIZES = dict(vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=8,
             n_kv_heads=2, d_ff=96, moe_d_ff=48, n_routed_experts=8,
             top_k=2, layer_types=PATTERN, num_dense_layers=2)
N_CONV, N_ATTN = PATTERN.count("conv"), PATTERN.count("full_attention")


def config(**kw):
    return lfm2_moe.lfm2_moe_config(**{**SIZES, **kw})


def weights_for(cfg, seed=2 ** 31 + 7):
    """The benchmark's seeded weights: the ones a chip run hands to the
    program and to the reference alike."""
    return family.tree(weights.base_key(seed), cfg)


def reference_logits(params, tokens, cfg, **kw):
    return ref.forward(params, jnp.asarray(tokens),
                       family.reference_config(cfg), **kw)


def engine_for(cfg, params, **kw):
    model = lfm2_moe.Lfm2MoeDecoder(cfg=cfg)
    model.set_params(params)
    kw.setdefault("replicas", 1)
    kw.setdefault("prefill_ladder", (8, 16, 32))
    kw.setdefault("decode_ladder", (1, 4))
    kw.setdefault("page_size", 4)
    return DecodeEngine(model, **kw)


def served_gap(params, doc, cfg):
    """How far each served token's logit lies below the reference's best."""
    z = np.asarray(reference_logits(params, doc["tokens"][:-1], cfg))
    z = z[doc["prompt_len"] - 1:]
    return z.max(axis=1) - z[np.arange(len(z)), doc["generated"]]


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
    got = lfm2_moe.forward(params, jnp.asarray(tokens), cfg)
    want = reference_logits(params, tokens, cfg)
    assert got.shape == (40, VOCAB)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("q_block", [8, 16])
def test_reference_in_blocks_equals_reference_in_one_piece(q_block):
    cfg = config()
    params = weights_for(cfg)
    tokens = np.random.default_rng(1).integers(0, VOCAB, 40)
    np.testing.assert_allclose(
        reference_logits(params, tokens, cfg, q_block=q_block),
        reference_logits(params, tokens, cfg), atol=2e-5, rtol=0)


def _without_memory(conv, y, prec=ref.FLOAT32):
    """The reference's convolution with only its last tap: no state."""
    last = {**conv, "kernel": conv["kernel"].at[:, :-1].set(0.0)}
    return _REAL["short_conv"](last, y, prec)


def _without_qk_norm(w, x, eps):
    return x if x.ndim == 3 else _REAL["rms_norm"](w, x, eps)


def _interleaved_heads(x, group):
    """K/V head i serving query heads i, i + kv, ...: the wrong mapping."""
    return jnp.tile(x, (1, group, 1))


_REAL = {"short_conv": ref.short_conv, "rms_norm": ref.rms_norm}
LEFT_OUT = {
    "the_convolutions_memory": ("short_conv", _without_memory),
    "the_qk_norms": ("rms_norm", _without_qk_norm),
    "the_head_mapping": ("kv_for_query_heads", _interleaved_heads),
    "the_selection_bias": ("bias", None),
}


@pytest.mark.parametrize("part", sorted(LEFT_OUT))
def test_the_comparison_sees_each_part(highest, monkeypatch, part):
    """The program against the reference with ``part`` left out: far
    outside the tolerance, so a program without it would fail above."""
    cfg = config()
    params = weights_for(cfg)
    tokens = np.random.default_rng(3).integers(0, VOCAB, 40)
    got = lfm2_moe.forward(params, jnp.asarray(tokens), cfg)
    name, mutant = LEFT_OUT[part]
    if name == "bias":
        params = jax.tree.map(lambda x: x, params)
        for blk in params["blocks"]:
            if "moe" in blk:
                blk["moe"]["router_bias"] = jnp.zeros_like(
                    blk["moe"]["router_bias"])
    else:
        monkeypatch.setattr(ref, name, mutant)
    want = reference_logits(params, tokens, cfg)
    assert float(jnp.abs(got - want).max()) > 30 * TOL


def test_benchmark_weights_are_in_the_programs_layout():
    cfg = config()
    mine = jax.eval_shape(lambda: weights_for(cfg))
    theirs = jax.eval_shape(
        lambda: lfm2_moe.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(mine)] == \
        [a.shape for a in jax.tree.leaves(theirs)]
    # made on the device a layer at a time: the same leaves
    device = family.device_tree(weights.base_key(5), cfg)
    for a, b in zip(jax.tree.leaves(device),
                    jax.tree.leaves(weights_for(cfg, 5))):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=0)


def test_serialization_round_trip_holds_no_second_set_of_weights():
    from dist_keras_tpu.utils.serialization import (
        deserialize_model,
        serialize_model,
    )

    cfg = config()
    model = lfm2_moe.Lfm2MoeDecoder(cfg=cfg, seed=3)
    back = deserialize_model(serialize_model(model))
    assert isinstance(back, lfm2_moe.Lfm2MoeDecoder) and back.cfg == cfg
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(back.params)):
        np.testing.assert_array_equal(a, b)
    fresh = lfm2_moe.Lfm2MoeDecoder(cfg=cfg)
    assert fresh._params is None          # made on first use, not before
    fresh.set_weights(model.get_weights())
    assert fresh._params is not None


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=["conv", "window"]), "layer_types"),
    (dict(n_kv_heads=3), "divisor"),
    (dict(top_k=9), "top_k"),
    (dict(conv_l_cache=1), "conv_l_cache"),
])
def test_config_refuses_what_the_family_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        config(**bad)


# -- (2) prefill, then decoding through both caches --------------------
def _pools(cfg, n_pages, ps, rows):
    (la, _, kv), (lc, _, st) = lfm2_moe.cache_pools(cfg)
    return (jnp.zeros((la, n_pages + 1, ps) + kv),
            jnp.zeros((lc, rows + 1) + st))


def test_steps_over_the_pools_equal_the_reference_at_every_position(highest):
    """Teacher-forced: two sequences prefilled into scattered pages and
    rows, then stepped together on a 4-slot rung whose other two slots
    are padding; the first crosses a page boundary (positions 6..13,
    pages of 4).  The logits of every step equal the reference's full
    forward, which has no cache and no state."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, VOCAB, 14), rng.integers(0, VOCAB, 17)]
    prompts = [6, 9]
    ps, n_pages, n_rows = 4, 12, 3
    kv, state = _pools(cfg, n_pages, ps, n_rows)
    # rows left dirty by a previous owner: a prefill overwrites them whole
    state = state + 7.0
    pages = [[7, 2, 9, 4, 0], [5, 11, 1, 8, 3]]       # scratch page is 12
    rows = [2, 0]                                     # scratch row is 3
    for toks, n, mine, row in zip(seqs, prompts, pages, rows):
        rung = 16
        padded = np.zeros((rung,), np.int32)
        padded[:n] = toks[:n]
        page_idx = np.full((rung,), n_pages, np.int32)
        page_idx[:n] = [mine[t // ps] for t in range(n)]
        out, kv, state = lfm2_moe.prefill_step(
            cfg, params, kv, state, jnp.asarray(padded), jnp.int32(n),
            jnp.asarray(page_idx), jnp.arange(rung, dtype=jnp.int32) % ps,
            jnp.int32(row))
        want = reference_logits(params, toks[:n], cfg)[-1]
        assert int(out[0]) == int(jnp.argmax(want))
    wants = [reference_logits(params, toks, cfg) for toks in seqs]
    for step in range(8):
        at = [n + step for n in prompts]
        tables = np.zeros((4, 5), np.int32)
        tables[0], tables[1] = pages
        lengths = np.array([at[0] + 1, at[1] + 1, 0, 0], np.int32)
        hs, counts, kv, state = lfm2_moe._decode_layers(
            cfg, params, kv, state,
            jnp.asarray([seqs[0][at[0]], seqs[1][at[1]], 0, 0], jnp.int32),
            jnp.asarray(at + [0, 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray([pages[0][at[0] // ps], pages[1][at[1] // ps],
                         n_pages, n_pages], jnp.int32),
            jnp.asarray([at[0] % ps, at[1] % ps, 0, 0], jnp.int32),
            jnp.asarray(lengths), jnp.asarray(rows + [n_rows, n_rows]))
        got = blocks.logits(params, hs, cfg)
        for slot in (0, 1):
            np.testing.assert_allclose(got[slot], wants[slot][at[slot]],
                                       atol=TOL, rtol=0)
        # padding slots reached no expert: two real tokens, six layers
        assert int(counts[-1]) == 2 * cfg["top_k"] * 6
    # the row nobody held was never written
    assert float(jnp.abs(state[:, 1] - 7.0).max()) == 0.0


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
                for n, m in ((7, 12), (13, 9), (1, 14))]
        docs = [g.result(timeout=600) for g in gens]
        assert ("decode", 4) in eng.stats()["shapes_dispatched"]
    for doc in docs:
        gap = served_gap(params, doc, cfg)
        assert gap.max() <= TOL, gap
    eng.assert_no_leaks()


def test_a_decode_step_without_its_state_is_not_the_references(highest):
    """What (2) can see: the same steps with the state rows zeroed before
    each one lie far outside the tolerance."""
    cfg = config()
    params = weights_for(cfg)
    toks = np.random.default_rng(6).integers(0, VOCAB, 12)
    kv, state = _pools(cfg, 4, 4, 1)
    padded = np.zeros((8,), np.int32)
    padded[:6] = toks[:6]
    _, kv, state = lfm2_moe.prefill_step(
        cfg, params, kv, state, jnp.asarray(padded), jnp.int32(6),
        jnp.asarray([0, 0, 0, 0, 1, 1, 4, 4], jnp.int32),
        jnp.arange(8, dtype=jnp.int32) % 4, jnp.int32(0))
    want = reference_logits(params, toks[:7], cfg)[-1]
    args = (jnp.asarray([toks[6]], jnp.int32), jnp.asarray([6], jnp.int32),
            jnp.asarray([[0, 1, 2, 3]], jnp.int32),
            jnp.asarray([1], jnp.int32), jnp.asarray([2], jnp.int32),
            jnp.asarray([7], jnp.int32), jnp.asarray([0], jnp.int32))
    hs, *_ = lfm2_moe._decode_layers(cfg, params, kv, state, *args)
    assert float(jnp.abs(blocks.logits(params, hs, cfg)[0]
                         - want).max()) <= TOL
    hs, *_ = lfm2_moe._decode_layers(cfg, params, kv,
                                     jnp.zeros_like(state), *args)
    assert float(jnp.abs(blocks.logits(params, hs, cfg)[0]
                         - want).max()) > 30 * TOL


# -- (3) a state row's life ---------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 5, 8])
def test_padded_prompt_leaves_the_unpadded_prompts_state(highest, n):
    """A prompt of ``n`` tokens padded to a rung of 16 (with other tokens
    behind it, not zeros) writes the state the same prompt writes at a
    rung of exactly ``n``: ``u`` at its TRUE last two positions, zeros on
    the left of a prompt shorter than two."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(n)
    toks = rng.integers(0, VOCAB, 16).astype(np.int32)

    def state_after(rung):
        kv, state = _pools(cfg, 8, 4, 2)
        page_idx = np.full((rung,), 8, np.int32)
        page_idx[:n] = np.arange(n) // 4
        _, _, state = lfm2_moe.prefill_step(
            cfg, params, kv, state + 3.0, jnp.asarray(toks[:rung]),
            jnp.int32(n), jnp.asarray(page_idx),
            jnp.arange(rung, dtype=jnp.int32) % 4, jnp.int32(1))
        return np.asarray(state)

    padded, exact = state_after(16), state_after(n)
    # products over 16 rows and over ``n`` sum in another order
    np.testing.assert_allclose(padded[:, 1], exact[:, 1], atol=1e-6,
                               rtol=1e-5)
    assert np.abs(padded[:, 1]).max() > 0
    if n == 1:
        assert np.abs(padded[:, 1, 0]).max() == 0.0   # before the start
    assert np.abs(padded[:, 0] - 3.0).max() == 0.0    # the other row


def test_two_owners_of_one_row_do_not_see_each_other(highest):
    """An engine with ONE state row: a second sequence is refused typed
    while the first holds it, and once admitted into the same row it
    decodes what it decodes in an engine of its own."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(8)
    first = rng.integers(0, VOCAB, 9).tolist()
    second = rng.integers(0, VOCAB, 2).tolist()
    with engine_for(cfg, params) as alone:
        want = alone.generate(second, max_new_tokens=10, timeout_s=600)
    with engine_for(cfg, params, state_rows=1) as eng:
        assert eng.pool_shapes[1] == (N_CONV, 2, 2, cfg["d_model"])
        rejected = metrics.counter("decode.rejected").value
        g = eng.submit_generate(first, max_new_tokens=12)
        assert g._seq.row == 0
        with pytest.raises(Overloaded) as e:
            eng.submit_generate(second, max_new_tokens=10)
        assert e.value.reason == "kv_exhausted"
        assert metrics.counter("decode.rejected").value == rejected + 1
        g.result(timeout=600)
        h = eng.submit_generate(second, max_new_tokens=10)
        assert h._seq.row == 0
        got = h.result(timeout=600)
        eng.assert_no_leaks()
    assert got["generated"] == want["generated"]
    assert served_gap(params, got, cfg).max() <= TOL


def test_allocator_hands_out_rows_with_pages_and_takes_both_back():
    cache = PagedKVCache(num_pages=8, page_size=4, state_rows=2)
    assert cache.scratch_row == 2 and cache.scratch_page == 8
    cache.alloc("a", 10)
    cache.alloc("b", 3)
    assert {cache.state_row("a"), cache.state_row("b")} == {0, 1}
    assert cache.used_rows() == 2 and cache.used_pages() == 4
    with pytest.raises(StateRowsExhausted) as e:
        cache.alloc("c", 1)
    assert isinstance(e.value, PagesExhausted) and e.value.needed == 1
    assert not cache.holds("c") and cache.used_pages() == 4   # no effect
    cache.assert_balanced()
    row = cache.state_row("a")
    assert cache.free("a") == 3
    assert cache.used_rows() == 1
    cache.alloc("c", 1)
    assert cache.state_row("c") == row            # the freed row, reused
    # pages exhausted first: the row is not taken either
    with pytest.raises(PagesExhausted):
        PagedKVCache(2, 4, state_rows=2).alloc("x", 100)
    cache.free("b")
    cache.free("c")
    cache.assert_balanced()
    st = cache.stats()
    assert (st["state_rows"], st["used_rows"], st["used_pages"]) == (2, 0, 0)
    # a row held by no page holder is a leak the balance sees
    cache._rows["ghost"] = cache._free_rows.pop()
    with pytest.raises(AssertionError, match="state row leak"):
        cache.assert_balanced()
    # a pool without state rows is the allocator it was
    plain = PagedKVCache(4, 4)
    plain.alloc("a", 5)
    with pytest.raises(KeyError):
        plain.state_row("a")
    assert plain.used_rows() == 0 and plain.free("a") == 2
    plain.assert_balanced()


def _rows_and_pages(eng):
    return [(r.cache.used_rows(), r.cache.used_pages())
            for r in eng._replicas]


@pytest.mark.parametrize("exit_path", ["completion", "cancel", "error",
                                       "prefill_error", "close"])
def test_row_and_pages_come_back_on_every_exit(exit_path):
    cfg = config()
    params = weights_for(cfg)
    eng = engine_for(cfg, params, step_retries=0)
    try:
        gauge = metrics.gauge("decode.state_rows_used")
        prompt = [3, 1, 4, 1, 5]
        if exit_path == "prefill_error":
            real = eng._prefill_jit
            eng._prefill_jit = lambda *a: (_ for _ in ()).throw(
                RuntimeError("prefill"))
            with pytest.raises(RuntimeError):
                eng.generate(prompt, max_new_tokens=4, timeout_s=600)
            eng._prefill_jit = real
        elif exit_path == "error":
            seen = []
            g = eng.submit_generate(prompt, max_new_tokens=30,
                                    on_token=seen.append)
            while len(seen) < 2:
                time.sleep(0.01)
            assert _rows_and_pages(eng) == [(1, 9)]
            assert gauge.value == 1
            with faults.armed("decode.step", times=1):
                with pytest.raises(FaultInjected):
                    g.result(timeout=600)
        else:
            seen = []
            g = eng.submit_generate(prompt, max_new_tokens=30,
                                    on_token=seen.append)
            while len(seen) < 2:
                time.sleep(0.01)
            assert _rows_and_pages(eng) == [(1, 9)]
            if exit_path == "cancel":
                g.cancel()
                assert g.result(timeout=600)["finish"] == "cancelled"
            elif exit_path == "close":
                eng.close(drain=False)
                with pytest.raises(Overloaded):
                    g.result(timeout=600)
            else:
                assert g.result(timeout=600)["finish"] == "length"
        assert _rows_and_pages(eng) == [(0, 0)]
        assert gauge.value == 0
        eng.assert_no_leaks()
        assert eng.self_check() == 0
        assert eng.kv_stats()["used_rows"] == 0
    finally:
        eng.close(drain=False)


def test_recovered_sequence_is_bit_identical_and_nothing_leaks(highest):
    """``kill_replica`` mid-decode: the dead replica's rows and pages are
    freed, the survivor's prefill and teacher-forced steps rebuild the
    K/V AND the convolution state, and the document is the undisturbed
    greedy run's, token for token."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (5, 11, 2)]
    with engine_for(cfg, params) as alone:
        wants = [alone.generate(p, max_new_tokens=14, timeout_s=600)
                 for p in prompts]
    eng = engine_for(cfg, params, replicas=2)
    try:
        seen = [[] for _ in prompts]
        killed = []

        def on_token(i, t):
            seen[i].append(t)
            if not killed and len(seen[0]) == 5:
                killed.append(eng.kill_replica(0))

        gens = [eng.submit_generate(
            p, max_new_tokens=14,
            on_token=lambda t, i=i: on_token(i, t))
            for i, p in enumerate(prompts)]
        docs = [g.result(timeout=600) for g in gens]
        for doc, want, stream in zip(docs, wants, seen):
            assert doc["generated"] == want["generated"]
            assert stream == want["generated"]    # no duplicate, no gap
            assert served_gap(params, doc, cfg).max() <= TOL
        st = eng.stats()
        assert st["quarantines"] == 1 and st["recovered"] >= 1
        assert st["errors"] == 0
        assert sum(d["recoveries"] for d in docs) == st["recovered"]
        assert _rows_and_pages(eng) == [(0, 0), (0, 0)]
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


# -- (4) routing ---------------------------------------------------------
def _moe_with(router, bias, cfg):
    moe = weights_for(cfg)["blocks"][2]["moe"]
    return {**moe, "router": jnp.asarray(router, jnp.float32),
            "router_bias": jnp.asarray(bias, jnp.float32)}


def _logit(p):
    return float(np.log(p / (1 - p)))


ROUTING = {
    # scores s by expert, selection bias b -> the chosen experts
    "by_score": ([.9, .8, .7, .6, .5, .4, .3, .2], [0] * 8, [0, 1]),
    "bias_lifts_a_lower_score": ([.9, .8, .7, .6, .5, .4, .3, .2],
                                 [0, 0, 0, 0, 0, 0, 0, .65], [0, 7]),
    "bias_sinks_the_best": ([.9, .8, .7, .6, .5, .4, .3, .2],
                            [-.5, 0, 0, 0, 0, 0, 0, 0], [1, 2]),
    "first_of_equals": ([.5] * 8, [0] * 8, [0, 1]),
    # scores whose sum is of the size of the 1e-6 in the renormalisation
    "tiny_scores": ([2e-6, 1e-6] + [1e-8] * 6, [0] * 8, [0, 1]),
}


@pytest.mark.parametrize("case", sorted(ROUTING))
def test_routing_selects_by_score_plus_bias_and_weighs_by_score(case):
    scores, bias, chosen = ROUTING[case]
    cfg = config(d_model=8, n_heads=2, n_kv_heads=1)
    # x = e_0, router row 0 = logit(s): the scores are exactly ``scores``
    router = np.zeros((8, 8), np.float32)
    router[0] = [_logit(p) for p in scores]
    x = jnp.zeros((1, 8)).at[0, 0].set(1.0)
    idx, w = blocks.route_sigmoid(_moe_with(router, bias, cfg), x, cfg)
    assert sorted(np.asarray(idx[0]).tolist()) == chosen
    s = np.asarray(scores)[np.asarray(idx[0])]
    # weights from s alone (no bias), over their sum PLUS 1e-6, times the
    # scaling factor (1): with tiny scores the weights sum to 0.75, and
    # the latent family's 1e-20 would make them sum to 1
    np.testing.assert_allclose(w[0], s / (s.sum() + 1e-6), rtol=2e-4)
    if case == "tiny_scores":
        assert abs(float(w[0].sum()) - 0.75) < 1e-3
        other = {**cfg, "route_norm_eps": 1e-20}
        _, w20 = blocks.route_sigmoid(_moe_with(router, bias, cfg), x, other)
        assert abs(float(w20[0].sum()) - 1.0) < 1e-3
    want_idx, want_w = ref.routing(_moe_with(router, bias, cfg), x,
                                   family.reference_config(cfg))
    assert sorted(np.asarray(want_idx[0]).tolist()) == chosen
    np.testing.assert_allclose(np.sort(w[0]), np.sort(want_w[0]),
                               rtol=2e-4)


def test_every_expert_is_held_and_none_is_shared(highest):
    """All 8 experts held, no shared expert: the layer is the reference's
    routed sum, every chosen pair is a held pair, and padding tokens
    reach no expert."""
    cfg = config()
    moe = weights_for(cfg)["blocks"][3]["moe"]
    assert "shared" not in moe
    x = jax.random.normal(jax.random.PRNGKey(0), (24, cfg["d_model"]))
    got, counts = blocks.moe_layer(moe, x, cfg, jnp.ones((24,), bool))
    want = ref.expert_layer(moe, x, family.reference_config(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert int(counts[:8].sum()) == int(counts[-1]) == 24 * cfg["top_k"]
    _, counts = blocks.moe_layer(moe, x, cfg, jnp.arange(24) < 10)
    assert int(counts[:8].sum()) == int(counts[-1]) == 10 * cfg["top_k"]


# -- (5) the pools --------------------------------------------------------
def test_pools_span_their_own_layers_and_the_old_families_are_unchanged():
    from dist_keras_tpu.models import transformer
    from dist_keras_tpu.models.transformer import (
        Transformer,
        transformer_config,
    )

    cfg = config()
    kv = 2 * cfg["n_kv_heads"] * (cfg["d_model"] // cfg["n_heads"])
    assert lfm2_moe.cache_pools(cfg) == (
        (N_ATTN, "page", (kv,)), (N_CONV, "sequence", (2, cfg["d_model"])))
    with engine_for(cfg, weights_for(cfg), num_pages=20,
                    max_queue=7) as eng:
        # a row for every sequence the door can admit
        assert eng.state_rows == 7
        assert eng.pool_shapes == ((N_ATTN, 21, 4, kv),
                                   (N_CONV, 8, 2, cfg["d_model"]))
        assert tuple(p.shape for p in eng._replicas[0].pools) == \
            eng.pool_shapes
        assert eng.kv_stats()["state_rows"] == 7
    old = transformer_config(input_dim=16, seq_len=32, d_model=16, n_heads=2,
                             n_layers=2, n_classes=16)
    assert transformer.cache_pools(old) == ((2, "page", (32,)),)
    with DecodeEngine(Transformer(old), replicas=1, prefill_ladder=(4,),
                      decode_ladder=(1,), page_size=4) as eng:
        assert eng.pool_shapes == ((2, eng.num_pages + 1, 4, 32),)
        assert eng.state_rows == 0 and not eng._state
    latent = mla_moe.mla_moe_config(
        vocab_size=32, seq_len=16, d_model=16, n_heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        kv_lora_rank=8, d_ff=16, moe_d_ff=8, n_routed_experts=4,
        n_shared_experts=1, top_k=2, n_layers=3)
    assert mla_moe.cache_pools(latent) == ((3, "page", (128,)),)
    with pytest.raises(ValueError, match="state_rows"):
        engine_for(cfg, weights_for(cfg), state_rows=0)


def test_worker_packs_the_state_rows_behind_the_lengths():
    """A family with per-sequence state gets ONE more column in the packed
    array (a decode step's slots' rows, a prefill's row behind its
    length); the views cut it out where the compiled step does."""
    from dist_keras_tpu.serving.decode import _prefill_views, _step_views

    cfg = config()
    sent = {"decode": [], "prefill": []}
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,)) as eng:
        for phase in sent:
            real = getattr(eng, f"_{phase}_jit")
            setattr(eng, f"_{phase}_jit", lambda *a, real=real, phase=phase:
                    (sent[phase].append(np.array(a[-1])), real(*a))[1])
        gens = [eng.submit_generate([5, 6, 7][:n], max_new_tokens=6)
                for n in (3, 2)]
        rows = [g._seq.row for g in gens]
        for g in gens:
            g.result(timeout=600)
        pmax, scratch = eng.max_pages_per_seq, eng.state_rows
    assert sorted(rows) == [0, 1]
    for packed, n, row in zip(sent["prefill"], (3, 2), rows):
        assert packed.shape == (3 * 8 + 2,)
        toks, length, _, _, at = _prefill_views(packed, True)
        assert (int(length), int(at)) == (n, row) and len(toks) == 8
    both = [p for p in sent["decode"]
            if (_step_views(p, pmax, True)[5] > 0).sum() == 2]
    assert both
    for packed in sent["decode"]:
        assert packed.shape == (4 * (pmax + 6),)
        *_, lengths, at = _step_views(packed, pmax, True)
        live = lengths > 0
        assert set(at[live]) <= set(rows) and (at[~live] == scratch).all()
    assert list(_step_views(both[0], pmax, True)[6][:2]) == rows
    # the other families' views are the six and the four they were
    flat = np.arange(4 * (pmax + 5), dtype=np.int32)
    assert len(_step_views(flat, pmax)) == 6
    assert len(_prefill_views(np.arange(25, dtype=np.int32))) == 4


# -- (6) the counters -----------------------------------------------------
HISTOGRAMS = ("decode.moe.load_max_over_mean", "decode.moe.experts_hit",
              "decode.kv.live_positions")
PAIRS = ("decode.moe.pairs_total", "decode.moe.pairs_held")


def test_counters_exist_and_are_stamped():
    cfg = config()
    for name in PAIRS + HISTOGRAMS:
        assert name in metrics.KNOWN_METRICS
    assert metrics.KNOWN_METRICS["decode.state_rows_used"] == "gauge"
    before = [metrics.counter(n).value for n in PAIRS]
    latent = metrics.histogram("decode.latent.live_positions")
    lo = time.perf_counter()
    rng = np.random.default_rng(4)
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,)) as eng:
        gens = [eng.submit_generate(rng.integers(0, VOCAB, 30).tolist(),
                                    max_new_tokens=16) for _ in range(4)]
        docs = [g.result(timeout=600) for g in gens]
    hi = time.perf_counter()
    total, held = (metrics.counter(n).value - b
                   for n, b in zip(PAIRS, before))
    # every real token of every prefill and step, in all six expert
    # layers, and every pair held: the share is 100% by construction
    tokens = sum(d["prompt_len"] + len(d["generated"]) - 1 for d in docs)
    assert total == held == tokens * cfg["top_k"] * 6
    steps = metrics.histogram("decode.step_s").samples_between(lo, hi)[0]
    for name in HISTOGRAMS:
        pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
        assert not truncated and pairs, name
        assert {at for at, _ in pairs} <= {at for at, _ in steps}, name
    live = [v for _, v in metrics.histogram(
        "decode.kv.live_positions").samples_between(lo, hi)[0]]
    assert min(live) >= 31 and max(live) <= 4 * 46
    hit = [v for _, v in metrics.histogram(
        "decode.moe.experts_hit").samples_between(lo, hi)[0]]
    assert all(0 < v <= 6 * 8 for v in hit)
    # this family stamps nothing of the latent family's
    assert latent.samples_between(lo, hi)[0] == []


# -- (7) scopes -------------------------------------------------------------
SCOPES = {"decode": ("embed", "conv_in", "conv_mix", "conv_out",
                     "state_read", "state_write", "qkv", "qk_norm_rope",
                     "kv_write", "attend_pool", "attn_out", "moe_route",
                     "moe_experts", "mlp", "head"),
          "prefill": ("embed", "conv_in", "conv_mix", "conv_out",
                      "state_write", "qkv", "qk_norm_rope", "kv_write",
                      "attend", "attn_out", "moe_route", "moe_experts",
                      "mlp", "head")}


@pytest.mark.parametrize("phase", sorted(SCOPES))
def test_steps_carry_their_names_and_scopes(phase):
    """The engine's jitted steps are ``_packed_prefill_fn`` /
    ``_packed_decode_fn`` for this family too, every new part lies under a
    named scope, and there is no shared expert's scope."""
    cfg = config()
    i32 = jnp.int32
    with engine_for(cfg, weights_for(cfg)) as eng:
        rep = eng._replicas[0]
        if phase == "decode":
            lowered = eng._decode_jit.lower(
                rep.params, *rep.pools, rep.no_tokens,
                jnp.zeros((4 * (12 + 6),), i32))
        else:
            lowered = eng._prefill_jit.lower(
                rep.params, *rep.pools, jnp.zeros((3 * 8 + 2,), i32))
    text = lowered.as_text(debug_info=True)
    assert f"jit__packed_{phase}_fn" in text
    for scope in SCOPES[phase]:
        assert f"jit(_packed_{phase}_fn)/{scope}/" in text, scope
    assert "moe_shared" not in text


# -- (8) the flash forward over grouped heads -------------------------------
@pytest.mark.parametrize("heads,kv_heads,t,d", [(8, 2, 64, 64),
                                                (4, 4, 32, 16),
                                                (6, 1, 48, 32)])
def test_flash_forward_over_grouped_heads(highest, heads, kv_heads, t, d):
    """K/V head i serves query heads g i .. g i + g - 1 through the
    kernel's index map (interpret mode here): the ``jnp`` reference on
    repeated K/V heads, and on a TPU the same call the prefill makes."""
    from dist_keras_tpu.ops.attention import attention
    from dist_keras_tpu.ops.pallas.flash_attention import (
        flash_attention,
        repeat_kv_heads,
    )

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(kq, (2, t, heads, d))
    k = jax.random.normal(kk, (2, t, kv_heads, d))
    v = jax.random.normal(kv, (2, t, kv_heads, d))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    kr, vr = repeat_kv_heads(heads, k, v)
    assert kr.shape == q.shape
    if heads == kv_heads:
        assert kr is k and vr is v
    want = attention(q, kr, vr, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # by hand for one query head: its K/V head is h // g
    h = heads - 1
    one = attention(q[:, :, h:h + 1], k[:, :, h // (heads // kv_heads)][
        :, :, None], v[:, :, h // (heads // kv_heads)][:, :, None],
        causal=True)
    np.testing.assert_allclose(got[:, :, h:h + 1], one, atol=2e-5, rtol=0)
