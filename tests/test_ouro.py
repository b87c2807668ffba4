"""The looped family (``models/ouro.py``: one stack of layers run
``ut_steps`` times a token on shared weights) on the CPU at a toy size
(three layers of weights, four passes, 4 heads of 16), on the benchmark's
seeded weights: ``forward`` against the plain reference, the gates of all
four passes, the exit rule (at the published threshold and below it),
prefill then decode through ``DecodeEngine`` against the reference's full
forward, the cache of ``passes x layers`` entries, and the loop as the
compiled programs hold it.

Tolerances: float32 at "highest" on both sides.  The program's loop
(``fori_loop``, a cache entry a (pass, layer), the paged read) and the
reference's nested Python passes sum the same terms in another order;
through twelve layer applications whose sub-blocks are each renormalised
the logits agree to under 1e-5 of values of about 3 and the gates to
under 1e-5 of values of about 1 (``TOL`` 2e-4 leaves a factor of twenty;
a part of the mathematics left out moves the logits by 0.1 and more, and
a forward carried in bfloat16 by 0.02: the fourth and fifth tests)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import ouro as family
from benchmark.reference import ouro_ref as ref
from benchmark.reference.transformer_ref import Precision
from dist_keras_tpu.models import ouro
from dist_keras_tpu.models.families import FAMILIES
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.resilience import faults
from dist_keras_tpu.resilience.faults import FaultInjected
from dist_keras_tpu.serving.decode import DecodeEngine
from dist_keras_tpu.serving.engine import Overloaded
from dist_keras_tpu.utils.serialization import (
    deserialize_model,
    serialize_model,
)

TOL = 2e-4
VOCAB = 128
LAYERS, PASSES = 3, 4
SIZES = dict(vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=4,
             n_kv_heads=4, head_dim=16, d_ff=96, n_layers=LAYERS)
ROW = 2 * 4 * 16


def config(**kw):
    return ouro.ouro_config(**{**SIZES, **kw})


def weights_for(cfg, seed=2 ** 31 + 7, gate_scale=1.0):
    """The benchmark's seeded weights: the ones a chip run hands to the
    program and to the reference alike.  ``gate_scale`` widens the exit
    gate's weights, so that the gates of a seeded model differ enough
    from position to position for the rule to pick passes apart."""
    params = family.tree(weights.base_key(seed), cfg)
    params["gate"]["w"] = params["gate"]["w"] * gate_scale
    return params


_COMPILED = {}


def _padded(fn, tag, params, tokens, cfg, **kw):
    """``fn(params, tokens, cfg, **kw)`` compiled once a (cfg, options)
    at ``seq_len`` positions and cut back to the tokens given: the model
    is causal, so what stands behind them moves nothing before it (an op
    at a time, every new length would compile every op anew)."""
    tokens = np.asarray(tokens)
    n, room = len(tokens), max(len(tokens), cfg["seq_len"])
    key = (tag, tuple(sorted(cfg.items())), room, tuple(sorted(kw.items())))
    if key not in _COMPILED:
        _COMPILED[key] = jax.jit(lambda p, t: fn(p, t, cfg, **kw))
    out = _COMPILED[key](params, jnp.asarray(np.pad(tokens, (0, room - n))))
    if not kw.get("with_gates"):
        return out[:n]
    logits, gates, exits = out
    return logits[:n], gates[:, :n], exits[:n]


def reference(params, tokens, cfg, tag="", **kw):
    """The plain reference's forward (``tag`` names a patched variant of
    it, compiled apart)."""
    return _padded(
        lambda p, t, c, **k: ref.forward(p, t, family.reference_config(c),
                                         **k),
        "reference" + tag, params, tokens, cfg, **kw)


def program(params, tokens, cfg, **kw):
    return _padded(ouro.forward, "program", params, tokens, cfg, **kw)


def engine_for(cfg, params, **kw):
    model = ouro.OuroDecoder(cfg=cfg)
    model.set_params(params)
    kw.setdefault("replicas", 1)
    kw.setdefault("prefill_ladder", (8, 16, 32))
    kw.setdefault("decode_ladder", (1, 4))
    kw.setdefault("page_size", 4)
    return DecodeEngine(model, **kw)


def served_gap(params, doc, cfg):
    """How far each served token's logit lies below the reference's best."""
    z = np.asarray(reference(params, doc["tokens"][:-1], cfg))
    z = z[doc["prompt_len"] - 1:]
    return z.max(axis=1) - z[np.arange(len(z)), doc["generated"]]


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (1) forward, the gates and the exit rule against the reference --------
@pytest.mark.parametrize("seed,heads", [(1, {}), (2 ** 31 + 7, {}),
                                        (3, dict(n_heads=8, n_kv_heads=2))])
def test_forward_and_all_four_gates_equal_the_reference(highest, seed,
                                                        heads):
    cfg = config(**heads)
    params = weights_for(cfg, seed)
    tokens = np.random.default_rng(seed).integers(0, VOCAB, 40)
    got, gates, exits = program(params, tokens, cfg, with_gates=True)
    want, want_gates, want_exits = reference(params, tokens, cfg,
                                             with_gates=True)
    assert got.shape == (40, VOCAB) and gates.shape == (PASSES, 40)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) <= TOL
    # the gates are no constant: they move from pass to pass and from
    # position to position by more than a thousand tolerances
    assert float(jnp.std(want_gates)) > 0.2
    assert float(jnp.abs(gates - want_gates).max()) <= TOL
    # at the published threshold of 1 no gate of a seeded model saturates
    assert exits.tolist() == want_exits.tolist() == [PASSES - 1] * 40


@pytest.mark.parametrize("threshold", [0.35, 0.6, 0.85])
def test_below_one_the_rule_picks_passes_apart(highest, threshold):
    """The gate's weights widened eightfold and a threshold below 1:
    positions leave at different passes, the program and the reference
    choose the same pass at every position, and the logits are those of
    the chosen pass's state."""
    cfg = config(early_exit_threshold=threshold)
    params = weights_for(cfg, 5, gate_scale=8.0)
    tokens = np.random.default_rng(5).integers(0, VOCAB, 40)
    got, gates, exits = program(params, tokens, cfg, with_gates=True)
    want, want_gates, want_exits = reference(params, tokens, cfg,
                                             with_gates=True)
    assert len(set(exits.tolist())) >= 3
    assert exits.tolist() == want_exits.tolist()
    assert float(jnp.abs(gates - want_gates).max()) <= TOL
    assert float(jnp.abs(got - want).max()) <= TOL
    # and they are NOT the last pass's: the rule is what the head reads
    last = reference(params, tokens, {**cfg, "early_exit_threshold": 1.0})
    early = np.asarray(exits) < PASSES - 1
    assert float(jnp.abs(got - last)[early].max()) > 100 * TOL


def _logit(p):
    return float(np.log(p / (1.0 - p)))


@pytest.mark.parametrize("lams,threshold,want", [
    # C = 0.5, 0.75, 0.875, 1: the first pass to reach the threshold
    ((0.5, 0.5, 0.5, 0.5), 0.5, 0),
    ((0.5, 0.5, 0.5, 0.5), 0.6, 1),
    ((0.5, 0.5, 0.5, 0.5), 0.8, 2),
    ((0.5, 0.5, 0.5, 0.5), 0.9, 3),
    # the last pass takes what is left whatever its own gate says
    ((0.1, 0.1, 0.1, 0.0001), 0.99, 3),
    # the published threshold: the last pass, unless a gate saturates
    ((0.9, 0.9, 0.9, 0.9), 1.0, 3),
    ((0.2, 1.0, 0.3, 0.3), 1.0, 1),
    ((1.0, 0.0, 0.0, 0.0), 1.0, 0),
])
def test_exit_rule_by_hand(lams, threshold, want):
    gates = jnp.asarray([[100.0 if p == 1.0 else -100.0 if p == 0.0
                          else _logit(p)] for p in lams], jnp.float32)
    assert ouro.exit_pass(gates, threshold).tolist() == [want]
    assert ref.exit_pass(list(gates), threshold).tolist() == [want]


def _layer_without(norm):
    def layer(blk, x, conf, prec=ref.FLOAT32, q_block=None):
        return _REAL["layer"]({**blk, norm: jnp.ones_like(blk[norm])
                               * 3.0}, x, conf, prec, q_block)
    return layer


def _no_rotation(x, positions, theta):
    return x


def _end_of_pass_without_norm(outer, x, conf):
    _, g = _REAL["end_of_pass"](outer, x, conf)
    return x, g


_REAL = {"layer": ref.layer, "end_of_pass": ref.end_of_pass}
LEFT_OUT = {
    "the norm on the attention's output": ("layer",
                                           _layer_without("attn_out_norm")),
    "the norm on the SwiGLU's output": ("layer",
                                        _layer_without("mlp_out_norm")),
    "the rotation": ("rotate_half", _no_rotation),
    "the final norm between passes": ("end_of_pass",
                                      _end_of_pass_without_norm),
}


@pytest.mark.parametrize("part", sorted(LEFT_OUT) + ["the fourth pass"])
def test_the_comparison_sees_each_part(highest, monkeypatch, part):
    """A reference with one part of the mathematics changed is far from
    the program: the tolerance is not what lets the program pass."""
    cfg = config()
    params = weights_for(cfg)
    tokens = np.random.default_rng(3).integers(0, VOCAB, 40)
    got = program(params, tokens, cfg)
    if part == "the fourth pass":
        wrong = reference(params, tokens, {**cfg, "ut_steps": 3})
    else:
        name, fake = LEFT_OUT[part]
        monkeypatch.setattr(ref, name, fake)
        wrong = reference(params, tokens, cfg, tag=part)
    assert float(jnp.abs(got - wrong).max()) > 100 * TOL, part


def test_a_forward_carried_in_bfloat16_fails_the_tolerance():
    """The nearest precision below the stated one: the reference carried
    wholly in bfloat16 lies a hundred tolerances from itself in float32,
    in the logits and in the gates."""
    cfg = config()
    params = weights_for(cfg)
    tokens = np.random.default_rng(3).integers(0, VOCAB, 40)
    want, want_gates, _ = reference(params, tokens, cfg, with_gates=True)
    low, low_gates, _ = reference(
        params, tokens, cfg, with_gates=True,
        prec=Precision("bfloat16", jnp.bfloat16, False))
    assert float(jnp.abs(low - want).max()) > 50 * TOL
    assert float(jnp.abs(low_gates - want_gates).max()) > 50 * TOL


# -- (2) the family behind the seam ----------------------------------------
def test_benchmark_weights_are_in_the_programs_layout():
    cfg = config()
    mine = jax.eval_shape(lambda k: ouro.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: family.tree(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(mine)] == \
        [a.shape for a in jax.tree.leaves(theirs)]
    params = weights_for(cfg)
    # ONE stack of weights, whatever the passes; four norms a layer, no
    # bias and no per-head norm; the gate a d -> 1 product and its bias
    assert len(params["blocks"]) == LAYERS
    assert sorted(params["blocks"][0]) == [
        "attn", "attn_norm", "attn_out_norm", "mlp", "mlp_norm",
        "mlp_out_norm"]
    assert sorted(params["blocks"][0]["attn"]) == ["wk", "wo", "wq", "wv"]
    assert params["gate"]["w"].shape == (64,)
    assert params["gate"]["b"].shape == ()
    assert params["head"].shape == (64, VOCAB)            # untied


def test_every_leaf_is_a_function_of_key_layer_and_leaf_alone():
    cfg, deeper = config(), config(n_layers=5)
    a = ouro.init_params(jax.random.PRNGKey(4), cfg)
    b = ouro.init_params(jax.random.PRNGKey(4), deeper)
    for x, y in zip(jax.tree.leaves(a["blocks"]),
                    jax.tree.leaves(b["blocks"][:LAYERS])):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(
        jax.tree.leaves(ouro.init_layer_params(jax.random.PRNGKey(4),
                                               cfg, 2))[0],
        jax.tree.leaves(a["blocks"][2])[0])


def test_serialization_round_trip_holds_no_second_set_of_weights():
    cfg = config()
    model = ouro.OuroDecoder(cfg=cfg, seed=3)
    back = deserialize_model(serialize_model(model))
    assert isinstance(back, ouro.OuroDecoder)
    assert back.cfg == cfg
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(back.params)):
        np.testing.assert_array_equal(a, b)
    assert FAMILIES["ouro"] is ouro and len(FAMILIES) == 6
    # a cfg that names no family is still a Transformer's
    assert FAMILIES["transformer"].FAMILY == "transformer"


@pytest.mark.parametrize("bad,match", [
    (dict(n_heads=6, n_kv_heads=4), "n_kv_heads"),
    (dict(head_dim=15), "head_dim"),
    (dict(ut_steps=0), "ut_steps"),
])
def test_config_refuses_what_the_family_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        config(**bad)


def test_the_pool_spans_passes_times_layers():
    """The seam's "layers a pool spans" is the family's count of ENTRIES:
    ``ut_steps x n_layers`` over ``n_layers`` layers of weights."""
    cfg = config()
    assert ouro.cache_pools(cfg) == ((PASSES * LAYERS, "page", (ROW,)),)
    assert ouro.step_width(cfg) == 1 and ouro.vocab(cfg) == VOCAB
    assert ouro.cache_pools(config(ut_steps=2)) == (
        (2 * LAYERS, "page", (ROW,)),)
    with engine_for(cfg, weights_for(cfg)) as eng:
        assert eng.pool_shapes == ((PASSES * LAYERS, eng.num_pages + 1, 4,
                                    ROW),)
        assert not eng._state and eng.state_rows == 0
        # the tokens, then the exit passes summed, the passes, the
        # layer applications
        assert eng._out_width == eng.max_slots + ouro.N_COUNTS
        assert eng.kv_stats()["num_pages"] == eng.num_pages


# -- (3) prefill then decode through the engine ----------------------------
@pytest.fixture(scope="module")
def served():
    """One engine for the tests that only send it requests."""
    cfg = config()
    params = weights_for(cfg)
    with jax.default_matmul_precision("highest"), \
            engine_for(cfg, params) as eng:
        yield cfg, params, eng
        eng.assert_no_leaks()


@pytest.mark.parametrize("n", [1, 2, 5, 8, 16, 30])
def test_engine_tokens_are_the_references_own(served, n):
    """Prompts at, under and over a rung and a page: every served token is
    the argmax of the reference's full forward over what came before it,
    teacher-forced, and its logit lies within the tolerance of the
    reference's best."""
    cfg, params, eng = served
    prompt = np.random.default_rng(n).integers(0, VOCAB, n).tolist()
    doc = eng.generate(prompt, max_new_tokens=12, timeout_s=600)
    assert doc["finish"] == "length" and len(doc["generated"]) == 12
    assert doc["steps"] == 11
    assert served_gap(params, doc, cfg).max() <= TOL
    want = program(params, doc["tokens"][:-1], cfg)
    assert np.asarray(want.argmax(-1))[n - 1:].tolist() == doc["generated"]


def test_slots_decode_together_as_each_does_alone(served):
    cfg, params, eng = served
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (3, 9, 17, 30)]
    alone = [eng.generate(p, max_new_tokens=10, timeout_s=600)
             for p in prompts]
    gens = [eng.submit_generate(p, max_new_tokens=10) for p in prompts]
    together = [g.result(timeout=600) for g in gens]
    for a, b in zip(alone, together):
        assert a["generated"] == b["generated"]
        assert served_gap(params, b, cfg).max() <= TOL


def test_exit_below_one_through_the_engine(highest):
    """Widened gates and a threshold of 0.6 through prefill and decode:
    the engine's tokens are the reference's (whose head reads each
    position's own exit pass), and ``decode.loop.exit_pass`` reads the
    mean of the slots' ``e + 1``, under 4 in the mean and not always a whole
    number."""
    cfg = config(early_exit_threshold=0.6)
    params = weights_for(cfg, 5, gate_scale=8.0)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (4, 11, 19)]
    lo = time.perf_counter()
    with engine_for(cfg, params) as eng:
        gens = [eng.submit_generate(p, max_new_tokens=12) for p in prompts]
        docs = [g.result(timeout=600) for g in gens]
    hi = time.perf_counter()
    exits = []
    for doc in docs:
        assert served_gap(params, doc, cfg).max() <= TOL
        _, _, e = reference(params, doc["tokens"][:-1], cfg,
                            with_gates=True)
        exits += (np.asarray(e)[doc["prompt_len"]:] + 1).tolist()
    assert len(set(exits)) >= 3
    pairs, _ = metrics.histogram("decode.loop.exit_pass").samples_between(
        lo, hi)
    seen = [v for _, v in pairs]
    assert seen and 1.0 <= min(seen) < PASSES and max(seen) <= PASSES
    assert any(v != int(v) for v in seen)       # a mean over the slots
    # every decoded token's exit pass is in some step's mean: the means,
    # weighted by their slots, are the reference's
    assert abs(np.mean(seen) - np.mean(exits)) < 0.5


def _pool(cfg, n_pages, ps):
    (entries, _, row), = ouro.cache_pools(cfg)
    return jnp.zeros((entries, n_pages + 1, ps) + tuple(row), jnp.float32)


def _prefill(cfg, params, toks, n, rung, n_pages=8, ps=4):
    page_idx = np.full((rung,), n_pages, np.int32)
    page_idx[:n] = np.arange(n) // ps
    return ouro.prefill_step(
        cfg, params, _pool(cfg, n_pages, ps), jnp.asarray(toks[:rung]),
        jnp.int32(n), jnp.asarray(page_idx),
        jnp.arange(rung, dtype=jnp.int32) % ps)


def test_a_prefill_writes_every_pass_and_layer_entry(highest):
    """A prompt's rows stand in all ``passes x layers`` entries, each
    (pass, layer) with its own values (a pass never reads, and never
    shares, another pass's keys), pages past the prompt stay zero, and the
    counts behind the first token say four passes of three layers."""
    cfg = config()
    params = weights_for(cfg)
    toks = np.random.default_rng(2).integers(0, VOCAB, 16).astype(np.int32)
    out, kv = _prefill(cfg, params, toks, 7, 8)
    kv = np.asarray(kv)
    assert out.shape == (1 + ouro.N_COUNTS,)
    assert out[1:].tolist() == [PASSES, PASSES, PASSES * LAYERS]
    rows = kv[:, :2].reshape(PASSES * LAYERS, 8, ROW)[:, :7]
    assert np.abs(rows).min(axis=(1, 2)).max() > 0      # every entry
    assert (np.abs(rows).max(axis=2) > 0).all()         # every position
    for a in range(PASSES * LAYERS):
        for b in range(a):
            assert np.abs(rows[a] - rows[b]).max() > 1e-3, (a, b)
    assert np.abs(kv[:, 2:8]).max() == 0.0              # pages not its own
    want = program(params, toks[:7], cfg)
    assert int(out[0]) == int(jnp.argmax(want[-1]))


@pytest.mark.parametrize("n", [1, 3, 5, 13])
def test_padded_prompt_leaves_the_unpadded_prompts_rows(highest, n):
    """A prompt of ``n`` tokens padded to a rung of 16 (with other tokens
    behind it, not zeros) writes the rows, in every entry, that the same
    prompt writes at the smallest rung that holds it, and yields the same
    first token: the padding goes to the scratch page."""
    cfg = config()
    params = weights_for(cfg)
    toks = np.random.default_rng(n).integers(0, VOCAB, 16).astype(np.int32)
    exact_rung = -(-n // 4) * 4
    (first_a, *_), padded = _prefill(cfg, params, toks, n, 16)
    (first_b, *_), exact = _prefill(cfg, params, toks, n, exact_rung)
    assert int(first_a) == int(first_b)
    padded, exact = np.asarray(padded), np.asarray(exact)
    pages = -(-n // 4)
    np.testing.assert_allclose(
        padded[:, :pages].reshape(12, -1, ROW)[:, :n],
        exact[:, :pages].reshape(12, -1, ROW)[:, :n], atol=1e-5, rtol=1e-5)
    assert np.abs(padded[:, pages:8]).max() == 0.0


def test_a_decode_step_writes_one_row_an_entry_and_reads_its_own_pass(
        highest):
    """One decode step on a prefilled pool: every entry gains exactly the
    step's row, and the step's token is the full forward's.  With another
    pass's entries wiped (pass 1's pages of this sequence zeroed before
    the step) the token's logits move: each pass reads what IT wrote."""
    cfg = config()
    params = weights_for(cfg)
    toks = np.random.default_rng(4).integers(0, VOCAB, 16).astype(np.int32)
    n = 6
    (first, *_), kv = _prefill(cfg, params, toks, n, 8)
    tables = jnp.asarray([[0, 1, 8, 8]], jnp.int32)

    def step(pool):
        return ouro.decode_step(
            cfg, params, pool, jnp.asarray([int(first)]),
            jnp.asarray([n]), tables, jnp.asarray([1]), jnp.asarray([2]),
            jnp.asarray([n + 1]))

    before = np.asarray(kv)
    out, after = step(kv)
    after = np.asarray(after)
    changed = np.argwhere(np.abs(after - before).max(-1) > 0)
    assert sorted(map(tuple, changed)) == [(e, 1, 2)
                                           for e in range(PASSES * LAYERS)]
    assert out[1:].tolist() == [PASSES, PASSES, PASSES * LAYERS]
    want = program(params, list(toks[:n]) + [int(first)], cfg)
    assert int(out[0]) == int(jnp.argmax(want[-1]))
    wiped = jnp.asarray(before).at[LAYERS:2 * LAYERS, :2].set(0.0)
    seen = []
    real = jnp.argmax
    try:
        jnp.argmax = lambda z, *a, **k: (seen.append(z), real(z, *a, **k))[1]
        step(jnp.asarray(before))
        step(wiped)
    finally:
        jnp.argmax = real
    assert float(jnp.abs(seen[0] - seen[1]).max()) > 100 * TOL


def _used_pages(eng):
    return [r.cache.used_pages() for r in eng._replicas]


@pytest.mark.parametrize("exit_path", ["completion", "cancel", "error",
                                       "close"])
def test_pages_come_back_on_every_exit(exit_path):
    cfg = config()
    eng = engine_for(cfg, weights_for(cfg), step_retries=0)
    try:
        seen = []
        g = eng.submit_generate([3, 1, 4, 1, 5], max_new_tokens=30,
                                on_token=seen.append)
        while len(seen) < 2:
            time.sleep(0.01)
        assert _used_pages(eng) == [9]       # pages of POSITIONS: the
        if exit_path == "error":             # entries share a page table
            with faults.armed("decode.step", times=1):
                with pytest.raises(FaultInjected):
                    g.result(timeout=600)
        elif exit_path == "cancel":
            g.cancel()
            assert g.result(timeout=600)["finish"] == "cancelled"
        elif exit_path == "close":
            eng.close(drain=False)
            with pytest.raises(Overloaded):
                g.result(timeout=600)
        else:
            assert g.result(timeout=600)["finish"] == "length"
        assert _used_pages(eng) == [0]
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=False)


def test_recovered_sequence_is_bit_identical_and_nothing_leaks(highest):
    """``kill_replica`` mid-decode: the survivor's prefill and
    teacher-forced steps rebuild all twelve entries of every position,
    and the document is the undisturbed greedy run's, token for token."""
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
        assert _used_pages(eng) == [0, 0]
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


# -- (4) the counters -------------------------------------------------------
def test_counters_exist_and_are_stamped():
    cfg = config()
    hists = ("decode.kv.live_positions", "decode.loop.passes",
             "decode.loop.exit_pass")
    for name in hists:
        assert metrics.KNOWN_METRICS[name] == "histogram"
    assert metrics.KNOWN_METRICS["decode.loop.layer_passes"] == "counter"
    counter = metrics.counter("decode.loop.layer_passes")
    was = counter.value
    lo = time.perf_counter()
    rng = np.random.default_rng(4)
    lengths = (30, 11, 5, 2)
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,)) as eng:
        gens = [eng.submit_generate(rng.integers(0, VOCAB, n).tolist(),
                                    max_new_tokens=16) for n in lengths]
        for g in gens:
            g.result(timeout=600)
        steps_run = eng.stats()["steps"]
    hi = time.perf_counter()

    def window(name):
        pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
        assert not truncated and pairs, name
        return pairs

    steps = {at for at, _ in window("decode.step_s")}
    for name in hists:
        assert {at for at, _ in window(name)} == steps, name
    assert {v for _, v in window("decode.loop.passes")} == {PASSES}
    assert {v for _, v in window("decode.loop.exit_pass")} == {float(PASSES)}
    live = [v for _, v in window("decode.kv.live_positions")]
    # positions, not entries: the slots' lengths summed
    assert min(live) >= 2 and max(live) <= sum(lengths) + 4 * 16
    # layer applications: passes x layers a step and a prefill
    assert counter.value - was == PASSES * LAYERS * (steps_run
                                                     + len(lengths))


@pytest.mark.parametrize("counts,lengths,want", [
    ((8, 4, 12), (5, 9, 0, 0), 4.0),
    ((5, 4, 12), (5, 9, 0, 0), 2.5),
    ((3, 4, 12), (7, 0, 0, 0), 3.0),
    ((0, 4, 12), (0, 0, 0, 0), None),
])
def test_observe_step_reads_the_mean_over_the_live_slots(counts, lengths,
                                                         want):
    at = time.perf_counter()
    hist = metrics.histogram("decode.loop.exit_pass")
    ouro.observe_step(np.asarray(counts), at, np.asarray(lengths), 4)
    pairs, _ = hist.samples_between(at - 1e-9, at + 1e-9)
    assert [v for _, v in pairs] == ([] if want is None else [want])
    live, _ = metrics.histogram("decode.kv.live_positions").samples_between(
        at - 1e-9, at + 1e-9)
    assert [v for _, v in live] == [sum(lengths)]


# -- (5) the loop, as the programs hold it ---------------------------------
# scopes of a step outside its loop, and under ``loop_pass`` in the body
OUTSIDE = ("embed", "exit_gate", "head")
INSIDE = {"decode": ("qkv", "kv_write", "attend_pool", "attn_out", "mlp"),
          "prefill": ("qkv", "kv_write", "attend", "attn_out", "mlp")}


def _lowered(phase, platform="cpu"):
    cfg = config()
    real = jax.default_backend
    with engine_for(cfg, weights_for(cfg)) as eng:
        rep = eng._replicas[0]
        jax.default_backend = lambda: platform
        try:
            if phase == "decode":
                traced = eng._decode_jit.trace(
                    rep.params, *rep.pools, rep.no_tokens,
                    jnp.zeros((4 * (12 + 5),), jnp.int32))
            else:
                traced = eng._prefill_jit.trace(
                    rep.params, *rep.pools,
                    jnp.zeros((3 * 8 + 1,), jnp.int32))
            return traced.lower(lowering_platforms=(platform,))
        finally:
            jax.default_backend = real


@pytest.mark.parametrize("phase", sorted(INSIDE))
def test_steps_carry_their_names_and_scopes(phase):
    """The engine's jitted steps are ``_packed_prefill_fn`` /
    ``_packed_decode_fn`` for this family too; the stack lies under
    ``loop_pass`` inside the loop's body (its own function of the
    program), the gate's product there under ``exit_gate`` and the rule
    under the same name behind the loop."""
    text = _lowered(phase).as_text(debug_info=True)
    assert f"jit__packed_{phase}_fn" in text
    for scope in OUTSIDE:
        assert f'"jit(_packed_{phase}_fn)/{scope}/' in text, scope
    for scope in INSIDE[phase]:
        assert f'"loop_pass/{scope}/' in text, scope
        assert f"_fn)/{scope}/" not in text, scope      # and nowhere else
    assert '"exit_gate/dot_general"' in text


@pytest.mark.parametrize("phase", sorted(INSIDE))
def test_the_program_holds_the_stack_once(phase):
    """Lowered for a TPU, a program holds ONE loop over the passes and in
    its body ``n_layers`` reads (the paged ``latent_decode`` kernel in a
    decode step, ``flash_fwd`` in a prefill) and ``n_layers`` writes of
    the pool: not ``ut_steps x n_layers`` of either."""
    import re

    text = _lowered(phase, "tpu").as_text()
    assert text.count("stablehlo.while") == 1
    # a decode step's reads go through the kernel's own jitted function
    reads = text.count("call @latent_attention_kernel(") \
        if phase == "decode" else text.count("call @tpu_custom_call(")
    assert reads == LAYERS, reads
    assert ("latent_decode" if phase == "decode" else "flash_fwd") in text
    pool = rf"-> tensor<{PASSES * LAYERS}x\d+x4x{ROW}xf32>"
    body = text[text.index("func.func private @closed_call"):]
    body = body[:body.index("\n  }\n")]
    assert len(re.findall(r"stablehlo.scatter", body)) >= LAYERS
    assert len(re.findall(pool, body)) == LAYERS        # the scatters'
