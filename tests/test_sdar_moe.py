"""The block-diffusion, grouped-query, sparse-expert decoder family
(``models/sdar_moe.py``) at a small size on the CPU (every width shrunk,
every ratio kept: 8 query heads over 2 K/V heads of 16, 16 experts of
which 4 are chosen and 4 held, blocks of 4 positions), against the plain
reference the benchmark keeps (``benchmark/reference/sdar_moe_ref.py``):
the whole-sequence forward under the block-causal mask, generation through
``DecodeEngine`` (prefill, then passes over the paged pool) against the
reference's ``generate`` token for token AND pass for pass, the choice on
the device, the share, the kernel's mask, the counters and the scopes.

Tolerances.  Everything here is float32 on the CPU, the program under
``jax.default_matmul_precision("highest")`` where it is compared (the
reference sets it product by product), so program and reference differ by
the order of float32 sums only: logits of magnitude up to 3 agree to
about 1e-6, and the limit is 1e-4 (``TOL``).  Generation is compared
EXACTLY (tokens and passes): with seeded weights the two most confident
masked positions of a block lie 1e-3 to 1e-1 apart in log-probability, a
thousand times the rounding, and ``test_choice_is_stable_under_the_
tolerance`` holds every choice these tests compare to a margin of ten
limits.  The flash kernel in interpret mode sums a row in tiles: 2e-5, as
the grouped-heads test of ``test_lfm2_moe.py``.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.families import sdar_moe as family
from benchmark.reference import sdar_moe_ref as ref
from dist_keras_tpu.models import blocks, mla_moe, sdar_moe
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.serving import DecodeEngine
from dist_keras_tpu.serving.decode import _step_views
from dist_keras_tpu.utils.serialization import (
    deserialize_model,
    serialize_model,
)

TOL = 1e-4
VOCAB, MASK = 128, 127
HELD = [4, 5, 6, 7]
SIZES = dict(vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=8,
             n_kv_heads=2, head_dim=16, moe_d_ff=48, n_routed_experts=16,
             top_k=4, n_layers=3, held_experts=HELD, mask_token_id=MASK)


def config(**kw):
    return sdar_moe.sdar_moe_config(**{**SIZES, **kw})


def weights_for(cfg, seed=2 ** 31 + 7):
    """The benchmark's seeded weights: the ones a chip run hands to the
    program and to the reference alike."""
    return family.tree(weights.base_key(seed), cfg)


def engine_for(cfg, params, **kw):
    model = sdar_moe.SdarMoeDecoder(cfg=cfg)
    model.set_params(params)
    kw.setdefault("replicas", 1)
    kw.setdefault("prefill_ladder", (8, 16, 32))
    kw.setdefault("decode_ladder", (1, 4))
    kw.setdefault("page_size", 4)
    return DecodeEngine(model, **kw)


def reference_generate(params, prompt, n, cfg):
    return ref.generate(params, prompt, n, family.reference_config(cfg),
                        cfg["held_experts"])


def prompt_of(length, seed=0):
    """Ids below the mask id, as the traffic draws them."""
    return np.random.default_rng([seed, length]).integers(
        0, MASK, length).tolist()


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
    got = sdar_moe.forward(params, jnp.asarray(tokens), cfg)
    want = ref.forward(params, jnp.asarray(tokens),
                       family.reference_config(cfg), HELD)
    assert got.shape == (40, VOCAB)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_mask_is_block_causal_and_the_comparison_sees_it(highest):
    """A token changed inside a block moves the logits of its whole block
    (earlier positions of it too) and of every later one, and of no
    earlier block; the plain causal mask lies 1,000 limits away."""
    cfg = config()
    params = weights_for(cfg)
    tokens = np.random.default_rng(3).integers(0, MASK, 24)
    base = np.asarray(sdar_moe.forward(params, jnp.asarray(tokens), cfg))
    moved = tokens.copy()
    moved[14] = (moved[14] + 1) % MASK              # block 3: 12..15
    other = np.asarray(sdar_moe.forward(params, jnp.asarray(moved), cfg))
    change = np.abs(other - base).max(axis=1)
    assert (change[:12] == 0).all()
    assert (change[12:] > 10 * TOL).all()
    causal = np.asarray(sdar_moe.forward(
        params, jnp.asarray(tokens), {**cfg, "block_length": 1}))
    assert np.abs(causal - base).max() > 1000 * TOL


@pytest.mark.parametrize("q_block", [8, 16])
def test_reference_in_blocks_equals_reference_in_one_piece(q_block):
    cfg = config()
    params = weights_for(cfg)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, MASK, 32))
    conf = family.reference_config(cfg)
    whole = ref.forward(params, tokens, conf, HELD)
    parts = ref.forward(params, tokens, conf, HELD, q_block=q_block)
    np.testing.assert_allclose(parts, whole, atol=TOL / 10, rtol=0)


@pytest.mark.parametrize("tq,tk,block", [(32, 32, 4), (48, 48, 8),
                                         (24, 24, 2)])
def test_flash_forward_under_the_block_causal_mask(highest, tq, tk, block):
    """The kernel (interpret mode here) with the mask's block length, its
    tile skipping untouched (tiles are multiples of the block), against
    the ``jnp`` reference and against the mask written out by hand; with
    a block of 1 it is today's causal call."""
    from dist_keras_tpu.ops.attention import attention
    from dist_keras_tpu.ops.pallas.flash_attention import (
        flash_attention,
        repeat_kv_heads,
    )

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(tq + block), 3)
    q = jax.random.normal(kq, (2, tq, 8, 16))
    k = jax.random.normal(kk, (2, tk, 2, 16))
    v = jax.random.normal(kv, (2, tk, 2, 16))
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                          interpret=True, mask_block=block)
    kr, vr = repeat_kv_heads(8, k, v)
    want = attention(q, kr, vr, causal=True, mask_block=block)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    qpos, kpos = np.arange(tk - tq, tk), np.arange(tk)
    mask = ref.block_causal(qpos, kpos, block)
    logits = np.einsum("bthd,bshd->bhts", q, kr) * 16 ** -0.5
    p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), -1)
    np.testing.assert_allclose(
        got, np.einsum("bhts,bshd->bthd", p, vr), atol=2e-5, rtol=0)
    plain = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                            interpret=True, mask_block=1)
    np.testing.assert_array_equal(plain, flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8, interpret=True))


# -- (2) the model contract ---------------------------------------------
def test_benchmark_weights_are_in_the_programs_layout():
    cfg = config()
    ours = jax.eval_shape(lambda k: sdar_moe.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: family.tree(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] \
        == [x.shape for x in jax.tree.leaves(theirs)]
    # the chip's share: the router over all 16, the weights of 4
    moe = ours["blocks"][0]["moe"]
    assert moe["router"].shape == (64, 16)
    assert moe["experts"]["w_gate"].shape == (4, 64, 48)


def test_serialization_round_trip_holds_no_second_set_of_weights():
    cfg = config()
    model = sdar_moe.SdarMoeDecoder(cfg=cfg, seed=3)
    back = deserialize_model(serialize_model(model))
    assert isinstance(back, sdar_moe.SdarMoeDecoder)
    assert back.cfg == cfg
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(back.params)):
        np.testing.assert_array_equal(a, b)
    fresh = sdar_moe.SdarMoeDecoder(cfg=cfg)
    fresh.set_weights(model.get_weights())
    assert fresh._params is not None and fresh._seed == 0


@pytest.mark.parametrize("bad,match", [
    (dict(held_experts=[3, 5]), "consecutive"),
    (dict(top_k=17), "top_k"),
    (dict(n_kv_heads=3), "divide"),
    (dict(denoising_steps=3), "denoising_steps"),
    (dict(seq_len=46), "seq_len"),
    (dict(mask_token_id=128), "mask_token_id"),
])
def test_config_refuses_what_the_family_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        config(**bad)


def test_engine_refuses_a_page_a_block_would_straddle():
    cfg = config()
    with pytest.raises(ValueError, match="straddles"):
        engine_for(cfg, weights_for(cfg), page_size=6)


def test_one_pool_of_rows_and_the_other_families_are_a_token_wide():
    from dist_keras_tpu.models import (
        lfm2_moe,
        mla_moe,
        olmo_hybrid,
        transformer,
    )

    cfg = config()
    assert sdar_moe.cache_pools(cfg) == ((3, "page", (2 * 2 * 16,)),)
    assert sdar_moe.step_width(cfg) == 4
    assert sdar_moe.step_fixes(cfg) == (MASK, 1)
    assert sdar_moe.step_fixes(config(denoising_steps=2)) == (MASK, 2)
    for module in (transformer, mla_moe, lfm2_moe, olmo_hybrid):
        assert module.step_width({}) == 1
    with engine_for(cfg, weights_for(cfg), num_pages=20) as eng:
        assert eng.pool_shapes == ((3, 21, 4, 64),)
        # the top rung's four sequences and the one spare entry a block
        # of four passes asks for: five blocks, then the counts
        assert eng._entries(4) == 5 and eng._entries(1) == 2
        assert eng._out_width == 5 * 4 + len(HELD) + 2


# -- (3) generation through the engine -----------------------------------
# prompt lengths with P % B = 0, 1 and 3 (and one shorter than a block),
# replies that end inside a block
CASES = [(8, 9), (5, 6), (7, 10), (3, 5), (12, 3)]


@pytest.mark.parametrize("steps", [4, 2])
@pytest.mark.parametrize("flight", ["in_flight", "drained"])
def test_engine_generates_what_the_reference_generates(
        highest, monkeypatch, steps, flight):
    """Prefill, then passes through the paged pool, alone in the bottom
    rung: token for token and pass for pass the reference's ``generate``,
    which forwards the whole sequence anew for every pass.  ``drained``
    lands every pass before the next is launched (nothing carried on the
    device: every block the host hands over is host-known)."""
    cfg = config(denoising_steps=steps)
    params = weights_for(cfg)
    with engine_for(cfg, params) as eng:
        sources = _record_sources(eng, monkeypatch)
        if flight == "drained":
            real = eng._step_group

            def land_at_once(rep, group):
                real(rep, group)
                if rep.flight is not None:
                    real(rep, [])
            monkeypatch.setattr(eng, "_step_group", land_at_once)
        for length, n in CASES:
            prompt = prompt_of(length)
            doc = eng.generate(prompt, max_new_tokens=n, timeout_s=600)
            tokens, passes = reference_generate(params, prompt, n, cfg)
            assert doc["generated"] == tokens, (length, n)
            assert doc["passes"] == passes, (length, n)
            assert doc["finish"] == "length" and MASK not in tokens
            assert doc["steps"] == eng._steps_for(length, n)
        carried = [s for s in sources if s < 0]
        assert bool(carried) == (flight == "in_flight")
        eng.assert_no_leaks()


def _record_sources(eng, monkeypatch):
    """Every block entry the worker hands the device, in order."""
    seen = []
    real = eng._decode_jit

    def recording(*args):
        packed = np.array(args[-1])
        toks = _step_views(packed, eng.max_pages_per_seq, False,
                           eng._width)[0]
        seen.extend(toks.reshape(-1).tolist())
        return real(*args)
    monkeypatch.setattr(eng, "_decode_jit", recording)
    return seen


@pytest.mark.parametrize("steps", [4, 2])
@pytest.mark.parametrize("phase", ["apart", "together"])
def test_slots_generate_together_as_each_does_alone(highest, steps, phase):
    """The top rung: four requests in one pass, each the reference's own.
    ``apart``: different prompt tails and lengths, some committing while
    others denoise, a commit's next block in the pass's one spare entry.
    ``together``: four prompts of whole blocks, so all four commit in one
    pass and three of them find no entry to spare: those commit alone,
    open their next block a pass later, and lose nothing."""
    cfg = config(denoising_steps=steps)
    params = weights_for(cfg)
    sizes = CASES[:4] if phase == "apart" else [(8, 9), (4, 6), (12, 7),
                                                (8, 5)]
    with engine_for(cfg, params, decode_ladder=(1, 4)) as eng:
        gens = [(prompt_of(length, 1), n) for length, n in sizes]
        gens = [(p, n, eng.submit_generate(p, max_new_tokens=n))
                for p, n in gens]
        for prompt, n, gen in gens:
            doc = gen.result(timeout=600)
            tokens, passes = reference_generate(params, prompt, n, cfg)
            assert (doc["generated"], doc["passes"]) == (tokens, passes)
        # a rung is named by its SEQUENCES, whatever entries its pass holds
        assert ("decode", 4) in eng.stats()["shapes_dispatched"]
        assert {rung for phase_, rung in eng.stats()["shapes_dispatched"]
                if phase_ == "decode"} <= {1, 4}
        eng.assert_no_leaks()


def test_choice_is_stable_under_the_tolerance(highest):
    """What makes the exact comparisons above sound: at every pass the
    reference compares, the confidence that wins and the token that wins
    lead the runner-up by at least ten limits."""
    cfg = config()
    params = weights_for(cfg)
    conf = family.reference_config(cfg)
    margins = []
    for length, n in CASES:
        prompt = prompt_of(length)
        tokens, passes = reference_generate(params, prompt, n, cfg)
        seq = prompt + tokens
        for block in range(length // 4, (length + n) // 4):
            lo = 4 * block
            for p in range(4):
                state = [t if at < length or passes[at - length] < p
                         else MASK for at, t in
                         enumerate(seq[lo:lo + 4], lo)]
                if MASK not in state:
                    continue
                z = np.asarray(ref.forward(
                    params, jnp.asarray(seq[:lo] + state), conf,
                    HELD))[-4:]
                best, c = ref.confidences(z, MASK)
                masked = sorted((c[b] for b in range(4)
                                 if state[b] == MASK), reverse=True)
                if len(masked) > 1:
                    margins.append(masked[0] - masked[1])
                row = np.delete(z[int(np.argmax(c + np.where(
                    np.asarray(state) == MASK, 0, -np.inf)))], MASK)
                top = np.sort(row)[-2:]
                margins.append(top[1] - top[0])
    assert len(margins) > 20 and min(margins) > 10 * TOL


def test_the_mask_id_is_never_chosen(highest):
    """A head that favours the mask id at every position (a seeded model
    can; a trained one does not): the choice leaves it out on the device
    as in the reference, and its probability still counts in every
    confidence."""
    cfg = config()
    params = weights_for(cfg)
    params["head"] = params["head"].at[:, MASK].set(
        10.0 * params["head"][:, 5])
    z = sdar_moe.forward(params, jnp.asarray(prompt_of(8) + [MASK] * 4),
                         cfg)
    assert (np.asarray(z).argmax(-1) == MASK).any()
    with engine_for(cfg, params) as eng:
        doc = eng.generate(prompt_of(8), max_new_tokens=8, timeout_s=600)
    tokens, passes = reference_generate(params, prompt_of(8), 8, cfg)
    assert MASK not in doc["generated"]
    assert (doc["generated"], doc["passes"]) == (tokens, passes)


UNMASK = {
    # the block, the logits' winners and their margins -> after the pass
    "most_confident_first": ([MASK] * 4, [1.0, 3.0, 2.0, 0.5], 1,
                             [MASK, 11, MASK, MASK]),
    "two_a_pass": ([MASK] * 4, [1.0, 3.0, 2.0, 0.5], 2,
                   [MASK, 11, 12, MASK]),
    "fixed_positions_stay": ([7, MASK, 9, MASK], [9.0, 1.0, 9.0, 2.0], 1,
                             [7, MASK, 9, 13]),
    "first_of_equals": ([MASK] * 4, [2.0, 2.0, 2.0, 2.0], 1,
                        [10, MASK, MASK, MASK]),
    "commit_fixes_nothing": ([7, 8, 9, MASK], [1.0] * 4, 0,
                             [7, 8, 9, MASK]),
    "no_more_than_are_masked": ([7, 8, MASK, 6], [1.0] * 4, 2,
                                [7, 8, 12, 6]),
}


@pytest.mark.parametrize("case", sorted(UNMASK))
def test_unmask_fixes_the_most_confident_masked_positions(case):
    block, peaks, fix, want = UNMASK[case]
    logits = np.zeros((1, 4, VOCAB), np.float32)
    for b, peak in enumerate(peaks):
        logits[0, b, 10 + b] = peak         # position b prefers id 10 + b
        logits[0, b, MASK] = peak + 5.0     # and the mask id above all
    got = sdar_moe.unmask(jnp.asarray(logits), jnp.asarray([block]),
                          jnp.asarray([fix]), MASK)
    assert got.tolist() == [want]
    best, c = ref.confidences(logits[0], MASK)
    masked = [t == MASK for t in block]
    mine = [int(best[b]) if b in ref.most_confident(c, masked, fix)
            else block[b] for b in range(4)]
    assert mine == want


# -- (4) the share ---------------------------------------------------------
def test_the_shares_parts_add_up_to_the_uncut_layer(highest):
    """Section 4 of the model-configs guide: the parts of one expert
    layer's result that the shares give (four of four experts each here;
    eight of sixteen at the published size) add up to what the uncut
    reference gives for the whole layer, and a share alone is the
    reference's for the same held ids.  There is no shared expert to
    count once."""
    whole = config(held_experts=list(range(16)))
    moe = weights_for(whole)["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 64))
    conf = family.reference_config(whole)
    want = ref.expert_layer(moe, x, conf, list(range(16)))
    total = jnp.zeros_like(want)
    hit = 0
    for first in range(0, 16, 4):
        held = list(range(first, first + 4))
        share = {"router": moe["router"], "experts": jax.tree.map(
            lambda leaf: leaf[first:first + 4], moe["experts"])}
        part, counts = blocks.moe_layer(
            share, x, config(held_experts=held), jnp.ones((24,), bool),
            router=sdar_moe.route)
        np.testing.assert_allclose(
            part, ref.expert_layer(share, x, conf, held), atol=TOL, rtol=0)
        total = total + part
        hit += int(counts[:4].sum())
        assert int(counts[-1]) == 24 * 4
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    assert hit == 24 * 4            # every chosen pair lies on one share
    assert float(jnp.abs(want).max()) > 100 * TOL


ROUTING = {
    # the router's scores by expert -> the chosen four, their weights
    "by_probability": ([4., 3., 2., 1.] + [0.] * 12, [0, 1, 2, 3]),
    "first_of_equals": ([1.] * 16, [0, 1, 2, 3]),
    "anywhere": ([0.] * 9 + [5., 0., 4., 0., 3., 2., 0.], [9, 11, 13, 14]),
}


@pytest.mark.parametrize("case", sorted(ROUTING))
def test_routing_is_a_softmax_renormalised_over_the_chosen(case):
    scores, chosen = ROUTING[case]
    cfg = config()
    moe = {"router": jnp.zeros((64, 16)).at[0].set(jnp.asarray(scores))}
    x = jnp.zeros((1, 64)).at[0, 0].set(1.0)
    idx, w = sdar_moe.route(moe, x, cfg)
    assert sorted(idx[0].tolist()) == chosen
    p = np.exp(scores) / np.exp(scores).sum()
    want = p[idx[0]] / p[idx[0]].sum()
    np.testing.assert_allclose(w[0], want, atol=1e-6, rtol=0)
    assert abs(float(w.sum()) - 1.0) < 1e-6
    ridx, rw = ref.routing(moe, x, family.reference_config(cfg))
    assert ridx[0].tolist() == idx[0].tolist()
    np.testing.assert_allclose(rw, w, atol=1e-6, rtol=0)


# -- (5) counters and scopes -----------------------------------------------
PAIRS = ("decode.moe.pairs_total", "decode.moe.pairs_held")
STAMPED = ("decode.block.slots", "decode.block.tokens_fixed",
           "decode.block.commit_share", "decode.kv.live_positions",
           "decode.moe.experts_hit")


def _record_passes(eng, monkeypatch):
    """Every pass the worker hands the device, in order: (its live
    entries, those that commit, the commits whose sequence's next block
    is the entry right behind, recognised by the page table they share)."""
    seen = []
    real = eng._decode_jit

    def recording(*args):
        _, _, tables, _, _, lengths, fix = _step_views(
            np.array(args[-1]), eng.max_pages_per_seq, False, eng._width)
        live = int((lengths > 0).sum())
        folded = sum(1 for i in range(live - 1) if fix[i] == 0
                     and (tables[i] == tables[i + 1]).all())
        seen.append((live, int((fix[:live] == 0).sum()), folded))
        return real(*args)
    monkeypatch.setattr(eng, "_decode_jit", recording)
    return seen


def test_counters_exist_and_are_stamped(monkeypatch):
    cfg = config()
    for name in PAIRS + STAMPED + ("decode.block.passes",
                                   "decode.block.commit_folded",
                                   "decode.block.tokens_trimmed",
                                   "decode.moe.load_max_over_mean"):
        assert name in metrics.KNOWN_METRICS, name
    before = [metrics.counter(n).value for n in PAIRS]
    trimmed = metrics.counter("decode.block.tokens_trimmed")
    was = trimmed.value
    lo = time.perf_counter()
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,)) as eng:
        seen = _record_passes(eng, monkeypatch)
        sizes = [(8, 9), (5, 6), (7, 10), (6, 4)]
        gens = [eng.submit_generate(prompt_of(p), max_new_tokens=n)
                for p, n in sizes]
        docs = [g.result(timeout=600) for g in gens]
        passes = eng.stats()["steps"]
    hi = time.perf_counter()
    assert len(seen) == passes
    steps = metrics.histogram("decode.step_s").samples_between(lo, hi)[0]
    assert len(steps) == passes
    for name in STAMPED:
        pairs, cut = metrics.histogram(name).samples_between(lo, hi)
        assert not cut and len(pairs) == passes, name
        assert {at for at, _ in pairs} == {at for at, _ in steps}, name

    def window(name):
        return [v for _, v in
                metrics.histogram(name).samples_between(lo, hi)[0]]

    # every generated token and every trimmed one was fixed by some pass
    blocks = [-(-(p % 4 + n) // 4) for p, n in sizes]
    computed = sum(4 * b - p % 4 for b, (p, _) in zip(blocks, sizes))
    assert sum(window("decode.block.tokens_fixed")) == computed
    assert trimmed.value - was == computed - sum(n for _, n in sizes)
    assert sum(len(d["generated"]) for d in docs) \
        == sum(n for _, n in sizes)
    # the SEQUENCES of a pass (a commit and the block that opens behind
    # it are two entries of one), the share of its entries that commit
    # and, where any does, the share of those whose next block rides along
    entries, commits, folded = (list(col) for col in zip(*seen))
    slots = window("decode.block.slots")
    assert slots == [e - f for e, f in zip(entries, folded)]
    assert max(slots) == 4 and min(slots) >= 1 and max(entries) <= 5
    share = window("decode.block.commit_share")
    assert share == pytest.approx(
        [100.0 * c / e for c, e in zip(commits, entries)])
    assert 0.0 in share and max(share) <= 100.0
    assert sum(commits) == sum(blocks)
    assert window("decode.block.commit_folded") == pytest.approx(
        [100.0 * f / c for c, f in zip(commits, folded) if c])
    # every block but a request's last has the next one open behind its
    # commit, unless its pass had no entry to spare
    assert 1 <= sum(folded) <= sum(blocks) - len(sizes)
    # a block of four masks takes 4 passes, the one that holds a prompt's
    # tail fewer, and a commit that rode alone is one more.  One sample a
    # pass that committed blocks, their mean: weighted by the blocks a
    # pass committed the samples give back every block
    took = window("decode.block.passes")
    assert len(took) == sum(1 for c in commits if c)
    assert round(sum(v * c for v, c in zip(
        took, (c for c in commits if c)))) == \
        4 * (sum(blocks) - 4) + sum(4 - p % 4 for p, _ in sizes) \
        + sum(commits) - sum(folded)
    assert all(1.0 <= v <= 5.0 for v in took)
    live = window("decode.kv.live_positions")
    assert min(live) >= 8 and max(live) <= 5 * 20
    total, held = (metrics.counter(n).value - b
                   for n, b in zip(PAIRS, before))
    # the rows of every pass (a block an entry) and the committed
    # positions of every prefill, in all three layers
    rows = 4 * sum(entries) + sum(p - p % 4 for p, _ in sizes)
    assert total == rows * cfg["top_k"] * 3
    assert 0 < held < total


SCOPES = {"decode": ("carried_tokens", "embed", "qkv", "qk_norm_rope",
                     "kv_write", "attend_pool", "attn_out", "moe_route",
                     "moe_experts", "head", "unmask"),
          "prefill": ("embed", "qkv", "qk_norm_rope", "kv_write", "attend",
                      "attn_out", "moe_route", "moe_experts")}


@pytest.mark.parametrize("phase", sorted(SCOPES))
def test_steps_carry_their_names_and_scopes(phase):
    """The engine's jitted steps are ``_packed_prefill_fn`` /
    ``_packed_decode_fn`` for this family too, every part lies under a
    named scope, and a prefill, which yields no token, has no head."""
    cfg = config()
    i32 = jnp.int32
    with engine_for(cfg, weights_for(cfg)) as eng:
        rep = eng._replicas[0]
        if phase == "decode":
            lowered = eng._decode_jit.lower(
                rep.params, *rep.pools, rep.no_tokens,
                jnp.zeros((4 * (12 + 4 + 5),), i32))
        else:
            lowered = eng._prefill_jit.lower(
                rep.params, *rep.pools, jnp.zeros((3 * 8 + 1,), i32))
    text = lowered.as_text(debug_info=True)
    assert f"jit__packed_{phase}_fn" in text
    for scope in SCOPES[phase]:
        assert f"jit(_packed_{phase}_fn)/{scope}/" in text, scope
    assert "moe_shared" not in text
    if phase == "prefill":
        assert "/head/" not in text
