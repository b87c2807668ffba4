"""Decode serving (round 23): paged KV allocator invariants, the
continuous-batching engine bit-matching the full-forward oracle, typed
admission control, params pinned across hot reloads, decode.* chaos
with zero leaked pages, the HTTP /generate surface, and single-query
paged-attention kernel parity at every decode-ladder shape."""

import functools
import json
import re
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dist_keras_tpu.models import blocks, lfm2_moe, mla_moe
from dist_keras_tpu.models.transformer import (
    Transformer,
    apply_block,
    layer_norm,
    transformer_config,
)
from dist_keras_tpu.observability import metrics as _metrics
from dist_keras_tpu.ops.pallas import decode_attention
from dist_keras_tpu.resilience import faults
from dist_keras_tpu.resilience.faults import FaultInjected
from dist_keras_tpu.serving import (
    BlueGreenEngine,
    DecodeEngine,
    Overloaded,
    PagedKVCache,
    PagesExhausted,
    RouterServer,
    ServingServer,
)

VOCAB = 16
CFG = dict(input_dim=VOCAB, seq_len=32, d_model=16, n_heads=2,
           n_layers=2, n_classes=VOCAB)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _model(seed=0):
    return Transformer(transformer_config(**CFG), seed=seed)


def _engine(model=None, **kw):
    kw.setdefault("replicas", 1)
    kw.setdefault("prefill_ladder", (4, 8))
    kw.setdefault("decode_ladder", (1, 4))
    kw.setdefault("page_size", 4)
    return DecodeEngine(model or _model(), **kw)


# -- the oracle: full forward over the growing sequence ----------------
def _oracle_logits(params, cfg, tokens):
    """The logits at every position by the same shared-block math the
    engine's incremental KV path must reproduce bit-for-bit."""
    from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

    x = jax.nn.one_hot(jnp.asarray([tokens]), cfg["input_dim"])
    h = x @ params["proj"] + params["pos"][None, :len(tokens)]
    for blk in params["blocks"]:
        h = apply_block(blk, h, attention_auto, True)
    hs = layer_norm(params["ln_f"], h)[0]
    return hs @ params["head"]["kernel"] + params["head"]["bias"]


def _oracle_next(params, cfg, tokens):
    """Greedy next token: a full forward over the growing sequence."""
    return int(jnp.argmax(_oracle_logits(params, cfg, tokens)[-1]))


def _oracle_generate(params, cfg, tokens, max_new, eos_id=None):
    toks, out = list(tokens), []
    for _ in range(max_new):
        nxt = _oracle_next(params, cfg, toks)
        out.append(nxt)
        toks.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
    return out


@pytest.fixture(scope="module")
def engine_and_model():
    m = _model()
    eng = _engine(m, max_new_default=8)
    yield eng, m
    eng.close(drain=True)


# -- paged KV allocator ------------------------------------------------
def test_kv_pages_for_math():
    c = PagedKVCache(8, page_size=4)
    assert c.pages_for(1) == 1
    assert c.pages_for(4) == 1
    assert c.pages_for(5) == 2
    assert c.pages_for(32) == 8


def test_kv_alloc_free_exact_accounting():
    c = PagedKVCache(10, page_size=4)
    a = c.alloc("a", 6)     # 2 pages
    b = c.alloc("b", 9)     # 3 pages
    assert len(a) == 2 and len(b) == 3
    assert c.used_pages() == 5
    assert set(a).isdisjoint(b)
    c.free("a")
    assert c.used_pages() == 3
    c.free("b")
    assert c.used_pages() == 0
    c.assert_balanced()


def test_kv_exhaustion_typed_and_side_effect_free():
    c = PagedKVCache(3, page_size=4)
    c.alloc("a", 8)         # 2 of 3 pages
    with pytest.raises(PagesExhausted) as ei:
        c.alloc("b", 8)     # needs 2, only 1 free
    assert ei.value.needed == 2
    assert ei.value.free == 1
    assert ei.value.capacity == 3
    # the failed alloc left nothing behind
    assert c.used_pages() == 2
    c.free("a")
    c.assert_balanced()
    assert c.used_pages() == 0


def test_kv_free_unknown_sequence_raises():
    c = PagedKVCache(4, page_size=4)
    with pytest.raises(KeyError):
        c.free("ghost")


def test_kv_scratch_page_outside_pool():
    c = PagedKVCache(4, page_size=4)
    held = [c.alloc(i, 16) for i in range(1)]
    assert c.scratch_page == 4              # == num_pages: never handed out
    assert all(p != c.scratch_page for p in held[0])


def test_pool_shape_is_page_major_and_what_replicas_hold(engine_and_model):
    eng, m = engine_and_model
    # ONE pool, a row ``v | k`` a position: every head's values, then
    # every head's keys
    shape = (m.cfg["n_layers"], eng.num_pages + 1, eng.page_size,
             2 * m.cfg["d_model"])
    assert eng.pool_shapes == (shape,)
    for rep in eng._replicas:
        assert rep.cache.scratch_page == shape[1] - 1
        assert tuple(pool.shape for pool in rep.pools) == eng.pool_shapes
        for pool in rep.pools:
            assert pool.dtype == jnp.float32


# -- one packed array a dispatch ---------------------------------------
def _family_engine(family, decode_ladder):
    if family == "transformer":
        return _engine(decode_ladder=decode_ladder, prefill_ladder=(8, 16))
    if family == "lfm2_moe":
        cfg = lfm2_moe.lfm2_moe_config(
            vocab_size=128, seq_len=48, d_model=64, n_heads=8, n_kv_heads=2,
            d_ff=96, moe_d_ff=48, n_routed_experts=8, top_k=2,
            layer_types=["conv", "conv", "full_attention", "conv"] * 2)
        return DecodeEngine(lfm2_moe.Lfm2MoeDecoder(cfg=cfg, seed=1),
                            replicas=1, prefill_ladder=(8, 16),
                            decode_ladder=decode_ladder, page_size=4)
    cfg = mla_moe.mla_moe_config(
        vocab_size=128, seq_len=48, d_model=64, n_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, d_ff=96, moe_d_ff=48, n_routed_experts=8,
        n_shared_experts=1, top_k=3, n_layers=3, held_experts=[2, 3, 4],
        routed_scaling_factor=2.446, rope_theta=800000.0)
    return DecodeEngine(mla_moe.LatentMoEDecoder(cfg=cfg, seed=1),
                        replicas=1, prefill_ladder=(8, 16),
                        decode_ladder=decode_ladder, page_size=4)


def _filled_pools(eng, seed):
    """Pools with something in every position, so a wrong table, write
    page or length shows in the tokens or in the pools."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(eng.pool_shapes))
    return [jax.random.normal(k, shape, jnp.float32)
            for k, shape in zip(keys, eng.pool_shapes)]


def _assert_same_step(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("rung", [4, 8])
@pytest.mark.parametrize("family", ["transformer", "mla_moe"])
def test_packed_decode_step_is_the_familys_own(family, rung):
    """The dispatched program, handed the worker's ONE packed array and
    the output of the step before it, gives bit for bit the tokens and
    pools of the family's ``decode_step`` on the six arrays apart; the
    rung's last slots are padding.  Half of the live slots name their
    token's SOURCE, a slot of the carried output, in place of the token;
    and the output is as wide at every rung (tokens to the top rung, then
    the family's counts)."""
    from dist_keras_tpu.serving.decode import _step_views

    with _family_engine(family, (1, 4, 8)) as eng:
        rep, ps, pmax = eng._replicas[0], eng.page_size, \
            eng.max_pages_per_seq
        rng = np.random.default_rng(rung)
        live = rung - 2
        carried = rng.integers(0, eng.vocab, eng._out_width).astype(np.int32)
        assert rep.no_tokens.shape == carried.shape
        packed = np.zeros((rung * (pmax + 5),), np.int32)
        toks, positions, tables, wpage, woff, lengths = \
            _step_views(packed, pmax)
        assert tables.shape == (rung, pmax) and toks.shape == (rung,)
        wpage[:] = rep.cache.scratch_page
        pages = rng.permutation(eng.num_pages).astype(np.int32)
        for i in range(live):
            at = int(rng.integers(1, eng.seq_len - 1))
            mine = pages[i * pmax:(i + 1) * pmax]
            toks[i] = rng.integers(0, eng.vocab)
            positions[i] = at
            tables[i] = mine
            wpage[i], woff[i] = mine[at // ps], at % ps
            lengths[i] = at + 1
        assert np.count_nonzero(packed) > live * pmax     # views, not copies
        # (done before the views change below: on the CPU ``jnp.asarray``
        # may alias the host array, and the launch does not wait)
        want = jax.block_until_ready(
            jax.jit(functools.partial(eng._family.decode_step, eng.cfg))(
                rep.params, *_filled_pools(eng, 5), *map(
                    jnp.asarray, (toks, positions, tables, wpage, woff,
                                  lengths))))
        # every other live slot: "slot j of the carried output", j any
        # slot of the top rung
        for i in range(0, live, 2):
            j = int(rng.integers(0, 8))
            carried[j] = toks[i]
            toks[i] = -(j + 1)
        got = eng._decode_jit(rep.params, *_filled_pools(eng, 5),
                              jnp.asarray(carried), jnp.asarray(packed))
        out, *pools = got
        assert out.shape == (eng._out_width,)
        assert not np.asarray(out[rung:8]).any()
        _assert_same_step(
            [jnp.concatenate([out[:rung], out[8:]]), *pools], want)


@pytest.mark.parametrize("rung,n", [(8, 5), (16, 13)])
@pytest.mark.parametrize("family", ["transformer", "mla_moe"])
def test_packed_prefill_is_the_familys_own(family, rung, n):
    """The same for a prompt of ``n`` tokens padded to its rung: the four
    arrays of ``prefill_step`` out of one."""
    from dist_keras_tpu.serving.decode import _prefill_views

    with _family_engine(family, (1, 4)) as eng:
        rep, ps = eng._replicas[0], eng.page_size
        rng = np.random.default_rng(rung)
        packed = np.zeros((3 * rung + 1,), np.int32)
        toks, _, page_idx, page_off = _prefill_views(packed)
        pages = rng.permutation(eng.num_pages)[:-(-n // ps)]
        toks[:n] = rng.integers(0, eng.vocab, n)
        page_idx[:] = rep.cache.scratch_page
        for t in range(n):        # the loop the worker's np.repeat replaced
            page_idx[t] = pages[t // ps]
        page_off[:] = np.arange(rung) % ps
        packed[-1] = n
        want = jax.jit(functools.partial(eng._family.prefill_step, eng.cfg))(
            rep.params, *_filled_pools(eng, 6), jnp.asarray(toks),
            jnp.int32(n), jnp.asarray(page_idx), jnp.asarray(page_off))
        got = eng._prefill_jit(rep.params, *_filled_pools(eng, 6),
                               jnp.asarray(packed))
        _assert_same_step(got, want)


def test_worker_packs_what_the_per_token_loops_built():
    """Through a real ``generate`` (a 6-token prompt over pages of 4, then
    5 steps): every array the worker hands to the device is what the
    per-token loops it replaced would build from the sequence's pages."""
    from dist_keras_tpu.serving.decode import _prefill_views, _step_views

    with _engine(max_new_default=6) as eng:
        seen = {"prefill": [], "decode": []}

        def recording(phase, jitted):
            def call(*args):
                seen[phase].append(np.array(args[-1]))
                return jitted(*args)
            return call

        eng._prefill_jit = recording("prefill", eng._prefill_jit)
        eng._decode_jit = recording("decode", eng._decode_jit)
        prompt = [3, 1, 4, 1, 5, 9]
        out = eng.generate(prompt)["tokens"]
        ps, pmax = eng.page_size, eng.max_pages_per_seq
        scratch = eng._replicas[0].cache.scratch_page
    assert len(seen["prefill"]) == 1 and len(seen["decode"]) == 5
    # the sequence's pages: row 0 of any step's table (12 positions' worth)
    pages = _step_views(seen["decode"][0], pmax)[2][0][:3]
    assert len(set(pages.tolist())) == 3 and scratch not in pages

    rung = 8
    toks, length, page_idx, page_off = _prefill_views(seen["prefill"][0])
    assert toks.tolist() == prompt + [0, 0] and length == 6
    assert page_idx.tolist() == [pages[t // ps] for t in range(6)] \
        + [scratch] * 2
    assert page_off.tolist() == [t % ps for t in range(rung)]

    for k, packed in enumerate(seen["decode"]):
        assert packed.dtype == np.int32 and packed.shape == (pmax + 5,)
        toks, positions, tables, wpage, woff, lengths = \
            _step_views(packed, pmax)
        at = 6 + k
        # the first step's token is the prefill's, which the host knows;
        # every later one is slot 0 of the step in flight's output
        assert (toks.tolist(), positions.tolist(), lengths.tolist()) == \
            ([out[at] if k == 0 else -1], [at], [at + 1])
        assert tables[0].tolist() == pages.tolist() + [0] * (pmax - 3)
        assert (wpage.tolist(), woff.tolist()) == \
            ([pages[at // ps]], [at % ps])


# -- engine vs oracle --------------------------------------------------
def test_greedy_decode_matches_oracle(engine_and_model):
    eng, m = engine_and_model
    prompt = [3, 1, 4, 1, 5]
    doc = eng.generate(prompt, max_new_tokens=6, timeout_s=300)
    want = _oracle_generate(m.params, m.cfg, prompt, 6)
    assert doc["generated"] == want
    assert doc["finish"] == "length"
    assert doc["prompt_len"] == 5
    assert doc["tokens"] == prompt + want


def test_concurrent_mixed_lengths_match_oracle(engine_and_model):
    eng, m = engine_and_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, size=int(n)).tolist()
               for n in rng.integers(2, 8, size=7)]
    gens = [eng.submit_generate(p, max_new_tokens=4 + i % 3)
            for i, p in enumerate(prompts)]
    for i, (p, g) in enumerate(zip(prompts, gens)):
        doc = g.result(timeout=300)
        assert doc["generated"] == _oracle_generate(
            m.params, m.cfg, p, 4 + i % 3), f"sequence {i} diverged"
    st = eng.stats()
    assert st["retrace_count"] <= st["retrace_bound"]
    phases = {ph for ph, _ in st["shapes_dispatched"]}
    assert phases <= {"prefill", "decode"}


def test_eos_stops_early(engine_and_model):
    eng, m = engine_and_model
    prompt = [2, 7, 2]
    free = _oracle_generate(m.params, m.cfg, prompt, 8)
    eos = free[2]
    want = free[:free.index(eos) + 1]
    doc = eng.generate(prompt, max_new_tokens=8, eos_id=eos,
                       timeout_s=300)
    assert doc["generated"] == want
    assert doc["finish"] == "eos"


# -- a decode step in flight -------------------------------------------
def _family_logits(eng, tokens):
    """The oracle of the engine's own family: one full forward over the
    whole sequence, no cache -> the logits at every position."""
    params, cfg = eng._host_params, eng.cfg
    if eng._family.FAMILY == "transformer":
        return _oracle_logits(params, cfg, tokens)
    return eng._family.forward(params, jnp.asarray(tokens), cfg)


def _assert_greedy(eng, doc):
    """Every generated token is the oracle's greedy choice after the
    tokens before it (the families are causal: position ``t``'s logits
    are those of a forward over ``tokens[:t + 1]`` alone)."""
    best = np.asarray(jnp.argmax(_family_logits(eng, doc["tokens"][:-1]), -1))
    assert doc["generated"] == best[doc["prompt_len"] - 1:].tolist()


def _held_until_all_are_in(eng, requests):
    """Submit ``requests`` (``submit_generate`` keywords; ``on_token`` is
    the test's own list's ``append``) so that the worker sees them all in
    ONE scheduling pass after the first one's prefill: that prefill's
    token callback waits for the last submit.  From then on no prefill
    cuts in between two steps, and which step had a successor is known
    from the counts alone."""
    all_in = threading.Event()
    streams = [[] for _ in requests]

    def first(t):
        streams[0].append(t)
        assert all_in.wait(60)

    gens = [eng.submit_generate(
        **req, on_token=first if i == 0 and not streams[0] else
        streams[i].append) for i, req in enumerate(requests)]
    all_in.set()
    return gens, streams


@pytest.mark.parametrize("family", ["transformer", "mla_moe", "lfm2_moe"])
def test_overlapped_steps_return_the_oracles_tokens(family):
    """Mixed-length concurrent requests, some ending on ``eos_id`` and
    some on ``max_new``, with a step in flight all the way: every reply
    is the per-token oracle's (each request alone first, held to a full
    forward over its tokens; then all together, held to those replies cut
    at their ``eos``), no callback fires after an ``eos``, and
    ``decode.tokens_discarded`` counts exactly the ``eos`` endings that
    had a successor step launched (an ``eos`` that is also the count's
    last token had none: the sequence was left out of that step)."""
    discarded = _metrics.counter("decode.tokens_discarded")
    rng = np.random.default_rng(5)
    with _family_engine(family, (1, 4, 8)) as eng:
        plans = []
        for n, max_new, stop in ((3, 9, None), (7, 6, 4), (5, 8, 8),
                                 (2, 7, 3), (6, 5, None), (4, 9, 6)):
            prompt = rng.integers(0, eng.vocab, n).tolist()
            alone = eng.generate(prompt, max_new_tokens=max_new,
                                 timeout_s=600)
            _assert_greedy(eng, alone)
            free, eos = alone["generated"], None
            if stop is not None:
                # the first token from ``stop`` on that did not occur
                # before: the reply ends there, at the latest on its count
                at = next((k for k in range(stop - 1, max_new)
                           if free[k] not in free[:k]), None)
                eos = None if at is None else free[at]
            want = free if eos is None else free[:free.index(eos) + 1]
            plans.append((prompt, max_new, eos, want))
        before = discarded.value
        gens, streams = _held_until_all_are_in(eng, [
            dict(tokens=p, max_new_tokens=m, eos_id=e)
            for p, m, e, _ in plans])
        docs = [g.result(timeout=600) for g in gens]
        late = 0
        for doc, stream, (_, max_new, eos, want) in zip(docs, streams, plans):
            assert doc["generated"] == want == stream
            ended = eos is not None and want[-1] == eos
            assert doc["finish"] == ("eos" if ended else "length")
            late += ended and len(want) < max_new
        assert any(d["finish"] == "eos" for d in docs) and late
        eng.drain(timeout_s=60)       # the last discarded slot has landed
        assert discarded.value - before == late
        eng.assert_no_leaks()


@pytest.mark.parametrize("how", ["cancel", "deadline", "eos"])
def test_pages_reused_under_a_step_in_flight(how):
    """A sequence leaves mid-decode while a launched step still names its
    pages, and the pool is sized so that the next admission MUST take
    them: the pages are free at once, the newcomer's prefill is ordered
    behind the stale step by the pools it takes, and its reply is the
    oracle's."""
    m = _model()
    # 3 pages of 4: exactly one reservation of 4 + 8 tokens
    eng = _engine(m, num_pages=3, decode_ladder=(1, 4))
    discarded = _metrics.counter("decode.tokens_discarded")
    try:
        first, second = [1, 2, 3, 4], [9, 8, 7, 6]
        free = _oracle_generate(m.params, m.cfg, first, 8)
        eos = free[3] if how == "eos" and free[3] not in free[:3] else None
        if how == "eos":
            assert eos is not None
        before = discarded.value
        seen, gen = [], []

        def on_token(t):
            seen.append(t)
            if len(seen) == 4 and how == "cancel":
                # inside the landing of a step whose successor is out
                assert gen[0].cancel()
            if len(seen) == 4 and how == "deadline":
                gen[0]._seq.deadline = time.monotonic() - 1.0   # ran out

        gen.append(eng.submit_generate(first, max_new_tokens=8, eos_id=eos,
                                       on_token=on_token))
        pages = set(gen[0]._seq.pages)
        doc = gen[0].result(timeout=300)
        assert doc["finish"] == ("cancelled" if how == "cancel" else how)
        assert doc["generated"] == seen == free[:4]
        newcomer = None
        deadline = time.monotonic() + 60
        while newcomer is None and time.monotonic() < deadline:
            try:
                newcomer = eng.submit_generate(second, max_new_tokens=8)
            except Overloaded as e:       # not retired yet: its pages
                assert e.reason == "kv_exhausted"
                time.sleep(0.002)
        assert set(newcomer._seq.pages) == pages
        assert newcomer.result(timeout=300)["generated"] == \
            _oracle_generate(m.params, m.cfg, second, 8)
        # the step launched on the leaver's fourth token was thrown away
        assert discarded.value - before == 1
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


def test_set_params_during_traffic_keeps_generations_apart():
    """Two params generations decode side by side (their steps alternate,
    each launched behind the other's): neither sequence sees the other's
    params, token for token."""
    m = _model()
    eng = _engine(m, num_pages=32, decode_ladder=(1, 4))
    try:
        old = jax.tree.map(np.asarray, m.params)
        new = jax.tree.map(lambda a: np.asarray(a) * 0.5, m.params)
        under_way, swapped = threading.Event(), threading.Event()
        first = []

        def on_token(t):
            first.append(t)
            if len(first) == 3:
                under_way.set()
                assert swapped.wait(60)

        a = eng.submit_generate([5, 3, 1], max_new_tokens=12,
                                on_token=on_token)
        assert under_way.wait(300)
        eng.set_params({"params": new}, step=1)
        second = []
        b = eng.submit_generate([5, 3, 1], max_new_tokens=9,
                                on_token=second.append)
        c = eng.submit_generate([2, 6], max_new_tokens=7)
        swapped.set()
        want_a = _oracle_generate(old, m.cfg, [5, 3, 1], 12)
        want_b = _oracle_generate(new, m.cfg, [5, 3, 1], 9)
        assert want_a[:9] != want_b           # the two generations differ
        assert a.result(timeout=300)["generated"] == want_a == first
        assert b.result(timeout=300)["generated"] == want_b == second
        assert c.result(timeout=300)["generated"] == \
            _oracle_generate(new, m.cfg, [2, 6], 7)
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


def _recording_steps(eng):
    """Every packed array the engine launches a decode step on."""
    from dist_keras_tpu.serving.decode import _step_views

    real, sent = eng._decode_jit, []

    def step(*args):
        sent.append(_step_views(np.array(args[-1]), eng.max_pages_per_seq))
        return real(*args)

    eng._decode_jit = step
    return sent


def test_steps_overlapped_and_no_step_for_a_sequence_past_its_count():
    """A lone 6-token request runs 5 decode steps, 4 of them launched
    under their predecessor; and with no ``eos`` in play no step is spent
    on a sequence that ended on its count: the live slots of all launched
    steps are exactly the tokens the steps delivered."""
    discarded = _metrics.counter("decode.tokens_discarded")
    overlapped = _metrics.histogram("decode.step_overlapped")
    with _engine(decode_ladder=(1, 4)) as eng:
        eng.generate([1, 2], max_new_tokens=2)          # compiles outside
        sent = _recording_steps(eng)
        lo = time.perf_counter()
        doc = eng.generate([3, 1, 4], max_new_tokens=6, timeout_s=300)
        hi = time.perf_counter()
        flags = [v for _, v in overlapped.samples_between(lo, hi)[0]]
        assert doc["steps"] == 5 and flags == [0.0, 1.0, 1.0, 1.0, 1.0]
        # a step's token is the host's only with nothing in flight
        assert [int(toks[0]) < 0 for toks, *_ in sent] == \
            [False, True, True, True, True]

        del sent[:]
        before = discarded.value
        gens, _ = _held_until_all_are_in(eng, [
            dict(tokens=[7, i], max_new_tokens=n)
            for i, n in enumerate((3, 5, 7, 2))])
        docs = [g.result(timeout=300) for g in gens]
        assert [len(d["generated"]) for d in docs] == [3, 5, 7, 2]
        live = sum(int((lengths > 0).sum()) for *_, lengths in sent)
        assert live == sum(d["steps"] for d in docs) == 2 + 4 + 6 + 1
        assert discarded.value == before
        eng.assert_no_leaks()


# -- admission control -------------------------------------------------
def test_admission_validates_inputs(engine_and_model):
    eng, _ = engine_and_model
    with pytest.raises(ValueError):
        eng.submit_generate([])
    with pytest.raises(ValueError):
        eng.submit_generate([0, VOCAB])        # token out of vocab
    with pytest.raises(ValueError):
        eng.submit_generate([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError):
        eng.submit_generate(list(range(1, 10)))  # prompt > ladder top


def test_kv_exhausted_is_typed_backpressure():
    # pool of 3 pages (page_size 4): one 12-token reservation fits,
    # a concurrent second one must be refused at the door, typed
    eng = _engine(num_pages=3, max_new_default=8)
    try:
        g = eng.submit_generate([1, 2, 3, 4], max_new_tokens=8)
        with pytest.raises(Overloaded) as ei:
            eng.submit_generate([1, 2, 3, 4], max_new_tokens=8)
        assert ei.value.reason == "kv_exhausted"
        assert ei.value.pending is not None
        assert ei.value.capacity is not None
        g.result(timeout=300)                  # first one still delivers
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


def test_cancel_reclaims_pages():
    eng = _engine(num_pages=12)   # 3 sequences x 3 pages each
    try:
        gens = [eng.submit_generate([1, 2, 3], max_new_tokens=8)
                for _ in range(3)]
        for g in gens:
            eng.cancel(g)
        for g in gens:
            try:
                g.result(timeout=300)          # cancelled or finished —
            except Overloaded:                 # never hung, never untyped
                pass
        deadline = time.monotonic() + 60
        while eng.stats()["outstanding"] and time.monotonic() < deadline:
            time.sleep(0.01)
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


def test_close_without_drain_fails_orphans_typed():
    eng = _engine(num_pages=32)
    gens = [eng.submit_generate([1, 2], max_new_tokens=8)
            for _ in range(4)]
    eng.close(drain=False)
    resolved = 0
    for g in gens:
        try:
            g.result(timeout=60)
            resolved += 1                       # raced completion: fine
        except Overloaded as e:
            assert e.reason == "stopped"
            resolved += 1
    assert resolved == 4
    eng.assert_no_leaks()


# -- hot reload: params pinned at admission ----------------------------
def test_set_params_pins_inflight_sequences():
    m = _model()
    eng = _engine(m, num_pages=32, max_new_default=10)
    try:
        old = jax.tree.map(np.asarray, m.params)
        g = eng.submit_generate([5, 3, 1], max_new_tokens=10)
        new = jax.tree.map(lambda a: np.asarray(a) * 0.5, m.params)
        eng.set_params({"params": new}, step=1)  # may land mid-decode
        doc = g.result(timeout=300)
        cfg = m.cfg
        assert doc["generated"] == _oracle_generate(old, cfg,
                                                    [5, 3, 1], 10)
        after = eng.generate([5, 3, 1], max_new_tokens=10,
                             timeout_s=300)
        assert after["generated"] == _oracle_generate(new, cfg,
                                                      [5, 3, 1], 10)
        assert eng.stats()["reloads"] == 1
    finally:
        eng.close(drain=True)


def test_bluegreen_cutover_drops_nothing():
    models = []

    def make_engine():
        m = _model()
        models.append(m)
        return _engine(m, num_pages=64, max_new_default=8,
                       max_queue=4096)

    bg = BlueGreenEngine(make_engine)
    try:
        gens = [bg.submit_generate([1 + i % 5, 2], max_new_tokens=8)
                for i in range(6)]
        state = {"params": jax.tree.map(
            lambda a: np.asarray(a) * 0.5, models[0].params)}
        bg.set_params(state, step=1)            # cutover mid-decode
        gens += [bg.submit_generate([3, 4], max_new_tokens=4)
                 for _ in range(3)]
        docs = [g.result(timeout=300) for g in gens]
        assert all(d["finish"] == "length" for d in docs)
        assert bg.cutovers == 1
        deadline = time.monotonic() + 60
        while (bg.stats()["standby_outstanding"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        st = bg.stats()
        assert st["outstanding"] == 0
        assert st["standby_outstanding"] == 0
        for e in (bg.active, bg.standby):
            e.assert_no_leaks()
    finally:
        bg.close()


# -- decode.* faults: typed failures, zero leaked pages ----------------
def test_fault_points_typed(engine_and_model):
    eng, _ = engine_and_model
    with faults.armed("decode.admit"):
        with pytest.raises(FaultInjected):
            eng.submit_generate([1, 2], max_new_tokens=4)
    with faults.armed("decode.kv_alloc"):
        with pytest.raises(FaultInjected):
            eng.submit_generate([1, 2], max_new_tokens=4)
    # a single step fault is absorbed by the in-place retry (the
    # survivability retry policy); past the retry, a single-replica
    # engine has no survivor to quarantine onto, so it lands TYPED
    with faults.armed("decode.step", times=2):
        g = eng.submit_generate([1, 2], max_new_tokens=6)
        with pytest.raises(FaultInjected):
            g.result(timeout=300)
    # the engine keeps serving after every fault
    doc = eng.generate([1, 2], max_new_tokens=2, timeout_s=300)
    assert len(doc["generated"]) == 2
    eng.assert_no_leaks()


class _Poisoned:
    """A step's output that fails when it is fetched, as an asynchronous
    device failure does: launched fine, raises at ``np.asarray``."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device failure surfaced at the fetch")


def _poison_fetches(monkeypatch, eng, which):
    """The engine's decode program, its ``which``-th outputs (counting
    the launches that ran) poisoned.  The pools move on as they do; a
    successor handed the poisoned output fails at its launch."""
    real, ran = eng._decode_jit, []

    def step(*args):
        out, *pools = real(*args)
        ran.append(1)
        return (_Poisoned() if len(ran) in which else out, *pools)

    monkeypatch.setattr(eng, "_decode_jit", step)


def _arm_step_fault(monkeypatch, eng, where, times):
    """``times`` consecutive failures of one step: at the ``launch`` of
    the first step (nothing in flight), at a launch ``under_flight``
    (the third dispatch, its predecessor running), or at the ``fetch`` of
    the second step's tokens with the third already launched on them."""
    if where == "fetch":
        _poison_fetches(monkeypatch, eng, set(range(2, 2 + times)))
    else:
        faults.inject("decode.step", at=0 if where == "launch" else 2,
                      times=times)


@pytest.mark.parametrize("where", ["launch", "under_flight", "fetch"])
def test_step_fault_absorbed_by_retry(engine_and_model, monkeypatch, where):
    # one transient step failure: the step retries in place and the
    # caller never notices (pools and kv_len advance only when a step
    # LANDS, so the retry is sound; a failed fetch drops the successor
    # launched on its tokens with it, and counts once)
    eng, m = engine_and_model
    _arm_step_fault(monkeypatch, eng, where, times=1)
    seen = []
    doc = eng.submit_generate([2, 4, 6], max_new_tokens=6,
                              on_token=seen.append).result(timeout=300)
    want = _oracle_generate(m.params, m.cfg, [2, 4, 6], 6)
    assert doc["generated"] == want and seen == want
    assert doc["recoveries"] == 0 and doc["steps"] == 5
    eng.assert_no_leaks()


def test_seeded_chaos_sweep_zero_leaks():
    eng = _engine(num_pages=24, max_queue=32)
    rng = np.random.default_rng(7)
    points = ("decode.admit", "decode.kv_alloc", "decode.step")
    typed = 0
    try:
        for trial in range(9):
            faults.inject(points[trial % 3],
                          at=int(rng.integers(0, 3)), times=1)
            gens = []
            for _ in range(3):
                try:
                    gens.append(eng.submit_generate(
                        [int(rng.integers(0, VOCAB)), 1],
                        max_new_tokens=int(rng.integers(2, 7))))
                except (FaultInjected, Overloaded):
                    typed += 1
            for g in gens:
                try:
                    g.result(timeout=300)
                except (FaultInjected, Overloaded):
                    typed += 1
            faults.clear()
        assert typed >= 1, "chaos never fired"
        eng.drain(timeout_s=300)
        eng.assert_no_leaks()
    finally:
        eng.close(drain=False)


# -- survivability: quarantine + sequence-level recovery ---------------
def _owner_index(eng, gen):
    """Replica currently holding a generation (whitebox: the engine
    deliberately does not expose placement)."""
    with eng._cond:
        for rep in eng._replicas:
            if gen._seq in rep.active or gen._seq in rep.queue:
                return rep.index
    return None


def test_kill_replica_racing_prefill_bit_identical():
    # the kill lands while the sequence is queued or mid-prefill (the
    # first jit compile is slow); either way the survivor replays it
    # and the future never sees the failure
    m = _model()
    eng = _engine(m, replicas=2, num_pages=32)
    try:
        prompt = [3, 1, 4, 1]
        seen = []
        g = eng.submit_generate(prompt, max_new_tokens=6,
                                on_token=seen.append)
        eng.kill_replica(0)      # first admission lands on replica 0
        doc = g.result(timeout=300)
        want = _oracle_generate(m.params, m.cfg, prompt, 6)
        assert doc["generated"] == want
        assert seen == want      # streaming resumed: no dup, no skip
        st = eng.stats()
        assert st["quarantines"] == 1
        assert st["replicas_dead"] == 1
        assert st["replicas"] == 1
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


@pytest.mark.parametrize("after", [1, 2, 4])
def test_kill_replica_mid_decode_bit_identical(after):
    # the kill fires from the token stream itself: after the prefill's
    # token (nothing in flight), or after a step's, when its successor is
    # already launched on the device's tokens and is dropped with the
    # replica; the replay starts from what LANDED and is teacher-forced,
    # so the stream resumes exactly where it stopped
    m = _model()
    eng = _engine(m, replicas=2, num_pages=32)
    try:
        prompt = [2, 7, 1]
        seen = []

        def on_token(t):
            seen.append(t)
            if len(seen) == after:
                eng.kill_replica(0)

        g = eng.submit_generate(prompt, max_new_tokens=6,
                                on_token=on_token)
        doc = g.result(timeout=300)
        want = _oracle_generate(m.params, m.cfg, prompt, 6)
        assert doc["generated"] == want
        assert seen == want
        assert doc["recoveries"] == 1
        assert doc["finish"] == "length"
        st = eng.stats()
        assert st["quarantines"] == 1
        assert st["recovered"] == 1
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


@pytest.mark.parametrize("where", ["launch", "under_flight", "fetch"])
def test_step_fault_past_retry_quarantines_and_recovers(monkeypatch, where):
    # one step fails twice (beats the 1 in-place retry) on the owning
    # replica, at its launch or at the fetch of its tokens; a survivor
    # exists, so the replica quarantines and the sequence replays to a
    # bit-identical doc — the caller never sees the failure
    m = _model()
    eng = _engine(m, replicas=2, num_pages=32)
    try:
        prompt = [5, 3]
        _arm_step_fault(monkeypatch, eng, where, times=2)
        seen = []
        doc = eng.submit_generate(prompt, max_new_tokens=5,
                                  on_token=seen.append).result(timeout=300)
        assert doc["generated"] == seen == _oracle_generate(
            m.params, m.cfg, prompt, 5)
        assert doc["recoveries"] == 1
        st = eng.stats()
        assert st["quarantines"] == 1
        assert st["recovered"] == 1
        assert st["errors"] == 0
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


def test_recover_fault_fails_orphans_typed():
    # recovery itself is the injected failure: orphans resolve typed
    # (never hung), pages reclaimed
    eng = _engine(replicas=2, num_pages=32)
    try:
        g = eng.submit_generate([1, 2], max_new_tokens=6)
        with faults.armed("decode.recover"):
            eng.kill_replica(0)
            with pytest.raises(FaultInjected):
                g.result(timeout=300)
        eng.assert_no_leaks()
        assert eng.stats()["errors"] == 1
    finally:
        eng.close(drain=True)


def test_kill_last_live_replica_refused():
    eng = _engine(replicas=2, num_pages=32)
    try:
        eng.kill_replica(1)
        deadline = time.monotonic() + 60
        while (eng.stats()["replicas_dead"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        with pytest.raises(ValueError):
            eng.kill_replica(0)   # whole-pod loss is out of scope
        with pytest.raises(ValueError):
            eng.kill_replica(1)   # already dead
        doc = eng.generate([1, 2], max_new_tokens=2, timeout_s=300)
        assert len(doc["generated"]) == 2
    finally:
        eng.close(drain=True)


def test_churn_many_sequences_zero_lost():
    # several in-flight sequences, one replica killed mid-load: every
    # future resolves to the oracle answer, nothing lost, no leaks
    m = _model()
    eng = _engine(m, replicas=3, num_pages=48, max_queue=64)
    try:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, VOCAB, size=int(n)).tolist()
                   for n in rng.integers(2, 6, size=6)]
        gens = [eng.submit_generate(p, max_new_tokens=5)
                for p in prompts]
        eng.kill_replica(0)
        for p, g in zip(prompts, gens):
            doc = g.result(timeout=300)
            assert doc["generated"] == _oracle_generate(
                m.params, m.cfg, p, 5)
        st = eng.stats()
        assert st["quarantines"] == 1
        assert st["completed"] == 6
        assert st["errors"] == 0
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


def test_kill_with_full_survivor_orphans_wait_not_fail():
    # the survivor's pool cannot hold the orphans at quarantine time:
    # they WAIT for capacity (they were admitted once — the door
    # contract is spent) and complete bit-identically as pages free,
    # instead of resolving Overloaded("replica_lost")
    m = _model()
    # 8 pages/replica; each sequence reserves 4 (2 prompt + 14 new =
    # 16 tokens): two sequences fill a replica exactly
    eng = _engine(m, replicas=2, num_pages=8, max_queue=64)
    try:
        prompts = [[1, 2], [3, 4], [5, 6], [7, 8]]
        gens = [eng.submit_generate(p, max_new_tokens=14)
                for p in prompts]
        eng.kill_replica(0)
        for p, g in zip(prompts, gens):
            doc = g.result(timeout=300)
            assert doc["generated"] == _oracle_generate(
                m.params, m.cfg, p, 14)
        st = eng.stats()
        assert st["quarantines"] == 1
        assert st["recovered"] == 2
        assert st["completed"] == 4
        assert st["errors"] == 0
        assert st["orphans_pending"] == 0
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


# -- deadlines + shedding ----------------------------------------------
def test_deadline_infeasible_rejected_at_door(engine_and_model):
    eng, _ = engine_and_model
    # warm the prefill/step EWMAs so feasibility has an estimate
    eng.generate([1, 2], max_new_tokens=2, timeout_s=300)
    with pytest.raises(Overloaded) as ei:
        eng.submit_generate([1, 2], max_new_tokens=8,
                            deadline_s=1e-6)
    assert ei.value.reason == "deadline_infeasible"
    assert eng.stats()["deadline_infeasible"] >= 1
    with pytest.raises(ValueError):
        eng.submit_generate([1, 2], deadline_s=0)


def test_deadline_expiry_frees_slot_mid_decode():
    # fresh engine: no EWMAs yet, so the door admits; the token
    # callback stalls past the deadline and the scheduler retires the
    # sequence between steps with the tokens produced so far
    eng = _engine(num_pages=32)
    try:
        def stall(_t):
            time.sleep(0.4)

        g = eng.submit_generate([1, 2], max_new_tokens=8,
                                deadline_s=0.2, on_token=stall)
        doc = g.result(timeout=300)
        assert doc["finish"] == "deadline"
        assert 1 <= len(doc["generated"]) < 8
        st = eng.stats()
        assert st["deadline_expired"] == 1
        assert st["completed"] == 0
        eng.assert_no_leaks()
    finally:
        eng.close(drain=True)


def test_brownout_sheds_batch_keeps_interactive():
    # watermark 0: every batch admission sheds, interactive sails
    # through — and sheds land on their own meter, not rejected
    eng = _engine(num_pages=32, shed_watermark=0.0)
    try:
        with pytest.raises(Overloaded) as ei:
            eng.submit_generate([1, 2], max_new_tokens=2,
                                priority="batch")
        assert ei.value.reason == "shed_batch"
        doc = eng.generate([1, 2], max_new_tokens=2, timeout_s=300)
        assert len(doc["generated"]) == 2
        st = eng.stats()
        assert st["shed"] == 1
        assert st["rejected"] == 0
        with pytest.raises(ValueError):
            eng.submit_generate([1, 2], priority="bulk")
    finally:
        eng.close(drain=True)


def test_batch_admits_below_watermark(engine_and_model):
    eng, m = engine_and_model
    doc = eng.generate([4, 2], max_new_tokens=3, timeout_s=300)
    g = eng.submit_generate([4, 2], max_new_tokens=3,
                            priority="batch")
    assert g.result(timeout=300)["generated"] == doc["generated"]


# -- KV-leak regression: races + the periodic self-check ---------------
def test_cancel_after_completion_returns_false(engine_and_model):
    eng, _ = engine_and_model
    g = eng.submit_generate([1, 2], max_new_tokens=2)
    g.result(timeout=300)
    assert g.cancel() is False    # finished: nothing left to cancel
    eng.assert_no_leaks()


def test_cancel_race_with_sequence_done_never_leaks():
    # hammer the cancel/completion race: whichever side wins, pages
    # reclaim exactly once and the future resolves exactly once
    eng = _engine(num_pages=32)
    try:
        for _ in range(8):
            g = eng.submit_generate([1, 2], max_new_tokens=1)
            g.cancel()
            doc_or_err = None
            try:
                doc_or_err = g.result(timeout=300)
            except Overloaded:
                pass
            if doc_or_err is not None:
                assert doc_or_err["finish"] in ("cancelled", "length",
                                                "eos")
        deadline = time.monotonic() + 60
        while (eng.stats()["outstanding"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


def test_self_check_reclaims_and_counts_unowned_pages():
    eng = _engine(num_pages=32)
    try:
        eng._replicas[0].cache.alloc("ghost", 4)   # a planted leak
        freed = eng.self_check()
        assert freed == 1                          # one 4-token page
        assert eng.stats()["kv_leaked"] == 1
        eng.assert_no_leaks()
        assert eng.self_check() == 0               # idempotent
    finally:
        eng.close(drain=True)


# -- HTTP surface ------------------------------------------------------
def _post(url, doc, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture()
def served_decode():
    m = _model()
    eng = _engine(m, num_pages=64, max_queue=256)
    srv = ServingServer(eng, port=0)
    host, port = srv.start()
    yield eng, m, f"http://{host}:{port}"
    srv.close()


def test_generate_endpoint_batched(served_decode):
    eng, m, url = served_decode
    code, doc = _post(url + "/generate",
                      {"tokens": [3, 1, 4], "max_new_tokens": 5})
    assert code == 200
    assert doc["generated"] == _oracle_generate(m.params, m.cfg,
                                                [3, 1, 4], 5)
    assert doc["finish"] == "length"


def test_generate_endpoint_streams_ndjson(served_decode):
    eng, m, url = served_decode
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps({"tokens": [3, 1, 4], "max_new_tokens": 5,
                         "stream": True}).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        lines = [json.loads(ln) for ln in r.read().splitlines() if ln]
    toks = [ln["token"] for ln in lines if "token" in ln]
    assert toks == _oracle_generate(m.params, m.cfg, [3, 1, 4], 5)
    done = lines[-1]
    assert done["done"] is True and done["finish"] == "length"


def test_generate_endpoint_rejects_bad_input(served_decode):
    eng, _, url = served_decode
    code, doc = _post(url + "/generate", {"tokens": []})
    assert code == 400
    code, doc = _post(url + "/generate",
                      {"tokens": [0, VOCAB], "max_new_tokens": 2})
    assert code == 400


# -- the read of a K and a V pool: the oracle of the read through rows --
_NEG_INF = -1e30


def paged_attention_reference(q, k_pages, v_pages, page_table, lengths,
                              *, scale=None):
    """The read of a K and a V pool of ``(page_size, H, D)`` pages, pure
    ``jnp``: the oracle of the read through ``v | k`` rows (the package
    decoded through it until PR 37 and kept it until PR 48).

    Gathers each slot's pages, whole ``(page_size, H, D)`` blocks by
    page id, into a contiguous ``(S, T, H, D)`` view (T = max_pages *
    page_size), masks positions past ``lengths``, and softmaxes — with
    the flash dead-row guards so a ``length == 0`` padding slot yields
    exact zeros, not NaN.
    """
    s, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    # (S, max_pages, ps, H, D) -> (S, T, H, D)
    k = k_pages[page_table].reshape(s, -1, h, d)
    v = v_pages[page_table].reshape(s, -1, h, d)
    t = k.shape[1]
    logits = (jnp.einsum("shd,sthd->sht", q, k)
              .astype(jnp.float32) * scale)
    kpos = jnp.arange(t, dtype=jnp.int32)
    mask = kpos[None, None, :] < lengths.astype(jnp.int32)[:, None, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - jnp.where(m <= _NEG_INF / 2, 0.0, m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = (jnp.einsum("sht,sthd->shd", p, v)
           / jnp.maximum(l, 1e-30))
    return out.astype(q.dtype)


def test_paged_attention_reference_matches_dense():
    # the reference itself against plain dense attention over the
    # gathered pages — anchors the whole parity chain
    rng = np.random.default_rng(3)
    heads, dh, ps, npg = 2, 8, 4, 3
    pool = 7
    q = jnp.asarray(rng.normal(size=(2, heads, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(pool, ps, heads, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool, ps, heads, dh)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, pool, size=(2, npg)), jnp.int32)
    lengths = jnp.asarray([5, 12], jnp.int32)
    got = paged_attention_reference(q, kp, vp, pt, lengths)
    for s in range(2):
        t = int(lengths[s])
        k = np.concatenate([np.asarray(kp[pt[s, j]])
                            for j in range(npg)], axis=0)[:t]
        v = np.concatenate([np.asarray(vp[pt[s, j]])
                            for j in range(npg)], axis=0)[:t]
        for h in range(heads):
            logits = np.asarray(q[s, h]) @ k[:, h].T * dh ** -0.5
            w = np.exp(logits - logits.max())
            w /= w.sum()
            want = w @ v[:, h]
            assert np.allclose(np.asarray(got[s, h]), want, atol=1e-5)


def _paged_case(lengths, heads=2, head_dim=64, page_size=8, n_pages=3,
                layer=0, layers=1, seed=0):
    """A flat K/V pool of ``layers`` layers, each slot's pages scattered
    over its layer and offset to it (as ``decode_step`` offsets them),
    entries past a slot's length left at page 0 of the pool as the
    engine leaves them."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    per_layer = slots * n_pages + 1
    q = jnp.asarray(rng.normal(size=(slots, heads, head_dim)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(
        size=(layers * per_layer, page_size, heads, head_dim)), jnp.float32)
        for _ in range(2))
    table = (rng.permutation(per_layer)[:slots * n_pages]
             .reshape(slots, n_pages) + layer * per_layer)
    for i, n in enumerate(lengths):
        table[i, -(-n // page_size):] = 0
    return (q, kp, vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


# -- the transformer step's read: ``v | k`` rows through ``attend_rows`` --
def _rows_case(lengths, n_pages, **kw):
    """``_paged_case`` at 4 heads of 128 and the same pool as rows ``v |
    k`` of 1,024 lanes, as ``transformer.decode_step`` lays it out."""
    q, kp, vp, table, lengths = _paged_case(
        lengths, heads=4, head_dim=128, n_pages=n_pages, **kw)
    rows = jnp.concatenate([vp.reshape(*vp.shape[:2], -1),
                            kp.reshape(*kp.shape[:2], -1)], -1)
    return (q, kp, vp, table, lengths), rows


def _read_through_the_interpreted_kernel(monkeypatch):
    """What ``attend_rows`` dispatches to on a TPU, interpreted here."""
    monkeypatch.setattr(
        blocks, "latent_attention_auto", functools.partial(
            decode_attention.latent_attention_kernel, interpret=True))


# pages of 8 positions, blocks of 2 pages: a block is 16 positions
_ROWS_CASES = {
    "padding_slot_then_one_position": dict(lengths=[0, 1], n_pages=4),
    "lengths_end_inside_a_block": dict(lengths=[5, 17, 30], n_pages=4),
    "block_boundary": dict(lengths=[16, 32, 0, 15], n_pages=4),
    "table_wider_than_whole_blocks": dict(lengths=[40, 33, 0, 16, 7],
                                          n_pages=5),
    "second_layer_of_a_flat_pool": dict(lengths=[9, 24, 32], n_pages=4,
                                        layer=1, layers=2),
    "rung_8_with_padding_slots": dict(
        lengths=[21, 0, 8, 0, 0, 30, 0, 1], n_pages=4),
    # a table of 3 pages (a block and a half): lengths that end inside a
    # page, on a page's edge and at the table's end
    "partial_page": dict(lengths=[3, 13], n_pages=3),
    "page_boundary": dict(lengths=[8, 16], n_pages=3),
    "whole_table": dict(lengths=[24, 24], n_pages=3),
}


@pytest.mark.parametrize("path", ["kernel", "reference"])
@pytest.mark.parametrize("name", sorted(_ROWS_CASES))
def test_read_through_rows_equals_the_kv_reference(name, path, monkeypatch):
    """What ``transformer.decode_step`` reads its pool with (head h's
    query laid into the lanes of its own keys, the row's leading half the
    values, head h keeping its own lanes of the sum) against the plain
    read of a K and a V pool, both float32 under "highest": on a TPU's
    path the kernel (interpreted here), elsewhere the ``jnp`` reference.
    The other heads' lanes meet exact zeros, so the two differ by the
    order of float32 sums alone (limit 1e-5 of the largest output); a
    ``length == 0`` slot yields exact zeros."""
    (q, kp, vp, table, lengths), rows = _rows_case(**_ROWS_CASES[name])
    if path == "kernel":
        _read_through_the_interpreted_kernel(monkeypatch)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(paged_attention_reference(
            q, kp, vp, table, lengths))
        got = np.asarray(blocks.attend_rows(q, rows, table, lengths,
                                            q.shape[1], 2))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))
    for i, n in enumerate(np.asarray(lengths)):
        if n == 0:
            assert not got[i].any(), i


def test_decode_step_writes_its_row_and_reads_through_the_kernel(
        monkeypatch):
    """The family's own ``decode_step`` on a filled pool, its read the
    interpreted kernel, against the step whose read is the ``jnp``
    reference: the same tokens, and bit for bit the same pool (each live
    slot's new row ``v | k`` at its write page and offset, the padding
    slots' on the scratch page)."""
    from dist_keras_tpu.models import transformer

    with _engine(decode_ladder=(1, 4), prefill_ladder=(8, 16)) as eng:
        rep, ps, pmax = eng._replicas[0], eng.page_size, \
            eng.max_pages_per_seq
        rng = np.random.default_rng(3)
        pages = rng.permutation(eng.num_pages).astype(np.int32)
        tables = np.zeros((4, pmax), np.int32)
        toks, positions, wpage, woff, lengths = (
            np.zeros((4,), np.int32) for _ in range(5))
        wpage[:] = rep.cache.scratch_page
        for i, at in enumerate([1, 9, 22]):         # slot 3 is padding
            tables[i] = pages[i * pmax:(i + 1) * pmax]
            toks[i], positions[i] = rng.integers(0, VOCAB), at
            wpage[i], woff[i] = tables[i, at // ps], at % ps
            lengths[i] = at + 1
        args = tuple(map(jnp.asarray, (toks, positions, tables, wpage,
                                       woff, lengths)))
        (pool,) = _filled_pools(eng, 7)
        step = functools.partial(transformer.decode_step, eng.cfg)
        with jax.default_matmul_precision("highest"):
            want_toks, want_pool = jax.jit(step)(rep.params, pool, *args)
            _read_through_the_interpreted_kernel(monkeypatch)
            got_toks, got_pool = jax.jit(step)(rep.params, pool, *args)
        np.testing.assert_array_equal(np.asarray(got_toks)[:3],
                                      np.asarray(want_toks)[:3])
        np.testing.assert_array_equal(np.asarray(got_pool),
                                      np.asarray(want_pool))
        changed = np.argwhere((np.asarray(got_pool) != np.asarray(pool))
                              .any(-1))
        assert sorted(map(tuple, changed)) == sorted(
            (li, int(wpage[i]), int(woff[i]))
            for li in range(eng.cfg["n_layers"]) for i in range(4))


@pytest.mark.parametrize("rung,n,ps", [(16, 13, 4), (16, 16, 4), (8, 1, 8),
                                       (14, 9, 4)])
def test_prompt_rows_are_written_a_page_an_update(rung, n, ps):
    """``transformer._write_prompt_rows`` against the scatter of a row a
    position, on the arrays the worker builds (``page_idx`` the
    sequence's pages repeated, the scratch page from the prompt's length
    on; ``page_off`` the position modulo the page size): every position
    of the prompt holds its row, every other page is untouched, and what
    differs lies behind the prompt's length in its last page or on the
    scratch page.  A rung that is no whole number of pages takes the
    scatter of rows."""
    from dist_keras_tpu.models import transformer

    rng = np.random.default_rng(rung + n)
    n_pages, width, li = 9, 8, 1
    pool = jnp.asarray(rng.normal(size=(2, n_pages + 1, ps, width)),
                       jnp.float32)
    rows = jnp.asarray(rng.normal(size=(rung, width)), jnp.float32)
    pages = rng.permutation(n_pages)[:-(-n // ps)]
    page_idx = np.full((rung,), n_pages, np.int32)          # scratch
    page_idx[:n] = np.repeat(pages, ps)[:n]
    page_off = (np.arange(rung) % ps).astype(np.int32)
    got = np.asarray(transformer._write_prompt_rows(
        pool, li, jnp.asarray(page_idx), jnp.asarray(page_off), rows))
    per_row = np.asarray(pool.at[li, page_idx, page_off].set(rows))
    for t in range(n):
        np.testing.assert_array_equal(got[li, page_idx[t], page_off[t]],
                                      np.asarray(rows[t]))
    differs = {tuple(ix[:3]) for ix in np.argwhere(got != per_row)}
    behind = {(li, int(pages[-1]), off) for off in range(n % ps or ps, ps)}
    scratch = {(li, n_pages, off) for off in range(ps)}
    assert differs <= behind | scratch
    if rung % ps:
        assert not differs
    np.testing.assert_array_equal(got[1 - li], np.asarray(pool[1 - li]))


def test_decode_steps_stamp_live_and_walked_positions():
    """``transformer.observe_step``: a decode step stamps the sum of its
    slots' lengths on ``decode.kv.live_positions`` and what the read's
    blocks of ``kv_block_pages(page_size)`` pages fetch for them on
    ``decode.kv.walked_positions`` (host arithmetic on the lengths the
    worker already has); a prefill stamps nothing."""
    from dist_keras_tpu.models import transformer

    assert _metrics.KNOWN_METRICS["decode.kv.walked_positions"] == \
        "histogram"
    live_h = _metrics.histogram("decode.kv.live_positions")
    walked_h = _metrics.histogram("decode.kv.walked_positions")
    assert [transformer.kv_block_pages(ps) for ps in (4, 8, 16, 64, 128)] \
        == [16, 8, 4, 1, 1]
    lengths = np.asarray([0, 1, 64, 65, 200], np.int32)
    at = time.perf_counter()
    transformer.observe_step((), at, lengths=lengths, page_size=8)
    assert live_h.samples_between(at, at + 1e-6)[0] == [(at, 330)]
    assert walked_h.samples_between(at, at + 1e-6)[0] == [
        (at, (0 + 1 + 1 + 2 + 4) * 64)]
    transformer.observe_step((), at + 1e-3)
    assert walked_h.samples_between(at + 1e-3, at + 2e-3)[0] == []

    # through the engine: a sample a decode step, with the step's stamp
    lo = time.perf_counter()
    with _engine(decode_ladder=(1, 4), prefill_ladder=(8, 16),
                 page_size=4) as eng:
        eng.generate(list(range(1, 7)), max_new_tokens=5, timeout_s=300)
        steps = eng._replicas[0].steps
    hi = time.perf_counter()
    live = [v for _, v in live_h.samples_between(lo, hi)[0]]
    walked = [v for _, v in walked_h.samples_between(lo, hi)[0]]
    assert len(live) == len(walked) == steps >= 4
    # a prompt of 6: the steps read 7, 8, ... positions, one block of 64
    assert live == list(range(7, 7 + steps))
    assert walked == [64] * steps


# -- drain / stats contract --------------------------------------------
def test_drain_reports_and_closes_admission():
    eng = _engine(num_pages=32)
    gens = [eng.submit_generate([1, 2], max_new_tokens=4)
            for _ in range(3)]
    out = eng.drain(timeout_s=300)
    assert out["delivered"] == 3
    for g in gens:
        assert g.result(timeout=5)["finish"] == "length"
    with pytest.raises(Overloaded):
        eng.submit_generate([1, 2], max_new_tokens=2)
    eng.close(drain=False)


def test_stats_shape_and_ttft(engine_and_model):
    eng, _ = engine_and_model
    eng.generate([1, 2, 3], max_new_tokens=3, timeout_s=300)
    st = eng.stats()
    assert st["retrace_bound"] == len(st["prefill_ladder"]) + \
        len(st["decode_ladder"])
    assert st["retrace_count"] <= st["retrace_bound"]
    assert st["ttft_s"]["count"] >= 1
    assert st["kv"]["used_pages"] == 0


# -- HTTP deadline/priority + disconnect reclaim -----------------------
def test_generate_endpoint_deadline_body_and_priority(served_decode):
    eng, m, url = served_decode
    eng.generate([1, 2], max_new_tokens=2, timeout_s=300)  # warm EWMAs
    code, doc = _post(url + "/generate",
                      {"tokens": [1, 2], "max_new_tokens": 8,
                       "deadline_s": 1e-9})
    assert code == 503
    assert doc["reason"] == "deadline_infeasible"
    code, doc = _post(url + "/generate",
                      {"tokens": [1, 2], "max_new_tokens": 2,
                       "deadline_s": 300.0, "priority": "batch"})
    assert code == 200 and len(doc["generated"]) == 2
    code, doc = _post(url + "/generate",
                      {"tokens": [1, 2], "priority": "bogus"})
    assert code == 400


def test_client_disconnect_mid_stream_reclaims_pages(served_decode):
    # the client reads ONE token line and slams the socket shut: the
    # server's next chunk write fails and the generation cancels, so
    # the slot and its KV pages reclaim instead of decoding to nobody
    eng, m, url = served_decode
    host, port = url.replace("http://", "").split(":")
    body = json.dumps({"tokens": [3, 1], "max_new_tokens": 30,
                       "stream": True}).encode()
    s = socket.create_connection((host, int(port)), timeout=30)
    s.sendall(b"POST /generate HTTP/1.1\r\n"
              b"Host: x\r\nContent-Type: application/json\r\n"
              + b"Content-Length: %d\r\n\r\n" % len(body) + body)
    buf = b""
    while b'"token"' not in buf:
        buf += s.recv(4096)
    s.close()                           # mid-stream disconnect
    deadline = time.monotonic() + 60
    while eng.stats()["outstanding"] and time.monotonic() < deadline:
        time.sleep(0.01)
    st = eng.stats()
    assert st["outstanding"] == 0
    assert st["cancelled"] >= 1
    eng.assert_no_leaks()
    assert eng.self_check() == 0


# -- router: deadline propagation, stream relay, hedging ---------------
class _StallBackend:
    """Accepts and reads the request, then never answers — the router-
    visible signature of a wedged host (the hedge's raison d'etre)."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.addr = "127.0.0.1:%d" % self.sock.getsockname()[1]
        self.hits = 0
        self._conns = []
        self._stop = False
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while not self._stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.hits += 1
            self._conns.append(conn)   # held open, never answered

    def close(self):
        self._stop = True
        for c in self._conns:
            try:
                c.close()
            except OSError:
                pass
        try:
            self.sock.close()
        except OSError:
            pass


class _DyingStreamBackend:
    """Answers /generate with a 200 chunked NDJSON stream, emits two
    token lines, then dies abruptly — a backend crash mid-stream."""

    def __init__(self):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.addr = "127.0.0.1:%d" % self.sock.getsockname()[1]
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            try:
                self._serve(conn)
            except OSError:
                pass

    def _serve(self, conn):
        data = b""
        while b"\r\n\r\n" not in data:
            got = conn.recv(65536)
            if not got:
                return
            data += got
        head, _, rest = data.partition(b"\r\n\r\n")
        if head.startswith(b"GET"):    # health probe: stay in rotation
            body = b'{"ok": true}'
            conn.sendall(b"HTTP/1.1 200 OK\r\n"
                         b"Content-Type: application/json\r\n"
                         + b"Content-Length: %d\r\n\r\n" % len(body)
                         + body)
            conn.close()
            return
        m = re.search(rb"content-length:\s*(\d+)", head, re.I)
        need = int(m.group(1)) if m else 0
        while len(rest) < need:
            rest += conn.recv(65536)
        out = (b"HTTP/1.1 200 OK\r\n"
               b"Content-Type: application/x-ndjson\r\n"
               b"Transfer-Encoding: chunked\r\n\r\n")
        for ln in (b'{"i": 0, "token": 1}\n', b'{"i": 1, "token": 2}\n'):
            out += b"%x\r\n" % len(ln) + ln + b"\r\n"
        conn.sendall(out)
        time.sleep(0.05)
        conn.close()                   # no terminating chunk: death

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


@pytest.fixture()
def routed_decode():
    m = _model()
    eng = _engine(m, num_pages=64, max_queue=256)
    srv = ServingServer(eng, port=0)
    host, port = srv.start()
    router = RouterServer([f"{host}:{port}"], port=0, probe_s=30.0,
                          forward_timeout_s=60.0)
    rhost, rport = router.start()
    yield eng, m, f"http://{rhost}:{rport}", router
    router.close()
    srv.close()


def test_router_relays_generate_stream(routed_decode):
    eng, m, url, _router = routed_decode
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps({"tokens": [3, 1, 4], "max_new_tokens": 5,
                         "stream": True}).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200
        lines = [json.loads(ln) for ln in r.read().splitlines() if ln]
    toks = [ln["token"] for ln in lines if "token" in ln]
    assert toks == _oracle_generate(m.params, m.cfg, [3, 1, 4], 5)
    assert lines[-1]["done"] is True
    assert lines[-1]["finish"] == "length"


def test_router_deadline_header_reaches_admission(routed_decode):
    eng, m, url, _router = routed_decode
    eng.generate([1, 2], max_new_tokens=2, timeout_s=300)  # warm EWMAs
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps({"tokens": [1, 2],
                         "max_new_tokens": 8}).encode("utf-8"),
        headers={"Content-Type": "application/json",
                 "x-dk-deadline-s": "1e-9"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=60)
    assert ei.value.code == 503
    ei.value.read()
    # the header crossed the hop: the BACKEND's admission counted it
    assert eng.stats()["deadline_infeasible"] >= 1


def test_router_priority_header_sheds_batch():
    m = _model()
    eng = _engine(m, num_pages=64, shed_watermark=0.0)
    srv = ServingServer(eng, port=0)
    host, port = srv.start()
    router = RouterServer([f"{host}:{port}"], port=0, probe_s=30.0,
                          forward_timeout_s=60.0)
    rhost, rport = router.start()
    try:
        req = urllib.request.Request(
            f"http://{rhost}:{rport}/generate",
            data=json.dumps({"tokens": [1, 2],
                             "max_new_tokens": 2}).encode("utf-8"),
            headers={"Content-Type": "application/json",
                     "x-dk-priority": "batch"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        assert ei.value.code == 503
        ei.value.read()
        assert eng.stats()["shed"] >= 1       # shed at the backend door
        assert eng.stats()["rejected"] == 0   # on its own meter
    finally:
        router.close()
        srv.close()


def test_router_stream_backend_death_typed_final_record():
    dying = _DyingStreamBackend()
    router = RouterServer([dying.addr], port=0, probe_s=30.0,
                          forward_timeout_s=30.0)
    rhost, rport = router.start()
    c_err = _metrics.counter("route.stream_errors")
    v0 = c_err.value
    try:
        req = urllib.request.Request(
            f"http://{rhost}:{rport}/generate",
            data=json.dumps({"tokens": [1, 2], "stream": True}
                            ).encode("utf-8"),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            lines = [json.loads(ln) for ln in r.read().splitlines()
                     if ln]
        # the relayed tokens arrived, then the TYPED loss record —
        # never a silently truncated stream
        assert [ln["token"] for ln in lines if "token" in ln] == [1, 2]
        assert lines[-1]["error"] == "backend_stream_lost"
        assert lines[-1]["retryable"] is True
        assert c_err.value == v0 + 1
    finally:
        router.close()
        dying.close()


def test_router_hedged_generate_first_wins():
    # primary wedges; past the observed latency tail the router hedges
    # onto the sibling, whose answer wins — reassembled into the same
    # batched doc a direct /generate returns
    m = _model()
    eng = _engine(m, num_pages=64)
    srv = ServingServer(eng, port=0)
    host, port = srv.start()
    stall = _StallBackend()
    router = RouterServer([stall.addr, f"{host}:{port}"], port=0,
                          probe_s=30.0, forward_timeout_s=60.0)
    for _ in range(400):   # feed the tail estimate (>= 20 samples)
        router._m_forward.observe(0.005)
    real_pick = router.pool.pick
    router.pool.pick = (lambda exclude=():
                        stall.addr if not exclude
                        else real_pick(exclude=exclude))
    c_hedge = _metrics.counter("route.hedges")
    c_wins = _metrics.counter("route.hedge_wins")
    h0, w0 = c_hedge.value, c_wins.value
    try:
        body = json.dumps({"tokens": [3, 1, 4],
                           "max_new_tokens": 5}).encode("utf-8")
        code, payload, ctype, _retry = router.forward_generate(body)
        assert code == 200
        doc = json.loads(payload.decode("utf-8"))
        assert doc["generated"] == _oracle_generate(m.params, m.cfg,
                                                    [3, 1, 4], 5)
        assert doc["tokens"] == [3, 1, 4] + doc["generated"]
        assert doc["finish"] == "length"
        assert c_hedge.value == h0 + 1
        assert c_wins.value == w0 + 1
        assert stall.hits == 1           # the loser was tried once...
        eng.assert_no_leaks()            # ...and the winner cleaned up
    finally:
        router.close()
        srv.close()
        stall.close()


def test_router_hedge_denied_without_budget():
    m = _model()
    eng = _engine(m, num_pages=64)
    srv = ServingServer(eng, port=0)
    host, port = srv.start()
    stall = _StallBackend()
    router = RouterServer([stall.addr, f"{host}:{port}"], port=0,
                          probe_s=30.0, forward_timeout_s=3.0)
    for _ in range(400):
        router._m_forward.observe(0.005)
    real_pick = router.pool.pick
    router.pool.pick = (lambda exclude=():
                        stall.addr if not exclude
                        else real_pick(exclude=exclude))
    router._hedge_budget.ratio = 0.0     # budget drained for good
    router._hedge_budget._tokens = 0.0
    c_denied = _metrics.counter("route.hedge_denied")
    d0 = c_denied.value
    try:
        body = json.dumps({"tokens": [1, 2],
                           "max_new_tokens": 2}).encode("utf-8")
        code, payload, _ctype, retry = router.forward_generate(body)
        # no budget -> no duplicate: the wedged primary times out into
        # a typed 503 (the caller's whole-request retry is the bound)
        assert code == 503
        assert retry is not None
        assert c_denied.value == d0 + 1
    finally:
        router.close()
        srv.close()
        stall.close()
