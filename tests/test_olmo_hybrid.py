"""The gated-delta-rule, full-attention family (``models/olmo_hybrid.py``)
on the CPU at a toy size (two periods, 4 heads, ``dk`` 8, ``dv`` 16), on
the benchmark's seeded weights: the chunked scan against the recurrence,
``forward`` against the plain reference, prefill then decode through
``DecodeEngine`` against the reference's full forward, and the life of a
sequence's two state rows (padding, a row's next owner, every exit,
recovery, a closed loop against as many rows as callers).

Tolerances: float32 at "highest" on both sides.  The scan and the
recurrence sum the same terms in another order (1e-5 at outputs of about
1); through eight layers whose sub-blocks are each renormalised the
logits of the program and of the reference agree to under 1e-4 of values
of about 3 (``TOL``; a part of the mathematics left out moves them by
0.1 and more: the third test)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import olmo_hybrid as family
from benchmark.reference import olmo_hybrid_ref as ref
from dist_keras_tpu.models import olmo_hybrid
from dist_keras_tpu.models.families import FAMILIES
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.ops import gated_delta
from dist_keras_tpu.ops.pallas import gated_delta as pallas_gated_delta
from dist_keras_tpu.resilience import faults
from dist_keras_tpu.resilience.faults import FaultInjected
from dist_keras_tpu.serving.decode import DecodeEngine
from dist_keras_tpu.serving.engine import Overloaded
from dist_keras_tpu.utils.serialization import (
    deserialize_model,
    serialize_model,
)

TOL = 5e-4
VOCAB = 128
PATTERN = ["linear_attention"] * 3 + ["full_attention"]
SIZES = dict(vocab_size=VOCAB, seq_len=48, d_model=64, n_heads=4, d_ff=96,
             layer_types=PATTERN * 2, linear_heads=4, linear_key_dim=8,
             linear_value_dim=16)
N_LIN, N_FULL = 6, 2
CHANNELS = 4 * (2 * 8 + 16)


def config(**kw):
    return olmo_hybrid.olmo_hybrid_config(**{**SIZES, **kw})


def weights_for(cfg, seed=2 ** 31 + 7):
    """The benchmark's seeded weights: the ones a chip run hands to the
    program and to the reference alike."""
    return family.tree(weights.base_key(seed), cfg)


def reference_logits(params, tokens, cfg, **kw):
    return ref.forward(params, jnp.asarray(tokens),
                       family.reference_config(cfg), **kw)


def engine_for(cfg, params, **kw):
    model = olmo_hybrid.OlmoHybridDecoder(cfg=cfg)
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


def scan_inputs(t, seed, h=4, dk=8, dv=16):
    """q and k normalised as the layer normalises them, ``g`` over the
    seeded decays' range, ``beta`` in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (t, h, dk))
    k = jax.random.normal(ks[1], (t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -jax.random.uniform(ks[3], (t, h), minval=0.001, maxval=1.6)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    return q, k, v, g, beta


# -- (1) the scan ---------------------------------------------------------
@pytest.mark.parametrize("t,length", [
    (200, 200), (200, 137), (64, 64), (128, 128), (40, 40), (40, 3),
    (128, 1), (96, 65)])
def test_chunked_scan_equals_the_recurrence(highest, t, length):
    """Lengths that are a multiple of the chunk, that are not, and that
    are shorter than one; the padding behind ``length`` (other values,
    not zeros) moves neither the outputs before it nor the state."""
    assert gated_delta.CHUNK == 64
    x = scan_inputs(t, seed=t + length)
    want_o, want_s = gated_delta.gated_delta_recurrent(
        *(a[:length] for a in x))
    o, s = jax.jit(gated_delta.gated_delta_chunked)(*x, length)
    assert o.shape == x[2].shape
    assert float(jnp.abs(o[:length] - want_o).max()) <= 1e-5
    assert float(jnp.abs(s - want_s).max()) <= 1e-5
    assert float(jnp.abs(want_s).max()) > 0.1


def test_decode_update_lands_in_the_pool_and_nowhere_else(highest):
    """The Pallas update (interpreted) against gather / recurrence /
    scatter: the slots' rows of ONE layer one position on, every other row
    and layer of the pool bit for bit what it was."""
    s, h, dk, dv, rows = 5, 4, 8, 16, 7
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(ks[0], (2, rows, h, dk, dv))
    at = jnp.asarray([3, 0, 6, 1, 5])
    q, k, v, g, beta = scan_inputs(s, seed=9)
    want_o, want = gated_delta.gated_delta_step(pool[1, at], q, k, v, g,
                                                beta)
    o, flat = pallas_gated_delta.state_step_kernel(
        pool.reshape(-1, h, dk, dv), at + rows, q, k, v, g, beta,
        interpret=True)
    got = np.asarray(flat).reshape(pool.shape)
    assert float(jnp.abs(o - want_o).max()) <= 1e-5
    np.testing.assert_allclose(got[1, np.asarray(at)], want, atol=1e-5)
    np.testing.assert_array_equal(got[0], pool[0])
    np.testing.assert_array_equal(got[1, [2, 4]], pool[1, [2, 4]])
    # off the TPU the same function is the jnp form
    o2, pool2 = pallas_gated_delta.state_step_auto(pool, 1, at, q, k, v, g,
                                                   beta)
    np.testing.assert_allclose(np.asarray(pool2), got, atol=1e-5)
    np.testing.assert_allclose(o2, want_o, atol=1e-6)


# -- (2) whole-sequence forward ------------------------------------------
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7])
def test_forward_equals_the_reference(highest, seed):
    cfg = config(seq_len=96)
    params = weights_for(cfg, seed)
    tokens = np.random.default_rng(seed).integers(0, VOCAB, 90)
    got = olmo_hybrid.forward(params, jnp.asarray(tokens), cfg)
    want = reference_logits(params, tokens, cfg, q_block=32)
    assert got.shape == (90, VOCAB)
    assert float(jnp.abs(got - want).max()) <= TOL
    assert float(jnp.abs(want).max()) > 1.0


def _conv_without_memory(kernel, x):
    return kernel[:, -1].astype(x.dtype) * x


def _rule_without_decay(q, k, v, g, beta, prec=ref.FLOAT32):
    return _REAL["delta_rule"](q, k, v, jnp.zeros_like(g), beta, prec)


def _rule_without_correction(q, k, v, g, beta, prec=ref.FLOAT32):
    """Plain decayed linear attention: ``u = beta v``."""
    def position(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[:, None, None] * s + jnp.einsum(
            "hi,hj->hij", k_t, beta_t[:, None] * v_t)
        return s, jnp.einsum("hij,hi->hj", s, q_t)
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(position, jnp.zeros((h, dk, dv)),
                        (q, k, v, g, beta))[1]


def _rule_with_beta_undoubled(q, k, v, g, beta, prec=ref.FLOAT32):
    return _REAL["delta_rule"](q, k, v, g, beta / 2, prec)


_REAL = {"delta_rule": ref.delta_rule}
LEFT_OUT = {
    "the convolutions' memory": ("causal_conv", _conv_without_memory),
    "the decay": ("delta_rule", _rule_without_decay),
    "the delta rule's correction": ("delta_rule", _rule_without_correction),
    "the doubling of beta": ("delta_rule", _rule_with_beta_undoubled),
}


@pytest.mark.parametrize("part", sorted(LEFT_OUT))
def test_the_comparison_sees_each_part(highest, monkeypatch, part):
    """A reference with one part of the mathematics left out is far from
    the program: the tolerance is not what lets the program pass."""
    cfg = config()
    params = weights_for(cfg)
    tokens = np.random.default_rng(3).integers(0, VOCAB, 40)
    got = olmo_hybrid.forward(params, jnp.asarray(tokens), cfg)
    name, fake = LEFT_OUT[part]
    monkeypatch.setattr(ref, name, fake)
    wrong = reference_logits(params, tokens, cfg)
    assert float(jnp.abs(got - wrong).max()) > 100 * TOL, part


def test_benchmark_weights_are_in_the_programs_layout():
    cfg = config()
    mine = jax.eval_shape(lambda k: olmo_hybrid.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: family.tree(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(mine)] == \
        [a.shape for a in jax.tree.leaves(theirs)]
    lin = weights_for(cfg)["blocks"][0]["linear"]
    assert lin["w_qkv"].shape == (64, CHANNELS)
    assert lin["conv"].shape == (CHANNELS, 4)
    # the decays as the delta-rule layers' reference code seeds them
    assert float(jnp.exp(lin["a_log"]).min()) >= 1.0
    assert float(jnp.exp(lin["a_log"]).max()) <= 16.0
    dt = jax.nn.softplus(lin["dt_bias"])
    assert 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001


def test_serialization_round_trip_holds_no_second_set_of_weights():
    cfg = config()
    model = olmo_hybrid.OlmoHybridDecoder(cfg=cfg, seed=3)
    back = deserialize_model(serialize_model(model))
    assert isinstance(back, olmo_hybrid.OlmoHybridDecoder)
    assert back.cfg == cfg
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(back.params)):
        np.testing.assert_array_equal(a, b)
    assert FAMILIES["olmo_hybrid"] is olmo_hybrid and len(FAMILIES) == 6


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=["linear_attention", "window"]), "layer_types"),
    (dict(n_heads=5), "n_heads"),
    (dict(conv_kernel=1), "conv_kernel"),
])
def test_config_refuses_what_the_family_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        config(**bad)


# -- (3) prefill then decode through the engine ----------------------------
def test_pools_are_three_of_two_kinds_over_their_own_layers():
    cfg = config()
    assert olmo_hybrid.cache_pools(cfg) == (
        (N_FULL, "page", (128,)), (N_LIN, "sequence", (3, CHANNELS)),
        (N_LIN, "sequence", (4, 8, 16)))
    with engine_for(cfg, weights_for(cfg), state_rows=5) as eng:
        pages = eng.num_pages
        assert eng.pool_shapes == (
            (N_FULL, pages + 1, 4, 128), (N_LIN, 6, 3, CHANNELS),
            (N_LIN, 6, 4, 8, 16))
        assert eng._state and eng.state_rows == 5
        assert eng._out_width == eng.max_slots    # tokens, nothing behind
    # the default still suits a family whose row is small
    with engine_for(cfg, weights_for(cfg), max_queue=7) as eng:
        assert eng.state_rows == 7


def test_engine_tokens_are_the_references_own(highest):
    """Prompts at, under and over a rung, shorter than the convolutions'
    three taps of memory and longer than a chunk of the scan's (in an
    engine whose ladder reaches it): every served token is the argmax of
    the reference's full forward over what came before it."""
    cfg = config(seq_len=112)
    params = weights_for(cfg)
    rng = np.random.default_rng(5)
    with engine_for(cfg, params, prefill_ladder=(8, 16, 32, 96)) as eng:
        for n in (1, 2, 5, 8, 30, 70):
            prompt = rng.integers(0, VOCAB, n).tolist()
            doc = eng.generate(prompt, max_new_tokens=12, timeout_s=600)
            assert doc["finish"] == "length" and len(doc["generated"]) == 12
            assert served_gap(params, doc, cfg).max() <= TOL, n
            want = olmo_hybrid.forward(
                params, jnp.asarray(doc["tokens"][:-1]), cfg)
            assert np.asarray(want.argmax(-1))[n - 1:].tolist() == \
                doc["generated"]
        eng.assert_no_leaks()


def test_slots_decode_together_as_each_does_alone(highest):
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (3, 9, 17, 30)]
    with engine_for(cfg, params) as eng:
        alone = [eng.generate(p, max_new_tokens=10, timeout_s=600)
                 for p in prompts]
        gens = [eng.submit_generate(p, max_new_tokens=10) for p in prompts]
        together = [g.result(timeout=600) for g in gens]
    for a, b in zip(alone, together):
        assert a["generated"] == b["generated"]
        assert served_gap(params, b, cfg).max() <= TOL


# -- (4) a state row's life -------------------------------------------------
def _pools(cfg, n_pages, ps, rows):
    shapes = [
        (layers,) + ((n_pages + 1, ps) if kind == "page" else (rows + 1,))
        + tuple(entry) for layers, kind, entry in
        olmo_hybrid.cache_pools(cfg)]
    return [jnp.zeros(s, jnp.float32) for s in shapes]


@pytest.mark.parametrize("n", [1, 2, 3, 5, 16])
def test_padded_prompt_leaves_the_unpadded_prompts_state(highest, n):
    """A prompt of ``n`` tokens padded to a rung of 16 (with other tokens
    behind it, not zeros) writes the rows the same prompt writes at a rung
    of exactly ``n``: the matrices after its TRUE last position, the
    convolutions' inputs at its true last three, zeros on the left of a
    prompt shorter than three; the other row is not touched."""
    cfg = config()
    params = weights_for(cfg)
    toks = np.random.default_rng(n).integers(0, VOCAB, 16).astype(np.int32)

    def rows_after(rung):
        kv, taps, states = _pools(cfg, 8, 4, 2)
        page_idx = np.full((rung,), 8, np.int32)
        page_idx[:n] = np.arange(n) // 4
        *_, taps, states = olmo_hybrid.prefill_step(
            cfg, params, kv, taps + 3.0, states + 3.0,
            jnp.asarray(toks[:rung]), jnp.int32(n), jnp.asarray(page_idx),
            jnp.arange(rung, dtype=jnp.int32) % 4, jnp.int32(1))
        return np.asarray(taps), np.asarray(states)

    for padded, exact in zip(rows_after(16), rows_after(n)):
        # products over 16 rows and over ``n`` sum in another order
        np.testing.assert_allclose(padded[:, 1], exact[:, 1], atol=1e-5,
                                   rtol=1e-5)
        assert np.abs(padded[:, 1]).max() > 0
        assert np.abs(padded[:, 0] - 3.0).max() == 0.0    # the other row
    taps, _ = rows_after(16)
    if n < 3:
        assert np.abs(taps[:, 1, :3 - n]).max() == 0.0   # before the start


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
        g = eng.submit_generate(first, max_new_tokens=12)
        assert g._seq.row == 0
        with pytest.raises(Overloaded) as e:
            eng.submit_generate(second, max_new_tokens=10)
        assert e.value.reason == "kv_exhausted"
        g.result(timeout=600)
        h = eng.submit_generate(second, max_new_tokens=10)
        assert h._seq.row == 0
        got = h.result(timeout=600)
        eng.assert_no_leaks()
    assert got["generated"] == want["generated"]
    assert served_gap(params, got, cfg).max() <= TOL


def _rows_and_pages(eng):
    return [(r.cache.used_rows(), r.cache.used_pages())
            for r in eng._replicas]


@pytest.mark.parametrize("exit_path", ["completion", "cancel", "error",
                                       "prefill_error", "close"])
def test_row_and_pages_come_back_on_every_exit(exit_path):
    cfg = config()
    params = weights_for(cfg)
    eng = engine_for(cfg, params, step_retries=0, state_rows=3)
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
        else:
            seen = []
            g = eng.submit_generate(prompt, max_new_tokens=30,
                                    on_token=seen.append)
            while len(seen) < 2:
                time.sleep(0.01)
            assert _rows_and_pages(eng) == [(1, 9)]
            assert gauge.value == 1
            if exit_path == "error":
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
        assert _rows_and_pages(eng) == [(0, 0)]
        assert gauge.value == 0
        eng.assert_no_leaks()
        assert eng.self_check() == 0
        assert eng.kv_stats()["used_rows"] == 0
    finally:
        eng.close(drain=False)


def test_recovered_sequence_is_bit_identical_and_nothing_leaks(highest):
    """``kill_replica`` mid-decode: the dead replica's rows and pages are
    freed, the survivor's prefill (the scan again) and teacher-forced
    steps rebuild the K/V, the convolutions' inputs AND the recurrent
    matrices, and the document is the undisturbed greedy run's, token for
    token."""
    cfg = config()
    params = weights_for(cfg)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, n).tolist() for n in (5, 11, 2)]
    with engine_for(cfg, params) as alone:
        wants = [alone.generate(p, max_new_tokens=14, timeout_s=600)
                 for p in prompts]
    eng = engine_for(cfg, params, replicas=2, state_rows=4)
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


def test_closed_loop_of_as_many_callers_as_rows_is_never_refused():
    """32 callers against ``state_rows`` 32, each sending its next request
    from inside the callback that resolves its last (the moment a
    closed-loop caller can first know), 33 callers' worth of requests and
    more in all: a finished sequence's row is back in the allocator
    BEFORE its future resolves, so the door never refuses one."""
    cfg = config()
    callers, each = 32, 3
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, VOCAB, int(n)).tolist()
               for n in rng.integers(1, 9, callers * each)]
    done, refused, lock = [], [], threading.Lock()
    finished = threading.Event()
    with engine_for(cfg, weights_for(cfg), decode_ladder=(8, 32),
                    state_rows=callers, max_queue=64) as eng:

        def send(i):
            try:
                gen = eng.submit_generate(prompts[i], max_new_tokens=3)
            except Overloaded as e:
                with lock:
                    refused.append((i, e.reason))
                finished.set()
                return
            gen.future.add_done_callback(lambda f, i=i: landed(i, f))

        def landed(i, future):
            with lock:
                done.append(future.result()["finish"])
                over = len(done) == callers * each
            if over:
                finished.set()
            elif i + callers < callers * each:
                send(i + callers)

        for i in range(callers):
            send(i)
        assert finished.wait(timeout=900)
        assert not refused, refused
        assert done == ["length"] * (callers * each)
        assert callers * each >= 33 * 2
        assert eng.stats()["rejected"] == 0
        assert eng.kv_stats()["used_rows"] == 0
        eng.assert_no_leaks()


def test_worker_packs_the_state_rows_behind_the_lengths():
    from dist_keras_tpu.serving.decode import _prefill_views, _step_views

    cfg = config()
    with engine_for(cfg, weights_for(cfg), state_rows=3) as eng:
        seen = {"decode": [], "prefill": []}
        real_d, real_p = eng._decode_jit, eng._prefill_jit
        eng._decode_jit = lambda *a: (
            seen["decode"].append(np.array(a[-1])), real_d(*a))[1]
        eng._prefill_jit = lambda *a: (
            seen["prefill"].append(np.array(a[-1])), real_p(*a))[1]
        g = eng.submit_generate([3, 1, 4], max_new_tokens=4)
        row = g._seq.row
        g.result(timeout=600)
    assert len(a := seen["prefill"]) == 1 and a[0].shape == (3 * 8 + 2,)
    _, length, _, _, prefill_row = _prefill_views(a[0], True)
    assert (int(length), int(prefill_row)) == (3, row)
    for packed in seen["decode"]:
        *_, lengths, rows = _step_views(packed, eng.max_pages_per_seq, True)
        assert rows[0] == row and lengths[0] > 0


# -- (5) the counters -------------------------------------------------------
def test_counters_exist_and_are_stamped():
    cfg = config()
    names = ("decode.kv.live_positions", "decode.state.live_rows",
             "prefill.scan_positions", "prefill.scan_padded_positions")
    for name in names:
        assert metrics.KNOWN_METRICS[name] == "histogram"
    lo = time.perf_counter()
    rng = np.random.default_rng(4)
    lengths = (30, 11, 5, 2)
    with engine_for(cfg, weights_for(cfg), decode_ladder=(4,)) as eng:
        gens = [eng.submit_generate(rng.integers(0, VOCAB, n).tolist(),
                                    max_new_tokens=16) for n in lengths]
        for g in gens:
            g.result(timeout=600)
    hi = time.perf_counter()

    def window(name):
        pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
        assert not truncated and pairs, name
        return pairs

    steps = {at for at, _ in window("decode.step_s")}
    prefills = {at for at, _ in window("decode.prefill_s")}
    for name in names[:2]:
        assert {at for at, _ in window(name)} <= steps, name
    for name in names[2:]:
        assert {at for at, _ in window(name)} == prefills, name
    assert sorted(v for _, v in window("prefill.scan_positions")) == \
        sorted(lengths)
    assert sorted(v for _, v in window("prefill.scan_padded_positions")) \
        == [8, 8, 16, 32]
    rows = [v for _, v in window("decode.state.live_rows")]
    assert max(rows) == 4 and min(rows) >= 1
    live = [v for _, v in window("decode.kv.live_positions")]
    assert max(live) <= sum(lengths) + 4 * 16


# -- (6) scopes ---------------------------------------------------------------
SCOPES = {"decode": ("embed", "gdn_in", "gdn_conv", "gdn_step", "gdn_out",
                     "state_read", "state_write", "qkv", "qk_norm",
                     "kv_write", "attend_pool", "attn_out", "mlp", "head"),
          "prefill": ("embed", "gdn_in", "gdn_conv", "gdn_scan", "gdn_out",
                      "state_write", "qkv", "qk_norm", "kv_write", "attend",
                      "attn_out", "mlp", "head")}


@pytest.mark.parametrize("phase", sorted(SCOPES))
def test_steps_carry_their_names_and_scopes(phase):
    """The engine's jitted steps are ``_packed_prefill_fn`` /
    ``_packed_decode_fn`` for this family too, and every new part lies
    under a named scope."""
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
