"""The transformer family embeds a token by reading its row of ``proj``
(PR 40): the steps' logits are those of the ``one_hot(tokens) @ proj`` form
they had, computed exactly, and a padded entry, whatever token id it
carries, moves no live one."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dist_keras_tpu.models import transformer
from dist_keras_tpu.models.transformer import Transformer, transformer_config

VOCAB, SEQ, PAGES = 16, 32, 24
CFG = transformer_config(input_dim=VOCAB, seq_len=SEQ, d_model=16, n_heads=2,
                         n_layers=2, n_classes=VOCAB)
# the toy ladders of tests/test_decode.py and tests/test_lowered_text.py
PREFILL_LADDER, DECODE_LADDER = (4, 8, 16), (1, 4, 8)
# what a padded entry is made to carry in place of the worker's own padding
# (token 0): ids of the table, and ids outside it on both sides
PADDING_IDS = [7, VOCAB - 1, VOCAB, 10 ** 6, -3]


def _params(seed=3):
    return Transformer(CFG, seed=seed).params


def _pool(page_size, seed=5):
    """A pool with something in every position (``PAGES`` pages and the
    scratch page behind them), so a read past a length would show."""
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (CFG["n_layers"], PAGES + 1, page_size, 2 * CFG["d_model"]))


class _OneHotRows:
    """``table[tokens]`` as the product the steps computed until PR 40,
    at the precision that rounds nothing."""

    def __init__(self, table):
        self.table = table

    def __getitem__(self, tokens):
        return jnp.matmul(
            jax.nn.one_hot(tokens, self.table.shape[0],
                           dtype=self.table.dtype),
            self.table, precision="highest")


def _logits_of(monkeypatch, step, params, *args):
    """The logits a step takes its ``argmax`` of: the step run op by op,
    the ``argmax`` watched."""
    seen = []
    real = jnp.argmax

    def watched(x, *a, **kw):
        seen.append(x)
        return real(x, *a, **kw)

    with monkeypatch.context() as m:
        m.setattr(jnp, "argmax", watched)
        out, _ = step(CFG, params, *args)
    (logits,) = seen
    assert logits.shape[-1] == VOCAB
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(real(logits, axis=-1)))
    return np.asarray(logits)


def _decode_args(rung, live, page_size, seed):
    """The six arrays of a decode step: ``live`` slots at lengths of their
    own over pages of their own, the rest padding as the worker makes it
    (token 0, ``length == 0``, the scratch page)."""
    rng = np.random.default_rng(seed)
    pmax = SEQ // page_size
    assert live * pmax <= PAGES
    toks, positions, wpage, woff, lengths = (
        np.zeros((rung,), np.int32) for _ in range(5))
    tables = np.zeros((rung, pmax), np.int32)
    wpage[:] = PAGES
    pages = rng.permutation(PAGES).astype(np.int32)
    for i in range(live):
        at = int(rng.integers(1, SEQ - 1))
        mine = pages[i * pmax:(i + 1) * pmax]
        toks[i] = rng.integers(0, VOCAB)
        positions[i], tables[i], lengths[i] = at, mine, at + 1
        wpage[i], woff[i] = mine[at // page_size], at % page_size
    return toks, positions, tables, wpage, woff, lengths


def _prefill_args(rung, n, page_size, seed):
    """The four arrays of a prefill: ``n`` tokens padded to ``rung`` with
    token 0, the padding's rows routed to the scratch page."""
    rng = np.random.default_rng(seed)
    toks = np.zeros((rung,), np.int32)
    toks[:n] = rng.integers(0, VOCAB, n)
    pages = rng.permutation(PAGES)[:-(-n // page_size)]
    page_idx = np.full((rung,), PAGES, np.int32)
    page_idx[:n] = pages[np.arange(n) // page_size]
    page_off = (np.arange(rung) % page_size).astype(np.int32)
    return toks, np.int32(n), page_idx, page_off


@pytest.mark.parametrize("phase,rung", [
    *(("decode", r) for r in DECODE_LADDER),
    *(("prefill", r) for r in PREFILL_LADDER)])
def test_step_gives_the_one_hot_products_logits(monkeypatch, phase, rung):
    """At every rung of the toy ladders, the logits of a step are those of
    the same step with ``proj[tokens]`` computed as ``one_hot(tokens) @
    proj`` at the precision that rounds nothing, to float32 round-off."""
    params, pool = _params(), _pool(4)
    if phase == "decode":
        step, shape = transformer.decode_step, (rung, VOCAB)
        args = _decode_args(rung, min(rung, 3), 4, seed=rung)
    else:
        step, shape = transformer.prefill_step, (VOCAB,)
        args = _prefill_args(rung, rung - 1, 4, seed=rung)
    args = [jnp.asarray(a) for a in args]
    got = _logits_of(monkeypatch, step, params, pool, *args)
    want = _logits_of(
        monkeypatch, step, {**params, "proj": _OneHotRows(params["proj"])},
        pool, *args)
    assert got.shape == shape
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("padding_id", PADDING_IDS)
def test_a_padding_slot_moves_no_live_slot(padding_id):
    """A rung of 8 with 3 live slots: whatever id the 5 padding slots
    carry, the live slots' next tokens and every page of the pool but the
    scratch page are bit for bit those of the worker's own padding, and
    the tokens those of the 3 slots stepped alone."""
    params, pool, live = _params(), _pool(4), 3
    step = jax.jit(functools.partial(transformer.decode_step, CFG))
    args = _decode_args(8, live, 4, seed=11)
    want_tokens, want_pool = jax.block_until_ready(
        step(params, pool, *map(jnp.asarray, args)))
    alone, alone_pool = step(
        params, pool, *(jnp.asarray(a[:live]) for a in args))
    np.testing.assert_array_equal(
        np.asarray(want_tokens[:live]), np.asarray(alone))
    np.testing.assert_allclose(
        np.asarray(want_pool[:, :PAGES]), np.asarray(alone_pool[:, :PAGES]),
        rtol=1e-6, atol=1e-6)

    args[0][live:] = padding_id
    tokens, got_pool = step(params, pool, *map(jnp.asarray, args))
    np.testing.assert_array_equal(
        np.asarray(tokens[:live]), np.asarray(want_tokens[:live]))
    np.testing.assert_array_equal(
        np.asarray(got_pool[:, :PAGES]), np.asarray(want_pool[:, :PAGES]))
    # the step wrote: the live slots' rows are no longer the pool's
    assert not np.array_equal(np.asarray(got_pool[:, :PAGES]),
                              np.asarray(pool[:, :PAGES]))


@pytest.mark.parametrize("page_size", [4, 3])
@pytest.mark.parametrize("padding_id", PADDING_IDS)
def test_a_prefills_padding_moves_no_live_position(padding_id, page_size):
    """A prompt of 9 tokens on a rung of 16, written a page an update
    (pages of 4) and a row an update (pages of 3): whatever id the 7
    padding positions carry, the first token and the rows of the prompt's
    9 positions are bit for bit those of the worker's own padding."""
    params, pool, n = _params(), _pool(page_size), 9
    step = jax.jit(functools.partial(transformer.prefill_step, CFG))
    toks, length, page_idx, page_off = _prefill_args(16, n, page_size, 13)

    def live_rows(p):
        return np.asarray(p)[:, page_idx[:n], page_off[:n]]

    # (landed before ``toks`` changes: on the CPU ``jnp.asarray`` may
    # alias the host array, and a launch does not wait)
    want_first, want_pool = jax.block_until_ready(step(
        params, pool, jnp.asarray(toks), length, jnp.asarray(page_idx),
        jnp.asarray(page_off)))
    toks[n:] = padding_id
    first, got_pool = step(
        params, pool, jnp.asarray(toks), length, jnp.asarray(page_idx),
        jnp.asarray(page_off))
    assert int(first) == int(want_first)
    np.testing.assert_array_equal(live_rows(got_pool), live_rows(want_pool))
    assert not np.array_equal(live_rows(got_pool), live_rows(pool))
    # pages the prompt does not own, the scratch page aside, are untouched
    others = np.setdiff1d(np.arange(PAGES), page_idx[:n])
    np.testing.assert_array_equal(
        np.asarray(got_pool)[:, others], np.asarray(pool)[:, others])
