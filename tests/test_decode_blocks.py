"""What ``DecodeEngine``'s seam gained for a family that generates in
BLOCKS (``models/sdar_moe.py``; PR 42): the packed array of a pass, what
the worker puts into it with a pass in flight, and a block's life under
every exit: commit, cancel, deadline, a finish inside the block, a killed
replica.  Since PR 43 a block's commit rides in the pass that opens its
sequence's next block (two ENTRIES of one pass; a rung's program holds a
few spare entries for them), so a block of ``T`` denoising passes costs
``T`` passes and only a sequence's last block, or a commit that found no
entry to spare, pays one more.  The family's own mathematics is held to
the plain reference in ``tests/test_sdar_moe.py``; here the undisturbed
engine is the oracle.
"""

import threading
import time

import numpy as np
import pytest

import jax

from dist_keras_tpu.models import sdar_moe
from dist_keras_tpu.observability import events, metrics
from dist_keras_tpu.serving import DecodeEngine
from dist_keras_tpu.serving.decode import _step_views
from dist_keras_tpu.serving.engine import Overloaded

MASK = 127
SIZES = dict(vocab_size=128, seq_len=48, d_model=64, n_heads=8,
             n_kv_heads=2, head_dim=16, moe_d_ff=48, n_routed_experts=16,
             top_k=4, n_layers=2, held_experts=[4, 5, 6, 7],
             mask_token_id=MASK)


def engine_for(seed=1, **kw):
    cfg_kw = {k: kw.pop(k) for k in list(kw) if k in (
        "denoising_steps", "block_length", "seq_len")}
    model = sdar_moe.SdarMoeDecoder(
        cfg=sdar_moe.sdar_moe_config(**{**SIZES, **cfg_kw}), seed=seed)
    kw.setdefault("replicas", 1)
    kw.setdefault("prefill_ladder", (8, 16, 32))
    kw.setdefault("decode_ladder", (1, 4))
    kw.setdefault("page_size", 4)
    return DecodeEngine(model, **kw)


def prompt_of(length, seed=0):
    return np.random.default_rng([seed, length]).integers(
        0, MASK, length).tolist()


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the packed array ---------------------------------------------------
@pytest.mark.parametrize("state", [False, True])
def test_step_views_of_a_pass_tile_the_packed_array(state):
    """Seven views (eight with a state row) of ``rung * (pmax + width +
    5)`` values: each value of the array belongs to exactly one view, and
    a width of 1 is the layout it always was."""
    rung, pmax, width = 4, 12, 4
    n = rung * (pmax + width + 5 + state)
    views = _step_views(np.arange(n, dtype=np.int32), pmax, state, width)
    assert len(views) == 7 + state
    toks, positions, tables, wpage, woff, lengths, fix, *rows = views
    assert toks.shape == (rung, width) and tables.shape == (rung, pmax)
    assert all(v.shape == (rung,)
               for v in (positions, wpage, woff, lengths, fix, *rows))
    seen = np.concatenate([v.reshape(-1) for v in (
        tables, toks, positions, wpage, woff, lengths, fix, *rows)])
    assert seen.tolist() == list(range(n))
    one = _step_views(np.arange(rung * (pmax + 5 + state), dtype=np.int32),
                      pmax, state)
    assert len(one) == 6 + state and one[0].shape == (rung,)
    assert [v.tolist() for v in one] == [v.tolist() for v in _step_views(
        np.arange(rung * (pmax + 5 + state), dtype=np.int32), pmax, state,
        1)]


@pytest.mark.parametrize("rung", [1, 4])
def test_packed_pass_is_the_familys_own(rung):
    """The dispatched program, handed the worker's ONE packed array and
    the output of the pass before it, gives bit for bit the blocks and
    the pool of the family's ``decode_step`` on its seven arrays apart.
    A rung's program holds the rung's sequences and its spare entries
    (2 and 5 here).  Every other live entry names its block's SOURCE, an
    entry of the carried output, in place of the tokens; the output is as
    wide at every rung (blocks to the top rung's entries, then the
    family's counts)."""
    import functools

    import jax.numpy as jnp

    with engine_for() as eng:
        rep, ps, pmax = eng._replicas[0], eng.page_size, \
            eng.max_pages_per_seq
        rng = np.random.default_rng(rung)
        room, top = eng._entries(rung), eng._entries(4)
        assert (room, top) == (rung + 1, 5)
        live = max(1, room - 1)
        carried = rng.integers(0, MASK, eng._out_width).astype(np.int32)
        assert rep.no_tokens.shape == carried.shape == (5 * 4 + 4 + 2,)
        packed = np.zeros((room * (pmax + 4 + 5),), np.int32)
        toks, positions, tables, wpage, woff, lengths, fix = _step_views(
            packed, pmax, False, 4)
        wpage[:] = rep.cache.scratch_page
        pages = rng.permutation(eng.num_pages).astype(np.int32)
        for i in range(live):
            at = 4 * int(rng.integers(0, eng.seq_len // 4 - 1))
            mine = pages[i * pmax:(i + 1) * pmax]
            toks[i] = rng.integers(0, MASK, 4)
            toks[i, rng.integers(0, 4)] = MASK
            positions[i], tables[i] = at, mine
            wpage[i], woff[i] = mine[at // ps], at % ps
            lengths[i], fix[i] = at + 4, i % 2
        pool = jax.random.normal(jax.random.PRNGKey(5), eng.pool_shapes[0])
        want = jax.block_until_ready(
            jax.jit(functools.partial(eng._family.decode_step, eng.cfg))(
                rep.params, pool, *map(jnp.asarray, (
                    toks, positions, tables, wpage, woff, lengths, fix))))
        for i in range(0, live, 2):
            j = int(rng.integers(0, top))
            carried[4 * j:4 * j + 4] = toks[i]
            toks[i] = -(j + 1)
        out, got_pool = eng._decode_jit(
            rep.params, pool + 0.0, jnp.asarray(carried),
            jnp.asarray(packed))
        assert out.shape == (eng._out_width,)
        np.testing.assert_array_equal(out[:4 * room], want[0][:4 * room])
        assert not np.asarray(out[4 * room:4 * top]).any()
        np.testing.assert_array_equal(out[4 * top:], want[0][4 * room:])
        np.testing.assert_array_equal(got_pool, want[1])
        # an entry that was to fix one position fixed one, a commit none
        after = np.asarray(out[:4 * live]).reshape(live, 4)
        for i in range(live):
            masks = int((after[i] == MASK).sum())
            assert masks == 1 - int(fix[i])


def record_passes(eng, monkeypatch):
    """Every pass the worker hands the device, in order: for each live
    entry ``(its block's tokens or their source, where the block starts,
    what the pass fixes there, the entry's first page)``."""
    seen = []
    real = eng._decode_jit

    def recording(*args):
        toks, positions, tables, wpage, woff, lengths, fix = _step_views(
            np.array(args[-1]), eng.max_pages_per_seq, False, 4)
        live = int((lengths > 0).sum())
        assert not lengths[live:].any()     # padding behind, all of it
        for i in range(live):
            assert wpage[i] == tables[i][positions[i] // 4] \
                and woff[i] == 0 and lengths[i] == positions[i] + 4
        seen.append([(toks[i].tolist(), int(positions[i]), int(fix[i]),
                      int(tables[i][0])) for i in range(live)])
        return real(*args)
    monkeypatch.setattr(eng, "_decode_jit", recording)
    return seen


def test_worker_packs_a_block_its_start_and_what_to_fix(monkeypatch):
    """One request of 7 prompt tokens and 6 new ones, alone: the passes'
    arrays, one by one.  The first block opens holding the prompt's tail
    (one mask: one pass); its commit and the block that opens behind it
    are two entries of ONE pass, on the same pages, the commit first; the
    pass after takes the open block from the SECOND entry of the pass in
    flight; the last block's commit rides alone.  A block comes from the
    host when the host knows it (after a prefill, behind a commit) and
    from the pass in flight otherwise."""
    with engine_for() as eng:
        seen = record_passes(eng, monkeypatch)
        prompt = prompt_of(7)
        doc = eng.generate(prompt, max_new_tokens=6, timeout_s=600)
    assert doc["steps"] == len(seen) == 1 + 4 + 4 + 1
    assert len({page for entries in seen for *_, page in entries}) == 1
    rows = [[entry[:3] for entry in entries] for entries in seen]
    first, second, masks = [-1] * 4, [-2] * 4, [MASK] * 4
    assert rows == [
        [(prompt[4:] + [MASK], 4, 1)],
        [(first, 4, 0), (masks, 8, 1)],
        [(second, 8, 1)], [(first, 8, 1)], [(first, 8, 1)],
        [(first, 8, 0), (masks, 12, 1)],
        [(second, 12, 1)], [(first, 12, 1)], [(first, 12, 1)],
        [(first, 12, 0)]]
    assert len(doc["generated"]) == 6 and len(doc["passes"]) == 6
    assert doc["passes"][0] == 0 and sorted(doc["passes"][1:5]) == [
        0, 1, 2, 3]


# -- a block's life -------------------------------------------------------
def test_tokens_come_out_a_block_at_its_commit_in_position_order(highest):
    """``on_token`` once a token, a block's tokens together at its commit,
    though its positions were fixed out of order; the stream is the doc."""
    with engine_for() as eng:
        stream, stamps = [], []

        def on_token(t):
            stream.append(t)
            stamps.append(eng.stats()["steps"])

        doc = eng.submit_generate(prompt_of(6), max_new_tokens=10,
                                  on_token=on_token).result(timeout=600)
    assert stream == doc["generated"] and len(stream) == 10
    # 2 tokens of the first block (its commit is the 3rd pass, which is
    # also the next block's first), then two whole blocks, each four
    # passes on; only the last commit is a pass of its own
    commits = sorted(set(stamps))
    assert [stamps.count(c) for c in commits] == [2, 4, 4]
    assert [b - a for a, b in zip(commits, commits[1:])] == [4, 4]
    assert doc["steps"] == 2 + 4 + 4 + 1
    assert any(doc["passes"][i] > doc["passes"][i + 1] for i in range(9))


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_an_open_block_is_never_emitted_and_its_pages_come_back(how):
    """A cancel or an expired deadline under an open block: the doc holds
    the committed blocks' tokens and nothing of the open one, the stream
    saw as much, and every page is back."""
    eng = engine_for()
    try:
        stream = []
        opened = threading.Event()

        def on_token(t):
            stream.append(t)
            if len(stream) == 6:        # the second commit (2 + 4)
                opened.set()

        if how == "cancel":
            gen = eng.submit_generate(prompt_of(6), max_new_tokens=30,
                                      on_token=on_token)
            assert opened.wait(600)
            gen.cancel()
        else:
            # passes on the CPU take milliseconds: slow them, so that the
            # deadline falls inside a block (compiled before the clock runs)
            eng.generate(prompt_of(6), max_new_tokens=3, timeout_s=600)
            with eng._cond:     # the door has seen compiles, not passes
                eng._ewma_prefill = eng._ewma_step = None
            real = eng._decode_jit

            def slow(*args):
                time.sleep(0.02)
                return real(*args)
            eng._decode_jit = slow
            gen = eng.submit_generate(prompt_of(6), max_new_tokens=30,
                                      on_token=on_token, deadline_s=0.5)
        doc = gen.result(timeout=600)
        assert doc["finish"] == ("cancelled" if how == "cancel"
                                 else "deadline")
        assert doc["generated"] == stream
        assert len(doc["passes"]) == len(stream)
        # whole blocks only: 2 tokens in the prompt's block, then fours
        assert len(stream) >= 2 and (len(stream) - 2) % 4 == 0
        assert len(stream) < 30
        deadline = time.monotonic() + 60
        while eng.kv_stats()["used_pages"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.kv_stats()["used_pages"] == 0
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=False)


def test_a_finish_inside_a_block_trims_what_lies_behind(highest):
    """``max_new_tokens`` inside a block, and ``eos_id`` inside a block:
    the block is computed and committed whole, the tokens behind the last
    one are counted and not emitted, and the pass launched behind an
    ``eos`` the host had not seen is discarded, and so is the next
    block's entry that rode with the commit that met the ``eos``."""
    trimmed = metrics.counter("decode.block.tokens_trimmed")
    discarded = metrics.counter("decode.tokens_discarded")
    with engine_for(seed=4) as eng:     # weights whose reply varies
        was = trimmed.value
        doc = eng.generate(prompt_of(8), max_new_tokens=6, timeout_s=600)
        assert doc["finish"] == "length" and len(doc["generated"]) == 6
        assert doc["steps"] == 9 and trimmed.value - was == 2
        whole = eng.generate(prompt_of(8), max_new_tokens=12,
                             timeout_s=600)
        assert whole["generated"][:6] == doc["generated"]
        # end on a token inside a later block that no earlier position
        # holds (seeded weights repeat themselves)
        tokens = whole["generated"]
        at = next(i for i in range(4, 7) if tokens[i] not in tokens[:i])
        was, thrown = trimmed.value, discarded.value
        doc = eng.generate(prompt_of(8), max_new_tokens=12,
                           eos_id=tokens[at], timeout_s=600)
        assert doc["finish"] == "eos"
        assert doc["generated"] == tokens[:at + 1]
        assert trimmed.value - was == 3 - at % 4
        deadline = time.monotonic() + 60
        while discarded.value < thrown + 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        # the third block's entry of the commit's own pass, and its entry
        # of the pass launched behind
        assert discarded.value - thrown == 2
        eng.assert_no_leaks()


@pytest.mark.parametrize("ladder", [(8, 16, 32), (8,)])
def test_killed_replica_mid_block_replays_to_the_undisturbed_doc(
        highest, ladder):
    """``kill_replica`` with blocks committed and one open: the survivor
    rebuilds the K/V of the prompt AND the committed tokens (ONE
    block-causal prefill where they fit the prefill ladder, ``(8, 16,
    32)``; the prompt's prefill and commit passes over the blocks the host
    knows where they do not, ``(8,)``), redoes the open block's passes,
    and the docs are the undisturbed run's: tokens, passes, streams."""
    prompts = [prompt_of(n, 3) for n in (5, 7, 8)]
    with engine_for(prefill_ladder=ladder) as alone:
        wants = [alone.generate(p, max_new_tokens=13, timeout_s=600)
                 for p in prompts]
    eng = engine_for(replicas=2, prefill_ladder=ladder)
    try:
        seen = [[] for _ in prompts]
        killed = []

        def on_token(i, t):
            seen[i].append(t)
            if not killed and len(seen[0]) == 7:
                killed.append(eng.kill_replica(0))

        gens = [eng.submit_generate(
            p, max_new_tokens=13, on_token=lambda t, i=i: on_token(i, t))
            for i, p in enumerate(prompts)]
        docs = [g.result(timeout=600) for g in gens]
        for doc, want, stream in zip(docs, wants, seen):
            assert doc["generated"] == want["generated"]
            assert doc["passes"] == want["passes"]
            assert stream == want["generated"]    # no duplicate, no gap
        st = eng.stats()
        assert st["quarantines"] == 1 and st["recovered"] >= 1
        assert st["errors"] == 0
        assert sum(d["recoveries"] for d in docs) == st["recovered"]
        assert docs[0]["recoveries"] == 1
        # the passes of the blocks that were open were spent twice
        assert sum(d["steps"] for d in docs) \
            > sum(w["steps"] for w in wants)
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=True)


# -- a commit rides with its sequence's next block (PR 43) -------------------
@pytest.mark.parametrize("steps", [4, 2])
def test_a_block_takes_its_denoising_passes_when_its_commit_folds(
        steps, monkeypatch):
    """One request of four blocks, alone (a rung of one holds a spare
    entry): every block but the last costs its ``T`` denoising passes,
    its commit an entry of the pass that opens the next block; the last
    block's commit has nothing to ride with and is a pass of its own.
    The program's counters say so: ``decode.block.passes`` reads ``T``
    and then ``T + 1``, ``decode.block.commit_folded`` 100 and then 0,
    and a pass with a folded commit still counts ONE sequence."""
    lo = time.perf_counter()
    with engine_for(denoising_steps=steps) as eng:
        seen = record_passes(eng, monkeypatch)
        doc = eng.generate(prompt_of(8), max_new_tokens=15, timeout_s=600)
    hi = time.perf_counter()

    def window(name):
        return [v for _, v in
                metrics.histogram(name).samples_between(lo, hi)[0]]

    assert doc["steps"] == len(seen) == 4 * steps + 1
    assert [len(entries) for entries in seen] == (
        [1] * steps + ([2] + [1] * (steps - 1)) * 3 + [1])
    fix = 4 // steps
    for entries in seen:
        if len(entries) == 2:
            (_, at, commits, page), (block, then, fixes, same) = entries
            assert (commits, fixes, then, same) == (0, fix, at + 4, page)
            assert block == [MASK] * 4
    assert seen[-1][0][2] == 0                  # the last commit, alone
    assert window("decode.block.passes") == [steps] * 3 + [steps + 1]
    assert window("decode.block.commit_folded") == [100.0] * 3 + [0.0]
    assert window("decode.block.slots") == [1] * len(seen)
    assert window("decode.block.commit_share") == [
        100.0 * sum(1 for e in entries if e[2] == 0) / len(entries)
        for entries in seen]
    assert sum(window("decode.block.tokens_fixed")) == 16
    assert max(doc["passes"]) == steps - 1 and len(doc["passes"]) == 15


def test_more_commits_than_spare_entries_fall_back_and_lose_nothing(
        highest, monkeypatch):
    """Four prompts of whole blocks in the rung of four, whose program has
    ONE spare entry: all four commit in one pass, one of them carries its
    next block along, three commit alone and open theirs a pass later
    (which moves their phase off the crowded pass), and every reply is
    what the request gets alone.  A rung goes by its SEQUENCES."""
    sizes = [(8, 12), (4, 12), (12, 12), (8, 12)]
    prompts = [prompt_of(p, 7 + i) for i, (p, _) in enumerate(sizes)]
    with engine_for() as alone:
        wants = [alone.generate(p, max_new_tokens=n, timeout_s=600)
                 for p, (_, n) in zip(prompts, sizes)]
        assert [w["steps"] for w in wants] == [13] * 4
    with engine_for(decode_ladder=(4,)) as eng:
        assert eng._entries(4) == 5
        seen = record_passes(eng, monkeypatch)
        gens = [eng.submit_generate(p, max_new_tokens=n)
                for p, (_, n) in zip(prompts, sizes)]
        docs = [g.result(timeout=600) for g in gens]
        st = eng.stats()
        assert {tuple(s) for s in st["shapes_dispatched"]} == {
            ("decode", 4), ("prefill", 8), ("prefill", 16)}
        assert st["retrace_count"] <= st["retrace_bound"]
        eng.assert_no_leaks()
    for doc, want in zip(docs, wants):
        assert doc["generated"] == want["generated"]
        assert doc["passes"] == want["passes"]
    assert max(len(entries) for entries in seen) == 5
    crowded = [entries for entries in seen
               if sum(1 for e in entries if e[2] == 0) == 4]
    assert crowded and all(len(entries) == 5 for entries in crowded)
    # one sequence kept its phase (13 passes); a commit that rode alone
    # cost its sequence a pass
    assert min(d["steps"] for d in docs) == 13
    assert max(d["steps"] for d in docs) > 13


@pytest.mark.parametrize("how", ["kill", "cancel", "fail"])
def test_a_fused_pass_in_flight_is_survived(highest, monkeypatch, how):
    """With a commit and its sequence's next block in ONE pass that is
    launched and not landed: a killed replica's survivor replays to the
    undisturbed doc (``kv_len`` advances at a commit's LANDING alone), a
    cancel returns the landed blocks and nothing of either entry, and a
    launch that fails is retried in place from the host's state."""
    prompt = prompt_of(7, 5)
    with engine_for() as alone:
        want = alone.generate(prompt, max_new_tokens=17, timeout_s=600)
    eng = engine_for(replicas=2 if how == "kill" else 1)
    try:
        stream, acted, gen = [], [], []
        real_launch, real_jit = eng._launch, eng._decode_jit

        def fused(packed):
            _, _, tables, _, _, lengths, fix = _step_views(
                np.array(packed), eng.max_pages_per_seq, False, 4)
            return bool(lengths[1] and fix[0] == 0
                        and (tables[0] == tables[1]).all())

        def jit(*args):
            if how == "fail" and not acted and len(stream) >= 5 \
                    and fused(args[-1]):
                acted.append("fail")
                raise RuntimeError("a launch that fails")
            return real_jit(*args)

        def launch(rep, group, rung, prev):
            flight = real_launch(rep, group, rung, prev)
            if how != "fail" and not acted and len(stream) >= 5 \
                    and len(flight.entries) > len(flight.group):
                acted.append(how)
                if how == "kill":
                    eng.kill_replica(rep.index)
                else:
                    gen[0].cancel()
            return flight

        monkeypatch.setattr(eng, "_decode_jit", jit)
        monkeypatch.setattr(eng, "_launch", launch)
        gen.append(eng.submit_generate(prompt, max_new_tokens=17,
                                       on_token=stream.append))
        doc = gen[0].result(timeout=600)
        assert acted == [how]
        assert doc["generated"] == stream
        if how == "cancel":
            # the blocks that had landed: the tail's one token, then
            # fours; the fused pass's commit never landed for the caller
            assert doc["finish"] == "cancelled"
            assert len(stream) in (5, 9) and stream == \
                want["generated"][:len(stream)]
            assert doc["passes"] == want["passes"][:len(stream)]
        else:
            assert doc["finish"] == "length"
            assert doc["generated"] == want["generated"]
            assert doc["passes"] == want["passes"]
            assert doc["recoveries"] == (how == "kill")
            # a failed launch costs no pass; a replay spends the open
            # block's passes again
            assert (doc["steps"] > want["steps"]) == (how == "kill")
        st = eng.stats()
        assert st["errors"] == 0
        deadline = time.monotonic() + 60
        while eng.kv_stats()["used_pages"] and time.monotonic() < deadline:
            time.sleep(0.01)
        eng.assert_no_leaks()
        assert eng.self_check() == 0
    finally:
        eng.close(drain=how != "cancel")


# -- the door and the counters ----------------------------------------------
@pytest.mark.parametrize("steps,prompt,new,passes", [
    (4, 8, 8, 9),       # two blocks of four masks, the last commit: 2 x 4 + 1
    (4, 7, 6, 1 + 4 + 4 + 1),   # the tail's block opens with one mask
    (4, 5, 3, 4),       # one block: 3 masks, 3 passes and the commit
    (2, 8, 8, 5),       # two tokens a pass: 2 x 2 + 1
    (2, 7, 6, 1 + 2 + 2 + 1),
])
def test_the_door_reckons_passes(steps, prompt, new, passes):
    with engine_for(denoising_steps=steps) as eng:
        assert eng._steps_for(prompt, new) == passes
        assert eng._positions_for(prompt, new) == -(-(prompt + new) // 4) * 4
        doc = eng.generate(prompt_of(prompt), max_new_tokens=new,
                           timeout_s=600)
        assert doc["steps"] == passes
        # a deadline that a token a step would meet and the passes do not
        with eng._cond:
            eng._ewma_prefill, eng._ewma_step = 0.0, 1.0
        with pytest.raises(Overloaded) as refused:
            eng.submit_generate(prompt_of(prompt), max_new_tokens=new,
                                deadline_s=passes - 0.5)
        assert refused.value.reason == "deadline_infeasible"
        assert eng.stats()["deadline_infeasible"] == 1
        gen = eng.submit_generate(prompt_of(prompt), max_new_tokens=new,
                                  deadline_s=passes + 60.0)
        assert gen.result(timeout=600)["finish"] == "length"


def test_reservation_is_whole_blocks_and_refused_past_a_slot():
    with engine_for() as eng:
        with pytest.raises(ValueError, match="seq_len"):
            eng.submit_generate(prompt_of(30), max_new_tokens=19)
        gen = eng.submit_generate(prompt_of(30), max_new_tokens=15)
        assert len(gen._seq.pages) == 12            # 48 positions
        assert gen.result(timeout=600)["finish"] == "length"


def test_stats_and_events_say_passes_and_tokens_apart(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("DK_OBS_DIR", str(tmp_path))
    events.reset()
    try:
        with engine_for() as eng:
            before = eng.stats()
            doc = eng.generate(prompt_of(8), max_new_tokens=7,
                               timeout_s=600)
            after = eng.stats()
        assert after["tokens"] - before["tokens"] == 7
        assert after["steps"] - before["steps"] == 9 == doc["steps"]
        assert after["step_s"]["count"] == 9
    finally:
        events.reset()
    from dist_keras_tpu.observability import report

    records = report.read_events(str(tmp_path))
    steps = [r for r in records if r["kind"] == "decode_step"]
    assert len(steps) == 9
    # four denoising passes of one position, twice (the fifth pass also
    # commits the first block), and the last block's commit
    assert [r["fixed"] for r in steps] == [1] * 8 + [0]
    done = [r for r in records if r["kind"] == "decode_complete"]
    assert [(r["generated"], r["steps"]) for r in done] == [(7, 9)]
    prefill = [r for r in records if r["kind"] == "decode_prefill"]
    assert prefill[0]["ttft_s"] is None     # a prefill yields no token
    assert doc["ttft_s"] > 0
