"""The decode worker loop measured from inside: ``perf.phase`` regions
that reach any profiler session, window-stamped histograms, and named
scopes on the jitted steps."""

import ast
import glob
import inspect
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from dist_keras_tpu.models.transformer import Transformer, transformer_config
from dist_keras_tpu.observability import metrics, perf, spans
from dist_keras_tpu.serving import decode
from dist_keras_tpu.serving.decode import DecodeEngine

DECODE_REGIONS = tuple(p for p in perf.PHASES if p.startswith("decode."))
DECODE_SCOPES = ("embed", "qkv", "kv_write", "attend", "attn_out", "mlp",
                 "head")


def _engine(**kw):
    cfg = transformer_config(input_dim=16, seq_len=32, d_model=16,
                             n_heads=2, n_layers=2, n_classes=16)
    return DecodeEngine(Transformer(cfg), replicas=1, prefill_ladder=(4, 8),
                        decode_ladder=(1, 4), page_size=4, **kw)


@pytest.fixture
def engine():
    metrics.reset()
    eng = _engine()
    yield eng
    eng.close(drain=False)
    metrics.reset()


# ------------------------------------------------- regions in the trace
@pytest.fixture(scope="module")
def traced_lines(tmp_path_factory):
    """A toy engine serving three requests under a profiler session that
    nothing in the program knows of (no ``utils.profiling.trace``, no
    flag) -> {line index: [(start, end, name, {field: value})]} of the
    ``perf.*`` events on the host plane."""
    from jax.profiler import ProfileData

    logdir = str(tmp_path_factory.mktemp("prof"))
    eng = _engine()
    try:
        eng.generate([1, 2, 3], max_new_tokens=3)     # compiles outside
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        assert not spans._device_trace_active
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            with perf.phase("step"):                   # the main thread's
                gens = [eng.submit_generate([1, 2, 3, 4, 5],
                                            max_new_tokens=4)
                        for _ in range(3)]
                for g in gens:
                    g.result(timeout=120)
            # a region still open when the session closes never reaches
            # the trace: let the worker leave its last step and park
            time.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close(drain=False)
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats))
                   for e in line.events if e.name.startswith("perf.")]
            if evs:
                lines[i] = evs
    return lines


def _worker_events(lines):
    (worker,) = [evs for evs in lines.values()
                 if any(n.startswith("perf.decode.") for _, _, n, _ in evs)]
    return worker


def test_regions_reach_a_session_nobody_announced(traced_lines):
    names = {n for _, _, n, _ in _worker_events(traced_lines)}
    for region in DECODE_REGIONS:
        if region != "decode.park":      # a busy engine may never park
            assert f"perf.{region}" in names, region


def test_regions_sit_on_the_worker_thread_alone(traced_lines):
    worker = _worker_events(traced_lines)
    others = [evs for evs in traced_lines.values() if evs is not worker]
    # the load's own region is on another line, and holds no decode region
    assert any(n == "perf.step" for evs in others for _, _, n, _ in evs)
    assert all(n.startswith("perf.decode.") for _, _, n, _ in worker)


@pytest.mark.parametrize("parent", ["decode.step", "decode.prefill"])
def test_children_lie_inside_their_parents(traced_lines, parent):
    """A step's ``build`` and ``dispatch`` lie in the parent region of the
    pass that launched it, its ``wait`` and ``emit`` in the pass that
    landed it (the next, with the successor's launch before them); a
    prefill's parent closes around its launch and opens again around its
    wait when a step in flight lands between.  So a parent holds at most
    one child of a kind, every child lies in one, and each kind counts
    one a step (one a prefill)."""
    worker = _worker_events(traced_lines)
    parents = [(a, b) for a, b, n, _ in worker if n == f"perf.{parent}"]
    children = [(a, b, n) for a, b, n, _ in worker
                if n.startswith(f"perf.{parent}.")]
    assert parents and children
    kinds = {n for _, _, n in children}
    assert kinds == {f"perf.{r}" for r in DECODE_REGIONS
                     if r.startswith(parent + ".")}
    held = {p: [] for p in parents}
    for a, b, n in children:
        (mine,) = [p for p in parents if p[0] <= a and b <= p[1]]
        held[mine].append(n)
    for names in held.values():
        assert names and len(names) == len(set(names)), names
    per_kind = {k: sum(n == k for _, _, n in children) for k in kinds}
    assert len(set(per_kind.values())) == 1, per_kind
    # three requests of four tokens: a prefill each, and three to nine
    # steps by how they were batched
    (count,) = set(per_kind.values())
    assert count == 3 if parent == "decode.prefill" else 3 <= count <= 9


def test_fields_reach_the_trace_as_arguments(traced_lines):
    worker = _worker_events(traced_lines)
    steps = [f for _, _, n, f in worker if n == "perf.decode.step"]
    assert steps and all(f["rung"] in (1, 4) and 1 <= f["n"] <= f["rung"]
                         for f in steps)
    # a prefill's region opens twice (around its launch, around its wait)
    # when a pass of steps runs between
    prefills = [f for _, _, n, f in worker if n == "perf.decode.prefill"]
    assert 3 <= len(prefills) <= 6 and all(f["rung"] == 8 for f in prefills)
    assert len({f["sid"] for f in prefills}) == 3
    sched = [f for _, _, n, f in worker if n == "perf.decode.sched"]
    assert all(set(f) == {"queued", "active"} for f in sched)


def test_every_decode_region_is_a_listed_phase():
    """The regions opened in ``serving/decode.py`` and ``perf.PHASES`` name
    the same set."""
    opened = set()
    for node in ast.walk(ast.parse(inspect.getsource(decode))):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "phase" and node.args:
            opened.add(node.args[0].value)
    assert opened == set(DECODE_REGIONS)


# ------------------------------------------------ the region primitive
def test_phase_stamps_the_start_and_survives_a_raise():
    metrics.reset()
    before = time.perf_counter()
    with pytest.raises(KeyError):
        with perf.phase("decode.step.build"):
            time.sleep(0.01)
            raise KeyError("x")
    after = time.perf_counter()
    hist = metrics.histogram("perf.phase.decode.step.build")
    ((at, value),), truncated = hist.samples_between(before, after)
    assert not truncated
    assert value >= 0.01 and at + value <= after   # the START, not the end
    metrics.reset()


def test_ten_thousand_phases_with_no_session_stay_in_budget():
    """Ten region entries and two observes a worker iteration are to cost
    under 50 us on the chip's host; a shared CPU core here gets five times
    that."""
    metrics.reset()
    for _ in range(200):
        with perf.phase("decode.step", n=8, rung=8):
            pass
    t0 = time.perf_counter()
    for _ in range(10_000):
        with perf.phase("decode.step", n=8, rung=8):
            pass
    each = (time.perf_counter() - t0) / 10_000
    assert metrics.histogram("perf.phase.decode.step").totals()["count"] \
        == 10_200
    assert 10 * each < 250e-6, each
    metrics.reset()


# ------------------------------------------------- stamped histograms
def test_samples_between_selects_by_stamp():
    h = metrics.Histogram()
    for i in range(10):
        h.observe(float(i), at=100.0 + i)
    pairs, truncated = h.samples_between(103.0, 107.0)
    assert pairs == [(103.0, 3.0), (104.0, 4.0), (105.0, 5.0),
                     (106.0, 6.0)] and not truncated
    assert h.samples_between(200.0, 300.0) == ([], False)
    # what was there behaves as before
    assert h.samples == [float(i) for i in range(10)]
    s = h.summary()
    assert s["count"] == 10 and s["total"] == 45.0 and s["p50"] == 4.5
    assert h.totals() == {"count": 10, "total": 45.0, "max": 9.0}


def test_samples_between_reports_truncation(monkeypatch):
    monkeypatch.setattr(metrics.Histogram, "WINDOW", 4)
    h = metrics.Histogram()
    for i in range(10):
        h.observe(float(i), at=100.0 + i)
    # the window holds stamps 106-109; 105 is the newest it dropped
    assert h.samples_between(106.0, 200.0) == (
        [(106.0, 6.0), (107.0, 7.0), (108.0, 8.0), (109.0, 9.0)], False)
    pairs, truncated = h.samples_between(105.0, 200.0)
    assert truncated and len(pairs) == 4
    assert h.samples_between(0.0, 200.0)[1]
    h.reset()
    h.observe(1.0, at=50.0)
    assert h.samples_between(0.0, 200.0) == ([(50.0, 1.0)], False)


def test_an_unstamped_observe_is_stamped_now():
    h = metrics.Histogram()
    lo = time.perf_counter()
    h.observe(2.5)
    hi = time.perf_counter()
    (pair,), truncated = h.samples_between(lo, hi + 1e-9)
    assert pair[1] == 2.5 and lo <= pair[0] <= hi and not truncated


# ------------------------------------------- counts at the boundaries
def test_prefill_and_queue_wait_count_one_sample_a_request(engine):
    lo = time.perf_counter()
    gens = [engine.submit_generate([1, 2, 3, 4, 5], max_new_tokens=4)
            for _ in range(5)]
    for g in gens:
        g.result(timeout=120)
    hi = time.perf_counter()
    for name in ("decode.prefill_s", "decode.queue_wait_s"):
        pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
        assert len(pairs) == 5 and not truncated, name
        assert all(v >= 0 for _, v in pairs)
    # a prefill's stamp is its start: each lies inside the run, in order
    stamps = [at for at, _ in metrics.histogram(
        "decode.prefill_s").samples_between(lo, hi)[0]]
    assert stamps == sorted(stamps)


def _window(name, lo, hi):
    pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
    assert not truncated, name
    return pairs


def test_step_histogram_keeps_its_meaning_and_gains_the_stamp(engine):
    """``decode.step_s`` has one sample a step and reads what the step
    cost the loop: from the later of its launch and its predecessor's
    tokens on the host to its own tokens on the host.  With nothing in
    flight that is dispatch + wait, as it was; under overlap consecutive
    samples tile the wall between the tokens' arrivals: each starts where
    its predecessor ended and ends where its own wait did."""
    lo = time.perf_counter()
    engine.generate([1, 2, 3], max_new_tokens=6)
    hi = time.perf_counter()
    steps = _window("decode.step_s", lo, hi)
    assert len(steps) == 5       # the first token is the prefill's
    for region in ("dispatch", "wait", "build", "emit"):
        assert len(_window(f"perf.phase.decode.step.{region}", lo, hi)) \
            == len(steps), region
    disp = _window("perf.phase.decode.step.dispatch", lo, hi)
    wait = _window("perf.phase.decode.step.wait", lo, hi)
    flags = [v for _, v in _window("decode.step_overlapped", lo, hi)]
    assert flags == [0.0, 1.0, 1.0, 1.0, 1.0]
    # step k's tokens reach the host at the end of the k-th wait
    landed = [w_at + w for w_at, w in wait]
    for k, ((at, whole), (d_at, d)) in enumerate(zip(steps, disp)):
        assert abs(at + whole - landed[k]) < 1e-4, k
        if not flags[k]:
            # nothing in flight: from before the dispatch, as ever
            assert at <= d_at and d_at + d <= wait[k][0] + 1e-6
        else:
            # launched under its predecessor, before that one's tokens
            # were fetched: the sample starts when they were
            assert d_at < landed[k - 1]
            assert abs(at - landed[k - 1]) < 1e-4, k
    # no overlap and no hole from the second sample on
    for (at, whole), (nxt_at, _) in zip(steps[1:], steps[2:]):
        assert abs(at + whole - nxt_at) < 1e-4
    assert engine.stats()["step_s"]["count"] == 5


def test_overlap_and_discards_are_counted(engine):
    """``decode.step_overlapped`` gains one stamped sample a step, 1.0
    when the step was launched with its predecessor in flight (stamped as
    ``decode.step_s`` is); ``decode.tokens_discarded`` counts the slots
    computed for a sequence that had already ended: here the one step
    launched before the ``eos`` of the step in front of it was seen."""
    free = engine.generate([2, 7, 2], max_new_tokens=8)["generated"]
    eos = free[2]
    assert eos not in free[:2]
    discarded = metrics.counter("decode.tokens_discarded")
    before = discarded.value
    lo = time.perf_counter()
    seen = []
    doc = engine.submit_generate([2, 7, 2], max_new_tokens=8, eos_id=eos,
                                 on_token=seen.append).result(timeout=120)
    # the future resolves when the eos LANDS; its successor lands after
    deadline = time.monotonic() + 60
    while discarded.value == before and time.monotonic() < deadline:
        time.sleep(0.01)
    hi = time.perf_counter()
    assert doc["finish"] == "eos" and doc["generated"] == free[:3] == seen
    steps = _window("decode.step_s", lo, hi)
    flags = _window("decode.step_overlapped", lo, hi)
    # tokens two and three are steps' (the eos the second's); the third
    # step was in flight when the eos landed, and lands for nothing
    assert len(steps) == len(flags) == 3
    assert [at for at, _ in flags] == [at for at, _ in steps]
    assert [v for _, v in flags] == [0.0, 1.0, 1.0]
    assert discarded.value - before == 1
    assert doc["steps"] == 2


def test_one_transfer_a_dispatch_is_counted(engine):
    """``perf.h2d_s`` gains exactly one sample a decode step and one a
    prefill, and ``perf.h2d_bytes`` grows by the packed arrays' sizes:
    how many transfers a dispatch made, and what their enqueue cost, read
    off the registry without a region of their own."""
    engine.generate([1, 2, 3], max_new_tokens=2)      # compiles outside
    before = metrics.counter("perf.h2d_bytes").value
    lo = time.perf_counter()
    engine.generate([1, 2, 3, 4, 5], max_new_tokens=4)
    hi = time.perf_counter()
    steps, _ = metrics.histogram("decode.step_s").samples_between(lo, hi)
    prefills, _ = metrics.histogram(
        "decode.prefill_s").samples_between(lo, hi)
    assert (len(prefills), len(steps)) == (1, 3)
    puts, truncated = metrics.histogram("perf.h2d_s").samples_between(lo, hi)
    assert len(puts) == len(steps) + len(prefills) and not truncated
    assert all(v >= 0 for _, v in puts)
    # one prompt of 5 on the 8 rung, then three steps alone on rung 1
    grown = metrics.counter("perf.h2d_bytes").value - before
    assert grown == 4 * (3 * 8 + 1) + 3 * 4 * (engine.max_pages_per_seq + 5)


def test_dispatch_and_wait_hold_no_child_region():
    """A trace's reader credits an idle gap to the INNERMOST
    ``perf.decode.*`` region by its exact name: a region inside a
    dispatch or a wait would blind ``idle_in_dispatch_pct`` /
    ``idle_in_wait_pct``."""
    for parent in ("decode.step.dispatch", "decode.step.wait",
                   "decode.prefill.dispatch", "decode.prefill.wait"):
        assert parent in perf.PHASES
        assert not [p for p in perf.PHASES if p.startswith(parent + ".")]


# ------------------------------------------------------- named scopes
def _scopes_of(lowered, fn_name):
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'"jit\(%s\)/(?:[a-z_]+\([a-z_(]*)?([a-z_]+)\)*/'
                          % re.escape(fn_name), text)), text


def _lowered_step(engine, phase):
    """The engine's jitted step of ``phase`` lowered the way the worker
    calls it: params, pools, ONE packed int32 array."""
    rep = engine._replicas[0]
    packed = jax.ShapeDtypeStruct(
        (4 * (engine.max_pages_per_seq + 5) if phase == "decode"
         else 3 * 8 + 1,), jnp.int32)
    if phase == "decode":       # and the output of the step before it
        return engine._decode_jit.lower(rep.params, *rep.pools,
                                        rep.no_tokens, packed)
    return engine._prefill_jit.lower(rep.params, *rep.pools, packed)


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_serving_steps_carry_their_scopes(engine, phase):
    found, _ = _scopes_of(_lowered_step(engine, phase),
                          f"_packed_{phase}_fn")
    assert set(DECODE_SCOPES) <= found, found


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_one_int32_array_crosses_and_the_program_keeps_its_name(engine,
                                                                phase):
    """Besides the params and the pools a dispatched step takes exactly
    one array from the host, the packed int32 one (a decode step also the
    output of the step before it, as wide as the top rung whatever rung
    ran: it is on the device already, ``test_one_transfer_a_dispatch_is_
    counted``); and its program's name still holds the family
    function's (``_decode_fn`` / ``_prefill_fn``): a trace's reader finds
    the step's program by it."""
    lowered = _lowered_step(engine, phase)
    rep = engine._replicas[0]
    args = jax.tree.leaves(lowered.args_info)
    ints = [a.shape for a in args if a.dtype == jnp.int32]
    carried = [(engine.max_slots,)] if phase == "decode" else []
    assert len(ints) == 1 + len(carried) and ints[:-1] == carried
    assert len(ints[-1]) == 1
    assert len(args) == (len(jax.tree.leaves(rep.params))
                         + len(rep.pools) + len(ints))
    (module,) = re.findall(r"^module @(\S+)", lowered.as_text(),
                           flags=re.M)
    assert re.search(f"_{phase}_fn", module), module
    other = "prefill" if phase == "decode" else "decode"
    assert not re.search(f"_{other}_fn", module), module


def test_train_step_carries_its_scopes():
    import optax

    from dist_keras_tpu.parallel.transformer_tp import (
        make_tp_mesh,
        make_tp_train_step,
    )

    cfg = transformer_config(input_dim=8, seq_len=16, d_model=16,
                             n_heads=2, n_layers=2, n_classes=4)
    mesh = make_tp_mesh(1, 1, 1, devices=jax.devices()[:1])
    factory, init = make_tp_train_step(mesh, cfg, optimizer=optax.adam(1e-3),
                                       causal=True)
    params, opt_state = init(0)
    step = factory(params, opt_state)
    text = step.lower(params, opt_state, jnp.zeros((2, 16, 8)),
                      jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    for scope in ("attention", "mlp", "loss", "optimizer"):
        # forward operations sit under the scope itself, the backward's
        # under transpose(jvp(<scope>))
        assert re.search(r'[/(]%s[/)]' % scope, text), scope
    assert re.search(r'transpose\(jvp\(mlp\)\)', text)
    assert "/optimizer/" in text
