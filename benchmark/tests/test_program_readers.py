"""The readers of what the program measures from inside: its stamped
histograms and its host regions in the device trace.  The trace is built
by hand: chip 0 runs four operations inside ``bench.window`` while a worker
thread is in ``perf.decode.*`` regions and the main thread sleeps in
``bench.wait_due``.
"""

import os
import types

import pytest

from benchmark import manifest
from benchmark.readers import program_hist, trace_span_idle
from benchmark.tests import rehearsal
from benchmark.trace import reduce

US = 1_000_000      # picoseconds in a microsecond

# (metadata id, HLO text)
OPS = [
    (1, "%fusion.1 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p.1)"),
    (2, "%copy.3 = f32[2,4,9,4,8]{4,3,2,1,0} copy(f32[2,4,9,4,8]{4,3,2,1,0} "
        "%p.2)"),
    (3, "%fusion.7 = f32[8,16]{1,0} fusion(f32[8,64]{1,0} %fusion.1)"),
    (4, "%fusion.9 = f32[8]{0} fusion(f32[8,16]{1,0} %fusion.7)"),
]
# chip 0, microseconds: (metadata id, start, duration)
DEVICE = [(1, 100, 100), (2, 200, 300), (3, 600, 100), (4, 900, 50)]
# host, microseconds: (name, start, duration)
WORKER = [
    ("perf.decode.sched", 40, 20),
    ("perf.decode.step", 60, 890),
    ("perf.decode.step.build", 62, 18),          # idle, as all of 50-100
    ("perf.decode.step.dispatch", 80, 40),       # idle 80-100 is under it
    ("perf.decode.step.wait", 120, 790),         # idle 500-600, 700-900
    ("perf.decode.step.emit", 910, 38),
]
MAIN = [("bench.window", 50, 950), ("bench.wait_due", 50, 950)]


def _text_proto():
    def events(rows, ids):
        return "".join(
            f"events {{ metadata_id: {ids[key]} offset_ps: {a * US} "
            f"duration_ps: {d * US} }} " for key, a, d in rows)

    dev_meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{text}" }} }} '
        for i, text in OPS)
    names = sorted({n for n, _, _ in WORKER + MAIN})
    ids = {n: i + 1 for i, n in enumerate(names)}
    host_meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for n, i in ids.items())
    return (
        'planes { name: "/device:TPU:0" '
        'lines { name: "XLA Ops" timestamp_ns: 0 '
        + events(DEVICE, {i: i for i, _ in OPS}) + "} "
        'lines { name: "Steps" timestamp_ns: 0 '
        "events { metadata_id: 1 offset_ps: 0 duration_ps: 1 } } "
        + dev_meta + "} "
        'planes { name: "/host:CPU" '
        'lines { name: "worker" timestamp_ns: 0 '
        + events(WORKER, ids) + "} "
        'lines { name: "main" timestamp_ns: 0 ' + events(MAIN, ids) + "} "
        + host_meta + "}")


@pytest.fixture
def traced(tmp_path):
    """(outcome, ctx) of a run whose trace is the hand-built one."""
    from jax.profiler import ProfileData

    folder = tmp_path / "trace" / "plugins" / "profile" / "by_hand"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_text_proto()))
    path = reduce.find_xplane(str(tmp_path / "trace"))
    reduced = reduce.Trace.from_file(path).reduce()
    ctx = types.SimpleNamespace(scratch=str(tmp_path))
    return {"trace": reduced}, ctx


def test_the_trace_by_hand_reads_as_meant(traced):
    outcome, _ = traced
    reduced = outcome["trace"]
    assert reduced["window_s"] == pytest.approx(950e-6)
    assert reduced["busy_s"] == pytest.approx(550e-6)
    # a gap is named by the worker's region where one is open, by the
    # load generator's span elsewhere
    idle = reduced["idle_gaps"]
    assert idle["bench.wait_due"] == pytest.approx(50e-6)
    assert {n for n in idle if not n.startswith("perf.decode.")} == {
        "bench.wait_due"}


def test_idle_goes_to_the_workers_innermost_region(traced, capsys):
    outcome, ctx = traced
    window = 950e-6
    assert trace_span_idle.read(outcome, ctx, span="step.dispatch") \
        == pytest.approx(100 * 20e-6 / window)
    assert trace_span_idle.read(outcome, ctx, span="step.wait") \
        == pytest.approx(100 * 300e-6 / window)
    assert trace_span_idle.read(outcome, ctx, span="step.build") \
        == pytest.approx(100 * 18e-6 / window)
    # a region that saw no idle time reads 0, not nothing
    assert trace_span_idle.read(outcome, ctx, span="step.emit") == 0.0
    # 50-60 lies in decode.sched, 60-62 in decode.step before its first
    # child; 950-1000 under no region of the program's, so under the load
    # generator's span: the table in the log says so
    idle = outcome["trace"]["idle_gaps"]
    assert outcome["trace"]["window_s"] == pytest.approx(window)
    assert idle["perf.decode.sched"] == pytest.approx(10e-6)
    assert idle["perf.decode.step"] == pytest.approx(2e-6)
    assert sum(idle.values()) == pytest.approx(400e-6)
    out = capsys.readouterr().out
    assert "perf.decode.step.wait" in out and "bench.wait_due" in out
    assert out.count("perf.decode.step.wait") == 1      # printed once


def test_without_the_programs_regions_idle_reads_nothing(tmp_path):
    """The parent commit opens no region: nothing, and no raise."""
    data = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_small.xplane.pb")
    folder = tmp_path / "trace" / "plugins" / "profile" / "parent"
    folder.mkdir(parents=True)
    os.symlink(data, folder / "host.xplane.pb")
    outcome = {"trace": reduce.Trace.from_file(data).reduce()}
    ctx = types.SimpleNamespace(scratch=str(tmp_path))
    assert trace_span_idle.read(outcome, ctx, span="step.wait") is None
    assert trace_span_idle.read({"trace": None}, ctx, span="x") is None


# ----------------------------------------------------------- histograms
@pytest.fixture
def registry():
    from dist_keras_tpu.observability import metrics

    metrics.reset()
    yield metrics
    metrics.reset()


def _ctx(process_start=1000.0, setup_s=30.0, seconds=51.0):
    return types.SimpleNamespace(process_start=process_start,
                                 setup_s=setup_s, seconds=seconds, tails=[])


def test_histogram_statistic_over_the_window_alone(registry):
    h = registry.histogram("decode.prefill_s")
    h.observe(9.0, at=1029.9)                  # warm-up: before the window
    for i, v in enumerate((0.030, 0.034, 0.070)):
        h.observe(v, at=1030.0 + 10 * i)
    h.observe(7.0, at=1081.0)                  # the reference: after it
    assert program_hist.read({}, _ctx(), histograms=["decode.prefill_s"],
                             stat="p50") == pytest.approx(34.0)
    assert program_hist.read({}, _ctx(), histograms=["decode.prefill_s"],
                             stat="p100") == pytest.approx(70.0)
    with pytest.raises(ValueError):
        program_hist.read({}, _ctx(), histograms=["decode.prefill_s"],
                          stat="mean")


def test_several_histograms_give_the_sum_of_their_statistics(registry):
    for name, values in (("perf.phase.decode.sched", (1e-4, 2e-4, 9e-4)),
                         ("perf.phase.decode.step.build", (3e-4,)),
                         ("perf.phase.decode.step.emit", (5e-4, 7e-4))):
        for v in values:
            registry.histogram(name).observe(v, at=1050.0)
    names = ["perf.phase.decode.step.build", "perf.phase.decode.step.emit",
             "perf.phase.decode.sched"]
    assert program_hist.read({}, _ctx(), histograms=names, stat="p50") \
        == pytest.approx(0.3 + 0.6 + 0.2)
    # one of them empty in the window: nothing, not a partial sum
    assert program_hist.read({}, _ctx(process_start=0.0), histograms=names,
                             stat="p50") is None


def test_a_window_the_histogram_holds_a_part_of(registry, monkeypatch,
                                               capsys):
    """The program keeps a histogram's most recent samples only: a
    percentile or a mean is then of the window's last ones and is never
    handed back as if it were the whole window's (the reader leaves the
    second it runs from in ``ctx.tails``, for the result line), a sum
    reads nothing, and so does a window of which nothing is left."""
    from benchmark.readers import program_window

    monkeypatch.setattr(registry.Histogram, "WINDOW", 4)
    h = registry.histogram("decode.step_s")
    for i in range(10):
        h.observe(0.01 * i, at=1030.0 + i)       # kept: 0.06 .. 0.09
    ctx = _ctx()
    assert program_hist.read({}, ctx, histograms=["decode.step_s"],
                             stat="p50") == pytest.approx(75.0)
    assert ctx.tails == [pytest.approx(6.0)]
    ctx = _ctx()
    assert program_window.read({}, ctx, "decode.step_s", "mean") \
        == pytest.approx(0.075)
    assert ctx.tails == [pytest.approx(6.0)]
    assert "the window's last 4 samples, from 6.0 s" in \
        capsys.readouterr().out
    ctx = _ctx()
    assert program_window.read({}, ctx, "decode.step_s", "sum_pct") is None
    assert ctx.tails == []
    assert program_hist.read({}, _ctx(setup_s=25.0, seconds=5.0),
                             histograms=["decode.step_s"], stat="p50") \
        is None
    assert "no longer holds the whole window" in capsys.readouterr().out
    # a later window that it still holds in full is read as it is
    ctx = _ctx(setup_s=36.0)
    assert program_hist.read({}, ctx, histograms=["decode.step_s"],
                             stat="p50") == pytest.approx(75.0)
    assert ctx.tails == [] and capsys.readouterr().out == ""


def test_a_tail_is_marked_in_the_result_line(monkeypatch, tmp_path):
    """A traced run whose window outgrew the program's histograms: every
    per-step statistic in the result line says from which second of the
    window it runs (``window_from_s``); what is counted whole, or read from
    the benchmark's own records, carries no such key."""
    from dist_keras_tpu.observability import metrics

    monkeypatch.setattr(metrics.Histogram, "WINDOW", 8)
    man = manifest.load()
    cell = next(c["name"] for c in man["workloads"]
                if manifest.traffic_of(c)["kind"] == "serve_open")
    got = rehearsal.rehearse(monkeypatch, tmp_path, cell, seed=12,
                             trace=True)["metrics"]
    per_step = [n for n in got if n.startswith(
        ("decode_step_p50_ms", "step_wait_p50_ms", "step_dispatch_p50_ms",
         "kv_live_positions_mean", "steps_fed_share"))]
    assert len(per_step) == 5
    for name in per_step:
        assert 0.0 < got[name]["window_from_s"] < 1.5, name
    whole = [n for n in got if n.startswith(
        ("sched_slots_mean", "ttft_p50_ms", "gap_p50_ms", "hbm_peak_gb"))]
    assert len(whole) == 4
    for name in whole:
        assert set(got[name]) == {"value", "unit"}, name


def test_a_program_without_stamps_reads_nothing(registry, monkeypatch):
    """The parent commit's histograms have no ``samples_between``."""
    bare = types.SimpleNamespace(samples=[0.05])
    monkeypatch.setattr(registry, "histogram", lambda name: bare)
    assert program_hist.read({}, _ctx(), histograms=["decode.step_s"],
                             stat="p50") is None


# ------------------------------------------------------ the whole path
def test_a_traced_serving_run_reports_the_engines_own_numbers(
        monkeypatch, tmp_path):
    man = manifest.load()
    cell = next(c["name"] for c in man["workloads"]
                if manifest.traffic_of(c)["kind"] == "serve_open")
    result = rehearsal.rehearse(monkeypatch, tmp_path, cell, seed=11,
                                trace=True)
    got = result["metrics"]
    from_hist = [e["name"] for e, spec, _ in
                 manifest.metrics_for(man, cell, "per_layer")
                 if spec["reader"] == "program_hist"]
    assert len(from_hist) == 4
    for name in from_hist:
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms", name
    # the step's two halves lie inside the step the engine times itself
    inside = sum(got[n]["value"] for n in from_hist
                 if n.startswith(("step_dispatch", "step_wait")))
    whole = next(v["value"] for n, v in got.items()
                 if n.startswith("decode_step_p50_ms"))
    assert inside <= 1.5 * whole
