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
    return {"trace": reduced, "shapes": {}}, ctx


def test_the_trace_by_hand_reads_as_meant(traced):
    outcome, _ = traced
    reduced = outcome["trace"]
    assert reduced["window_s"] == pytest.approx(950e-6)
    assert reduced["busy_s"] == pytest.approx(550e-6)
    # the reducer's own table knows the load generator's spans only
    assert set(reduced["idle_gaps"]) == {"bench.wait_due"}


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
    # child; 950-1000 under no region of the program's: the table in the
    # log says so
    idle, window_s = trace_span_idle.table(outcome["trace"], reduce.find_xplane(
        os.path.join(ctx.scratch, "trace")))
    assert window_s == pytest.approx(window)
    assert idle["perf.decode.sched"] == pytest.approx(10e-6)
    assert idle["perf.decode.step"] == pytest.approx(2e-6)
    assert idle["_no_span_"] == pytest.approx(50e-6)
    assert sum(idle.values()) == pytest.approx(400e-6)
    assert not any(name.startswith("bench.") for name in idle)
    out = capsys.readouterr().out
    assert "perf.decode.step.wait" in out and "_no_span_" in out
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
                                 setup_s=setup_s, seconds=seconds)


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


def test_a_window_the_histogram_no_longer_holds_reads_nothing(
        registry, monkeypatch, capsys):
    monkeypatch.setattr(registry.Histogram, "WINDOW", 4)
    h = registry.histogram("decode.step_s")
    for i in range(10):
        h.observe(0.05, at=1030.0 + i)
    assert program_hist.read({}, _ctx(), histograms=["decode.step_s"],
                             stat="p50") is None
    assert "no longer holds the whole window" in capsys.readouterr().out
    # a later window that it still holds in full is read
    assert program_hist.read({}, _ctx(setup_s=36.0),
                             histograms=["decode.step_s"], stat="p50") \
        == pytest.approx(50.0)


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
    assert len(from_hist) == 5
    for name in from_hist:
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms", name
    # the step's two halves lie inside the step the engine times itself
    inside = sum(got[n]["value"] for n in from_hist
                 if n.startswith(("step_dispatch", "step_wait")))
    whole = next(v["value"] for n, v in got.items()
                 if n.startswith("decode_step_p50_ms"))
    assert inside <= 1.5 * whole
