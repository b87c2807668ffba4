"""The readers of what a window holds taken whole: ``window_total`` (the
sum or the count of a histogram that observes what is rare: a window with
none reads 0.0, a program without the histogram nothing) and
``window_excess`` (what a loop's long iterations cost, apart from those
that held another piece of work's time)."""

import types

import pytest

from benchmark import manifest
from benchmark.readers import window_excess, window_total
from benchmark.tests import rehearsal


@pytest.fixture
def registry():
    from dist_keras_tpu.observability import metrics

    metrics.reset()
    yield metrics
    metrics.reset()


def _ctx(process_start=1000.0, setup_s=30.0, seconds=51.0):
    return types.SimpleNamespace(process_start=process_start,
                                 setup_s=setup_s, seconds=seconds, tails=[])


# ------------------------------------------------------- window_total
def test_a_program_without_the_histogram_reads_nothing(registry):
    """The parent commit has no witness: nothing, not "0 stalls", and the
    look itself must not make the histogram."""
    for stat in ("sum", "count"):
        assert window_total.read({}, _ctx(), "perf.host_stall_s",
                                 stat) is None
    assert "perf.host_stall_s" not in registry.snapshot(
        percentiles=False)["histograms"]
    with pytest.raises(ValueError):
        window_total.read({}, _ctx(), "perf.host_stall_s", "mean")


def test_a_window_without_a_sample_reads_zero(registry):
    h = registry.histogram("perf.host_stall_s")
    assert window_total.read({}, _ctx(), "perf.host_stall_s", "sum") == 0.0
    assert window_total.read({}, _ctx(), "perf.host_stall_s", "count") == 0.0
    h.observe(5.6, at=1012.0)           # the chip coming up: in set-up
    h.observe(0.11, at=1081.0)          # at the window's close: outside
    assert window_total.read({}, _ctx(), "perf.host_stall_s", "sum") == 0.0


def test_the_windows_samples_summed_and_counted(registry, capsys):
    h = registry.histogram("perf.host_stall_s")
    h.observe(5.6, at=1012.0)
    for at, v in ((1030.0, 0.09), (1044.5, 0.112), (1080.999, 0.105)):
        h.observe(v, at=at)
    h.observe(0.2, at=1081.0)
    assert window_total.read({}, _ctx(), "perf.host_stall_s", "sum") \
        == pytest.approx(0.307)
    assert window_total.read({}, _ctx(), "perf.host_stall_s", "count") \
        == 3.0
    out = capsys.readouterr().out
    assert "3 samples in the window" in out and "0.1120 at 14.50 s" in out


def test_a_total_of_a_tail_is_no_total(registry, monkeypatch, capsys):
    monkeypatch.setattr(registry.Histogram, "WINDOW", 4)
    h = registry.histogram("perf.host_stall_s")
    for i in range(10):
        h.observe(0.1, at=1030.0 + i)
    ctx = _ctx()
    assert window_total.read({}, ctx, "perf.host_stall_s", "sum") is None
    assert window_total.read({}, ctx, "perf.host_stall_s", "count") is None
    assert ctx.tails == []
    assert "no longer holds the whole window" in capsys.readouterr().out
    # a later window that it holds whole
    assert window_total.read({}, _ctx(setup_s=36.0), "perf.host_stall_s",
                             "count") == 4.0


def test_a_program_without_stamps_reads_nothing(registry, monkeypatch):
    registry.histogram("perf.host_stall_s")
    bare = types.SimpleNamespace(samples=[0.05])
    monkeypatch.setattr(registry, "histogram", lambda name: bare)
    assert window_total.read({}, _ctx(), "perf.host_stall_s", "sum") is None


# ------------------------------------------------------ window_excess
STEP, PREFILL = "decode.step_s", "decode.prefill_s"


def _excess(ctx=None, factor=1.5):
    return window_excess.read({}, ctx or _ctx(), STEP, PREFILL, factor)


def test_excess_needs_both_histograms_whole(registry, monkeypatch):
    assert _excess() is None                       # neither exists
    steps = registry.histogram(STEP)
    steps.observe(0.010, at=1040.0)
    assert _excess() is None                       # no prefill histogram
    assert PREFILL not in registry.snapshot(
        percentiles=False)["histograms"]
    registry.histogram(PREFILL)
    assert _excess() == 0.0                        # one step, none long
    assert _excess(_ctx(setup_s=90.0)) is None     # a window with no step
    monkeypatch.setattr(registry.Histogram, "WINDOW", 4)
    registry.reset()
    steps = registry.histogram(STEP)
    registry.histogram(PREFILL)
    for i in range(10):
        steps.observe(0.010, at=1030.0 + i)
    assert _excess() is None                       # a tail of the steps


def test_a_window_by_hand_whose_excess_is_known(registry, capsys):
    """Steps of 10 ms tile 1040.00-1040.32 but for: one of 120 ms that a
    stall made long (excess 0.110), one of 16 ms (over 1.5 medians: excess
    0.006), one of 14 ms (under: nothing), and one of 60 ms that was
    launched behind a prefill of 50 ms and is set apart however long it
    is; the step that ends where that prefill begins, and the one that
    begins where a second prefill ends, touch them and stay."""
    steps = registry.histogram(STEP)
    prefills = registry.histogram(PREFILL)
    steps.observe(9.0, at=1029.0)                  # before the window
    at = 1040.0
    for v in (0.010, 0.010, 0.120, 0.010, 0.016, 0.014, 0.010):
        steps.observe(v, at=at)
        at += v
    prefills.observe(0.050, at=at)                 # 1040.19 - 1040.24
    steps.observe(0.060, at=at)                    # launched behind it
    prefills.observe(0.050, at=at + 0.060)         # 1040.25 - 1040.30
    steps.observe(0.010, at=at + 0.110)            # from its end on
    steps.observe(0.010, at=at + 0.120)
    steps.observe(7.0, at=1081.5)                  # after the window
    assert _excess() == pytest.approx(0.110 + 0.006)
    assert "9 of 10 samples apart" in capsys.readouterr().out
    # a higher factor leaves the 16 ms step in
    assert _excess(factor=2.0) == pytest.approx(0.110)
    # with the prefills' histogram empty the step behind one counts too
    prefills.reset()
    assert _excess() == pytest.approx(0.116 + 0.050)


def test_apart_takes_a_union_of_the_other_intervals():
    others = [(10.0, 1.0), (10.5, 1.0), (20.0, 1.0)]
    pairs = [(9.0, 1.0), (9.5, 1.0), (11.2, 0.5), (11.5, 0.2), (19.0, 3.0),
             (21.0, 0.5)]
    assert window_excess.apart(pairs, others) == [1.0, 0.2, 0.5]
    assert window_excess.apart(pairs, []) == [v for _, v in pairs]


# ------------------------------------------------------ the whole path
MAN = manifest.load()
NEW = {"window_total", "window_excess"}


def _host_metrics(cell):
    """The cell's per-layer metrics that a reader of this file, or the
    histogram a launch fills, stands behind."""
    return [e["name"] for e, spec, _ in
            manifest.metrics_for(MAN, cell, "per_layer")
            if spec["reader"] in NEW
            or spec.get("args", {}).get("histogram") == "decode.launch_fed"]


SERVING = [c["name"] for c in MAN["workloads"] if _host_metrics(c["name"])]
@pytest.mark.parametrize("cell", SERVING)
def test_a_rehearsed_serving_cell_reports_the_hosts_numbers(
        monkeypatch, tmp_path, cell):
    """They come out of an UNTRACED rehearsal with a value each (they need
    no trace, so every run reads them, beside its end-to-end metrics), and
    none is a tail's.  The toy mix's replies of 2-6 tokens end a request
    nearly every step, so nearly every scheduling pass runs a prefill, and
    a launch is sampled as fed or drained only in a pass that runs none:
    longer replies."""
    wanted = _host_metrics(cell)
    assert len(wanted) == 4, wanted
    got = rehearsal.rehearse(monkeypatch, tmp_path, cell, seed=39,
                             outputs=(16, 30))["per_layer"]
    for name in wanted:
        assert name in got, (name, sorted(got))
        assert got[name]["value"] >= 0.0
        assert "window_from_s" not in got[name]
