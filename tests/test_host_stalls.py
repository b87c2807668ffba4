"""What every serving process says of its host: the stall witness
(``perf.watch_stalls``), a launch's "fed or drained" sample
(``decode.launch_fed``), and histograms that hold a whole window."""

import threading
import time

import pytest

from dist_keras_tpu.models.transformer import Transformer, transformer_config
from dist_keras_tpu.observability import metrics, perf
from dist_keras_tpu.serving import decode
from dist_keras_tpu.serving.decode import DecodeEngine

WITNESS = "dk-perf-stall-witness"
STALLS = ("perf.host_stall_s", "perf.host_stall_cpu_s")


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


def _witnesses():
    return [t for t in threading.enumerate() if t.name == WITNESS]


def _window(name, lo, hi):
    pairs, truncated = metrics.histogram(name).samples_between(lo, hi)
    assert not truncated, name
    return pairs


# ------------------------------------------------------- the witness
class _Machine:
    """A clock, a CPU clock and a wait that the test advances: each wait
    takes the next of ``waits`` seconds (of which ``busy`` were the
    process's own), and the loop is stopped when they run out."""

    def __init__(self, waits, busy=()):
        self.now, self.used = 500.0, 7.0
        self.waits = list(waits)
        self.busy = list(busy) + [0.0] * len(self.waits)
        self.asked = []

    def wait(self, timeout):
        self.asked.append(timeout)
        if not self.waits:
            return True
        self.now += self.waits.pop(0)
        self.used += self.busy.pop(0)
        return False

    def run(self):
        perf._witness_loop(self.wait, clock=lambda: self.now,
                           cpu=lambda: self.used)


@pytest.fixture
def registry():
    metrics.reset()
    yield metrics
    metrics.reset()


def test_a_late_wake_up_is_one_sample_stamped_when_it_was_due(registry):
    m = _Machine([0.010, 0.0104, 0.120, 0.010], busy=[0.001, 0.002, 0.023])
    m.run()
    assert m.asked == [perf.STALL_WAIT_S] * 5
    (stall,), truncated = registry.histogram(STALLS[0]).samples_between(
        0.0, 1e9)
    (cpu,), _ = registry.histogram(STALLS[1]).samples_between(0.0, 1e9)
    # the third wait began at 500.0204 and was due 10 ms later
    assert not truncated
    assert stall[0] == cpu[0] == pytest.approx(500.0304)
    assert stall[1] == pytest.approx(0.110)
    # the CPU seconds of THAT interval, not of the run so far
    assert cpu[1] == pytest.approx(0.023)


def test_wake_ups_on_time_are_no_sample(registry):
    # up to 30 ms late is a busy process's ordinary wake-up
    m = _Machine([0.010, 0.012, 0.039, 0.0399, 0.010])
    m.run()
    for name in STALLS:
        assert registry.histogram(name).totals()["count"] == 0
    m = _Machine([0.010, 0.0401 + 1e-9, 0.010])
    m.run()
    assert registry.histogram(STALLS[0]).totals()["count"] == 1


def test_the_constants_are_pr_38s():
    assert perf.STALL_WAIT_S == 0.010 and perf.STALL_LATE_S == 0.030


def test_two_engines_share_one_witness_and_the_last_close_ends_it(
        monkeypatch):
    # engines that tests before this one never closed keep the process's
    # witness: this test counts from a state of its own
    monkeypatch.setattr(perf, "_witness",
                        {"users": 0, "thread": None, "stop": None})
    others = _witnesses()
    metrics.reset()
    first = _engine()
    try:
        # its histograms exist from the start: "no stall" is not "no
        # witness"
        snap = metrics.snapshot(percentiles=False)["histograms"]
        assert all(snap[name]["count"] == 0 for name in STALLS)
        (thread,) = [t for t in _witnesses() if t not in others]
        assert thread.daemon and thread is perf._witness["thread"]
        second = _engine()
        try:
            assert perf._witness["thread"] is thread
            first.close(drain=False)
            first.close(drain=False)            # a second close counts once
            assert perf._witness["thread"] is thread and thread.is_alive()
        finally:
            second.close(drain=False)
        assert perf._witness["thread"] is None and not thread.is_alive()
        assert [t for t in _witnesses() if t not in others] == []
        # a later engine starts a new one, and a drain ends it as well
        third = _engine()
        try:
            again = perf._witness["thread"]
            assert again is not thread and again.is_alive()
        finally:
            third.drain()
        assert not again.is_alive()
    finally:
        first.close(drain=False)
        metrics.reset()


def test_users_come_and_go_from_many_threads(monkeypatch):
    """More threads than cores start and stop using the witness at once,
    under a short switch interval: the count of users ends at nought and
    no witness is left (a lost update of the count would leave one
    running, or stop one that still has a user)."""
    import sys

    state = {"users": 0, "thread": None, "stop": None}
    monkeypatch.setattr(perf, "_witness", state)
    others = _witnesses()
    seen = []

    def churn():
        for _ in range(6):
            perf.watch_stalls()
            seen.append(state["thread"] is not None and state["users"] > 0)
            perf.unwatch_stalls()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert state["users"] == 0 and state["thread"] is None
    deadline = time.monotonic() + 5
    while [t for t in _witnesses() if t not in others] \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert [t for t in _witnesses() if t not in others] == []
    # a user always had a witness
    assert len(seen) == 96 and all(seen)


def test_a_busy_interpreter_is_a_stall_with_its_cpu_seconds(engine):
    """The witness cannot tell who kept it from waking, so it says what
    the process used meanwhile: a call that holds the interpreter's lock
    for a tenth of a second (here a sum in C) is a stall whose CPU
    seconds are most of its length; a machine that stands still uses
    next to none (PR 38: 0.01-0.04 s in 0.11 s)."""
    time.sleep(0.05)
    lo = time.perf_counter()
    sum(range(8_000_000))
    time.sleep(0.05)
    hi = time.perf_counter()
    stalls = _window(STALLS[0], lo - 0.02, hi)
    cpus = _window(STALLS[1], lo - 0.02, hi)
    assert stalls and [at for at, _ in stalls] == [at for at, _ in cpus]
    longest = max(range(len(stalls)), key=lambda i: stalls[i][1])
    assert stalls[longest][1] > 0.03
    assert cpus[longest][1] > 0.25 * stalls[longest][1]


# ------------------------------------------------- fed or drained
@pytest.mark.parametrize("running, fed", [(False, 0.0), (True, 1.0)])
def test_a_launch_says_whether_its_predecessor_still_ran(
        engine, monkeypatch, running, fed):
    """A launch on a predecessor's output: 0.0 when that step had landed
    on the device (the chip had drained), 1.0 when it was still running;
    stamped as ``decode.step_overlapped`` is.  The first step has no
    predecessor and is launched by the pass that ran the prefill: no
    sample."""
    asked = []

    def still_running(out):
        asked.append(out.is_ready())      # the real answer is a bool
        return running

    monkeypatch.setattr(decode, "_still_running", still_running)
    lo = time.perf_counter()
    engine.generate([1, 2, 3], max_new_tokens=6)
    hi = time.perf_counter()
    flags = _window("decode.step_overlapped", lo, hi)
    assert [v for _, v in flags] == [0.0, 1.0, 1.0, 1.0, 1.0]
    assert _window("decode.launch_fed", lo, hi) == [
        (at, fed) for at, _ in flags[1:]]
    assert len(asked) == 4 and set(asked) <= {True, False}


def test_a_pass_that_runs_a_prefill_takes_no_sample(engine, monkeypatch):
    """A second request arrives while the first decodes: the pass that
    prefills it launches one step behind the prefill and one after the
    prefill's wait, both on a predecessor's output, and neither is a
    sample (fed by the prefill, drained by design)."""
    monkeypatch.setattr(decode, "_still_running", lambda out: True)
    late = []

    def on_token(_):
        seen.append(_)
        if len(seen) == 3:
            late.append(engine.submit_generate([4, 5, 6, 7],
                                               max_new_tokens=3))

    seen = []
    lo = time.perf_counter()
    first = engine.submit_generate([1, 2, 3], max_new_tokens=12,
                                   on_token=on_token)
    first.result(timeout=120)
    late[0].result(timeout=120)
    time.sleep(0.2)              # the last step in flight lands
    hi = time.perf_counter()
    flags = _window("decode.step_overlapped", lo, hi)
    fed = _window("decode.launch_fed", lo, hi)
    overlapped = [at for at, v in flags if v]
    assert len(_window("decode.prefill_s", lo, hi)) == 2
    assert len(fed) == len(overlapped) - 2
    assert {at for at, _ in fed} < set(overlapped)
    assert {v for _, v in fed} == {1.0}


def test_the_real_answer_is_a_share(engine):
    lo = time.perf_counter()
    engine.generate([1, 2, 3], max_new_tokens=8)
    hi = time.perf_counter()
    fed = [v for _, v in _window("decode.launch_fed", lo, hi)]
    assert len(fed) == 6 and set(fed) <= {0.0, 1.0}


# ------------------------------------------------------- retention
def test_a_histogram_holds_a_whole_window():
    """16,384 samples kept, each with its stamp (a 51 s window of 3.1 ms
    steps); ``summary()``'s percentiles stay over the newest 4,096."""
    assert metrics.Histogram.WINDOW == 16384
    assert metrics.Histogram.RECENT == 4096
    h = metrics.Histogram()
    for i in range(20_000):
        h.observe(float(i), at=100.0 + i)
    kept = h.samples
    assert len(kept) == 16384 and kept[0] == 3616.0 and kept[-1] == 19999.0
    pairs, truncated = h.samples_between(100.0 + 3616, 100.0 + 20_000)
    assert len(pairs) == 16384 and not truncated
    assert pairs[0] == (3716.0, 3616.0)
    # the newest sample it dropped is stamped 3715: a window from there
    # on is a tail's
    assert h.samples_between(100.0 + 3615, 1e9)[1]
    s = h.summary()
    assert s["count"] == 20_000 and s["max"] == 19999.0
    assert s["total"] == sum(range(20_000))
    # over 15904 .. 19999, not over what is retained (3616 .. 19999)
    assert s["p50"] == pytest.approx((15904 + 19999) / 2)
    assert s["p99"] > 19950


def test_a_short_histogram_summarises_all_it_has(monkeypatch):
    monkeypatch.setattr(metrics.Histogram, "RECENT", 4)
    h = metrics.Histogram()
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert h.summary()["p50"] == 2.0
    for v in (4.0, 5.0, 6.0):
        h.observe(v)
    assert h.samples == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert h.summary()["p50"] == 4.5          # of 3 .. 6


def test_a_chat_windows_steps_fit():
    """An untraced chat window holds 4,900-5,000 decode steps: every one
    is retained, so no per-step statistic is a tail's."""
    h = metrics.Histogram()
    for i in range(5_000):
        h.observe(0.0102, at=1000.0 + 0.0102 * i)
    pairs, truncated = h.samples_between(1000.0, 1051.0)
    assert len(pairs) == 5_000 and not truncated
