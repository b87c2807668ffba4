"""Observability subsystem: event log, metrics registry, spans, run
report, and the seam wiring (trainers / checkpoint / retry / faults /
preemption / coordination / launch)."""

import json
import os

import numpy as np
import pytest

from dist_keras_tpu.observability import events, metrics, report, spans


@pytest.fixture
def obs_dir(tmp_path, monkeypatch):
    """Enable the event log into a temp dir; reset all process-global
    observability state on the way in AND out (other tests must keep
    seeing the disabled fast path)."""
    d = tmp_path / "obs"
    monkeypatch.setenv("DK_OBS_DIR", str(d))
    events.reset()
    metrics.reset()
    yield d
    events.reset()
    metrics.reset()


def _read_events(d):
    return report.read_events(d)


# ---------------------------------------------------------------- events
def test_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv("DK_OBS_DIR", raising=False)
    events.reset()
    assert not events.enabled()
    assert events.obs_dir() is None
    events.emit("anything", x=1)  # dropped silently
    assert list(tmp_path.iterdir()) == []


def test_emit_writes_one_json_line_per_event(obs_dir):
    assert events.enabled()
    events.emit("alpha", x=1)
    events.emit("beta", msg="hi", val=2.5)
    files = os.listdir(obs_dir)
    assert files == ["events-rank_0.jsonl"]
    lines = (obs_dir / files[0]).read_text().splitlines()
    assert len(lines) == 2
    e0, e1 = (json.loads(ln) for ln in lines)
    assert e0["kind"] == "alpha" and e0["x"] == 1
    assert e1["kind"] == "beta" and e1["val"] == 2.5
    # ordering metadata on every record
    assert e0["seq"] == 0 and e1["seq"] == 1
    assert e0["rank"] == 0 and e0["t"] <= e1["t"]


def test_rank_resolved_from_coord_env(tmp_path, monkeypatch):
    monkeypatch.setenv("DK_OBS_DIR", str(tmp_path))
    monkeypatch.setenv("DK_COORD_RANK", "3")
    events.reset()
    events.emit("x")
    events.reset()
    assert (tmp_path / "events-rank_3.jsonl").exists()


def test_exotic_field_types_never_drop_the_event(obs_dir):
    events.emit("weird", arr=np.float32(1.5), path=obs_dir,
                err=ValueError("boom"))
    (ev,) = _read_events(obs_dir)
    assert ev["kind"] == "weird"  # default=str serialized everything


def test_emit_never_throws_into_training_code(obs_dir, monkeypatch,
                                              capsys):
    events.emit("fine")

    def broken_write(*a, **k):
        raise OSError("disk on fire")

    monkeypatch.setattr(events.os, "write", broken_write)
    events.emit("dropped-1")  # must NOT raise
    events.emit("dropped-2")
    err = capsys.readouterr().err
    assert err.count("WARNING") == 1  # one warning, then silence


# ---------------------------------------------------------------- metrics
def test_counter_gauge_histogram_registry():
    metrics.reset()
    metrics.counter("c").inc()
    metrics.counter("c").inc(4)
    metrics.gauge("g").set(7)
    metrics.histogram("h").observe(1.0)
    metrics.histogram("h").observe(3.0)
    snap = metrics.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 7
    h = snap["histograms"]["h"]
    assert h["count"] == 2 and h["total"] == 4.0 and h["max"] == 3.0
    metrics.reset()


def test_metric_name_type_conflict_is_loud():
    metrics.reset()
    metrics.counter("same")
    with pytest.raises(TypeError):
        metrics.gauge("same")
    metrics.reset()


def test_histogram_window_bounded_but_totals_exact(monkeypatch):
    monkeypatch.setattr(metrics.Histogram, "WINDOW", 16)
    monkeypatch.setattr(metrics.Histogram, "RECENT", 8)
    h = metrics.Histogram()
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100            # exact lifetime count
    assert s["total"] == sum(range(100))
    assert s["max"] == 99.0
    assert len(h.samples) == 16         # what is retained is bounded
    assert s["p50"] == 95.5             # ...percentiles: the RECENT 8


def test_empty_histogram_summary_guarded():
    h = metrics.Histogram()
    s = h.summary()
    assert s["count"] == 0 and s["total"] == 0.0
    assert s["p50"] is None and s["p99"] is None and s["max"] is None


def test_snapshot_rides_event_stream(obs_dir):
    metrics.counter("job.rsync.retries").inc(2)
    metrics.emit_snapshot(epoch=4)
    (ev,) = _read_events(obs_dir)
    assert ev["kind"] == "metrics" and ev["epoch"] == 4
    assert ev["counters"]["job.rsync.retries"] == 2


# ---------------------------------------------------------------- spans
def test_span_nesting_and_durations(obs_dir):
    with spans.span("outer"):
        assert spans.current_path() == "outer"
        with spans.span("inner", i=3):
            assert spans.current_path() == "outer.inner"
    evs = _read_events(obs_dir)
    kinds = [(e["kind"], e.get("span")) for e in evs]
    assert kinds == [("span_begin", "outer"),
                     ("span_begin", "outer.inner"),
                     ("span_end", "outer.inner"),
                     ("span_end", "outer")]
    ends = {e["span"]: e for e in evs if e["kind"] == "span_end"}
    assert ends["outer"]["duration_s"] >= \
        ends["outer.inner"]["duration_s"] >= 0
    assert ends["outer.inner"]["i"] == 3
    # durations also landed in the registry
    assert metrics.snapshot()["histograms"]["span.outer"]["count"] == 1


def test_span_disabled_is_noop(monkeypatch):
    monkeypatch.delenv("DK_OBS_DIR", raising=False)
    events.reset()
    with spans.span("nothing"):
        # no stack bookkeeping on the no-op path either
        assert spans.current_path() == ""


# ---------------------------------------------------------------- report
def test_report_merges_ranks_in_time_order(tmp_path):
    w0 = events.EventWriter(tmp_path, rank=0)
    w1 = events.EventWriter(tmp_path, rank=1)
    w0.emit("a")
    w1.emit("b")
    w0.emit("c")
    w0.close()
    w1.close()
    merged = report.read_events(tmp_path)
    assert [e["kind"] for e in merged] == ["a", "b", "c"]
    assert [e["rank"] for e in merged] == [0, 1, 0]


def test_report_skips_torn_tail_line(tmp_path):
    w = events.EventWriter(tmp_path, rank=0)
    w.emit("whole")
    w.close()
    with open(os.path.join(tmp_path, "events-rank_0.jsonl"), "a") as f:
        f.write('{"t": 1.0, "kind": "torn...')  # kill mid-write
    evs = report.read_events(tmp_path)
    assert [e["kind"] for e in evs] == ["whole"]


def test_summarize_attributes_preemption_and_phases(tmp_path):
    w0 = events.EventWriter(tmp_path, rank=0)
    w1 = events.EventWriter(tmp_path, rank=1)
    w0.emit("preempt_signal", signum=15)
    # both ranks honor the cluster vote, but only rank 0 got the OS
    # signal — rank 1's adopted verdict must NOT dilute attribution
    w0.emit("preempt", signum=15, adopted=False)
    w1.emit("preempt", signum=15, adopted=True)
    for w in (w0, w1):
        w.emit("epoch_end", epoch=1, nonfinite_steps=1)
        w.emit("span_end", span="ckpt.save", duration_s=0.25)
        w.emit("ckpt_save", step=7)
        w.emit("coord", op="barrier(preempt_exit)", duration_s=0.01)
    w0.emit("retry", name="job.rsync", attempt=1)
    w0.emit("fault", point="coord.flag")
    w0.close()
    w1.close()
    s = report.summarize(report.read_events(tmp_path))
    assert s["preempt_signalled"] == {0: 15}
    assert s["checkpoints"]["agreed_step"] == 7
    assert s["checkpoints"]["last_save_by_rank"] == {0: 7, 1: 7}
    assert s["phases"]["ckpt.save"]["count"] == 2
    assert abs(s["phases"]["ckpt.save"]["total_s"] - 0.5) < 1e-9
    assert s["coord"]["barrier(preempt_exit)"]["count"] == 2
    assert s["retries"]["job.rsync"]["attempts"] == 1
    assert s["faults"] == {"coord.flag": 1}
    assert s["epochs_by_rank"] == {0: 1, 1: 1}
    assert s["nonfinite_steps"] == 2
    rendered = report.render(tmp_path, last_n=3)
    assert "rank 0" in rendered and "rank 1" in rendered
    assert "agreed save step: 7" in rendered


def test_summarize_attributes_decode_recovery(tmp_path):
    w = events.EventWriter(tmp_path, rank=0)
    w.emit("decode_quarantine", replica=0, orphans=3,
           cause="Overloaded")
    w.emit("decode_recover", sid=1, src=0, dst=1, generated=2,
           recoveries=1)
    w.emit("decode_recover", sid=2, src=0, dst=1, generated=0,
           recoveries=1)
    w.emit("decode_recover", sid=3, src=None, dst=2, generated=4,
           recoveries=1)
    w.emit("decode_shed", reason="kv_watermark", prompt_len=4)
    w.emit("decode_deadline", phase="admission", deadline_s=0.1,
           estimate_s=0.4)
    w.emit("decode_deadline", sid=9, phase="expiry", generated=2)
    w.emit("decode_kv_leak", replica=1, sid=99, pages=2)
    w.close()
    s = report.summarize(report.read_events(tmp_path))
    dc = s["decode"]
    assert dc["quarantines"] == [{"replica": 0, "orphans": 3,
                                  "cause": "Overloaded"}]
    assert dc["recoveries_by_replica"] == {1: 2, 2: 1}
    assert dc["sheds_by_reason"] == {"kv_watermark": 1}
    assert dc["deadline"] == {"infeasible": 1, "expired": 1}
    assert dc["kv_pages_reclaimed"] == 2
    rendered = report.render(tmp_path)
    assert "decode survivability:" in rendered
    assert "replica 0 quarantined (Overloaded)" in rendered
    assert "3 recovered onto" in rendered
    assert "kv_watermark x1" in rendered
    assert "1 rejected at the door, 1 expired mid-decode" in rendered
    assert "self-check reclaimed 2 page(s)" in rendered


def test_report_cli_json_and_exit_codes(tmp_path, capsys):
    from dist_keras_tpu.observability.__main__ import main

    assert main([str(tmp_path / "empty")]) == 1  # nothing recorded
    capsys.readouterr()  # drain the rendered empty-dir report
    w = events.EventWriter(tmp_path, rank=0)
    w.emit("epoch_end", epoch=1)
    w.close()
    assert main([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["epochs_by_rank"]["0"] == 1  # json stringifies int keys


def test_write_report_creates_artifact(tmp_path):
    w = events.EventWriter(tmp_path, rank=0)
    w.emit("epoch_end", epoch=1)
    w.close()
    path = report.write_report(tmp_path)
    assert os.path.exists(path)
    assert "run report" in open(path).read()


# ------------------------------------------------------------ seam wiring
def test_trainer_run_emits_timeline(obs_dir, blobs_dataset):
    from dist_keras_tpu.models import mnist_mlp
    from dist_keras_tpu.trainers import SingleTrainer

    t = SingleTrainer(mnist_mlp(hidden=(8,), input_dim=8, num_classes=2),
                      batch_size=32, num_epoch=2,
                      label_col="label_encoded",
                      callbacks=[lambda tr, e, logs: None])
    t.train(blobs_dataset)
    kinds = [e["kind"] for e in _read_events(obs_dir)]
    assert kinds[0] == "train_start"
    assert kinds.count("epoch_end") == 2
    assert kinds.count("metrics") == 2  # one snapshot per epoch
    assert "chunk" in kinds
    assert kinds[-1] == "train_end"
    epoch_evs = [e for e in _read_events(obs_dir)
                 if e["kind"] == "epoch_end"]
    assert epoch_evs[0]["epoch"] == 1
    assert "mean_loss" in epoch_evs[0]
    # rank 0 (the only rank here) left the merged report artifact
    assert (obs_dir / "report.txt").exists()
    assert "epoch_end" in (obs_dir / "report.txt").read_text()


def test_checkpointer_emits_save_and_restore(obs_dir, tmp_path):
    from dist_keras_tpu.checkpoint import Checkpointer

    ck = Checkpointer(tmp_path / "ck")
    ck.save(3, {"x": np.arange(4)})
    ck.restore()
    evs = _read_events(obs_dir)
    kinds = [e["kind"] for e in evs]
    assert "ckpt_save" in kinds and "ckpt_restore" in kinds
    save = next(e for e in evs if e["kind"] == "ckpt_save")
    assert save["step"] == 3 and save["duration_s"] > 0
    # the save span gives the report its per-phase durations
    assert any(e["kind"] == "span_end" and e["span"] == "ckpt.save"
               for e in evs)


def test_failed_restore_emits_nothing(obs_dir, tmp_path):
    """Only COMPLETED restores are recorded — a crash-loop that never
    restores must not read as N successful restores."""
    from dist_keras_tpu.checkpoint import Checkpointer

    ck = Checkpointer(tmp_path / "ck")
    ck.save(1, {"x": np.arange(3)}).wait()
    pkl = tmp_path / "ck" / "step_00000001" / "state.pkl"
    if pkl.exists():  # corrupt the payload, whichever format wrote it
        pkl.write_bytes(b"not a pickle")
    else:
        import shutil

        shutil.rmtree(tmp_path / "ck" / "step_00000001")
        (tmp_path / "ck" / "step_00000001").mkdir()
    with pytest.raises(Exception):
        ck.restore()
    assert not any(e["kind"] == "ckpt_restore"
                   for e in _read_events(obs_dir))


def test_preempted_run_still_writes_report(obs_dir, blobs_dataset,
                                           tmp_path):
    """The post-mortem artifact must exist precisely for ABNORMAL
    exits: a preempted run leaves train_end + report.txt."""
    import signal as _signal

    from dist_keras_tpu.models import mnist_mlp
    from dist_keras_tpu.resilience import preemption
    from dist_keras_tpu.resilience.preemption import Preempted
    from dist_keras_tpu.trainers import SingleTrainer

    preemption.clear()

    def bomb(trainer, epoch, logs):
        preemption.request(_signal.SIGTERM)

    t = SingleTrainer(mnist_mlp(hidden=(8,), input_dim=8,
                                num_classes=2),
                      batch_size=32, num_epoch=4,
                      label_col="label_encoded",
                      checkpoint_dir=str(tmp_path / "ck"),
                      handle_preemption=True, callbacks=[bomb])
    try:
        with pytest.raises(Preempted):
            t.train(blobs_dataset)
    finally:
        preemption.clear()
    kinds = [e["kind"] for e in _read_events(obs_dir)]
    assert "preempt_exit" in kinds and "train_end" in kinds
    assert (obs_dir / "report.txt").exists()
    assert "preemption: rank 0" in (obs_dir / "report.txt").read_text()


def test_retry_emits_attempts_and_exhaustion(obs_dir):
    from dist_keras_tpu.resilience.retry import RetryPolicy

    metrics.reset()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    pol = RetryPolicy(attempts=5, backoff=0.0, name="job.rsync",
                      sleep=lambda s: None)
    assert pol.call(flaky) == "ok"
    with pytest.raises(OSError):
        RetryPolicy(attempts=2, backoff=0.0, name="job.rsync",
                    sleep=lambda s: None).call(
            lambda: (_ for _ in ()).throw(OSError("always")))
    evs = _read_events(obs_dir)
    retries = [e for e in evs if e["kind"] == "retry"]
    assert len(retries) == 3 and retries[0]["name"] == "job.rsync"
    assert any(e["kind"] == "retry_exhausted" for e in evs)
    assert metrics.counter("job.rsync.retries").value == 3
    assert metrics.counter("job.rsync.exhausted").value == 1
    metrics.reset()


def test_fault_fire_is_recorded(obs_dir):
    from dist_keras_tpu.resilience import faults

    faults.clear()
    with faults.armed("stream.fetch", at=0):
        with pytest.raises(faults.FaultInjected):
            faults.fault_point("stream.fetch")
    (ev,) = [e for e in _read_events(obs_dir) if e["kind"] == "fault"]
    assert ev["point"] == "stream.fetch" and ev["action"] == "raise"
    faults.clear()


def test_preemption_request_emits_signal_event(obs_dir):
    from dist_keras_tpu.resilience import preemption

    preemption.clear()
    preemption.request()
    preemption.clear()
    (ev,) = [e for e in _read_events(obs_dir)
             if e["kind"] == "preempt_signal"]
    assert ev["signum"] == 15


def test_coordinator_ops_emit_durations(obs_dir, monkeypatch):
    from dist_keras_tpu.resilience import coordination

    monkeypatch.delenv("DK_COORD_DIR", raising=False)
    coordination.reset()
    coord = coordination.get_coordinator()
    coord.any_flag(False)
    coord.agree_min(5)
    coord.barrier("tag")
    evs = [e for e in _read_events(obs_dir) if e["kind"] == "coord"]
    ops = [e["op"] for e in evs]
    assert ops == ["any_flag", "agree_min", "barrier(tag)"]
    assert all(e["duration_s"] >= 0 for e in evs)
    coordination.reset()


def test_nonfinite_sentinel_emits(obs_dir):
    from dist_keras_tpu.resilience.guards import check_losses

    metrics.reset()

    class Tr:
        nonfinite_steps = 0
        nan_policy = "halt"

    assert check_losses(Tr(), np.array([1.0, np.nan]), units_done=9)
    (ev,) = [e for e in _read_events(obs_dir)
             if e["kind"] == "nonfinite"]
    assert ev["count"] == 1 and ev["units_done"] == 9
    assert metrics.counter("train.nonfinite_steps").value == 1
    metrics.reset()


# ---------------------------------------------------------------- launch
def test_job_exports_obs_and_timeout_env(tmp_path):
    from dist_keras_tpu.launch import Job

    jobdir = tmp_path / "job"
    jobdir.mkdir()
    (jobdir / "main.py").write_text("print('hi')")
    job = Job("s", "j1", str(jobdir), hosts=["h0", "h1"], dry_run=True,
              coord_dir="/shared/coord", coord_timeout_s=45,
              obs_dir="/scratch/obs")
    env = job.host_env(1)
    assert env["DK_OBS_DIR"] == "/scratch/obs"
    assert env["DK_COORD_TIMEOUT_S"] == "45.0"
    assert env["DK_COORD_RANK"] == "1"
    launched = job.launch()
    assert launched == 0
    assert any("DK_OBS_DIR=/scratch/obs" in " ".join(c)
               for c in job.commands)


def test_job_collect_obs_rsyncs_back(tmp_path):
    from dist_keras_tpu.launch import Job

    jobdir = tmp_path / "job"
    jobdir.mkdir()
    (jobdir / "main.py").write_text("x")
    job = Job("s", "j1", str(jobdir), hosts=["h0", "h1"], dry_run=True,
              obs_dir="/scratch/obs")
    assert job.collect_obs(tmp_path / "collected") == 0
    pulls = [" ".join(c) for c in job.commands if c[0] == "rsync"]
    assert len(pulls) == 2
    assert "h0:/scratch/obs/" in pulls[0]
    assert str(tmp_path / "collected" / "host_1") in pulls[1]
    with pytest.raises(ValueError):
        Job("s", "j2", str(jobdir), hosts=["h0"],
            dry_run=True).collect_obs(tmp_path)


def test_jobconfig_new_fields_round_trip(tmp_path):
    from dist_keras_tpu.launch import JobConfig

    cfg = JobConfig.from_dict({
        "job_name": "j", "job_dir": str(tmp_path), "hosts": ["h0"],
        "coord_timeout_s": 30, "obs_dir": "/scratch/obs"})
    assert cfg.coord_timeout_s == 30
    job = cfg.to_job(dry_run=True)
    assert job.obs_dir == "/scratch/obs"
    with pytest.raises(ValueError):
        JobConfig.from_dict({"job_name": "j", "job_dir": str(tmp_path),
                             "obs_dir": 7})


def test_barrier_default_timeout_env(monkeypatch):
    from dist_keras_tpu.comm import backend

    monkeypatch.delenv("DK_COORD_TIMEOUT_S", raising=False)
    assert backend.barrier_default_timeout_s() == 120.0
    monkeypatch.setenv("DK_COORD_TIMEOUT_S", "33.5")
    assert backend.barrier_default_timeout_s() == 33.5
    monkeypatch.setenv("DK_COORD_TIMEOUT_S", "junk")
    assert backend.barrier_default_timeout_s() == 120.0


# -- event-file rotation (round 9: DK_OBS_ROTATE_MB) ------------------
def test_rotation_caps_file_size_and_keeps_segments(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("DK_OBS_ROTATE_KEEP", "2")
    # tiny cap so a handful of events rotates: 300 bytes
    w = events.EventWriter(str(tmp_path), rank=0, rotate_bytes=300,
                           rotate_keep=2)
    for i in range(40):
        w.emit("tick", i=i, pad="x" * 40)
    w.close()
    names = sorted(os.listdir(tmp_path))
    assert "events-rank_0.jsonl" in names
    assert "events-rank_0.jsonl.1" in names
    # keep=2 bounds the rotated segments — no .3 ever
    assert not any(n.endswith(".3") for n in names)
    for n in names:
        assert os.path.getsize(tmp_path / n) <= 300 + 120  # cap + 1 line


def test_rotation_report_merges_segments_in_order(tmp_path):
    w = events.EventWriter(str(tmp_path), rank=0, rotate_bytes=200,
                           rotate_keep=5)
    total = 25
    for i in range(total):
        w.emit("tick", i=i)
    w.close()
    assert any(".jsonl." in n for n in os.listdir(tmp_path)), \
        "cap never triggered — shrink the test cap"
    evs = report.read_events(tmp_path)
    # every retained segment merges into ONE timeline, ordered by
    # (t, rank, seq): seq stays monotonic across rotations
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    assert [e["i"] for e in evs] == list(range(total))[-len(evs):] \
        or len(evs) == total


def test_rotation_env_knob_and_disabled_default(tmp_path, monkeypatch):
    monkeypatch.setenv("DK_OBS_ROTATE_MB", "0.0002")  # ~210 bytes
    w = events.EventWriter(str(tmp_path / "a"), rank=1)
    assert w.rotate_bytes == int(0.0002 * 2**20)
    for i in range(20):
        w.emit("tick", i=i)
    w.close()
    assert any(".jsonl." in n for n in os.listdir(tmp_path / "a"))
    monkeypatch.delenv("DK_OBS_ROTATE_MB")
    w2 = events.EventWriter(str(tmp_path / "b"), rank=1)
    assert w2.rotate_bytes == 0  # unset = unbounded (old behaviour)
    w2.close()
    monkeypatch.setenv("DK_OBS_ROTATE_MB", "garbage")
    w3 = events.EventWriter(str(tmp_path / "c"), rank=1)
    assert w3.rotate_bytes == 0  # malformed knob never kills the run
    w3.emit("tick")
    w3.close()


# -- Job.monitor + serve_port (round 9 satellites) --------------------
def test_job_monitor_prints_rank_transitions(tmp_path):
    from dist_keras_tpu.launch.job import Job

    jobdir = tmp_path / "job"
    jobdir.mkdir()
    obs = tmp_path / "obs"
    w = events.EventWriter(str(obs), rank=0)
    w.emit("train_start")
    w.close()
    w = events.EventWriter(str(obs), rank=1)
    w.emit("train_start")
    w.emit("epoch_end", epoch=0)
    w.close()
    job = Job("s", "mon2", str(jobdir), hosts=["h0", "h1"],
              dry_run=True, obs_dir=str(obs))
    printed = []
    lines = job.monitor(interval_s=0.01, max_polls=1,
                        out=printed.append)
    assert printed == lines
    assert any("rank 0" in ln for ln in lines)
    assert any("rank 1" in ln and "epoch_end" in ln for ln in lines)
