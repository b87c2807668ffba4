"""The bench driver contract (round 5): whatever happens — budget
exhaustion, SIGTERM mid-run — the LAST stdout line is a parseable record
(round 4 lost its entire official perf record to a driver timeout with
the old print-once-at-the-end bench).  These run the real bench.py in
subprocesses on the CPU backend with a zero/short budget, so they are
cheap (~no configs actually measured)."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = (REPO + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    return env


def _last_record(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert lines, "bench printed nothing"
    return json.loads(lines[-1])


def test_zero_budget_still_yields_complete_record():
    env = _env()
    env["BENCH_BUDGET_S"] = "0"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = _last_record(proc.stdout)
    # the loop COMPLETED (every config marked skipped, none lost)
    assert rec["partial"] is False
    # 9 device configs + CPU serving + CPU decode-serving
    # + CPU decode-survivability + CPU router overhead/failover
    # + CPU ckpt-manifest overhead + CPU ckpt-async-save
    # + CPU diff-ckpt + CPU retrace-proxy attribution
    # + CPU reshard-restore + CPU comm-overlap proxy
    # + CPU ps-compress + CPU sim-swarm + CPU slo-overhead
    assert len(rec["configs"]) == 22
    assert all(c.get("skipped") == "budget" for c in rec["configs"])
    # driver-contract top-level keys exist even with no headline run
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec


def test_sigterm_mid_run_flushes_parseable_record():
    """The driver kills with SIGTERM on timeout (rc 124): the record
    must still be the last stdout line, marked partial."""
    env = _env()
    env["BENCH_BUDGET_S"] = "3600"  # would actually run configs
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
    try:
        # wait for the pre-config record line (bench emits one before
        # any jax/device touch) with a REAL deadline — a blocking
        # readline would hang the test if the bench never printed.
        # Binary pipes: non-blocking
        # reads on a text wrapper raise on empty reads.
        os.set_blocking(proc.stdout.fileno(), False)
        # _emit prefixes a newline (line-boundary guarantee), so wait
        # for a non-empty completed line, not just any newline
        def _first_record(b):
            *done, _tail = b.split(b"\n")
            for ln in done:
                if ln.strip():
                    return ln
            return None

        deadline = time.time() + 120
        buf = b""
        while time.time() < deadline and _first_record(buf) is None:
            try:
                chunk = os.read(proc.stdout.fileno(), 65536)
            except BlockingIOError:
                chunk = b""
            if chunk:
                buf += chunk
            elif proc.poll() is not None:
                # drain once more before declaring death: the record
                # may have landed in the pipe between the empty read
                # and the exit (atexit flushes on crash paths)
                try:
                    buf += os.read(proc.stdout.fileno(), 65536)
                except BlockingIOError:
                    pass
                if _first_record(buf) is None:
                    pytest.fail("bench died before emitting a record: "
                                + proc.stderr.read().decode()[-2000:])
                break
            else:
                time.sleep(0.2)
        line = _first_record(buf)
        assert line is not None, "no record line within 120s"
        json.loads(line.decode())  # the pre-config record parses
        os.set_blocking(proc.stdout.fileno(), True)
        proc.send_signal(signal.SIGTERM)
        try:
            stdout, stderr = proc.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            pytest.fail("bench did not exit after SIGTERM")
        rec = _last_record((buf + stdout).decode())
        assert rec["terminated_by"] == "SIGTERM", stderr.decode()[-2000:]
        assert rec["partial"] is True  # config loop did NOT complete
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
