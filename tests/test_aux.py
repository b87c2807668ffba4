"""Auxiliary subsystems: checkpoint/resume, profiling, comm backend,
job deployment (SURVEY.md §5 equivalents)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dist_keras_tpu.checkpoint import Checkpointer, load_model, save_model
from dist_keras_tpu.comm import (
    barrier,
    fetch_global,
    initialize,
    is_multi_host,
    local_data_slice,
    num_processes,
)
from dist_keras_tpu.launch import Job, Punchcard
from dist_keras_tpu.models import mnist_mlp
from dist_keras_tpu.observability import perf
from dist_keras_tpu.utils.profiling import trace


# ---------------------------------------------------------------- checkpoint
def test_model_save_load_round_trip(tmp_path):
    m = mnist_mlp(hidden=(8,), input_dim=4, num_classes=2)
    save_model(m, tmp_path / "m")
    m2 = load_model(tmp_path / "m")
    x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
    np.testing.assert_allclose(m.predict(x), m2.predict(x), atol=1e-6)


def test_checkpointer_save_restore_retention(tmp_path):
    ck = Checkpointer(tmp_path / "ck", max_to_keep=2)
    m = mnist_mlp(hidden=(4,), input_dim=3, num_classes=2)
    tx = optax.adam(1e-3)
    state = {"params": m.params, "opt_state": tx.init(m.params),
             "epoch": jnp.asarray(0)}
    for step in [1, 2, 3]:
        state["epoch"] = jnp.asarray(step)
        # waited per save: rapid unwaited async saves coalesce
        # latest-wins (by design), and this test wants all three
        ck.save(step, state).wait()
    assert ck.all_steps() == [2, 3]  # retention dropped step 1
    step, restored = ck.restore(template=state)
    assert step == 3
    assert int(restored["epoch"]) == 3
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpointer_resume_empty(tmp_path):
    ck = Checkpointer(tmp_path / "empty")
    assert ck.latest_step() is None
    with pytest.raises(FileNotFoundError):
        ck.restore()


# ---------------------------------------------------------------- profiling
def test_trace_smoke(tmp_path):
    with trace(tmp_path / "prof"):
        with perf.phase("step"):
            jnp.sum(jnp.ones((4, 4))).block_until_ready()
    # a trace directory with content must exist
    found = [f for _, _, fs in os.walk(tmp_path / "prof") for f in fs]
    assert found


# ---------------------------------------------------------------- comm
def test_comm_single_process():
    initialize()  # no-op single process
    assert num_processes() == 1
    assert not is_multi_host()
    assert local_data_slice(100) == (0, 100)
    assert local_data_slice(103, process=1, count=4) == (25, 50)
    assert local_data_slice(103, process=3, count=4) == (75, 103)
    assert barrier() == float(jax.device_count())


def test_fetch_global_single_host():
    out = fetch_global({"a": jnp.ones((2,))})
    assert isinstance(out["a"], np.ndarray)


# ---------------------------------------------------------------- launch
def test_job_dry_run(tmp_path):
    jobdir = tmp_path / "job"
    jobdir.mkdir()
    (jobdir / "main.py").write_text("print('hi')")
    job = Job("s3cret", "exp1", str(jobdir),
              hosts=["tpu-host-0", "tpu-host-1"], dry_run=True)
    assert job.send() == 0
    cmds = [" ".join(c) for c in job.commands]
    assert sum("rsync" in c for c in cmds) == 2
    launches = [c for c in cmds if "ssh" in c]
    assert len(launches) == 2
    assert "JAX_PROCESS_ID=0" in launches[0]
    assert "JAX_PROCESS_ID=1" in launches[1]
    assert "JAX_COORDINATOR_ADDRESS=tpu-host-0:8476" in launches[1]


def test_punchcard_secret_auth(tmp_path):
    jobdir = tmp_path / "job"
    jobdir.mkdir()
    (jobdir / "main.py").write_text("print('hi')")
    manifest = [
        {"secret": "good", "job_name": "a", "job_dir": str(jobdir),
         "hosts": ["h0"]},
        {"secret": "evil", "job_name": "b", "job_dir": str(jobdir),
         "hosts": ["h0"]},
    ]
    mpath = tmp_path / "manifest.json"
    mpath.write_text(json.dumps(manifest))
    pc = Punchcard(str(mpath), secrets=["good"], dry_run=True)
    ran = pc.run_once()
    assert [j.job_name for j in ran] == ["a"]
    # idempotent: second poll doesn't rerun
    assert pc.run_once() == []


def test_checkpointer_npz_fallback_round_trip(tmp_path, monkeypatch):
    """A checkpoint written without orbax must be readable (the old
    fallback could save but raised on restore)."""
    import dist_keras_tpu.checkpoint as ck

    monkeypatch.setattr(ck, "_HAVE_ORBAX", False)
    c = ck.Checkpointer(str(tmp_path / "ck"), max_to_keep=2)
    assert c._ckpt is None
    state = {"params": [np.arange(4, dtype=np.float32)], "epoch": 3}
    c.save(7, state)
    step, restored = c.restore()
    assert step == 7
    assert restored["epoch"] == 3
    np.testing.assert_array_equal(restored["params"][0], state["params"][0])


def test_auc_tie_handling_mean_ranks():
    """Tied scores take their mean rank; compare against sklearn."""
    from sklearn.metrics import roc_auc_score

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.data.evaluators import AUCEvaluator

    rng = np.random.default_rng(0)
    y = rng.integers(0, 2, 200)
    # heavily quantized scores -> many ties
    s = np.round(rng.random(200) * 4) / 4 + 0.1 * y
    s = np.clip(s, 0, 1)
    ds = Dataset({"prediction": s, "label": y})
    ours = AUCEvaluator(score_col="prediction").evaluate(ds)
    ref = roc_auc_score(y, s)
    assert abs(ours - ref) < 1e-9, (ours, ref)


def test_job_rejects_unsafe_names(tmp_path):
    from dist_keras_tpu.launch.job import Job

    with pytest.raises(ValueError):
        Job("s", "bad;rm -rf /", str(tmp_path), hosts=["h"], dry_run=True)
    with pytest.raises(ValueError):
        Job("s", "ok", str(tmp_path), hosts=["h"], dry_run=True,
            remote_root="~/jobs;evil")
    job = Job("s", "ok-name_1", str(tmp_path), hosts=["h"], dry_run=True)
    job.send()
    assert any("rsync" == c[0] for c in job.commands)


# ------------------------------------------------- launch config + CLI
def _write_jobdir(tmp_path):
    jobdir = tmp_path / "job"
    jobdir.mkdir(exist_ok=True)
    (jobdir / "main.py").write_text("print('hi')")
    return jobdir


def test_job_config_round_trip_and_validation(tmp_path):
    from dist_keras_tpu.launch import JobConfig

    jobdir = _write_jobdir(tmp_path)
    cfg = JobConfig.from_dict({"job_name": "exp1", "job_dir": str(jobdir),
                               "hosts": ["h0", "h1"]})
    assert cfg.coordinator_port == 8476  # defaults fill in
    job = cfg.to_job(dry_run=True)
    assert job.send() == 0
    assert sum(c[0] == "rsync" for c in job.commands) == 2
    # unknown and missing fields are named in the error
    with pytest.raises(ValueError, match="unknown JobConfig field"):
        JobConfig.from_dict({"job_name": "a", "job_dir": ".",
                             "hostz": ["h"]})
    with pytest.raises(ValueError, match="missing required"):
        JobConfig.from_dict({"job_name": "a"})
    # a JSON string where the hosts list belongs must not fan out to
    # one ssh target per character
    with pytest.raises(ValueError, match="hosts"):
        JobConfig.from_dict({"job_name": "a", "job_dir": ".",
                             "hosts": "localhost"})
    with pytest.raises(ValueError, match="coordinator_port"):
        JobConfig.from_dict({"job_name": "a", "job_dir": ".",
                             "hosts": ["h"], "coordinator_port": "8476"})
    # config -> dict -> manifest entry round trip keeps Job kwargs valid
    d = cfg.to_dict()
    assert JobConfig.from_dict(d) == cfg


def test_launch_cli_job_dry_run(tmp_path, capsys):
    from dist_keras_tpu.launch.__main__ import main

    jobdir = _write_jobdir(tmp_path)
    cfg_path = tmp_path / "job.json"
    cfg_path.write_text(json.dumps(
        {"job_name": "exp1", "job_dir": str(jobdir), "secret": "s",
         "hosts": ["tpu-host-0", "tpu-host-1"]}))
    rc = main(["--job", str(cfg_path), "--dry-run"])
    assert rc == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("DRY-RUN ")]
    assert sum("rsync" in ln for ln in lines) == 2
    assert sum("ssh" in ln for ln in lines) == 2
    assert any("JAX_PROCESS_ID=1" in ln for ln in lines)


def test_launch_cli_manifest_dry_run(tmp_path, capsys):
    from dist_keras_tpu.launch.__main__ import main

    jobdir = _write_jobdir(tmp_path)
    manifest = [
        {"secret": "good", "job_name": "a", "job_dir": str(jobdir),
         "hosts": ["h0"]},
        {"secret": "evil", "job_name": "b", "job_dir": str(jobdir),
         "hosts": ["h0"]},
    ]
    mpath = tmp_path / "manifest.json"
    mpath.write_text(json.dumps(manifest))
    rc = main(["--manifest", str(mpath), "--secret", "good", "--dry-run"])
    assert rc == 0
    out = capsys.readouterr().out
    # only the authenticated job ran; dry-run capped itself at one poll
    assert "/a/" in out and "/b/" not in out


def test_launch_cli_module_entry(tmp_path):
    """`python -m dist_keras_tpu.launch` is a real shell entrypoint."""
    import subprocess
    import sys

    jobdir = _write_jobdir(tmp_path)
    cfg_path = tmp_path / "job.json"
    cfg_path.write_text(json.dumps(
        {"job_name": "exp1", "job_dir": str(jobdir), "hosts": ["h0"]}))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "dist_keras_tpu.launch",
         "--job", str(cfg_path), "--dry-run"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DRY-RUN rsync" in proc.stdout
    assert "DRY-RUN ssh" in proc.stdout


def test_launch_cli_manifest_no_match_fails(tmp_path, capsys):
    """A finite manifest run where no job matched the secrets exits
    nonzero — a typo'd --secret must not read as success."""
    from dist_keras_tpu.launch.__main__ import main

    jobdir = _write_jobdir(tmp_path)
    mpath = tmp_path / "manifest.json"
    mpath.write_text(json.dumps(
        [{"secret": "good", "job_name": "a", "job_dir": str(jobdir),
          "hosts": ["h0"]}]))
    rc = main(["--manifest", str(mpath), "--secret", "typo", "--dry-run"])
    assert rc == 1
