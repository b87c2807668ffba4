"""Round-19 speed push: overlapped window collectives (AsyncMerge +
the DK_COMM_OVERLAP deferred-merge algebra), fused flash-backward
graduation (DK_FUSED_BWD selfcheck verdicts + routing), and compressed
PS commit deltas (DK_PS_COMPRESS codecs + error feedback).

The collectives edge cases here are the ones the overlap path newly
leans on (ISSUE 15 satellite): ``tree_pmean_sync`` inside
shard_map, zero-size leaves, and mixed-dtype trees through the
async merge.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dist_keras_tpu.data import Dataset
from dist_keras_tpu.models import mnist_mlp
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.parallel.collectives import (
    AsyncMerge,
    tree_pmean_sync,
    tree_pvary,
)
from dist_keras_tpu.parallel.mesh import WORKER_AXIS, worker_mesh
from dist_keras_tpu.resilience import faults
from dist_keras_tpu.resilience.faults import FaultInjected
from dist_keras_tpu.trainers import ADAG, AEASGD, DOWNPOUR, EAMSGD
from dist_keras_tpu.utils.misc import one_hot


def _model(seed=0):
    return mnist_mlp(hidden=(16,), input_dim=8, num_classes=2, seed=seed)


_KW = dict(num_workers=2, communication_window=4, batch_size=16,
           num_epoch=2, label_col="label_encoded",
           worker_optimizer="sgd",
           optimizer_kwargs={"learning_rate": 0.05}, seed=0)


def _weights(model):
    return [np.asarray(w) for w in model.get_weights()]


def _same(wa, wb):
    return all(np.array_equal(a, b) for a, b in zip(wa, wb))


# ---------------------------------------------------------------------
# AsyncMerge (parallel/collectives.py)
# ---------------------------------------------------------------------
def test_async_merge_submit_wait_roundtrip():
    am = AsyncMerge(lambda c, d: jax.tree.map(jnp.add, c, d))
    c = {"w": jnp.ones((8,)), "b": jnp.zeros((4,))}
    d = {"w": jnp.full((8,), 2.0), "b": jnp.ones((4,))}
    assert not am.pending
    am.submit(c, d)
    assert am.pending
    out = am.wait()
    assert not am.pending
    np.testing.assert_array_equal(np.asarray(out["w"]), np.full(8, 3.0))
    np.testing.assert_array_equal(np.asarray(out["b"]), np.ones(4))
    # wait with nothing in flight returns the LAST result again
    assert am.wait() is out


def test_async_merge_double_buffer_auto_waits_previous():
    am = AsyncMerge(lambda c, d: jax.tree.map(jnp.add, c, d))
    c = {"w": jnp.zeros((4,))}
    one = {"w": jnp.ones((4,))}
    am.submit(c, one)
    # second submit must retire the first (at most ONE in flight)
    am.submit(am._inflight, one)
    out = am.wait()
    np.testing.assert_array_equal(np.asarray(out["w"]), np.full(4, 2.0))
    assert am.submits == 2 and am.waits == 2  # one implicit + one explicit


def test_async_merge_mixed_dtype_and_zero_size_leaves():
    """The satellite edge cases: a mixed-dtype tree (f32 + bf16 + int32
    RNG state) with a zero-size leaf must round-trip the async merge
    untouched in structure and dtype."""
    from dist_keras_tpu.utils.pytree import tree_add, tree_merge_floats

    am = AsyncMerge(lambda c, p: tree_merge_floats(tree_add(c, p), c))
    c = {"f32": jnp.ones((4,), jnp.float32),
         "bf16": jnp.ones((4,), jnp.bfloat16),
         "rng": jnp.array([3, 7], jnp.uint32),
         "empty": jnp.zeros((0,), jnp.float32)}
    p = {"f32": jnp.full((4,), 0.5, jnp.float32),
         "bf16": jnp.full((4,), 0.5, jnp.bfloat16),
         "rng": jnp.array([9, 9], jnp.uint32),
         "empty": jnp.zeros((0,), jnp.float32)}
    out = am.submit(c, p).wait()
    assert out["f32"].dtype == jnp.float32
    assert out["bf16"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["f32"]), np.full(4, 1.5))
    # integer leaves pass through the float-merge exemption untouched
    np.testing.assert_array_equal(np.asarray(out["rng"]), [3, 7])
    assert out["empty"].shape == (0,)


def test_async_merge_comm_merge_fault_point():
    am = AsyncMerge(lambda c: c)
    with faults.armed("comm.merge"):
        with pytest.raises(FaultInjected):
            am.submit({"w": jnp.ones(2)})
    # nothing half-dispatched: the accumulator stays usable
    assert not am.pending
    out = am.submit({"w": jnp.ones(2)}).wait()
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(2))


def test_async_merge_phase_split_recorded():
    before = metrics.snapshot()["histograms"]
    b0 = before.get("perf.phase.comm_blocked", {}).get("count", 0)
    o0 = before.get("perf.phase.comm_overlap", {}).get("count", 0)
    am = AsyncMerge(lambda c, d: jax.tree.map(jnp.add, c, d))
    am.submit({"w": jnp.ones((128,))}, {"w": jnp.ones((128,))})
    am.wait()
    after = metrics.snapshot()["histograms"]
    assert after["perf.phase.comm_blocked"]["count"] == b0 + 1
    assert after["perf.phase.comm_overlap"]["count"] == o0 + 1


def test_tree_pmean_sync_zero_size_and_int_leaves_in_shard_map():
    """tree_pmean_sync inside shard_map with the edge
    leaves the overlap path can carry: zero-size float arrays (pmean)
    and integer RNG counters (pmax, axis-invariant typed)."""
    mesh = worker_mesh(2)

    def body(tree):
        tree = jax.tree.map(lambda t: t[0], tree)  # drop the shard axis
        tree = tree_pvary(tree)
        merged = tree_pmean_sync(tree)
        return jax.tree.map(lambda t: t[None], merged)

    tree = {
        "w": jnp.stack([jnp.full((4,), 1.0), jnp.full((4,), 3.0)]),
        "empty": jnp.zeros((2, 0), jnp.float32),
        "rng": jnp.array([[5, 5], [5, 5]], jnp.uint32),
    }
    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(WORKER_AXIS),),
        out_specs=P(WORKER_AXIS)))(tree)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.full((2, 4), 2.0))
    assert np.asarray(out["empty"]).shape == (2, 0)
    np.testing.assert_array_equal(np.asarray(out["rng"]),
                                  np.full((2, 2), 5, np.uint32))


# ---------------------------------------------------------------------
# DK_COMM_OVERLAP (trainers/windowed.py)
# ---------------------------------------------------------------------
@pytest.fixture
def blobs():
    rng = np.random.default_rng(0)
    n, d = 512, 8
    y = rng.integers(0, 2, size=n)
    centers = np.stack([np.full(d, -1.0), np.full(d, 1.0)])
    x = (centers[y] + rng.normal(size=(n, d))).astype(np.float32)
    return Dataset({"features": x, "label": y,
                    "label_encoded": one_hot(y, 2)})


def test_overlap_off_is_bit_identical_to_unset(blobs, monkeypatch):
    monkeypatch.delenv("DK_COMM_OVERLAP", raising=False)
    w_unset = _weights(DOWNPOUR(_model(), **_KW).train(blobs))
    w_off = _weights(DOWNPOUR(_model(), comm_overlap=False,
                              **_KW).train(blobs))
    assert _same(w_unset, w_off)


def test_overlap_knob_resolved_at_train_time(blobs, monkeypatch):
    monkeypatch.setenv("DK_COMM_OVERLAP", "1")
    t = DOWNPOUR(_model(), **_KW)
    t.train(blobs)
    assert t._overlap is True
    # an explicit ctor False wins over the env
    t2 = DOWNPOUR(_model(), comm_overlap=False, **_KW)
    t2.train(blobs)
    assert t2._overlap is False


@pytest.mark.parametrize("cls,extra", [
    (DOWNPOUR, {}),
    (ADAG, {}),
    (AEASGD, {"rho": 1.0, "learning_rate": 0.25}),
    (EAMSGD, {"rho": 1.0, "learning_rate": 0.25}),
])
def test_overlap_trains_and_differs_from_blocked(blobs, cls, extra):
    kw = dict(_KW)
    kw.update(extra)
    w_blk = _weights(cls(_model(), **kw).train(blobs))
    w_ovl = _weights(cls(_model(), comm_overlap=True, **kw).train(blobs))
    # the one-window staleness must actually be IN the algebra
    assert not _same(w_blk, w_ovl)
    # and the run still learns: final mean loss below the first
    t = cls(_model(), comm_overlap=True, **kw)
    t.train(blobs)
    h = np.asarray(t.get_history(), np.float64)
    assert h.reshape(-1)[-8:].mean() < h.reshape(-1)[:8].mean()


def test_overlap_chunk_plan_invariant(blobs):
    """The staleness algebra must not depend on how the run is cut into
    dispatches: a per-window streamed run (blocking at every boundary)
    is bit-equal to the one-dispatch fused run — `pending` rides the
    chunk carry."""
    t1 = DOWNPOUR(_model(), comm_overlap=True, **_KW)
    m1 = t1.train(blobs)
    t2 = DOWNPOUR(_model(), comm_overlap=True, stream_chunk_windows=1,
                  **_KW)
    m2 = t2.train(blobs)
    assert _same(_weights(m1), _weights(m2))
    assert np.array_equal(np.asarray(t1.get_history()).reshape(-1),
                          np.asarray(t2.get_history()).reshape(-1))


def test_overlap_center_recurrence_via_checkpoints(blobs, tmp_path,
                                                   monkeypatch):
    """The deferred-apply recurrence, observed through per-window
    checkpoint states: center_{k+1} == center_k + pending_k (float
    leaves) — the previous window's psum'd commit lands exactly one
    window late.  Sync saves + wide retention so EVERY window's state
    survives (async cadence saves legitimately coalesce)."""
    from dist_keras_tpu.checkpoint import Checkpointer

    monkeypatch.setenv("DK_CKPT_ASYNC", "0")
    ck = str(tmp_path / "ck")
    t = DOWNPOUR(_model(), comm_overlap=True, checkpoint_dir=ck,
                 checkpoint_every_windows=1, max_checkpoints=40, **_KW)
    t.train(blobs)
    reader = Checkpointer(ck)
    steps = [s for s in reader.all_steps()]
    # consecutive window states only (the recurrence is one-window)
    consecutive = [(a, b) for a, b in zip(steps, steps[1:])
                   if b == a + 1]
    assert len(consecutive) >= 3
    states = {s: reader.restore(step=s)[1]
              for pair in consecutive[:3] for s in pair}
    for a, b in consecutive[:3]:
        got = states[b]["center"]
        want = jax.tree.map(
            lambda c, p: np.asarray(c) + np.asarray(p)
            if np.issubdtype(np.asarray(c).dtype, np.floating)
            else np.asarray(c),
            states[a]["center"], states[a]["pending"])
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_overlap_resume_matches_uninterrupted(blobs, tmp_path):
    """A resumed overlapped run (pending restored from the checkpoint)
    is bit-equal to the uninterrupted run on the same cadence grid."""
    kw = {k: v for k, v in _KW.items() if k != "num_epoch"}
    ck = str(tmp_path / "ck")
    straight = DOWNPOUR(_model(), comm_overlap=True, num_epoch=4,
                        checkpoint_dir=str(tmp_path / "ref"),
                        checkpoint_every_windows=4, **kw)
    w_ref = _weights(straight.train(blobs))
    # first half, then resume for the rest
    DOWNPOUR(_model(), comm_overlap=True, num_epoch=2,
             checkpoint_dir=ck, checkpoint_every_windows=4,
             **kw).train(blobs)
    resumed = DOWNPOUR(_model(), comm_overlap=True, num_epoch=4,
                       checkpoint_dir=ck, checkpoint_every_windows=4,
                       resume=True, **kw)
    w_res = _weights(resumed.train(blobs))
    assert _same(w_ref, w_res)


def test_overlap_checkpoint_refuses_blocked_resume(blobs, tmp_path):
    """A checkpoint carrying an in-flight overlapped commit must not
    silently resume blocked (the pending delta would be dropped)."""
    ck = str(tmp_path / "ck")
    DOWNPOUR(_model(), comm_overlap=True, checkpoint_dir=ck,
             checkpoint_every_windows=4, **_KW).train(blobs)
    t = DOWNPOUR(_model(), comm_overlap=False, checkpoint_dir=ck,
                 resume=True, **_KW)
    with pytest.raises(ValueError, match="DK_COMM_OVERLAP"):
        t.train(blobs)


def test_overlap_cache_key_separates_executables(blobs):
    """Overlap on/off compiles different scan bodies — the flag must
    key the executable cache (same trainer class, same window)."""
    t_off = DOWNPOUR(_model(), **_KW)
    t_off.train(blobs)
    t_on = DOWNPOUR(_model(), comm_overlap=True, **_KW)
    t_on.train(blobs)
    assert t_off._cache_extras() != t_on._cache_extras()


# ---------------------------------------------------------------------
# DK_FUSED_BWD (ops/pallas)
# ---------------------------------------------------------------------
def _qkv(t=16, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, t, 1, d)).astype(np.float32))
    return mk(), mk(), mk()


def test_selfcheck_unverifiable_off_tpu():
    from dist_keras_tpu.ops.pallas import fused_bwd_experimental as fused

    v = fused.selfcheck(bh=1, t=16, d=8, block_q=8, block_k=8)
    ok, err = v  # the round-5 pair still unpacks
    assert v.status == "unverifiable"
    assert ok is False and err is None
    assert "backend" in v.reason


def test_selfcheck_interpret_detects_multiblock_corruption():
    """Interpret mode is structurally last-write-wins on the aliased dq
    revisit: a 2-kv-block parity run must come back 'mismatch' — the
    guard demonstrably catches the corruption it exists for."""
    from dist_keras_tpu.ops.pallas import fused_bwd_experimental as fused

    v = fused.selfcheck(bh=1, t=16, d=8, block_q=8, block_k=8,
                        dtype=jnp.float32, interpret=True)
    assert v.status == "mismatch"
    assert v.err is not None and v.err > 1e-3


def test_selfcheck_interpret_single_kv_block_exact():
    from dist_keras_tpu.ops.pallas import fused_bwd_experimental as fused

    v = fused.selfcheck(bh=1, t=16, d=8, block_q=8, block_k=16,
                        dtype=jnp.float32, interpret=True)
    assert v.status == "exact"
    assert v.ok is True and v.err <= 1e-6


def test_fused_routing_off_by_default(monkeypatch):
    import importlib

    # the package re-exports the flash_attention FUNCTION under the
    # same name, shadowing the submodule on attribute imports
    fa = importlib.import_module(
        "dist_keras_tpu.ops.pallas.flash_attention")
    monkeypatch.delenv("DK_FUSED_BWD", raising=False)
    q, k, v = _qkv()
    called = []
    orig = fa._fused_bwd_graduated

    def spy(*a, **kw):
        out = orig(*a, **kw)
        called.append(out)
        return out

    monkeypatch.setattr(fa, "_fused_bwd_graduated", spy)
    jax.grad(lambda a: jnp.sum(fa.flash_attention(
        a, k, v, block_q=8, block_k=8, interpret=True) ** 2))(q)
    assert called == [False]


def test_fused_routing_fallback_and_graduation(monkeypatch, tmp_path):
    """DK_FUSED_BWD=1: a 2-kv-block interpret shape REJECTS (typed
    fallback + fused_bwd_rejected event, grads equal the reference); a
    1-kv-block shape GRADUATES (fused serves, grads still equal)."""
    import json

    from dist_keras_tpu.observability import events
    from dist_keras_tpu.ops.attention import attention
    from dist_keras_tpu.ops.pallas import fused_bwd_experimental as fused
    from dist_keras_tpu.ops.pallas.flash_attention import flash_attention

    monkeypatch.setenv("DK_FUSED_BWD", "1")
    monkeypatch.setenv("DK_OBS_DIR", str(tmp_path))
    events.reset()
    fused.clear_verdicts()
    try:
        q, k, v = _qkv()
        ref = jax.grad(lambda a, b, c: jnp.sum(attention(a, b, c) ** 2),
                       argnums=(0, 1, 2))(q, k, v)
        for block_k in (8, 16):
            got = jax.grad(
                lambda a, b, c, bk=block_k: jnp.sum(flash_attention(
                    a, b, c, block_q=8, block_k=bk,
                    interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
            for g, r in zip(got, ref):
                np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                           atol=2e-4, rtol=1e-3)
        statuses = sorted(vv.status for vv in fused._VERDICTS.values())
        assert statuses == ["exact", "mismatch"]
        kinds = []
        for name in os.listdir(tmp_path):
            if name.startswith("events-"):
                with open(tmp_path / name) as f:
                    kinds += [json.loads(ln).get("kind") for ln in f
                              if ln.strip()]
        assert "fused_bwd_rejected" in kinds
    finally:
        events.reset()
        fused.clear_verdicts()


def test_fused_verdict_cached_one_parity_run(monkeypatch):
    from dist_keras_tpu.ops.pallas import fused_bwd_experimental as fused

    fused.clear_verdicts()
    calls = []
    orig = fused.selfcheck

    def spy(*a, **kw):
        calls.append(kw)
        return orig(*a, **kw)

    monkeypatch.setattr(fused, "selfcheck", spy)
    try:
        for _ in range(3):
            v = fused.graduate(1, 16, 16, 8, jnp.float32, True, 8, 16,
                               interpret=True)
        assert v.status == "exact"
        assert len(calls) == 1  # parity ran ONCE, then the cache served
    finally:
        fused.clear_verdicts()


def test_fused_offsets_never_graduate():
    from dist_keras_tpu.ops.pallas import fused_bwd_experimental as fused

    fused.clear_verdicts()
    v = fused.graduate(1, 16, 16, 8, jnp.float32, True, 8, 16,
                       q_offset=16, interpret=True)
    assert v.status == "unverifiable"
    assert "offset" in v.reason
    fused.clear_verdicts()


# ---------------------------------------------------------------------
# DK_PS_COMPRESS (ps/compress.py + worker/server)
# ---------------------------------------------------------------------
def test_parse_spec_valid_and_malformed():
    from dist_keras_tpu.ps import compress

    assert compress.parse_spec(None) is None
    assert compress.parse_spec("") is None
    # the uniform boolean-off spellings disable, never parse as codecs
    for off in ("0", "off", "no", "false", "OFF"):
        assert compress.parse_spec(off) is None
    assert compress.parse_spec("fp16")["codec"] == "fp16"
    s = compress.parse_spec("int8@0.25")
    assert s["codec"] == "int8" and s["topk"] == 0.25
    for bad in ("gzip", "int4", "int8@0", "int8@2", "int8@x"):
        with pytest.raises(ValueError):
            compress.parse_spec(bad)


def test_codec_roundtrip_bounds_and_bytes():
    from dist_keras_tpu.ps import compress

    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(size=(256, 64)).astype(np.float32),
            "rng": np.zeros((), np.int32)}
    raw = compress.payload_nbytes(tree)
    for spec_s, ratio_floor, tol in (("fp16", 1.9, 1e-3),
                                     ("int8", 2.0, 1e-2)):
        spec = compress.parse_spec(spec_s)
        wire = compress.encode_tree(tree, spec)
        assert compress.is_encoded(wire)
        dec = compress.decode_tree(wire)
        amax = np.max(np.abs(tree["w"]))
        assert np.max(np.abs(dec["w"] - tree["w"])) <= tol * amax
        assert raw / compress.payload_nbytes(wire) >= ratio_floor
        # int leaves decode to the zeros the uncompressed path sends
        assert np.asarray(dec["rng"]).item() == 0


def test_topk_keeps_largest_magnitudes():
    from dist_keras_tpu.ps import compress

    x = np.array([[0.1, -5.0, 0.2, 4.0, -0.3, 0.05, 3.0, -0.01]],
                 np.float32)
    wire = compress.encode_tree({"w": x},
                                compress.parse_spec("fp16@0.375"))
    dec = compress.decode_tree(wire)["w"]
    nz = np.flatnonzero(dec)
    assert set(nz.tolist()) == {1, 3, 6}  # the 3 largest |values|
    assert np.allclose(dec[0, [1, 3, 6]], x[0, [1, 3, 6]], atol=1e-2)


def test_topk_values_align_with_sorted_indices():
    """Regression (round-19 drive): the stored values must be gathered
    with the SAME (sorted) index order the record ships — a mismatch
    scatters every kept value to the wrong position and silently
    destroys convergence.  Also pins the leaf-sized index dtype."""
    from dist_keras_tpu.ps import compress

    rng = np.random.default_rng(3)
    x = rng.normal(size=(64, 32)).astype(np.float32)
    wire = compress.encode_tree({"w": x},
                                compress.parse_spec("int8@0.5"))
    rec = wire["leaves"]["w"]
    assert rec["idx"].dtype == np.uint16  # 2048 elements <= 64Ki
    flat = x.reshape(-1)
    got = np.asarray(rec["values"], np.float32) * rec["scale"]
    np.testing.assert_allclose(
        got, flat[rec["idx"].astype(np.int64)],
        atol=float(rec["scale"]))
    big = rng.normal(size=(2**16 + 8,)).astype(np.float32)
    wire2 = compress.encode_tree({"w": big},
                                 compress.parse_spec("fp16@0.1"))
    assert wire2["leaves"]["w"]["idx"].dtype == np.uint32


def test_error_feedback_residual_identity():
    from dist_keras_tpu.ps import compress

    rng = np.random.default_rng(1)
    delta = {"w": rng.normal(size=(64,)).astype(np.float32)}
    spec = compress.parse_spec("int8@0.25")
    wire = compress.encode_tree(delta, spec)
    residual = compress.residual_update(delta, wire)
    decoded = compress.decode_tree(wire)
    # decoded + residual == the delta that was meant to ship
    np.testing.assert_allclose(decoded["w"] + residual["w"], delta["w"],
                               atol=1e-6)


def test_decode_malformed_record_typed():
    from dist_keras_tpu.ps import compress

    with pytest.raises(ValueError):
        compress.decode_tree({"__dk_ps_codec__": "int8",
                              "leaves": {"w": {"kind": "huffman"}}})


def test_ps_encode_fault_point_typed():
    from dist_keras_tpu.ps import compress

    with faults.armed("ps.encode"):
        with pytest.raises(FaultInjected):
            compress.encode_tree({"w": np.ones(4, np.float32)},
                                 compress.parse_spec("int8"))


def test_compressed_worker_end_to_end(blobs):
    """A compressed worker against a live server: completes, decodes
    server-side (the center moves), >= 2x byte reduction, and the
    center still learns the task."""
    from dist_keras_tpu.ps import PSServer, PSWorkerTrainer

    srv = PSServer(params=_model().params, port=0, window=4)
    srv.start()
    try:
        addr = f"{srv.address[0]}:{srv.address[1]}"
        t = PSWorkerTrainer(
            _model(), server_addr=addr, communication_window=4,
            worker_optimizer="sgd",
            optimizer_kwargs={"learning_rate": 0.05}, batch_size=16,
            num_epoch=4, label_col="label_encoded", seed=1,
            compress="int8")
        model = t.train(blobs)
        assert len(t.commit_log) > 0
        assert t.commit_bytes["raw"] / t.commit_bytes["wire"] >= 2.0
        from dist_keras_tpu.data import (AccuracyEvaluator,
                                         LabelIndexTransformer,
                                         ModelPredictor)

        pred = ModelPredictor(model, features_col="features")\
            .predict(blobs)
        idx = LabelIndexTransformer(input_col="prediction")\
            .transform(pred)
        acc = AccuracyEvaluator(prediction_col="prediction_index",
                                label_col="label").evaluate(idx)
        assert acc > 0.9
    finally:
        srv.close()


def test_worker_ctor_rejects_malformed_spec():
    from dist_keras_tpu.ps import PSWorkerTrainer

    with pytest.raises(ValueError):
        PSWorkerTrainer(_model(), server_addr="h:1", compress="zstd")


def test_compress_knob_resolved_at_train(monkeypatch):
    from dist_keras_tpu.ps import compress

    monkeypatch.setenv("DK_PS_COMPRESS", "fp16@0.5")
    spec = compress.resolve_spec(None)
    assert spec["codec"] == "fp16" and spec["topk"] == 0.5
    # explicit argument wins over the env
    assert compress.resolve_spec("int8")["codec"] == "int8"
