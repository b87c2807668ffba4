"""Benchmark suite: samples/sec/chip + MFU for the BASELINE.md configs.

Driver contract: the LAST JSON line on stdout is the record.  The line
is (re)printed after EVERY config completes — a driver timeout or
SIGTERM mid-run still leaves a valid record holding every config
measured so far (round 4 lost its entire perf record to a timeout with
the old print-once-at-the-end structure; BENCH_r04.json rc=124,
parsed=null).  Top-level keys keep the driver contract
(``metric/value/unit/vs_baseline`` = the headline ADAG MNIST-CNN
config); ``configs`` carries the full per-config list:

  {"metric": ..., "value": N, "unit": "samples/sec/chip",
   "vs_baseline": N, "partial": bool, "configs": [
      {"name": ..., "samples_per_sec_per_chip": N, "mfu": N,
       "flops_per_sample": N, "vs_baseline": N|null}, ...]}

Budget: ``BENCH_BUDGET_S`` (default 1400 s) bounds the run.  Configs
are ordered headline-first / reference-parity-first / slowest-last;
past 50% of the budget the remaining configs downshift to median-of-3,
and once the budget is exhausted the tail configs are skipped (each
records ``{"skipped": "budget"}``).  SIGTERM/SIGINT/atexit all flush
the current line, so the record survives however the driver ends us.

Configs (all six BASELINE.json rows + the new-capability showcases),
in run order:
1. ADAG — MNIST CNN, communication_window=12, bf16 (headline).
2. SingleTrainer — MNIST MLP (1 worker, no PS).
3. AveragingTrainer — MNIST CNN sync DP (per-step lax.cond
   reset/merge hot path vs the windowed family's, same model/batch
   as the ADAG row so the two are directly comparable).
4. AEASGD — ATLAS-Higgs dense classifier (elastic averaging).
5. DOWNPOUR — MNIST CNN, sgd + lr warmup, 8 workers (capped at the
   device count).
6. DynSGD — CIFAR-10 ConvNet (staleness-scaled commits).
7. ADAG streamed-vs-resident — the round-4 streaming input pipeline's
   parity ratio on a compute-dense config (target >= 0.9).
8. Serving — sustained QPS + p50/p99 latency at fixed offered load
   (``dist_keras_tpu.serving``), in a CPU-pinned subprocess.
   The router row rides the same mechanics: /predict p50/p99 DIRECT
   against one backend vs ROUTED through ``RouterServer`` over two,
   plus the worst single-request latency while one backend dies
   mid-stream (the sibling-retry failover blip).
9. Checkpoint-manifest overhead — ``Checkpointer.save`` with vs
   without ``DK_CKPT_VERIFY`` (integrity manifests) + raw SHA-256
   throughput, CPU-pinned subprocess.
10. Async checkpoint save — train-loop save-stall seconds vs payload
   size (64 MB / 256 MB), ``DK_CKPT_ASYNC`` off vs on, with the async
   step verified + promoted (durability-equal) and the one-pass
   incremental-hash write wall; CPU-pinned subprocess.
11. Retrace proxy — CPU-measurable attribution rows (jit retrace +
   dispatch counts, H2D/D2H proxy bytes, data/step/comm/ckpt host
   walls) for a streamed windowed trainer, CPU-pinned subprocess; the
   warm-run retrace delta is the "no steady-state retraces" claim.
12. Reshard restore — restore wall of one promoted world-2 step
   same-world vs through the world-1 elastic resharding path (verify
   every manifest, gather by global index, re-split), CPU-pinned
   subprocess.
13. Transformer — composite dp x tp x sp step (ring + flash attention);
   new capability, no reference counterpart (vs_baseline: null).
14. Long-context — T=32k causal step, flash kernels + remat="mlp";
   reports hardware MFU (attention-aware) AND param-only MFU.

Baseline denominators (measured in this image with Keras 3 + TF CPU
``train_on_batch`` — the identical hot loop a dist-keras Spark executor
runs, reference workers.py:~115; an ideal 8-executor cluster is 8x the
single-core rate with zero Spark/PS overhead, so the comparison favours
the reference; see BASELINE.md):
  MNIST-CNN 1155/core -> 9243;  Higgs-MLP 16537/core -> 132298;
  CIFAR-ConvNet 456/core -> 3646;  MNIST-MLP (SingleTrainer, 1 worker
  vs 1 executor) single-core rate, see BASELINES below.

MFU: executed-FLOPs utilisation — the compiled train step's XLA
cost-analysis FLOPs (forward+backward+optimizer, i.e. everything the
chip actually runs) per sample, times measured samples/sec, over the
chip's bf16 peak.  Peak is looked up from device_kind
(override: BENCH_PEAK_TFLOPS env var); a TPU kind missing from the
table is an error, and only a CPU run reports ``mfu: null``.

Platform: whatever JAX gives this process — no probe, no fallback.  The
record and every row carry ``platform`` / ``device_kind`` /
``n_devices``; a row that raises is recorded as ``{"error": ...}`` and
makes the exit code non-zero.

Method per config: train on synthetic device-resident data with the REAL
trainer (windowed commits, dropout active, f32 master weights); first
.train() compiles (shared executable cache), then MEDIAN-OF-5 timed
runs, reporting the per-run list and the spread (max-min)/median.  The
trainers drain the H2D transfer before starting their clock and drain
the outputs before stopping it (utils/sync.py) — data distribution is
not training.
"""

import atexit
import json
import os
import signal
import sys
import time

import numpy as np

BASELINES = {  # ideal 8-executor Spark/CPU samples/sec (see header)
    "adag_mnist_cnn": 9243.0,
    "aeasgd_higgs_mlp": 132298.0,
    "dynsgd_cifar10": 3646.0,
    "downpour_mnist_cnn": 9243.0,
    # the reference AveragingTrainer runs the identical executor hot
    # loop on the same model (trainers.py:~160), so the same ideal
    # 8-executor denominator applies
    "averaging_mnist_cnn": 9243.0,
    # SingleTrainer is 1 worker vs 1 executor: single-core TF rate
    # (measured in this image 2026-07-30, batch 32)
    "single_mnist_mlp": 9323.0,
}

# Median-of-N cap installed by the budget downshift (None = as asked)
_RUNS_CAP = None


def _obs_emit(kind, **fields):
    """Bench-phase telemetry (observability subsystem), gated on the
    env BEFORE any import: with DK_OBS_DIR unset nothing is imported."""
    if not os.environ.get("DK_OBS_DIR"):
        return
    try:
        from dist_keras_tpu.observability import events

        events.emit(kind, **fields)
    except Exception:  # never let telemetry kill the record
        pass


def _cap_runs(runs):
    return min(runs, _RUNS_CAP) if _RUNS_CAP else runs

_PEAK_BY_KIND = {  # bf16 TFLOP/s per chip
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v4": 275.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
}


def _peak_flops():
    import jax

    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None  # a CPU run reports no utilization
    for key, tf in _PEAK_BY_KIND.items():
        if key.lower() in dev.device_kind.lower():
            return tf * 1e12
    raise ValueError(
        f"no bf16 peak for device_kind {dev.device_kind!r}: add it to "
        f"_PEAK_BY_KIND (known: {sorted(_PEAK_BY_KIND)}) or set "
        "BENCH_PEAK_TFLOPS")


def _cost_flops(compiled):
    """XLA cost-analysis FLOPs of one compiled executable (a compile or
    cost-analysis failure surfaces; it is never reported as mfu: null)."""
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    return float(ca["flops"])


def _step_flops_per_sample(model, batch, x_shape, y_dim, loss, optimizer,
                           compute_dtype):
    """XLA cost-analysis FLOPs of the compiled train step / batch."""
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.ops.losses import get_loss
    from dist_keras_tpu.ops.optimizers import get_optimizer
    from dist_keras_tpu.trainers.step import make_model_step

    step, opt_init = make_model_step(
        model, get_loss(loss), get_optimizer(optimizer), compute_dtype)
    params = model.params
    carry = (params, opt_init(params), jax.random.PRNGKey(0))
    xb = jnp.zeros((batch,) + tuple(x_shape), jnp.float32)
    yb = jnp.zeros((batch, y_dim), jnp.float32)
    comp = jax.jit(step).lower(carry, (xb, yb)).compile()
    return _cost_flops(comp) / batch


def _run_trainer_config(name, make_trainer, ds, batch, flops_per_sample,
                        peak, baseline, runs=5):
    import jax

    runs = _cap_runs(runs)

    # two warm-up runs (shared jit cache): the first compiles, the
    # second warms device-side caches — without it the first TIMED run
    # reads ~20% slow on some configs and pollutes the spread
    make_trainer().train(ds)
    make_trainer().train(ds)
    sps_runs = []
    for _ in range(runs):
        t = make_trainer()
        t.train(ds)
        dt = t.get_training_time()  # drained: excludes H2D, covers compute
        samples = np.asarray(t.get_history()).size * batch
        nchips = min(len(jax.devices()), t.num_workers) if hasattr(
            t, "num_workers") else 1
        sps_runs.append(samples / dt / nchips)
    med = float(np.median(sps_runs))
    spread = (max(sps_runs) - min(sps_runs)) / med if med else None
    mfu = (med * flops_per_sample / peak
           if (peak and flops_per_sample) else None)
    return {
        "name": name,
        "samples_per_sec_per_chip": round(med, 1),
        "n_runs": runs,
        "spread": round(spread, 4) if spread is not None else None,
        "runs": [round(s, 1) for s in sps_runs],
        "flops_per_sample": flops_per_sample,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "vs_baseline": (round(med / baseline, 2)
                        if baseline else None),
    }


def bench_adag_mnist_cnn(peak):
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import mnist_cnn
    from dist_keras_tpu.trainers import ADAG
    from dist_keras_tpu.utils.misc import one_hot
    import jax

    # batch 2048: the round-4 sweep measured MFU 0.20 -> 0.25 going
    # 512 -> 2048 (saturating toward the conv lane-bound ceiling, see
    # BASELINE.md); rows sized so 4 workers still run the window=12
    # config as written (98304 / (4*2048) = 12 steps/worker/epoch)
    batch, steps, epochs = 2048, 48, 128
    rng = np.random.default_rng(0)
    n = batch * steps
    y = rng.integers(0, 10, n)
    ds = Dataset({"features": rng.normal(
        size=(n, 28, 28, 1)).astype(np.float32),
        "label": y, "label_encoded": one_hot(y, 10)})
    workers = min(len(jax.devices()), 4)
    fps = _step_flops_per_sample(mnist_cnn(), batch, (28, 28, 1), 10,
                                 "categorical_crossentropy", "adam",
                                 jnp.bfloat16)
    return _run_trainer_config(
        "adag_mnist_cnn",
        lambda: ADAG(mnist_cnn(), num_workers=workers,
                     communication_window=12, worker_optimizer="adam",
                     batch_size=batch, num_epoch=epochs,
                     label_col="label_encoded",
                     compute_dtype=jnp.bfloat16),
        ds, batch, fps, peak, BASELINES["adag_mnist_cnn"])


def bench_aeasgd_higgs(peak):
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import higgs_mlp
    from dist_keras_tpu.trainers import AEASGD
    from dist_keras_tpu.utils.misc import one_hot

    # 6400 epochs (~800M samples): the tiny MLP runs tens of millions
    # of samples/s, so a short window leaves per-dispatch jitter as a
    # double-digit error bar (round 3's 400-epoch window: 10.7% spread)
    batch, steps, epochs = 1024, 120, 6400
    rng = np.random.default_rng(0)
    n = batch * steps
    y = rng.integers(0, 2, n)
    ds = Dataset({"features": rng.normal(size=(n, 28)).astype(np.float32),
                  "label": y, "label_encoded": one_hot(y, 2)})
    workers = min(len(jax.devices()), 4)
    fps = _step_flops_per_sample(higgs_mlp(), batch, (28,), 2,
                                 "categorical_crossentropy", "adam",
                                 jnp.bfloat16)
    return _run_trainer_config(
        "aeasgd_higgs_mlp",
        lambda: AEASGD(higgs_mlp(), num_workers=workers,
                       communication_window=32, rho=1.0, learning_rate=0.2,
                       worker_optimizer="adam", batch_size=batch,
                       num_epoch=epochs, label_col="label_encoded",
                       compute_dtype=jnp.bfloat16),
        ds, batch, fps, peak, BASELINES["aeasgd_higgs_mlp"], runs=7)


def bench_averaging_mnist_cnn(peak):
    """Sync-DP AveragingTrainer on the ADAG row's exact model/batch/data
    shape: the delta between this row and ``adag_mnist_cnn`` IS the cost
    of the per-step ``lax.cond`` epoch reset/merge hot path
    (averaging.py:85-108) plus the per-epoch pmean — the one trainer
    family that had no perf number before round 5 (VERDICT r4 weak #4).
    Reference counterpart: trainers.py:~160 (driver-side numpy mean)."""
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import mnist_cnn
    from dist_keras_tpu.trainers import AveragingTrainer
    from dist_keras_tpu.utils.misc import one_hot

    batch, steps, epochs = 2048, 48, 128
    rng = np.random.default_rng(0)
    n = batch * steps
    y = rng.integers(0, 10, n)
    ds = Dataset({"features": rng.normal(
        size=(n, 28, 28, 1)).astype(np.float32),
        "label": y, "label_encoded": one_hot(y, 10)})
    workers = min(len(jax.devices()), 4)
    fps = _step_flops_per_sample(mnist_cnn(), batch, (28, 28, 1), 10,
                                 "categorical_crossentropy", "adam",
                                 jnp.bfloat16)
    return _run_trainer_config(
        "averaging_mnist_cnn",
        lambda: AveragingTrainer(mnist_cnn(), num_workers=workers,
                                 worker_optimizer="adam",
                                 batch_size=batch, num_epoch=epochs,
                                 label_col="label_encoded",
                                 compute_dtype=jnp.bfloat16),
        ds, batch, fps, peak, BASELINES["averaging_mnist_cnn"])


def bench_dynsgd_cifar(peak):
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import cifar10_convnet
    from dist_keras_tpu.trainers import DynSGD
    from dist_keras_tpu.utils.misc import one_hot

    batch, steps, epochs = 256, 60, 24
    rng = np.random.default_rng(0)
    n = batch * steps
    y = rng.integers(0, 10, n)
    ds = Dataset({"features": rng.normal(
        size=(n, 32, 32, 3)).astype(np.float32),
        "label": y, "label_encoded": one_hot(y, 10)})
    workers = min(len(jax.devices()), 4)
    fps = _step_flops_per_sample(cifar10_convnet(), batch, (32, 32, 3), 10,
                                 "categorical_crossentropy", "adam",
                                 jnp.bfloat16)
    return _run_trainer_config(
        "dynsgd_cifar10",
        lambda: DynSGD(cifar10_convnet(), num_workers=workers,
                       communication_window=5, worker_optimizer="adam",
                       batch_size=batch, num_epoch=epochs,
                       label_col="label_encoded",
                       compute_dtype=jnp.bfloat16),
        ds, batch, fps, peak, BASELINES["dynsgd_cifar10"])


def bench_downpour_mnist_cnn(peak):
    """BASELINE.json configs[2]: DOWNPOUR SGD, MNIST CNN, lr warmup,
    8 workers (capped at the available device count)."""
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import mnist_cnn
    from dist_keras_tpu.trainers import DOWNPOUR
    from dist_keras_tpu.utils.misc import one_hot

    # batch 2048 (see the ADAG config note); at 8 workers this leaves
    # 6 steps/worker/epoch: window=5 runs as written with 1 step dropped
    batch, steps, epochs = 2048, 48, 128
    rng = np.random.default_rng(0)
    n = batch * steps
    y = rng.integers(0, 10, n)
    ds = Dataset({"features": rng.normal(
        size=(n, 28, 28, 1)).astype(np.float32),
        "label": y, "label_encoded": one_hot(y, 10)})
    workers = min(len(jax.devices()), 8)
    fps = _step_flops_per_sample(mnist_cnn(), batch, (28, 28, 1), 10,
                                 "categorical_crossentropy", "sgd",
                                 jnp.bfloat16)
    return _run_trainer_config(
        "downpour_mnist_cnn",
        lambda: DOWNPOUR(mnist_cnn(), num_workers=workers,
                         communication_window=5, worker_optimizer="sgd",
                         optimizer_kwargs={"learning_rate": 0.05,
                                           "warmup_steps": 120},
                         batch_size=batch, num_epoch=epochs,
                         label_col="label_encoded",
                         compute_dtype=jnp.bfloat16),
        ds, batch, fps, peak, BASELINES["downpour_mnist_cnn"])


def bench_single_mnist_mlp(peak):
    """BASELINE.json configs[0]: SingleTrainer, MNIST MLP, 1 worker."""
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import mnist_mlp
    from dist_keras_tpu.trainers import SingleTrainer
    from dist_keras_tpu.utils.misc import one_hot

    # 768 epochs (~47M samples): the MLP runs ~20M samples/s, so a
    # 192-epoch window is ~0.6 s and per-dispatch jitter reads as a
    # double-digit spread
    batch, steps, epochs = 512, 120, 768
    rng = np.random.default_rng(0)
    n = batch * steps
    y = rng.integers(0, 10, n)
    ds = Dataset({"features": rng.normal(
        size=(n, 784)).astype(np.float32),
        "label": y, "label_encoded": one_hot(y, 10)})
    fps = _step_flops_per_sample(mnist_mlp(), batch, (784,), 10,
                                 "categorical_crossentropy", "adam",
                                 jnp.bfloat16)
    return _run_trainer_config(
        "single_mnist_mlp",
        lambda: SingleTrainer(mnist_mlp(), worker_optimizer="adam",
                              batch_size=batch, num_epoch=epochs,
                              label_col="label_encoded",
                              compute_dtype=jnp.bfloat16),
        ds, batch, fps, peak, BASELINES["single_mnist_mlp"])


def bench_transformer_tp(peak):
    """Composite dp x tp x sp training step (flash attention + ring) on
    whatever mesh the chips allow (1x1x1 on a single chip)."""
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.models.transformer import transformer_config
    from dist_keras_tpu.parallel.transformer_tp import (
        make_tp_mesh,
        make_tp_train_step,
    )

    ndev = len(jax.devices())
    dp, tp, sp = (2, 2, 2) if ndev >= 8 else (1, 1, 1)
    # MXU-sized: head_dim 128 fills the 128-wide lane dimension (the
    # round-2 config's head_dim 32 left 3/4 of the systolic array idle);
    # measured on v5e: d768/h6 0.43 MFU vs d512/h4 0.34 vs d256/h8 0.07
    batch, seq = 16, 2048
    cfg = transformer_config(input_dim=32, seq_len=seq, d_model=768,
                             n_heads=6, n_layers=4, n_classes=2)
    mesh = make_tp_mesh(dp=dp, tp=tp, sp=sp)
    step_factory, init_fn = make_tp_train_step(
        mesh, cfg, causal=True, compute_dtype=jnp.bfloat16)
    params, opt_state = init_fn(0)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, seq, 32)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, batch), jnp.int32)
    fn = step_factory(params, opt_state)

    flops = _cost_flops(fn.lower(params, opt_state, x, y).compile()) / batch

    # warm-up + timed: params feed forward so steps chain (no caching);
    # the clock stops on the last step's UPDATED params, not its loss
    # (which is computed before the optimizer update)
    for _ in range(2):
        params, opt_state, loss = fn(params, opt_state, x, y)
    jax.block_until_ready(params)
    n_steps, reps = 20, _cap_runs(5)
    sps_runs = []
    for _ in range(reps):
        t0 = time.time()
        for _ in range(n_steps):
            params, opt_state, loss = fn(params, opt_state, x, y)
        jax.block_until_ready(params)
        sps_runs.append(n_steps * batch / (time.time() - t0)
                        / (dp * tp * sp))
    med = float(np.median(sps_runs))
    spread = (max(sps_runs) - min(sps_runs)) / med if med else None
    mfu = med * flops / peak if peak else None
    return {
        "name": f"transformer_dp{dp}_tp{tp}_sp{sp}_seq{seq}",
        "samples_per_sec_per_chip": round(med, 1),
        "n_runs": reps,
        "spread": round(spread, 4) if spread is not None else None,
        "runs": [round(s, 1) for s in sps_runs],
        "flops_per_sample": flops,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "vs_baseline": None,  # no reference counterpart (SURVEY §2.3)
    }


def bench_long_context(peak):
    """T=32k causal training step (flash kernels + remat='mlp'), the
    long-context headline.  Reports BOTH MFU conventions: hardware MFU
    counts the causal attention matmuls (half the T^2 square) as useful
    work — flat in T; param-only MFU is the round-3 convention (6N per
    token), which mechanically decays as attention flops grow.  No
    reference counterpart (SURVEY §2.3: upstream has no attention)."""
    import jax
    import jax.numpy as jnp

    from dist_keras_tpu.models.transformer import transformer_config
    from dist_keras_tpu.parallel.transformer_tp import (
        make_tp_mesh,
        make_tp_train_step,
    )

    B, T, L, DM, H = 1, 32768, 4, 768, 6
    cfg = transformer_config(input_dim=32, seq_len=T, d_model=DM,
                             n_heads=H, n_layers=L, n_classes=2)
    mesh = make_tp_mesh(1, 1, 1)
    sf, init_fn = make_tp_train_step(mesh, cfg, causal=True,
                                     compute_dtype=jnp.bfloat16,
                                     remat="mlp")
    params, opt_state = init_fn(0)
    fn = sf(params, opt_state)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, T, 32)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, B), jnp.int32)

    for _ in range(2):  # compile + warm
        params, opt_state, loss = fn(params, opt_state, x, y)
    jax.block_until_ready(params)
    n_steps, reps, runs = 10, _cap_runs(5), []
    for _ in range(reps):
        t0 = time.time()
        for _ in range(n_steps):
            params, opt_state, loss = fn(params, opt_state, x, y)
        jax.block_until_ready(params)
        runs.append(n_steps * B * T / (time.time() - t0))
    med = float(np.median(runs))
    spread = (max(runs) - min(runs)) / med if med else None
    # analytic useful flops: causal attention at half the square + dense
    attn = L * (4 * T * T * DM / 2) * 3.5          # fwd + 2.5x bwd
    dense = L * T * (2 * DM * 4 * DM * 2 + 2 * DM * DM * 4) * 3
    hw_flops_per_token = (attn + dense) / T
    n_params = 28.8e6
    return {
        "name": f"long_context_seq{T}_remat_mlp",
        "tokens_per_sec_per_chip": round(med, 1),
        "n_runs": reps,
        "spread": round(spread, 4) if spread is not None else None,
        "runs": [round(s, 1) for s in runs],
        "hw_mfu": (round(med * hw_flops_per_token / peak, 4)
                   if peak else None),
        "param_mfu": (round(med * 6 * n_params / peak, 4)
                      if peak else None),
        "vs_baseline": None,  # no reference counterpart (SURVEY §2.3)
    }


def bench_adag_streamed(peak):
    """ADAG with the round-4 streaming input pipeline vs whole-run
    resident data, on a compute-dense transformer-scale MLP: proves the
    double-buffered ChunkFeed hides the H2D stream under compute (the
    dataset no longer needs to fit in HBM).  Reported as the
    streamed/resident throughput ratio; the parity target is >= 0.9.

    Config note: the model is deep/wide on a small feature dim and the
    feed is uint8 cast-late (``data_dtype=None``), so the training data
    rate (bytes/s) sits far below the host's H2D bandwidth.
    """
    import jax.numpy as jnp

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.models import mnist_mlp
    from dist_keras_tpu.trainers import ADAG
    from dist_keras_tpu.utils.misc import one_hot

    rng = np.random.default_rng(0)
    n, feat = 1048576, 8
    hidden = (4096,) * 6
    x = rng.integers(0, 256, size=(n, feat)).astype(np.uint8)
    yv = rng.integers(0, 10, size=n)
    ds = Dataset({"features": x, "label": yv,
                  "label_encoded": one_hot(yv, 10, dtype=np.uint8)})
    common = dict(num_workers=1, worker_optimizer="sgd",
                  optimizer_kwargs={"learning_rate": 0.01},
                  batch_size=512, num_epoch=2, label_col="label_encoded",
                  communication_window=8, compute_dtype=jnp.bfloat16,
                  data_dtype=None)

    def run(**kw):
        t = ADAG(mnist_mlp(hidden=hidden, input_dim=feat, num_classes=10),
                 **common, **kw)
        t.train(ds)     # compile + warm
        t2 = ADAG(mnist_mlp(hidden=hidden, input_dim=feat,
                            num_classes=10), **common, **kw)
        t2.train(ds)
        return n * common["num_epoch"] / t2.get_training_time()

    resident = run()
    streamed = run(stream_chunk_windows=32)
    return {
        "name": "adag_streamed_vs_resident",
        "resident_samples_per_sec": round(resident, 1),
        "streamed_samples_per_sec": round(streamed, 1),
        "streamed_over_resident": round(streamed / resident, 4),
        "vs_baseline": None,  # internal parity ratio, not a reference row
    }


# a CPU-pinned child's rows are stamped with where the CHILD ran (one
# CPU device: XLA_FLAGS is stripped), not with this process's device
_CPU_CHILD = {"platform": "cpu", "device_kind": "cpu", "n_devices": 1}


def _run_cpu_worker(name, argv=None, source=None, args=(),
                    strip_prefixes=(), timeout_s=300):
    """Run one CPU-pinned bench worker in a subprocess and parse the last
    JSON line of its stdout into a named record — the shared mechanics
    of every host-side row (``bench_serving``, ``bench_retrace_proxy``,
    ``bench_ckpt_manifest``).  ``argv`` runs as-is (module workers);
    ``source`` is written to a temp script first (inline workers, with
    ``args`` appended).  The telemetry/fault/alert knobs of the OUTER
    process are ALWAYS stripped — an inherited ``DK_OBS_SAMPLE_S`` would
    run the sampler inside a measured latency, an inherited
    ``DK_METRICS_PORT`` would fight the live exporter for its socket,
    and an injected fault or alert webhook must never cross into a
    measurement; ``strip_prefixes`` adds each row's own extras. Timeouts
    and non-zero exits return typed error records, never raise."""
    import subprocess
    import tempfile

    strip = ("DK_OBS", "DK_FAULTS", "DK_METRICS", "DK_WATCHDOG",
             "DK_ALERT") + tuple(strip_prefixes)
    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS" and not k.startswith(strip)}
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = (repo + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    script = None
    if source is not None:
        with tempfile.NamedTemporaryFile(
                "w", suffix=".py", delete=False) as f:
            f.write(source)
            script = f.name
        argv = [script, *[str(a) for a in args]]
    try:
        proc = subprocess.run(
            [sys.executable, *argv],
            capture_output=True, text=True, timeout=timeout_s, env=env,
            cwd=repo)
    except subprocess.TimeoutExpired:
        return {"name": name,
                "error": f"{name} timed out after {timeout_s}s"}
    finally:
        if script is not None:
            os.unlink(script)
    rec = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
            break
        except ValueError:
            continue
    if proc.returncode != 0 or rec is None:
        return {"name": name,
                "error": f"rc={proc.returncode}: "
                         + (proc.stderr or proc.stdout)[-200:]}
    rec["name"] = name
    rec.update(_CPU_CHILD)
    rec["vs_baseline"] = None  # host-side rows have no reference rate
    return rec


def bench_serving(peak=None, timeout_s=300):
    """Online-serving benchmark: sustained QPS + p50/p99 latency at
    fixed offered load (``dist_keras_tpu.serving.bench``), run in a
    CPU-PINNED SUBPROCESS: the child sets ``JAX_PLATFORMS=cpu`` and
    never needs the chip this process holds.
    No reference counterpart for ``vs_baseline`` (SURVEY §2.4 is
    pull-based streaming, not serving)."""
    return _run_cpu_worker(
        "serving_cpu_offered_load",
        argv=["-m", "dist_keras_tpu.serving.bench",
              "--qps", "400", "--seconds", "4"],
        timeout_s=timeout_s)


def bench_decode_serving(peak=None, timeout_s=300):
    """Decode-serving benchmark: tokens/sec, time-to-first-token
    p50/p99 and KV-page occupancy under paced open-loop generation
    load against the continuous-batching ``DecodeEngine``
    (``dist_keras_tpu.serving.bench --decode``), in the same CPU-pinned
    subprocess harness as ``bench_serving``.  No reference
    counterpart for ``vs_baseline`` (the lineage is training-side)."""
    return _run_cpu_worker(
        "decode_serving",
        argv=["-m", "dist_keras_tpu.serving.bench", "--decode",
              "--rps", "40", "--seconds", "4"],
        timeout_s=timeout_s)


def bench_decode_survivability(peak=None, timeout_s=300):
    """Decode survivability benchmark: a 2-replica ``DecodeEngine``
    under ~2x offered overload with a batch/interactive priority mix
    loses replica 0 a third of the way in
    (``dist_keras_tpu.serving.bench --survivability``).  Reports the
    recovered-sequence latency tax (teacher-forced replay is not
    free), interactive p99 across the kill, the brownout shed rate,
    and the ledger the gate enforces (zero errors, zero leaked
    pages).  Same CPU-pinned subprocess harness as the other serving
    rows; no reference counterpart for ``vs_baseline``."""
    return _run_cpu_worker(
        "decode_survivability",
        argv=["-m", "dist_keras_tpu.serving.bench", "--survivability",
              "--seconds", "4"],
        timeout_s=timeout_s)


# The router bench worker: the same single-row /predict measured
# DIRECT against one backend vs ROUTED through a RouterServer over two
# (the fabric hop's overhead), then a continuous routed stream with one
# backend dying mid-flight — the failover "blip" is the worst
# single-request latency while the router burns its sibling retry and
# evicts (every request still 200: the typed-503 path never fires with
# a live sibling).  All in-process HTTP over loopback, CPU-pinned.
_ROUTER_BENCH_WORKER = r"""
import json, os, sys, threading, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import urllib.request
import numpy as np
from dist_keras_tpu.models import mnist_mlp
from dist_keras_tpu.serving import (
    RouterServer, ServingEngine, ServingServer)

rng = np.random.default_rng(0)
rows = rng.normal(size=(8, 4)).astype(np.float32)
body = json.dumps({"rows": rows[:1].tolist()}).encode("utf-8")


def make_backend():
    eng = ServingEngine(mnist_mlp(hidden=(8,), input_dim=4,
                                  num_classes=3),
                        replicas=1, batch_ladder=(1, 8),
                        max_latency_s=0.001, max_queue=1024)
    for r in (1, 8):
        eng.predict(rows[:r], timeout_s=120)  # warm the jit ladder
    srv = ServingServer(eng, port=0)
    srv.start()
    return srv


def post(addr, n, timeout=15):
    lats, codes = [], []
    for _ in range(n):
        req = urllib.request.Request(
            "http://%s/predict" % addr, data=body, method="POST",
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                resp.read()
                codes.append(resp.status)
        except Exception:
            codes.append(-1)
        lats.append((time.perf_counter() - t0) * 1000.0)
    return lats, codes


def pct(lats, q):
    return round(float(np.percentile(np.asarray(lats), q)), 3)


N = 150
b0, b1 = make_backend(), make_backend()
a0 = "%s:%d" % b0.address
a1 = "%s:%d" % b1.address
post(a0, 20)                                   # connection warmup
direct, dcodes = post(a0, N)

router = RouterServer([a0, a1], port=0, probe_s=0.1,
                      forward_timeout_s=10.0, fail_threshold=2,
                      stale_s=1.0, readmit_checks=2)
ra = "%s:%d" % router.start()
time.sleep(0.3)                                # first probe rounds
post(ra, 20)
routed, rcodes = post(ra, N)

blat, bcodes = [], []
stop = threading.Event()


def blip_load():
    while not stop.is_set():
        lat, c = post(ra, 1)
        blat.extend(lat)
        bcodes.extend(c)


t = threading.Thread(target=blip_load)
t.start()
time.sleep(0.5)
b0._stop_listener()                  # abrupt death: connect refused
time.sleep(1.0)                      # retry + evict + steady sibling
stop.set()
t.join(timeout=60)

router.close()
b1.close()
print(json.dumps({
    "requests": N,
    "direct_p50_ms": pct(direct, 50),
    "direct_p99_ms": pct(direct, 99),
    "routed_p50_ms": pct(routed, 50),
    "routed_p99_ms": pct(routed, 99),
    "routed_over_direct_p50": round(pct(routed, 50)
                                    / max(pct(direct, 50), 1e-9), 3),
    "direct_errors": sum(1 for c in dcodes if c != 200),
    "routed_errors": sum(1 for c in rcodes if c != 200),
    "failover_requests": len(blat),
    "failover_non200": sum(1 for c in bcodes if c != 200),
    "failover_blip_ms": pct(blat, 100) if blat else None,
}))
"""


def bench_router(peak=None, timeout_s=300):
    """Serving-fabric router row (``router_overhead``): p50/p99 of the
    same single-row ``/predict`` measured DIRECT against one backend vs
    ROUTED through :class:`RouterServer` over two, plus the worst-case
    single-request latency while one backend dies mid-stream (the
    sibling-retry failover blip, expected zero non-200s).  CPU-pinned
    subprocess like every host-side row.  No reference counterpart ->
    ``vs_baseline`` stays null."""
    return _run_cpu_worker(
        "router_overhead", source=_ROUTER_BENCH_WORKER,
        strip_prefixes=("DK_SERVE", "DK_ROUTE", "DK_COORD"),
        timeout_s=timeout_s)


# The retrace-proxy worker: CPU-measurable attribution rows for the
# device-only perf claims: jit retrace count (via the jax.monitoring
# listener), framework dispatch count, H2D/D2H proxy bytes and the
# per-phase host walls for
# a windowed trainer (ADAG, streamed so the ChunkFeed H2D path runs).
# Two back-to-back runs: the cold one owns the compiles; the warm one
# is the steady-state claim — its retrace delta SHOULD be 0 (recorded,
# not asserted: the bench records, gates assert).
_RETRACE_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from dist_keras_tpu.data import Dataset
from dist_keras_tpu.models import mnist_mlp
from dist_keras_tpu.observability import metrics, perf
from dist_keras_tpu.trainers import ADAG
from dist_keras_tpu.utils.misc import one_hot

perf.install()
rng = np.random.default_rng(0)
n = 256 * 16
y = rng.integers(0, 2, n)
ds = Dataset({"features": rng.normal(size=(n, 32)).astype(np.float32),
              "label": y, "label_encoded": one_hot(y, 2)})


def make():
    return ADAG(mnist_mlp(hidden=(64,), input_dim=32, num_classes=2),
                num_workers=1, communication_window=4, batch_size=256,
                num_epoch=8, label_col="label_encoded",
                stream_chunk_windows=2)


KEYS = ("perf.retraces", "perf.dispatches", "perf.h2d_bytes",
        "perf.d2h_bytes")


def counters():
    c = metrics.snapshot()["counters"]
    return {k: c.get(k, 0) for k in KEYS}


def phase_walls():
    h = metrics.snapshot()["histograms"]
    return {k[len("perf.phase."):]: {"count": v["count"],
                                     "total_s": round(v["total"], 4)}
            for k, v in h.items() if k.startswith("perf.phase.")}


c0 = counters()
make().train(ds)                       # cold: owns the compiles
c1 = counters()
t = make()
t.train(ds)                            # warm: the steady-state claim
c2 = counters()
print(json.dumps({
    "retraces_cold": c1["perf.retraces"] - c0["perf.retraces"],
    "retraces_warm": c2["perf.retraces"] - c1["perf.retraces"],
    "dispatches_warm": c2["perf.dispatches"] - c1["perf.dispatches"],
    "h2d_bytes_warm": c2["perf.h2d_bytes"] - c1["perf.h2d_bytes"],
    "d2h_bytes_warm": c2["perf.d2h_bytes"] - c1["perf.d2h_bytes"],
    "train_s_warm": round(t.get_training_time(), 4),
    "phase_walls": phase_walls(),
}))
"""


def bench_retrace_proxy(peak=None, timeout_s=300):
    """CPU-proxy attribution row (``bench_retrace_proxy``): retrace +
    dispatch counts, transfer-byte proxies and the data/step/comm/ckpt
    host walls for a streamed windowed trainer, in a CPU-pinned
    subprocess.  An attribution row, not a
    reference rate — ``vs_baseline`` stays null."""
    return _run_cpu_worker(
        "bench_retrace_proxy", source=_RETRACE_WORKER,
        timeout_s=timeout_s)


# The manifest-overhead worker: measures Checkpointer.save wall with
# integrity manifests ON vs OFF (the DK_CKPT_VERIFY knob — exactly the
# opt-out an operator would flip) on a fixed-size host pytree, plus the
# isolated hash cost of the committed payload.  Runs CPU-pinned in a
# subprocess (a pure host-side measurement, like bench_serving).
_CKPT_MANIFEST_WORKER = r"""
import json, os, statistics, sys, tempfile, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from dist_keras_tpu.checkpoint import Checkpointer, build_manifest

# async pinned OFF: this row measures the SYNCHRONOUS write's
# DK_CKPT_VERIFY hashing cost (with async on, save() returns after the
# snapshot and the timer would read enqueue stall, not hash cost —
# the async pipeline has its own ckpt_async_save row)
os.environ["DK_CKPT_ASYNC"] = "0"
mb, reps = int(sys.argv[1]), int(sys.argv[2])
state = {"w": np.random.default_rng(0).standard_normal(
    mb * 1024 * 1024 // 8)}
work = tempfile.mkdtemp(prefix="dk_bench_manifest_")


def timed_save(verify, rep):
    os.environ["DK_CKPT_VERIFY"] = "1" if verify else "0"
    d = os.path.join(work, ("v" if verify else "n") + str(rep))
    t0 = time.perf_counter()
    Checkpointer(d, max_to_keep=2).save(1, state)
    return time.perf_counter() - t0


timed_save(False, "warm")  # discarded: the first save pays one-time
#                            orbax/import costs neither side should own
# interleaved off/on pairs so fs-cache drift hits both sides equally
plain, verified = [], []
for rep in range(reps):
    plain.append(timed_save(False, rep))
    verified.append(timed_save(True, rep))
t0 = time.perf_counter()
build_manifest(os.path.join(work, "n0", "step_00000001"))
hash_s = time.perf_counter() - t0
import shutil
shutil.rmtree(work, ignore_errors=True)
p, v = statistics.median(plain), statistics.median(verified)
print(json.dumps({
    "payload_mb": mb,
    "save_s_plain": round(p, 4),
    "save_s_verified": round(v, 4),
    "manifest_overhead_s": round(v - p, 4),
    "manifest_overhead_frac": round((v - p) / p, 4) if p else None,
    "hash_mb_per_s": round(mb / hash_s, 1) if hash_s else None,
    "reps": reps,
}))
"""


# The reshard-restore worker: restore wall of the SAME promoted bytes
# through the two load paths — a same-world per-rank restore (world-2
# rank 0 reading its own payload) vs the elastic resharding restore
# (world-1 reading BOTH payloads, verifying each manifest, gathering
# the sharded leaves by global index and re-splitting) — so the price
# of "run continues smaller" is tracked per round, not asserted once.
# CPU-pinned subprocess like every host-side row.
_RESHARD_WORKER = r"""
import json, os, statistics, sys, tempfile, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from dist_keras_tpu.checkpoint import Checkpointer
from dist_keras_tpu.resilience import elastic

mb, reps = int(sys.argv[1]), int(sys.argv[2])
n = mb * 1024 * 1024 // 8
g = {"w": np.random.default_rng(0).standard_normal(n),
     "i": np.int64(1)}
dims = {"w": 0, "i": None}
work = tempfile.mkdtemp(prefix="dk_bench_reshard_")
ck_dir = os.path.join(work, "ck")
# a world-2 two-phase save (non-leader publishes its marker first, the
# leader's save then promotes) of the sharded halves
for rank in (1, 0):
    local = {"w": elastic.split_leaf(g["w"], 0, 2, rank),
             "i": g["i"]}
    # wait(): the async default hands the write to a background
    # thread, and the restores below use FRESH Checkpointer instances
    # (no join-on-read coverage) — the promotion must be durable first
    Checkpointer(ck_dir, rank=rank, world=2).save(
        1, local, shard_specs=dims).wait(timeout_s=60)

same_ck = Checkpointer(ck_dir, rank=0, world=2)
reshard_ck = Checkpointer(ck_dir, rank=0, world=1)
same, reshard = [], []
same_ck.restore()  # warm both paths' one-time import/fs costs
reshard_ck.restore()
for _ in range(reps):
    t0 = time.perf_counter()
    same_ck.restore()
    same.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    step, st = reshard_ck.restore()
    reshard.append(time.perf_counter() - t0)
assert np.array_equal(np.asarray(st["w"]), g["w"])
import shutil
shutil.rmtree(work, ignore_errors=True)
s, r = statistics.median(same), statistics.median(reshard)
print(json.dumps({
    "payload_mb": mb,
    "saved_world": 2,
    "restore_s_same_world": round(s, 4),
    "restore_s_reshard": round(r, 4),
    "reshard_overhead_s": round(r - s, 4),
    "reshard_over_same": round(r / s, 4) if s else None,
    "reps": reps,
}))
"""


def bench_reshard_restore(peak=None, mb=64, reps=5, timeout_s=300):
    """Elastic-restore cost: the wall of restoring one promoted
    world-2 step same-world (per-rank payload read) vs through the
    world-1 resharding path (verify every manifest, gather by global
    index, re-split) — the recovery-latency price of an elastic
    resize, measured per round.  No ``vs_baseline`` (the reference has
    no elasticity story beyond Spark partition re-runs)."""
    return _run_cpu_worker(
        "reshard_restore", source=_RESHARD_WORKER,
        args=(mb, reps),
        strip_prefixes=("DK_CKPT", "DK_COORD", "DK_ELASTIC"),
        timeout_s=timeout_s)


# The async-save worker: the train-loop SAVE STALL (wall spent inside
# Checkpointer.save before control returns to the loop) sync vs async
# on fixed-size host pytrees, plus the async write wall — and the
# durability check: after handle.wait() the async step must verify
# "ok" and be the latest PROMOTED step (async is a latency win, never
# a durability downgrade).  CPU-pinned subprocess like every
# host-side row.  argv: mb... reps
_CKPT_ASYNC_WORKER = r"""
import json, os, statistics, sys, tempfile, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from dist_keras_tpu.checkpoint import Checkpointer

sizes, reps = [int(a) for a in sys.argv[1:-1]], int(sys.argv[-1])
rows = []
for mb in sizes:
    # jax-array leaves, like a real training state: the boundary
    # snapshot of an IMMUTABLE device buffer needs no defensive copy
    # (host-numpy leaves are copied instead — the aliasing-safety
    # path tests/test_async_ckpt.py pins)
    w = jnp.asarray(np.random.default_rng(0).standard_normal(
        mb * 1024 * 1024 // 8))
    w.block_until_ready()
    state = {"w": w, "step": np.int64(1)}
    work = tempfile.mkdtemp(prefix="dk_bench_async_%d_" % mb)

    def run(async_on, rep):
        os.environ["DK_CKPT_ASYNC"] = "1" if async_on else "0"
        d = os.path.join(work, ("a" if async_on else "s") + str(rep))
        ck = Checkpointer(d, max_to_keep=2)
        t0 = time.perf_counter()
        h = ck.save(1, state)
        stall = time.perf_counter() - t0   # what the loop waited
        h.wait(timeout_s=180)
        total = time.perf_counter() - t0   # snapshot + write + commit
        return stall, total, ck.verify(1), ck.latest_step()

    run(False, "warm")  # discarded: one-time import/fs costs
    sync_stall, async_stall, async_total = [], [], []
    all_verified = True   # EVERY async rep must verify + promote
    for rep in range(reps):
        s, _t, _v, _l = run(False, rep)
        sync_stall.append(s)
        s, t, verified, promoted = run(True, rep)
        all_verified = all_verified and (
            verified == "ok" and promoted == 1)
        async_stall.append(s)
        async_total.append(t)
    import shutil
    shutil.rmtree(work, ignore_errors=True)
    ss = statistics.median(sync_stall)
    sa = statistics.median(async_stall)
    rows.append({
        "payload_mb": mb,
        "save_stall_s_sync": round(ss, 4),
        "save_stall_s_async": round(sa, 4),
        "stall_reduction_x": round(ss / sa, 1) if sa else None,
        "write_s_async_total": round(statistics.median(async_total), 4),
        "async_step_verified": all_verified,
    })
print(json.dumps({"reps": reps, "rows": rows}))
"""


def bench_ckpt_async_save(peak=None, sizes=(64, 256), reps=3,
                          timeout_s=360):
    """Async-checkpoint-pipeline cost: the train-loop save-stall of
    ``Checkpointer.save`` with ``DK_CKPT_ASYNC`` off vs on (median-of-
    ``reps`` per payload size), with the async step verified AND
    promoted — the tentpole claim is "the loop stops paying for the
    write without giving up 'promoted ⇒ verified'".  No ``vs_baseline``
    (the reference has no checkpointing at all)."""
    return _run_cpu_worker(
        "ckpt_async_save", source=_CKPT_ASYNC_WORKER,
        args=(*sizes, reps), strip_prefixes=("DK_CKPT",),
        timeout_s=timeout_s)


# Differential-checkpoint row: chunk bytes written + save wall vs
# churn fraction.  DK_CKPT_ASYNC=0 so the measured wall IS the write
# (the async row already owns the stall story); DK_CKPT_DIFF=1 with
# 4 MB chunks so churn granularity is 16/64 chunks at 64/256 MB.
# CPU-pinned subprocess like every host-side row.  argv: mb... reps
_DIFF_CKPT_WORKER = r"""
import json, os, shutil, statistics, sys, tempfile, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DK_CKPT_ASYNC"] = "0"
os.environ["DK_CKPT_DIFF"] = "1"
os.environ["DK_CKPT_CHUNK_MB"] = "4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from dist_keras_tpu.checkpoint import Checkpointer

sizes, reps = [int(a) for a in sys.argv[1:-1]], int(sys.argv[-1])
CHURNS = (0.0, 0.25, 1.0)
rows = []
for mb in sizes:
    n = mb * 1024 * 1024 // 8
    work = tempfile.mkdtemp(prefix="dk_bench_diff_%d_" % mb)
    ck = Checkpointer(work, max_to_keep=2)
    w = np.asarray(np.random.default_rng(0).standard_normal(n))
    t0 = time.perf_counter()
    ck.save(1, {"w": w}).wait()
    full_wall = time.perf_counter() - t0
    full_bytes = ck.last_diff_stats["bytes_written"]
    step = 1
    for churn in CHURNS:
        walls, written = [], []
        for rep in range(reps):
            step += 1
            if churn:
                # churn the FIRST fraction of elements: exactly
                # ceil(churn * chunks) chunk identities change
                w = w.copy()
                w[: int(n * churn)] += 1.0
            t0 = time.perf_counter()
            ck.save(step, {"w": w}).wait()
            walls.append(time.perf_counter() - t0)
            written.append(ck.last_diff_stats["bytes_written"])
        med = int(statistics.median(written))
        rows.append({
            "payload_mb": mb, "churn": churn,
            "save_wall_s": round(statistics.median(walls), 4),
            "full_save_wall_s": round(full_wall, 4),
            "chunk_bytes_written": med,
            "chunk_bytes_full": int(full_bytes),
            "write_ratio": round(med / full_bytes, 4),
        })
    shutil.rmtree(work, ignore_errors=True)
print(json.dumps({"reps": reps, "rows": rows}))
"""


def bench_diff_ckpt(peak=None, sizes=(64, 256), reps=3, timeout_s=360):
    """Differential-checkpoint cost (``diff_ckpt``): chunk bytes
    written and save wall vs churn fraction (0%/25%/100%) at 64/256 MB
    payloads, median-of-``reps``.  The tentpole claim tracked every
    round: a 25%-churn save writes < 40% of the full-save bytes (the
    ISSUE 14 acceptance floor), and a 0%-churn save writes ~nothing.
    No ``vs_baseline`` (the reference has no checkpointing at all)."""
    return _run_cpu_worker(
        "diff_ckpt", source=_DIFF_CKPT_WORKER,
        args=(*sizes, reps), strip_prefixes=("DK_CKPT",),
        timeout_s=timeout_s)


def bench_ckpt_manifest(peak=None, mb=64, reps=5, timeout_s=300):
    """Integrity-manifest cost: ``Checkpointer.save`` with vs without
    ``DK_CKPT_VERIFY`` (median-of-``reps`` on a ``mb``-MB pytree) plus
    the raw SHA-256 throughput — so the price of the self-healing layer
    is tracked in every BENCH round, not asserted once and forgotten.
    No ``vs_baseline`` (the reference has no checkpoint integrity)."""
    return _run_cpu_worker(
        "ckpt_manifest_overhead", source=_CKPT_MANIFEST_WORKER,
        args=(mb, reps), strip_prefixes=("DK_CKPT",),
        timeout_s=timeout_s)


# The comm-overlap worker: CPU-pinned proxy for the DK_COMM_OVERLAP win.
# The device-only claim ("the psum rides ICI under window k+1's
# compute") is not measured here; its HOST-side shape is: the wall the
# training loop spends BLOCKED at a window boundary before the next
# window's compute is enqueued.  Blocked mode pays
# dispatch + block_until_ready there; overlapped mode (AsyncMerge) pays
# only the async enqueue, with the block_until_ready deferred one
# window — the same double-buffer trick ChunkFeed plays for H2D.  The
# perf.phase comm_blocked/comm_overlap split is reported from the same
# run so the attribution story is exercised end to end.
_COMM_OVERLAP_WORKER = r"""
import json, os, statistics, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from dist_keras_tpu.observability import metrics
from dist_keras_tpu.parallel.collectives import AsyncMerge

n, windows = int(sys.argv[1]), int(sys.argv[2])
center = {"w": jnp.ones((n,), jnp.float32),
          "b": jnp.ones((n // 4,), jnp.float32)}
delta = {"w": jnp.full((n,), 1e-6, jnp.float32),
         "b": jnp.full((n // 4,), 1e-6, jnp.float32)}


def merge_fn(c, d):
    # a multi-pass merge so the collective-analog has a measurable wall
    for _ in range(8):
        c = jax.tree.map(lambda x, y: x + 0.125 * y, c, d)
    return c


compute = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
merge = jax.jit(merge_fn)
xw = jnp.ones((256, 256), jnp.float32)
# warm both executables outside the clock
jax.block_until_ready(compute(xw))
center = jax.block_until_ready(merge(center, delta))


def run_blocked():
    global center
    walls = []
    for _ in range(windows):
        t0 = time.perf_counter()
        center = merge(center, delta)
        jax.block_until_ready(center)      # the boundary stall
        walls.append(time.perf_counter() - t0)
        jax.block_until_ready(compute(xw))  # next window's local steps
    return walls


def run_overlapped():
    global center
    am = AsyncMerge(merge_fn)
    walls = []
    out = None
    for _ in range(windows):
        t0 = time.perf_counter()
        am.submit(center, delta)            # async enqueue only
        walls.append(time.perf_counter() - t0)
        out = compute(xw)                   # dispatched before the wait
        center = am.wait()                  # deferred one window
    jax.block_until_ready(out)
    return walls


blocked = run_blocked()
overlapped = run_overlapped()
h = metrics.snapshot()["histograms"]
split = {k[len("perf.phase."):]: {"count": v["count"],
                                  "total_s": round(v["total"], 6)}
         for k, v in h.items()
         if k.startswith("perf.phase.comm_")}
b, o = statistics.median(blocked), statistics.median(overlapped)
print(json.dumps({
    "windows": windows,
    "tree_mb": round((n + n // 4) * 4 / 2**20, 2),
    "blocked_boundary_wall_s": round(b, 6),
    "overlapped_boundary_wall_s": round(o, 6),
    "boundary_wall_ratio": round(o / b, 4) if b else None,
    "phase_split": split,
}))
"""


def bench_comm_overlap(peak=None, n=1 << 21, windows=16, timeout_s=300):
    """Overlapped-window-collective proxy (``comm_overlap``): the
    host wall spent blocked at a window boundary, blocked merge vs
    ``AsyncMerge`` (async submit, ``block_until_ready`` deferred one
    window), on a CPU-pinned subprocess — the host half of the
    DK_COMM_OVERLAP story, plus the
    ``perf.phase.comm_blocked``/``comm_overlap`` attribution split.
    No ``vs_baseline`` (an internal blocked-vs-overlapped ratio)."""
    return _run_cpu_worker(
        "comm_overlap", source=_COMM_OVERLAP_WORKER,
        args=(n, windows), strip_prefixes=("DK_COMM",),
        timeout_s=timeout_s)


# The PS-compression worker: commit payload bytes + encode/decode wall
# per DK_PS_COMPRESS variant on an MLP-shaped float32 delta — the
# ROADMAP round-17 "delta compression for WAN-separated workers"
# follow-up, measured.  Pure numpy host work.
_PS_COMPRESS_WORKER = r"""
import json, os, statistics, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from dist_keras_tpu.ps import compress

mb, reps = float(sys.argv[1]), int(sys.argv[2])
rng = np.random.default_rng(0)
n = int(mb * 2**20 / 4)
delta = {"dense": {"w": rng.normal(size=(n * 3 // 4,)
                                   ).astype(np.float32) * 1e-3,
                   "b": rng.normal(size=(n // 4,)
                                   ).astype(np.float32) * 1e-3},
         "seed": np.zeros((), np.int32)}
raw_bytes = compress.payload_nbytes(delta)
rows = []
for spec_s in (None, "fp16", "int8", "int8@0.1"):
    spec = compress.parse_spec(spec_s)
    enc_walls, dec_walls = [], []
    wire = delta
    for _ in range(reps):
        t0 = time.perf_counter()
        wire = compress.encode_tree(delta, spec)
        enc_walls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        dec = compress.decode_tree(wire)
        dec_walls.append(time.perf_counter() - t0)
    wire_bytes = compress.payload_nbytes(wire)
    rows.append({
        "spec": spec_s or "off",
        "payload_bytes": wire_bytes,
        "bytes_ratio": round(raw_bytes / wire_bytes, 3),
        "encode_wall_s": round(statistics.median(enc_walls), 5),
        "decode_wall_s": round(statistics.median(dec_walls), 5),
    })
print(json.dumps({"raw_bytes": raw_bytes, "reps": reps, "rows": rows}))
"""


def bench_ps_compress(peak=None, mb=8, reps=5, timeout_s=300):
    """PS commit-delta compression (``ps_compress``): payload bytes +
    encode/decode wall per ``DK_PS_COMPRESS`` variant on an
    ``mb``-MB MLP-shaped delta, CPU-pinned subprocess.  The acceptance
    floor tracked per round: int8 >= 2x byte reduction.  No
    ``vs_baseline`` (the reference ships full pickled weights)."""
    return _run_cpu_worker(
        "ps_compress", source=_PS_COMPRESS_WORKER,
        args=(mb, reps), strip_prefixes=("DK_PS",),
        timeout_s=timeout_s)


def bench_sim_swarm(peak=None, hosts=1000, timeout_s=300):
    """Deterministic cluster simulator throughput (``sim_swarm``): the
    1000-host PS-churn chaos scenario from ``dist_keras_tpu.sim``, run
    to completion in a CPU-pinned subprocess.  What gets measured is
    the simulator itself — wall seconds to execute thousands of
    simulated host-steps plus kill/reap/rejoin/partition chaos in
    simulated time — so the row tracks whether the sim stays fast
    enough to live inside gates and CI (acceptance: well under 60s
    wall).  No ``vs_baseline`` (the reference has no simulator)."""
    rec = _run_cpu_worker(
        "sim_swarm",
        argv=["-m", "dist_keras_tpu.sim", "--scenario", "ps_churn",
              "--seed", "0", "--hosts", str(hosts)],
        strip_prefixes=("DK_SIM", "DK_PS"),
        timeout_s=timeout_s)
    if "error" in rec:
        return rec
    # flatten the CLI's {"scenarios": [...]} doc into one bench row
    s = (rec.get("scenarios") or [{}])[0]
    return {
        "name": "sim_swarm",
        **_CPU_CHILD,
        "hosts": s.get("hosts"),
        "commits": s.get("commits"),
        "typed_faults": s.get("typed_faults"),
        "killed": s.get("killed"),
        "accuracy": s.get("accuracy"),
        "sim_elapsed_s": s.get("sim_elapsed_s"),
        "wall_s": s.get("wall_s"),
        "host_steps_per_wall_s": (
            round(s["hosts"] * s["steps_per_host"] / s["wall_s"], 1)
            if s.get("wall_s") else None),
        "digest": (s.get("digest") or "")[:16],
        "passed": bool(rec.get("passed")),
        "vs_baseline": None,
    }


_SLO_OVERHEAD_WORKER = r"""
import json, os, shutil, sys, tempfile, time
import urllib.request

os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

n = int(sys.argv[1]) if len(sys.argv) > 1 else 250


def run_variant(slo_on, n):
    # env BEFORE the resets: each observability module re-reads its
    # knobs on first use after reset(), so one process measures both
    # variants back to back (second variant also rides a warm jit)
    work = tempfile.mkdtemp(prefix="dk_slo_bench_")
    obs = os.path.join(work, "obs")
    os.environ["DK_OBS_DIR"] = obs
    os.environ["DK_OBS_SAMPLE_S"] = "0.25"
    for k in ("DK_SLO", "DK_TRACE_RETAIN", "DK_SLO_LATENCY_S"):
        os.environ.pop(k, None)
    if slo_on:
        os.environ["DK_SLO"] = "1"
        os.environ["DK_TRACE_RETAIN"] = "1"
        os.environ["DK_SLO_LATENCY_S"] = "0.05"
    from dist_keras_tpu.observability import (events, flight, metrics,
                                              slo, spans, timeseries)
    for mod in (timeseries, events, metrics, flight, spans, slo):
        mod.reset()
    from dist_keras_tpu.models import mnist_mlp
    from dist_keras_tpu.serving import ServingEngine, ServingServer
    model = mnist_mlp(hidden=(32,), input_dim=16, num_classes=4)
    eng = ServingEngine(model, replicas=1, batch_ladder=(1, 8),
                        max_latency_s=0.001, max_queue=1024)
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(1, 16)).astype(np.float32)
    eng.predict(rows, timeout_s=120)   # warm the ladder pre-listen
    srv = ServingServer(eng, port=0)
    host, port = srv.start()
    url = "http://%s:%d/predict" % (host, port)
    body = json.dumps({"rows": rows.tolist()}).encode("utf-8")
    lat = []
    for _ in range(n):
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        lat.append(time.perf_counter() - t0)
    srv.drain()
    srv.close()
    eng.close()
    size = (sum(os.path.getsize(os.path.join(obs, fn))
                for fn in os.listdir(obs))
            if os.path.isdir(obs) else 0)
    shutil.rmtree(work, ignore_errors=True)
    lat.sort()
    return {"p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "p99_ms": round(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 3),
            "trace_bytes_per_1k": int(size / n * 1000)}


off = run_variant(False, n)
on = run_variant(True, n)
print(json.dumps({
    "n_requests": n,
    "off": off,
    "on": on,
    "overhead_p50_pct": (round(100.0 * (on["p50_ms"] - off["p50_ms"])
                               / off["p50_ms"], 1)
                         if off["p50_ms"] else None),
    "overhead_p99_pct": (round(100.0 * (on["p99_ms"] - off["p99_ms"])
                               / off["p99_ms"], 1)
                         if off["p99_ms"] else None),
    "bytes_reduction_x": (round(off["trace_bytes_per_1k"]
                                / on["trace_bytes_per_1k"], 1)
                          if on["trace_bytes_per_1k"]
                          else float(off["trace_bytes_per_1k"] > 0)),
}), flush=True)
"""


def bench_slo_overhead(peak=None, n=250, timeout_s=300):
    """Request-level SLO plane overhead (``slo_overhead``): served
    HTTP p50/p99 with the full round-22 plane (trace exemplars +
    tail-based retention + per-tick burn evaluation) ON vs OFF on the
    same warm process, plus trace bytes per 1k healthy requests per
    variant — the sublinear-retention evidence: with the plane ON,
    healthy fast traces are dropped at request end, so the byte rate
    FALLS even though every breaching request would keep a full trace.
    CPU-pinned subprocess; no ``vs_baseline`` (the reference has no
    SLO plane)."""
    return _run_cpu_worker(
        "slo_overhead", source=_SLO_OVERHEAD_WORKER, args=(n,),
        strip_prefixes=("DK_SLO", "DK_TRACE"), timeout_s=timeout_s)


# The record under construction; _emit() reprints it after every config
# (last stdout line wins).  Kept module-global so the signal/atexit
# handlers can flush whatever exists at the moment the driver ends us.
_OUT = {
    "metric": "ADAG MNIST-CNN samples/sec/chip (window=12, bf16)",
    "value": None,
    "unit": "samples/sec/chip",
    "vs_baseline": None,
    "peak_tflops": None,
    "partial": True,
    "budget_s": None,
    "configs": [],
}
_FLUSHED_FINAL = False
_COMPLETED = False  # True only once the config loop ran to the end


def _emit(last=False):
    """Reprint the record (last stdout line wins).  ``partial`` reflects
    whether the config loop actually completed — a signal/atexit flush
    of a truncated run stays ``partial: true``."""
    global _FLUSHED_FINAL
    if _FLUSHED_FINAL:
        return
    if last:
        _FLUSHED_FINAL = True
    _OUT["partial"] = not _COMPLETED
    # leading newline: if the handler fires mid-line, the record still
    # starts a fresh line and stays the last parseable one
    sys.stdout.write("\n" + json.dumps(_OUT) + "\n")
    sys.stdout.flush()


def _on_signal(signum, frame):  # pragma: no cover - driver-kill path
    _OUT["terminated_by"] = signal.Signals(signum).name
    _emit(last=True)
    # conventional 128+signum (SIGTERM -> 143): a timeout-killing driver
    # that checks the return code sees failure, not a silent success —
    # the record line is flushed either way (ADVICE r5)
    os._exit(128 + signum)


def main():
    global _RUNS_CAP, _COMPLETED
    budget = float(os.environ.get("BENCH_BUDGET_S", "1400"))
    _OUT["budget_s"] = budget
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    atexit.register(_emit, last=True)
    _emit()  # a parseable record exists before the first device touch
    import jax

    from dist_keras_tpu.utils import compile_cache

    compile_cache.enable()
    # the platform is whatever JAX gives this process: no probe, no
    # fallback — a missing chip fails here, loudly
    dev = jax.devices()[0]
    stamp = {"platform": dev.platform, "device_kind": dev.device_kind,
             "n_devices": len(jax.devices())}
    _OUT.update(stamp)
    peak = _peak_flops()
    _OUT["peak_tflops"] = peak / 1e12 if peak else None
    _emit()  # record updated with the device and its peak

    # headline first, then the remaining reference-parity rows cheapest
    # first, then the internal parity ratio, then the no-baseline
    # showcases with the largest cold-compile exposure
    t_start = time.time()
    for fn in (bench_adag_mnist_cnn, bench_single_mnist_mlp,
               bench_averaging_mnist_cnn, bench_aeasgd_higgs,
               bench_downpour_mnist_cnn, bench_dynsgd_cifar,
               bench_adag_streamed, bench_serving,
               bench_decode_serving, bench_decode_survivability,
               bench_router,
               bench_ckpt_manifest,
               bench_ckpt_async_save, bench_diff_ckpt,
               bench_retrace_proxy, bench_reshard_restore,
               bench_comm_overlap, bench_ps_compress,
               bench_sim_swarm, bench_slo_overhead,
               bench_transformer_tp, bench_long_context):
        elapsed = time.time() - t_start
        if elapsed > budget:
            _OUT["configs"].append({"name": fn.__name__,
                                    "skipped": "budget", **stamp})
            _obs_emit("bench_config_skipped", name=fn.__name__,
                      elapsed_s=round(elapsed, 1))
            print(f"[bench] {fn.__name__}: skipped "
                  f"(elapsed {elapsed:.0f}s > budget {budget:.0f}s)",
                  file=sys.stderr, flush=True)
            continue
        if elapsed > 0.5 * budget and _RUNS_CAP is None:
            _RUNS_CAP = 3  # downshift the tail to median-of-3
            print(f"[bench] past 50% of budget at {elapsed:.0f}s: "
                  "downshifting to median-of-3", file=sys.stderr,
                  flush=True)
        t0 = time.time()
        _obs_emit("bench_config_begin", name=fn.__name__)
        try:
            row = fn(peak)
        except Exception as e:  # the record keeps the other rows; rc != 0
            row = {"name": fn.__name__, "error": repr(e)[:200]}
        row["duration_s"] = round(time.time() - t0, 1)
        for k, v in stamp.items():
            row.setdefault(k, v)  # CPU-worker rows keep their own stamp
        _obs_emit("bench_config_end", name=fn.__name__,
                  duration_s=row["duration_s"],
                  error=row.get("error"))
        _OUT["configs"].append(row)
        if row.get("name") == "adag_mnist_cnn" and "error" not in row:
            _OUT["value"] = row["samples_per_sec_per_chip"]
            _OUT["vs_baseline"] = row["vs_baseline"]
        _emit()
        print(f"[bench] {fn.__name__}: {row['duration_s']:.0f}s "
              f"-> {row}", file=sys.stderr, flush=True)

    _COMPLETED = True
    _obs_emit("bench_complete",
              n_configs=len(_OUT["configs"]),
              elapsed_s=round(time.time() - t_start, 1))
    _emit(last=True)
    errored = [c["name"] for c in _OUT["configs"] if "error" in c]
    if errored:
        print(f"[bench] {len(errored)} config(s) errored: {errored}",
              file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
