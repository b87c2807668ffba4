"""Pipeline-parallel transformer training — flat 1F1B, interleaved, x DP.

New capability relative to the reference (SURVEY.md §2.3: every upstream
worker holds the full model; there is no pipeline axis).  This example
trains the same transformer three ways over a ``stages`` mesh axis and
prints per-schedule losses + step times so the schedules can be compared
directly:

1. **flat 1F1B** (``make_pp_train_step``): one interleaved fwd+bwd ring
   schedule, recompute-vjp backward, O(P) activation stash
   (``parallel/pipeline.py:pipeline_1f1b``).
2. **interleaved 1F1B** (``virtual=2``): v non-contiguous layer chunks
   per device — the fill/drain bubble shrinks v-fold at v ring hops per
   microbatch per direction (Megatron's interleaved schedule;
   ``pipeline_interleaved_1f1b``).
3. **PP x DP**: the same 1F1B pipe composed with a ``workers`` data
   axis — batch sharded over worker columns, gradients pmean-ed across
   them before the update.

All three produce identical losses on identical data (the schedules are
exact, not approximations — tests/test_pipeline.py holds them to the
single-device oracle at 1e-5).

Run on whatever devices exist, e.g. an 8-virtual-device CPU mesh:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/pipeline_parallel.py [--stages 4] [--layers 8] \
      [--steps 3] [--microbatches 8]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import optax

from dist_keras_tpu.models.transformer import transformer_config
from dist_keras_tpu.parallel.pipeline import (
    bubble_fraction,
    make_pp_mesh,
    train_pp_transformer,
)
from dist_keras_tpu.utils import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=None,
                    help="pipeline depth (default: all devices)")
    ap.add_argument("--layers", type=int, default=None,
                    help="transformer blocks (default: 2*stages so "
                         "virtual=2 divides evenly)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--microbatches", type=int, default=None,
                    help="default: stages (interleaved needs a "
                         "multiple of stages)")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    args = ap.parse_args()

    ndev = len(jax.devices())
    stages = args.stages or ndev
    layers = args.layers or 2 * stages
    m = args.microbatches or stages
    # the batch must divide into m microbatches (and into 2 worker
    # columns for the DP variant) on ANY device count — round it up
    # rather than crash on e.g. a 6-device host with the default 16
    batch = max(args.batch, 2 * m)
    batch += (-batch) % (2 * m)
    cfg = transformer_config(input_dim=8, seq_len=args.seq, d_model=32,
                             n_heads=2, n_layers=layers, n_classes=4)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, args.seq, 8)).astype(np.float32)
    y = rng.integers(0, 4, batch).astype(np.int32)

    def run(name, mesh, **kw):
        t0 = time.time()
        (_, _), losses = train_pp_transformer(
            mesh, cfg, x, y, num_microbatches=m, steps=args.steps,
            optimizer=optax.adam(1e-3), causal=True, **kw)
        dt = time.time() - t0
        print(f"{name:<24} losses {[round(v, 4) for v in losses]} "
              f"({dt:.1f}s incl. compile)")
        return losses

    print(f"{ndev} devices; stages={stages} layers={layers} "
          f"microbatches={m}")
    print(f"analytic bubble: flat {bubble_fraction(stages, m):.3f} vs "
          f"interleaved v=2 {bubble_fraction(stages, m, v=2):.3f}")

    flat = run("flat 1F1B", make_pp_mesh(stages=stages))
    inter = run("interleaved 1F1B (v=2)", make_pp_mesh(stages=stages),
                virtual=2)
    # report the flat-vs-interleaved deviation instead of hard-asserting:
    # both schedules are exact but reduce in different orders, so on
    # large --layers/--seq/--steps settings f32 reassociation can exceed
    # a fixed tolerance — a demo should report, not crash (the real
    # parity guarantee lives in tests/test_pipeline.py).  Tolerance
    # scales with the trajectory's magnitude.
    fa, ia = np.asarray(flat), np.asarray(inter)
    dev = float(np.max(np.abs(fa - ia)))
    tol = 1e-4 + 1e-3 * float(np.max(np.abs(fa)))
    print(f"flat vs interleaved max |loss dev| {dev:.3e} "
          f"(tol {tol:.3e}): {'PASS' if dev <= tol else 'FAIL'}")
    if 2 * stages <= ndev:
        dp = run("1F1B x DP (2 workers)",
                 make_pp_mesh(stages=stages, dp=2))
        print("PP x DP losses match pure PP on the same data:",
              np.allclose(flat, dp, atol=1e-3))
    print("flat == interleaved loss trajectories: exact schedules, "
          "same math")


if __name__ == "__main__":
    main()
