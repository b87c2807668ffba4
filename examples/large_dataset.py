"""Training on a dataset larger than device memory — the streaming feed.

The reference's core workload is the big-DataFrame case: Spark streams
each worker's partition through an iterator (workers.py:~60), so an
epoch never has to fit in any executor's memory.  The TPU-native
equivalent (round 4; round 5 extended it to EVERY trainer — the
windowed family, DynSGD, SingleTrainer, AveragingTrainer, and
EnsembleTrainer all stream, so no trainer is HBM-capped):

- ``stream_chunk_windows=C`` — feed C communication windows per
  dispatch through a double-buffered ChunkFeed: at most TWO chunks
  device-resident, the next chunk's host->device transfer overlapped
  under the running computation;
- ``max_resident_bytes=B`` — auto-enable streaming only when the epoch
  tensor would exceed B bytes of per-device memory (otherwise the
  whole-run-resident fast path is kept);
- ``data_dtype=None`` — ship the dataset columns' native dtype (uint8
  image bytes at 1/4 the float32 volume) and cast on-device.

Streamed training is bit-equal to resident training (asserted in
tests/test_streaming_feed.py) and composes with mid-epoch
checkpoint/resume.  Measured on 1 x TPU v5e (uint8 feed, 6x4096 MLP,
1M rows): streamed/resident throughput ratio 0.99.

Run:  python examples/large_dataset.py [--rows 200000] [--stream 8]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from dist_keras_tpu.data import Dataset
from dist_keras_tpu.models import mnist_mlp
from dist_keras_tpu.trainers import ADAG
from dist_keras_tpu.utils.misc import one_hot
from dist_keras_tpu.utils import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--stream", type=int, default=8,
                    help="windows per streamed chunk (0 = use "
                         "max_resident_bytes auto-switch instead)")
    ap.add_argument("--budget-mb", type=float, default=16.0,
                    help="per-device residency budget for the "
                         "auto-switch path")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    # uint8 features: ships at 1/4 float32 H2D volume, cast on-device
    x = rng.integers(0, 256, size=(args.rows, 64)).astype(np.uint8)
    yv = rng.integers(0, 10, size=args.rows)
    ds = Dataset({"features": x, "label": yv,
                  "label_encoded": one_hot(yv, 10, dtype=np.uint8)})

    kw = dict(num_workers=min(4, len(jax.devices())),
              worker_optimizer="adam",
              optimizer_kwargs={"learning_rate": 1e-3},
              batch_size=256, num_epoch=2, label_col="label_encoded",
              communication_window=8, data_dtype=None)
    if args.stream:
        kw["stream_chunk_windows"] = args.stream
    else:
        kw["max_resident_bytes"] = int(args.budget_mb * 1024 * 1024)

    t = ADAG(mnist_mlp(hidden=(256, 256), input_dim=64, num_classes=10),
             **kw)
    t.train(ds)
    feed = getattr(t, "_last_feed", None)
    print(f"streamed={t._streamed}  "
          f"epochs={kw['num_epoch']}  rows={args.rows}  "
          f"{args.rows * kw['num_epoch'] / t.get_training_time() / 1e3:.1f}k "
          f"samples/s", flush=True)
    if feed is not None:
        print(f"chunks transferred={feed.put_count}  "
              f"peak device-resident chunks={feed.peak_resident_chunks} "
              f"(bound: 2)")


if __name__ == "__main__":
    main()
