"""Streaming inference pipeline — parity with the reference's Kafka example.

The reference pairs ``examples/kafka_producer.py`` (pushes rows onto a
Kafka topic) with a Spark Streaming notebook that runs a trained model over
each micro-batch (SURVEY.md §2.4).  Same pipeline here, TPU-native:

  producer thread --(TCP, length-prefixed JSON rows)--> SocketSource
      --> StreamingPredictor (fixed-shape micro-batches, one jitted
          executable for the whole stream) --> rolling accuracy sink

Run:  python examples/streaming_inference.py [--rows 2048] [--batch 256]

Swap ``SocketSource`` for ``KafkaSource("topic", bootstrap_servers=...)``
against a real cluster — the predictor is source-agnostic.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from dist_keras_tpu.data import (  # noqa: E402
    SocketSource,
    StreamingPredictor,
    send_rows,
)
from dist_keras_tpu.data.synthetic import synthetic_mnist  # noqa: E402
from dist_keras_tpu.models import mnist_mlp  # noqa: E402
from dist_keras_tpu.trainers import SingleTrainer  # noqa: E402
from dist_keras_tpu.utils import compile_cache  # noqa: E402


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--train-rows", type=int, default=4096)
    args = ap.parse_args()

    # 1. train the model that will serve the stream
    print(f"training mnist_mlp on {args.train_rows} rows ...")
    ds = synthetic_mnist(args.train_rows, seed=0)
    ds = ds.with_column("fn", ds["features"] / 255.0)
    ds = ds.with_column("le", np.eye(10, dtype=np.float32)[ds["label"]])
    trainer = SingleTrainer(mnist_mlp(), worker_optimizer="adam",
                            optimizer_kwargs={"learning_rate": 1e-3},
                            batch_size=64, num_epoch=4,
                            features_col="fn", label_col="le")
    model = trainer.train(ds, shuffle=True)

    # 2. the "topic": a socket the producer pushes rows onto
    stream = synthetic_mnist(args.rows, seed=7)
    feats = stream["features"] / 255.0
    labels = stream["label"]
    source = SocketSource()
    producer = threading.Thread(
        target=send_rows, args=(source.address, feats), daemon=True)
    producer.start()

    # 3. micro-batched streaming inference
    predictor = StreamingPredictor(model, batch_size=args.batch,
                                   max_latency_s=0.05)
    done = correct = 0
    t0 = time.time()
    for rows, preds in predictor.predict_stream(source):
        idx = preds.argmax(-1)
        correct += int((idx == labels[done:done + len(rows)]).sum())
        done += len(rows)
        print(f"  micro-batch of {len(rows):4d} rows | rolling accuracy "
              f"{correct / done:.4f} | {done / (time.time() - t0):,.0f} "
              "rows/s")
    print(f"\nstream done: {done} rows, accuracy {correct / done:.4f}, "
          f"{done / (time.time() - t0):,.0f} rows/s end-to-end")


if __name__ == "__main__":
    main()
