"""Online serving demo — train, serve over HTTP, hot-reload, drain.

The serving counterpart of ``examples/streaming_inference.py``: instead
of a pull-based micro-batch stream, a ``ServingEngine`` packs CONCURRENT
client requests into a fixed ladder of jitted batch shapes across model
replicas, a stdlib HTTP server fronts it, a ``CheckpointWatcher``
hot-swaps a newly promoted checkpoint with zero dropped requests, and a
graceful drain delivers every in-flight answer on shutdown.

Run:  python examples/serving.py [--rows 512] [--clients 4]

Pipeline:
  1. train a small MLP (SingleTrainer)
  2. start ServingEngine + ServingServer (+ /healthz, /metricsz)
  3. N client threads POST rows at /predict concurrently
  4. mid-traffic: promote a new checkpoint -> watcher hot-reloads it
  5. drain: every admitted request answered, late ones typed-rejected
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from dist_keras_tpu.checkpoint import Checkpointer  # noqa: E402
from dist_keras_tpu.data.synthetic import synthetic_mnist  # noqa: E402
from dist_keras_tpu.models import mnist_mlp  # noqa: E402
from dist_keras_tpu.serving import (  # noqa: E402
    CheckpointWatcher,
    ServingEngine,
    ServingServer,
)
from dist_keras_tpu.trainers import SingleTrainer  # noqa: E402
from dist_keras_tpu.utils import compile_cache  # noqa: E402


def _post(url, rows):
    req = urllib.request.Request(
        url + "/predict",
        data=json.dumps({"rows": rows}).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--train-rows", type=int, default=2048)
    args = ap.parse_args()

    # 1. train the model that will serve
    print(f"training mnist_mlp on {args.train_rows} rows ...")
    ds = synthetic_mnist(args.train_rows, seed=0)
    ds = ds.with_column("fn", ds["features"] / 255.0)
    ds = ds.with_column("le", np.eye(10, dtype=np.float32)[ds["label"]])
    trainer = SingleTrainer(mnist_mlp(), worker_optimizer="adam",
                            optimizer_kwargs={"learning_rate": 1e-3},
                            batch_size=64, num_epoch=3,
                            features_col="fn", label_col="le")
    model = trainer.train(ds, shuffle=True)

    # 2. engine + HTTP front end (port=None binds DK_SERVE_PORT when a
    #    launcher exported one; 0 picks a free port here)
    engine = ServingEngine(model, replicas=2,
                           batch_ladder=(1, 8, 32, 64),
                           max_latency_s=0.005, max_queue=2048)
    server = ServingServer(engine, port=0)
    host, port = server.start()
    url = f"http://{host}:{port}"
    print(f"serving on {url}  (endpoints: /predict /healthz /metricsz)")

    # 3. concurrent clients
    stream = synthetic_mnist(args.rows, seed=7)
    feats = (stream["features"] / 255.0).tolist()
    labels = stream["label"]
    done = [0] * args.clients
    correct = [0] * args.clients

    def client(ci):
        for i in range(ci, args.rows, args.clients):
            doc = _post(url, [feats[i]])
            if int(np.argmax(doc["predictions"][0])) == labels[i]:
                correct[ci] += 1
            done[ci] += 1

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    t0 = time.time()
    for t in threads:
        t.start()

    # 4. mid-traffic hot reload: promote a checkpoint, watcher swaps it
    ckptr = Checkpointer(os.path.join("/tmp", f"dk_serve_demo_{os.getpid()}"))
    # template -> exact-typed orbax restore (and no topology warning)
    watcher = CheckpointWatcher(engine, ckptr, poll_s=0.05,
                                template={"params": model.params}).start()
    time.sleep(0.3)
    ckptr.save(1, {"params": model.params})  # same params: a no-op roll
    deadline = time.time() + 30
    while watcher.reloads < 1 and time.time() < deadline:
        time.sleep(0.05)
    print(f"hot reload rolled in (reloads={watcher.reloads}) with "
          "traffic in flight")

    for t in threads:
        t.join()
    wall = time.time() - t0
    acc = sum(correct) / max(1, sum(done))
    print(f"{sum(done)} requests from {args.clients} clients in "
          f"{wall:.2f}s ({sum(done) / wall:,.0f} req/s), accuracy "
          f"{acc:.4f}")
    st = engine.stats()
    print(f"batches={st['batches']} mean fill="
          f"{st['fill_ratio']['mean']:.2f} "
          f"retraces={st['retrace_count']}/{st['retrace_bound']} "
          f"p99 predict={st['predict_s']['p99'] * 1e3:.2f}ms")

    # 5. graceful drain: everything admitted is answered, then the
    #    listener closes (a SIGTERM does the same via
    #    server.install_signal_drain())
    watcher.stop()
    out = server.drain(timeout_s=60)
    print(f"drained: {out['delivered']} delivered, "
          f"{out['errored']} errored — bye")


if __name__ == "__main__":
    main()
