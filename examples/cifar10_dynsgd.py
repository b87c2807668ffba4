"""CIFAR-10 DynSGD example — the fifth BASELINE.md config.

BASELINE.md targets "DynSGD — CIFAR-10 ConvNet, 32+ workers: accuracy parity
with stale-gradient correction reinterpretation".  DynSGD's staleness scaling
is reproduced as a staggered-commit scan (trainers/dynsgd.py); this script
trains the CIFAR convnet with it and reports accuracy vs a SingleTrainer run.

Run:  python examples/cifar10_dynsgd.py [--fast] [--workers 8]

(--workers defaults to 8 — the virtual-device count CI simulates; on a real
pod slice pass 32+ as BASELINE.md specifies.)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dist_keras_tpu.data import (  # noqa: E402
    AccuracyEvaluator,
    Dataset,
    LabelIndexTransformer,
    MinMaxTransformer,
    ModelPredictor,
    OneHotTransformer,
    ReshapeTransformer,
)
from dist_keras_tpu.data.synthetic import synthetic_cifar10, to_csv  # noqa: E402
from dist_keras_tpu.models import cifar10_convnet  # noqa: E402
from dist_keras_tpu.trainers import DynSGD, SingleTrainer  # noqa: E402
from dist_keras_tpu.utils import compile_cache  # noqa: E402

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load_cifar(n_train=8192, n_test=2048, data_dir=DATA_DIR):
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    for split, n, seed in (("train", n_train, 0), ("test", n_test, 1)):
        p = os.path.join(data_dir, f"cifar_{split}_{n}.csv")
        if not os.path.exists(p):
            to_csv(synthetic_cifar10(n, seed=seed), p)
        paths[split] = p
    return (Dataset.from_csv(paths["train"], label="label"),
            Dataset.from_csv(paths["test"], label="label"))


def preprocess(ds):
    ds = MinMaxTransformer(0.0, 1.0, 0.0, 255.0, input_col="features",
                           output_col="features_normalized").transform(ds)
    ds = OneHotTransformer(10, input_col="label",
                           output_col="label_encoded").transform(ds)
    ds = ReshapeTransformer(input_col="features_normalized",
                            output_col="features_img",
                            shape=(32, 32, 3)).transform(ds)
    return ds


def evaluate(model, test):
    pred = ModelPredictor(model, features_col="features_img").predict(test)
    pred = LabelIndexTransformer(input_col="prediction").transform(pred)
    return AccuracyEvaluator(prediction_col="prediction_index",
                             label_col="label").evaluate(pred)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-train", type=int, default=8192)
    ap.add_argument("--n-test", type=int, default=2048)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.fast:
        args.n_train, args.n_test, args.epochs = 2048, 512, 2

    import jax
    ndev = len(jax.devices())
    if args.workers > ndev:
        print(f"only {ndev} device(s) visible: clamping --workers "
              f"{args.workers} -> {ndev}")
        args.workers = ndev

    print(f"loading CIFAR-shaped data ({args.n_train} train / "
          f"{args.n_test} test) ...")
    train, test = load_cifar(args.n_train, args.n_test)
    train, test = preprocess(train), preprocess(test)

    common = dict(loss="categorical_crossentropy", worker_optimizer="adam",
                  optimizer_kwargs={"learning_rate": 1e-3},
                  features_col="features_img", label_col="label_encoded",
                  batch_size=args.batch_size, num_epoch=args.epochs)

    single = SingleTrainer(cifar10_convnet(), **common)
    ref = single.train(train, shuffle=True)
    ref_acc = evaluate(ref, test)
    print(f"SingleTrainer  acc={ref_acc:.4f}  "
          f"train={single.get_training_time():.1f}s")

    dyn = DynSGD(cifar10_convnet(), num_workers=args.workers,
                 communication_window=5, **common)
    trained = dyn.train(train, shuffle=True)
    acc = evaluate(trained, test)
    print(f"DynSGD({args.workers}w)    acc={acc:.4f}  "
          f"train={dyn.get_training_time():.1f}s")
    print(f"parity gap: {ref_acc - acc:+.4f}")


if __name__ == "__main__":
    main()
