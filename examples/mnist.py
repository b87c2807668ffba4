"""MNIST end-to-end example — parity with reference ``examples/mnist.py``.

The reference script (SURVEY.md §2.4) loads MNIST from CSV into a Spark
DataFrame, preprocesses with transformers, trains an MLP and a CNN with every
trainer side-by-side, then runs the predictor + label-index + accuracy
evaluator pipeline and prints a comparison table.  Same flow here, TPU-native:

    CSV -> Dataset -> MinMax/OneHot/Reshape -> {Single, Averaging, DOWNPOUR,
    ADAG, AEASGD, EAMSGD, DynSGD} -> ModelPredictor -> LabelIndexTransformer
    -> AccuracyEvaluator

Run:  python examples/mnist.py [--fast] [--workers 4] [--epochs 5]

This image has no network, so the MNIST-shaped sample data is generated
procedurally (stroke-rendered digits, see data/synthetic.py) and written to
``examples/data/mnist_{train,test}.csv`` on first use — the script then reads
it back through ``Dataset.from_csv`` (native C++ fastcsv parser), exercising
the same CSV ingestion path the reference example does.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

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
from dist_keras_tpu.data.synthetic import synthetic_mnist, to_csv  # noqa: E402
from dist_keras_tpu.models import mnist_cnn, mnist_mlp  # noqa: E402
from dist_keras_tpu.trainers import (  # noqa: E402
    ADAG,
    AEASGD,
    DOWNPOUR,
    EAMSGD,
    AveragingTrainer,
    DynSGD,
    SingleTrainer,
)
from dist_keras_tpu.utils import compile_cache  # noqa: E402

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load_mnist(n_train=8192, n_test=2048, data_dir=DATA_DIR):
    """Write-once CSV cache -> (train, test) Datasets via the CSV path."""
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    for split, n, seed in (("train", n_train, 0), ("test", n_test, 1)):
        p = os.path.join(data_dir, f"mnist_{split}_{n}.csv")
        if not os.path.exists(p):
            to_csv(synthetic_mnist(n, seed=seed), p)
        paths[split] = p
    return (Dataset.from_csv(paths["train"], label="label"),
            Dataset.from_csv(paths["test"], label="label"))


def preprocess(ds):
    """The reference's transformer chain: normalize, one-hot, reshape."""
    ds = MinMaxTransformer(n_min=0.0, n_max=1.0, o_min=0.0, o_max=255.0,
                           input_col="features",
                           output_col="features_normalized").transform(ds)
    ds = OneHotTransformer(10, input_col="label",
                           output_col="label_encoded").transform(ds)
    ds = ReshapeTransformer(input_col="features_normalized",
                            output_col="features_img",
                            shape=(28, 28, 1)).transform(ds)
    return ds


def evaluate(model, test, features_col):
    pred = ModelPredictor(model, features_col=features_col).predict(test)
    pred = LabelIndexTransformer(input_col="prediction").transform(pred)
    return AccuracyEvaluator(prediction_col="prediction_index",
                             label_col="label").evaluate(pred)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-train", type=int, default=8192)
    ap.add_argument("--n-test", type=int, default=2048)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--fast", action="store_true",
                    help="small data + 2 epochs (CI smoke)")
    args = ap.parse_args()
    if args.fast:
        args.n_train, args.n_test, args.epochs = 2048, 512, 2

    import jax
    ndev = len(jax.devices())
    if args.workers > ndev:
        print(f"only {ndev} device(s) visible: clamping --workers "
              f"{args.workers} -> {ndev} (the CI harness simulates 8 "
              "virtual CPU devices; see tests/conftest.py)")
        args.workers = ndev

    print(f"loading MNIST-shaped data ({args.n_train} train / "
          f"{args.n_test} test) ...")
    train, test = load_mnist(args.n_train, args.n_test)
    train, test = preprocess(train), preprocess(test)

    common = dict(loss="categorical_crossentropy", worker_optimizer="adam",
                  batch_size=args.batch_size, num_epoch=args.epochs,
                  label_col="label_encoded")
    dist = dict(num_workers=args.workers)

    # the reference's side-by-side trainer comparison (examples/mnist.py):
    # an MLP under the single trainer, the CNN under every distributed one.
    # Hyperparameters are the lockstep-stable settings from the accuracy
    # gates (tests/test_examples.py has the derivation — DOWNPOUR's center
    # step grows with num_workers; AEASGD needs alpha*num_workers <= 1).
    runs = [
        ("SingleTrainer (MLP)", "features_normalized",
         lambda: SingleTrainer(mnist_mlp(),
                               optimizer_kwargs={"learning_rate": 1e-3},
                               **common)),
        ("AveragingTrainer (CNN)", "features_img",
         lambda: AveragingTrainer(mnist_cnn(),
                                  optimizer_kwargs={"learning_rate": 1e-3},
                                  **common, **dist)),
        ("DOWNPOUR (CNN)", "features_img",
         lambda: DOWNPOUR(mnist_cnn(), communication_window=5,
                          optimizer_kwargs={"learning_rate": 7e-4},
                          **common, **dist)),
        ("ADAG (CNN)", "features_img",
         lambda: ADAG(mnist_cnn(), communication_window=12,
                      optimizer_kwargs={"learning_rate": 3e-3},
                      **common, **dist)),
        ("AEASGD (CNN)", "features_img",
         lambda: AEASGD(mnist_cnn(), communication_window=16, rho=1.0,
                        learning_rate=0.2,
                        optimizer_kwargs={"learning_rate": 1e-3},
                        **common, **dist)),
        ("EAMSGD (CNN)", "features_img",
         lambda: EAMSGD(mnist_cnn(), communication_window=16, rho=1.0,
                        learning_rate=0.2, momentum=0.9,
                        optimizer_kwargs={"learning_rate": 1e-3},
                        **common, **dist)),
        ("DynSGD (CNN)", "features_img",
         lambda: DynSGD(mnist_cnn(), communication_window=5,
                        optimizer_kwargs={"learning_rate": 1e-3},
                        **common, **dist)),
    ]

    rows = []
    for name, feat_col, make in runs:
        trainer = make()
        trainer.features_col = feat_col
        t0 = time.time()
        trained = trainer.train(train, shuffle=True)
        secs = time.time() - t0
        acc = evaluate(trained, test, feat_col)
        sps = args.n_train * args.epochs / trainer.get_training_time()
        rows.append((name, acc, trainer.get_training_time(), sps))
        print(f"  {name:28s} acc={acc:.4f}  "
              f"train={trainer.get_training_time():.1f}s  "
              f"({sps:,.0f} samples/s, wall {secs:.1f}s)")

    print("\n=== MNIST summary ===")
    print(f"{'trainer':30s} {'accuracy':>9s} {'train s':>9s} "
          f"{'samples/s':>12s}")
    for name, acc, secs, sps in rows:
        print(f"{name:30s} {acc:9.4f} {secs:9.1f} {sps:12,.0f}")


if __name__ == "__main__":
    main()
