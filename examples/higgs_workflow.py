"""ATLAS-Higgs workflow — parity with reference ``examples/workflow.ipynb``.

The reference notebook (SURVEY.md §2.4) is the CERN use case: a dense
classifier on ``data/atlas_higgs.csv``, trained with the elastic-averaging
family (AEASGD / EAMSGD), comparing accuracy/AUC and training time.  Same
workflow here:

    CSV -> Dataset -> StandardScale/OneHot -> higgs_mlp ->
    {SingleTrainer, AEASGD, EAMSGD} -> ModelPredictor -> AUC + accuracy

Run:  python examples/higgs_workflow.py [--fast]

No network in this image, so a Higgs-shaped sample set (28 physics-flavoured
features, overlapping signal/background — see data/synthetic.py) is written
to ``examples/data/higgs_*.csv`` on first use and read back through
``Dataset.from_csv``.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dist_keras_tpu.data import (  # noqa: E402
    AccuracyEvaluator,
    AUCEvaluator,
    Dataset,
    LabelIndexTransformer,
    ModelPredictor,
    OneHotTransformer,
    StandardScaleTransformer,
)
from dist_keras_tpu.data.synthetic import synthetic_higgs, to_csv  # noqa: E402
from dist_keras_tpu.models import higgs_mlp  # noqa: E402
from dist_keras_tpu.trainers import AEASGD, EAMSGD, SingleTrainer  # noqa: E402
from dist_keras_tpu.utils import compile_cache  # noqa: E402

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load_higgs(n_train=16384, n_test=4096, data_dir=DATA_DIR):
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    for split, n, seed in (("train", n_train, 0), ("test", n_test, 1)):
        p = os.path.join(data_dir, f"higgs_{split}_{n}.csv")
        if not os.path.exists(p):
            to_csv(synthetic_higgs(n, seed=seed), p)
        paths[split] = p
    return (Dataset.from_csv(paths["train"], label="label"),
            Dataset.from_csv(paths["test"], label="label"))


def preprocess(ds):
    ds = StandardScaleTransformer(input_col="features",
                                  output_col="features_scaled").transform(ds)
    ds = OneHotTransformer(2, input_col="label",
                           output_col="label_encoded").transform(ds)
    return ds


def evaluate(model, test):
    pred = ModelPredictor(model,
                          features_col="features_scaled").predict(test)
    auc = AUCEvaluator(score_col="prediction",
                       label_col="label").evaluate(pred)
    pred = LabelIndexTransformer(input_col="prediction").transform(pred)
    acc = AccuracyEvaluator(prediction_col="prediction_index",
                            label_col="label").evaluate(pred)
    return auc, acc


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-train", type=int, default=16384)
    ap.add_argument("--n-test", type=int, default=4096)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.fast:
        args.n_train, args.n_test, args.epochs = 4096, 1024, 3

    import jax
    ndev = len(jax.devices())
    if args.workers > ndev:
        print(f"only {ndev} device(s) visible: clamping --workers "
              f"{args.workers} -> {ndev}")
        args.workers = ndev

    print(f"loading Higgs-shaped data ({args.n_train} train / "
          f"{args.n_test} test) ...")
    train, test = load_higgs(args.n_train, args.n_test)
    train, test = preprocess(train), preprocess(test)

    common = dict(loss="categorical_crossentropy", worker_optimizer="adam",
                  optimizer_kwargs={"learning_rate": 1e-3},
                  features_col="features_scaled", label_col="label_encoded",
                  batch_size=args.batch_size, num_epoch=args.epochs)

    # the notebook's comparison: single-node vs the elastic-averaging
    # family.  rho=1, lr=0.2 keep alpha*num_workers <= 1 — the stability
    # bound for simultaneous lockstep commits (tests/test_examples.py).
    runs = [
        ("SingleTrainer", lambda: SingleTrainer(higgs_mlp(), **common)),
        ("AEASGD", lambda: AEASGD(higgs_mlp(), num_workers=args.workers,
                                  communication_window=16, rho=1.0,
                                  learning_rate=0.2, **common)),
        ("EAMSGD", lambda: EAMSGD(higgs_mlp(), num_workers=args.workers,
                                  communication_window=16, rho=1.0,
                                  learning_rate=0.2, momentum=0.9,
                                  **common)),
    ]

    print(f"\n{'trainer':15s} {'AUC':>7s} {'accuracy':>9s} {'train s':>9s}")
    for name, make in runs:
        trainer = make()
        trained = trainer.train(train, shuffle=True)
        auc, acc = evaluate(trained, test)
        print(f"{name:15s} {auc:7.4f} {acc:9.4f} "
              f"{trainer.get_training_time():9.1f}")


if __name__ == "__main__":
    main()
