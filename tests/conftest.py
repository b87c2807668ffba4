"""Test harness: run everything on 8 virtual CPU devices.

This is the JAX analogue of the reference's ``local[8]`` Spark master
(SURVEY.md §4): multi-worker code paths execute for real — shard_map,
collectives, staggered commits — without TPU hardware.  Must run before any
jax import.
"""

import os

# before the first ``import jax``: the platform and the device count are
# fixed at backend initialisation, and the env is all it takes
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-size accuracy gates (TPU-run sizing — gates.py runs "
        "them) and tests needing capabilities this image lacks "
        "(multiprocess CPU collectives); excluded from the budgeted "
        "tier-1 run via -m 'not slow'")


def pytest_addoption(parser):
    parser.addoption(
        "--fast", action="store_true", default=False,
        help="CI-sized accuracy gates: ~2k rows, few epochs, threshold "
             "~0.8 — finishes on one CPU core in minutes (the full gates "
             "are sized for a TPU run)")


@pytest.fixture(scope="session")
def fast_gates(request):
    return bool(request.config.getoption("--fast"))


@pytest.fixture
def flip_one_byte():
    """Corruption helper shared by the self-healing tests: bit-flip one
    byte of the largest non-manifest file under a checkpoint payload
    dir (largest = the real tensor bytes, not orbax metadata); -> the
    path flipped."""
    def _flip(payload_dir):
        from dist_keras_tpu.checkpoint import MANIFEST_NAME

        files = []
        for dirpath, _dirs, names in os.walk(str(payload_dir)):
            files += [os.path.join(dirpath, n) for n in names
                      if n != MANIFEST_NAME]
        tgt = max(files, key=os.path.getsize)
        with open(tgt, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        return tgt

    return _flip


@pytest.fixture(scope="session")
def blobs_dataset():
    """Tiny 2-class gaussian-blob classification set, one-hot labels."""
    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.utils.misc import one_hot

    rng = np.random.default_rng(0)
    n, d = 512, 8
    y = rng.integers(0, 2, size=n)
    centers = np.stack([np.full(d, -1.0), np.full(d, 1.0)])
    x = centers[y] + rng.normal(size=(n, d)).astype(np.float32)
    return Dataset({
        "features": x.astype(np.float32),
        "label": y,
        "label_encoded": one_hot(y, 2),
    })


@pytest.fixture(scope="session")
def digits_dataset():
    """sklearn 8x8 digits — the offline MNIST stand-in for convergence
    tests (10 classes, 1797 rows)."""
    from sklearn.datasets import load_digits

    from dist_keras_tpu.data import Dataset
    from dist_keras_tpu.utils.misc import one_hot

    digits = load_digits()
    x = (digits.data / 16.0).astype(np.float32)
    y = digits.target
    return Dataset({
        "features": x,
        "label": y,
        "label_encoded": one_hot(y, 10),
    })
