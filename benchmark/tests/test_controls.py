"""The controls: the reference carried in the nearest precision below the
one the configuration states, put in the program's place, has to come out
as not correct under the cell's own limits.  Here at a size a test run
holds; PERF.md has the readings at the cells' own sizes on the chip."""

import numpy as np
import pytest

from benchmark import checks, manifest, weights
from benchmark.reference import serve_check, train_check
from benchmark.reference import transformer_ref as ref
from benchmark.tests import rehearsal

MAN = manifest.load()
BY_KIND = {}
for _cell in MAN["workloads"]:
    BY_KIND.setdefault(manifest.traffic_of(_cell)["kind"], _cell)


def tiny_config():
    import json

    with open(rehearsal.TINY) as f:
        return json.load(f)


def test_bfloat16_state_training_fails_the_train_limits():
    import jax

    from dist_keras_tpu.models.transformer import transformer_config

    cell = BY_KIND["train_step"]
    conf = tiny_config()
    traffic = rehearsal.shrink(manifest.traffic_of(cell))
    train = conf["train"]
    cfg = transformer_config(
        input_dim=train["input_dim"], seq_len=traffic["seq_len"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_layers=2, d_ff=conf["ffn_dim"], n_classes=train["n_classes"])
    limits = checks.limits_for(cell["name"])
    failures = 0
    for seed in (1, 2 ** 31 + 2, 3):
        key = weights.base_key(seed)
        probes = train_check.Probes(
            jax.eval_shape(lambda k: weights.transformer(k, cfg), key), seed,
            cfg)
        sound = train_check.reference_steps(cfg, traffic, train, key, 3,
                                            probes)
        low = train_check.reference_steps(cfg, traffic, train, key, 3,
                                          probes, ref.BFLOAT16_STATE)
        got, _ = train_check.gaps(low, sound)
        same, _ = train_check.gaps(sound, sound)
        assert all(v == 0.0 for v in same.values())
        failures += any(got[name] > limits[name] for name in limits)
    assert failures == 3


@pytest.mark.parametrize("kind", ["serve_open", "serve_closed"])
def test_fp8_serving_fails_the_serve_limits(kind):
    cell = BY_KIND[kind]
    conf = tiny_config()
    from benchmark import serving

    cfg = serving.model_config(conf)
    limits = checks.limits_for(cell["name"])
    failures = 0
    for seed in (1, 2 ** 31 + 2, 3):
        rng = np.random.default_rng(seed)
        samples = [{"tokens": rng.integers(0, cfg["n_classes"], 60).tolist(),
                    "prompt_len": 12} for _ in range(6)]
        got = serve_check.control_numbers(cfg, weights.base_key(seed),
                                          samples, ref.FP8)
        assert got["positions"] == 6 * 48
        failures += any(got[name] > limits[name] for name in limits)
    assert failures == 3
