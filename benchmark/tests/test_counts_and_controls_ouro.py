"""The looped cell's cases of ``test_opcount.py`` and ``test_controls.py``,
in a file of their own: a PR that brings a configuration adds files to the
benchmark and edits none.  The counts of ``opcount_ouro`` against counts
made by hand at the published widths, and the controls (the reference
carried in a lower precision, put in the program's place) against the
cell's own limits."""

import json
import os

import numpy as np

from benchmark import checks, manifest, weights
from benchmark.families import ouro as family
from benchmark.reference import ouro_check as check
from benchmark.tests import rehearsal
from benchmark.trace import opcount, opcount_ouro

CELL = "ouro_serve_reason"


def published_cfg():
    man = manifest.load()
    return family.model_config(manifest.config_of(
        man, manifest.cell(man, CELL)))


def test_a_step_of_16_slots_at_6650_live_positions_must_read_15_5_gb():
    cfg = published_cfg()
    # a layer by hand: q, k, v, o 2048 x 2048 each, gate, up and down
    # 2048 x 5632 each, four norm vectors
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert opcount_ouro.layer_values(cfg) == layer == 51388416
    need = opcount_ouro.decode_step_bytes(cfg, 16)
    assert set(need) == {"fixed", "per_live_position"}
    # a position's v | k row, 16,384 B, in every one of 4 x 12 entries
    assert need["per_live_position"] == 4 * 4 * 12 * 2 * 16 * 128 == 786432
    # the 12 layers FOUR times (9.87 GB), the head 0.40 GB, the final
    # norm, the gate and its bias, 16 embedding rows
    head = 2048 * 49152
    assert need["fixed"] == 4 * (4 * 12 * layer + head + 2048 + 2049
                                 + 16 * 2048)
    assert 9.86e9 < 4 * 4 * 12 * layer < 9.87e9
    total = need["fixed"] + 6650 * need["per_live_position"]
    assert 15.45e9 < total < 15.55e9
    # without the loop the same twelve layers would read 4.2 GB a step
    once = opcount_ouro.decode_step_bytes({**cfg, "ut_steps": 1}, 16)
    assert 4.15e9 < once["fixed"] + 6650 * once["per_live_position"] < 4.2e9
    # a slot more is a row of the table more
    assert opcount_ouro.decode_step_bytes(cfg, 17)["fixed"] \
        - need["fixed"] == 4 * 2048
    assert opcount_ouro.kernel_unit_bytes(cfg) == {
        "latent_decode": ("decode.kv.live_positions", 16384)}


def test_a_prefill_makes_a_flash_forward_a_layer_a_pass():
    cfg = published_cfg()
    calls, ops, moved = opcount_ouro.prefill_flash(cfg, 288)
    assert calls == 48
    one = opcount.flash_fwd(16, 288, 288, 128, True, 4)
    assert (ops, moved) == (48 * one[0], 48 * one[1])
    # by hand: two products a kept pair a head, 288 x 289 / 2 pairs
    assert one[0] == 2 * 2 * 16 * (288 * 289 // 2) * 128
    assert one[1] == 4 * 16 * 128 * 4 * 288 + 4 * 16 * 288


def test_fp8_control_fails_the_cells_limits_and_bfloat16_passes():
    with open(os.path.join(manifest.ROOT,
                           rehearsal.toy_files()["ouro"])) as f:
        cfg = family.model_config(json.load(f))
    limits = checks.limits_for(CELL)
    failures = passes = 0
    for seed in (1, 2 ** 31 + 2, 3):
        rng = np.random.default_rng(seed)
        samples = [{"tokens": rng.integers(0, cfg["vocab_size"], 60).tolist(),
                    "prompt_len": 12} for _ in range(4)]
        key = weights.base_key(seed)
        got = check.control_numbers(cfg, key, samples, check.CONTROLS["fp8"])
        assert got["positions"] == 4 * 48
        failures += any(got[name] > limits[name] for name in limits)
        low = check.control_numbers(cfg, key, samples,
                                    check.CONTROLS["bfloat16"])
        passes += all(low[name] <= limits[name] for name in limits)
    assert failures == 3 and passes == 3
