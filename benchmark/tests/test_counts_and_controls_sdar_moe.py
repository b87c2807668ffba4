"""The block-diffusion cell's cases of ``test_opcount.py`` and
``test_controls.py``, in a file of their own: a PR that brings a
configuration adds files to the benchmark and edits none.  The counts of
``opcount_sdar_moe`` against counts made by hand, and the controls (the
reference carried in a lower precision, put in the program's place)
against the cell's own limits."""

import json
import os

import numpy as np

from benchmark import checks, manifest, weights
from benchmark.families import sdar_moe as family
from benchmark.reference import sdar_moe_check as check
from benchmark.reference import sdar_moe_ref as ref
from benchmark.tests import rehearsal
from benchmark.trace import opcount, opcount_lfm2_moe, opcount_sdar_moe

CELL = "sdar_serve_blocks"


def test_block_causal_flash_and_the_pass_over_blocks_by_hand():
    # by hand: two queries of the second block of two see four keys each
    assert opcount_sdar_moe.block_causal_pairs(2, 4, 2) == 8
    assert opcount_sdar_moe.block_causal_pairs(4, 4, 2) == 2 * 2 + 2 * 4
    assert opcount_sdar_moe.block_causal_pairs(8, 8, 4) == 4 * 4 + 4 * 8
    # a block of one position is the causal mask
    for tq, tk in ((4, 4), (2, 5), (1, 9), (768, 768)):
        assert opcount_sdar_moe.block_causal_pairs(tq, tk, 1) \
            == opcount.causal_pairs(tq, tk)
    # and counted pair by pair
    for tq, tk, b in ((8, 8, 4), (4, 12, 4), (6, 6, 2)):
        mask = ref.block_causal(np.arange(tk - tq, tk), np.arange(tk), b)
        assert opcount_sdar_moe.block_causal_pairs(tq, tk, b) \
            == int(np.asarray(mask).sum())
    ops, moved = opcount_sdar_moe.flash_fwd_block_causal(
        32, 4, 1024, 1024, 128, 4, 4)
    pairs = 1024 * (1024 + 4) // 2
    assert ops == 2 * 2 * 32 * pairs * 128
    assert moved == 4 * 128 * (2 * 32 * 1024 + 2 * 4 * 1024) + 4 * 32 * 1024
    causal = opcount_lfm2_moe.flash_fwd_grouped(32, 4, 1024, 1024, 128, True,
                                                4)
    assert moved == causal[1] and 1.0 < ops / causal[0] < 1.003
    man = manifest.load()
    cfg = family.model_config(manifest.config_of(
        man, manifest.cell(man, CELL)))
    need = opcount_sdar_moe.decode_step_bytes(cfg, 32)
    assert need["per_expert_cell"] == 4 * 3 * 2048 * 768
    assert need["per_live_position"] == 4 * 16 * 2 * 4 * 128 == 65536
    # 16 layers of 19.14 M outside their experts, the head 311 M, 128
    # embedding rows
    assert 2.46e9 < need["fixed"] < 2.48e9
    # with every (layer, held expert) cell reached: the weights outside
    # the embedding, 8.55 - 1.24 GB
    assert 7.29e9 < need["fixed"] + 256 * need["per_expert_cell"] < 7.31e9
    assert opcount_sdar_moe.kernel_unit_bytes(cfg) == {
        "latent_decode": ("decode.kv.live_positions", 4096)}


def test_fp8_trajectories_fail_the_block_cells_limits_and_bfloat16_passes(
        monkeypatch):
    """The cell whose ``correct`` compares a served TRAJECTORY (generation
    by diffusion over blocks): at every state of seeded trajectories the
    fp8 reference fixes what IT finds most confident, and fails the
    cell's limits; the bfloat16 one, the precision the configuration
    states, passes them."""
    monkeypatch.setattr(check, "BLOCKS", 6)
    with open(os.path.join(manifest.ROOT,
                           rehearsal.toy_files()["sdar_moe"])) as f:
        cfg = family.model_config(json.load(f))
    limits = checks.limits_for(CELL)
    failures = passes = 0
    for seed in (1, 2 ** 31 + 2, 3):
        rng = np.random.default_rng([seed, 5])
        samples = [check.seeded_trajectory(cfg, rng, n, m)
                   for n, m in ((21, 38), (12, 30))]
        assert all(len(s["tokens"]) % 4 == 0 for s in samples)
        key = weights.base_key(seed)
        got = check.control_numbers(cfg, key, samples, seed,
                                    check.CONTROLS["fp8"])
        assert got["passes"] == 3 + 5 * 4 + 6 * 4
        failures += any(got[name] > limits[name] for name in limits)
        low = check.control_numbers(cfg, key, samples, seed,
                                    check.CONTROLS["bfloat16"])
        passes += all(low[name] <= limits[name] for name in limits)
    assert failures == 3 and passes == 3
