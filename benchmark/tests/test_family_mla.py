"""The latent-attention, sparse-expert family in the harness, on the CPU at
a toy size: the cell rehearsed with the family's own toy configuration
(its engine, its reference, its counters and readers), the traffic mix as
stated, the reference in blocks equal to the reference in one piece, the
fp8 control failing the cell's limits, and the counts of required work."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmark import checks, manifest, trafficgen, weights
from benchmark.families import mla_moe as family
from benchmark.reference import mla_moe_check
from benchmark.reference import mla_moe_ref as ref
from benchmark.reference.transformer_ref import FP8
from benchmark.tests import rehearsal
from benchmark.trace import opcount, opcount_mla

CELL = "kimivl_serve_longgen"
TINY = os.path.join("benchmark", "tests", "data", "tiny-mla-config.json")


def tiny_cfg():
    with open(os.path.join(manifest.ROOT, TINY)) as f:
        return family.model_config(json.load(f))


def test_manifest_resolves_the_cell_to_this_family():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    conf = manifest.config_of(man, cell)
    assert cell["chips"] == 1 and conf["family"] == "mla_moe"
    assert manifest.kind_of(manifest.traffic_of(cell)).run
    cfg = family.model_config(conf)
    # every width as published, the cut as the file states it
    pub = conf["published"]
    assert (cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["d_ff"], cfg["moe_d_ff"], cfg["top_k"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["kv_lora_rank"],
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts_per_tok"], pub["n_routed_experts"],
        pub["vocab_size"])
    for key, value in pub.items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert cfg["held_experts"] == list(range(8)) and cfg["n_layers"] == 9
    names = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", [])}
    assert {"moe_held_share_pct.longgen", "decode_hbm_roofline.longgen",
            "flash_fwd_roofline.longgen"} <= names


def test_longgen_draws_the_stated_lengths_in_blocks_alike():
    traffic = manifest.traffic_of(manifest.cell(manifest.load(), CELL))
    assert (traffic["clients"], traffic["requests"], traffic["block"]) == (
        32, 128, 32)
    assert 0.0 <= traffic["lead_in_s"] <= 10.0
    a = trafficgen.requests(traffic, 128, 163840, 5)
    b = trafficgen.requests(traffic, 128, 163840, 2 ** 31 + 9)
    for reqs in (a, b):
        prompts = [len(r["prompt"]) for r in reqs]
        outputs = [r["max_new"] for r in reqs]
        assert 2048 <= min(prompts) and max(prompts) <= 6144
        # ISSUE 27's fallback range (the first was 128-512), the same mean
        assert 256 <= min(outputs) and max(outputs) <= 384
        assert abs(np.mean(prompts) - 4096) < 1 and \
            abs(np.mean(outputs) - 320) < 1
        assert max(p + o for p, o in zip(prompts, outputs)) <= 6656
        assert max(int(r["prompt"].max()) for r in reqs) > 160000
        # every block of 32 holds one request of each 32-quantile of the
        # output lengths: the same work whichever stretch a window reaches
        edges = sorted(outputs)[::4]
        for lo in range(0, 128, 32):
            block = sorted(outputs[lo:lo + 32])
            assert all(e <= v for e, v in zip(edges, block))
            assert abs(sum(block) - 32 * 320) <= 32 * 6
    # every seed offers the same lengths, each in an order of its own
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


@pytest.mark.parametrize("q_block", [8, 16, 64])
def test_reference_in_blocks_equals_reference_in_one_piece(q_block):
    cfg = tiny_cfg()
    params = family.tree(weights.base_key(2 ** 31 + 3), cfg)
    conf = family.reference_config(cfg)
    held = tuple(cfg["held_experts"])
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], 50)
    whole = ref.forward(params, tokens, conf, held)
    blocks = ref.forward(params, tokens, conf, held, q_block=q_block)
    # the same numbers; the float32 products sum in another order (4e-6
    # read, on logits up to 1)
    np.testing.assert_allclose(blocks, whole, atol=2e-5, rtol=0)


def test_fp8_control_fails_the_cells_limits():
    cfg = tiny_cfg()
    limits = checks.limits_for(CELL)
    failures = 0
    for seed in (1, 2 ** 31 + 2, 3):
        rng = np.random.default_rng(seed)
        samples = [{"tokens": rng.integers(0, cfg["vocab_size"], 60).tolist(),
                    "prompt_len": 12} for _ in range(4)]
        key = weights.base_key(seed)
        same = mla_moe_check.served_numbers(cfg, key, [
            {**s, "tokens": s["tokens"][:12] + greedy(cfg, key, s)}
            for s in samples[:1]])
        assert same["logit_gap_max"] == 0.0
        got = mla_moe_check.control_numbers(cfg, key, samples, FP8)
        assert got["positions"] == 4 * 48
        failures += any(got[name] > limits[name] for name in limits)
    assert failures == 3


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 6])
def test_control_mode_of_the_check_is_not_correct(monkeypatch, capsys, seed):
    """``python3 -m benchmark.reference.mla_moe_check``: the fp8 control
    at the cell's (here: the toy's) sizes through the cell's limits."""
    real_traffic = manifest.traffic_of
    with open(os.path.join(manifest.ROOT, TINY)) as f:
        tiny = json.load(f)
    monkeypatch.setattr(manifest, "config_of", lambda man, cell: tiny)
    monkeypatch.setattr(manifest, "traffic_of",
                        lambda cell: rehearsal.shrink(real_traffic(cell)))
    assert mla_moe_check.main(["--workload", CELL, "--seed", str(seed)]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["control"] == "fp8"
    assert last["positions"] == 6 + 4      # the longest and the middle reply


def greedy(cfg, key, sample):
    """The reference's own greedy continuation of a sample's prompt."""
    tokens = list(sample["tokens"][:sample["prompt_len"]])
    params = family.tree(key, cfg)
    conf = family.reference_config(cfg)
    for _ in range(6):
        z = ref.forward(params, np.asarray(tokens), conf,
                        tuple(cfg["held_experts"]))
        tokens.append(int(np.asarray(z[-1]).argmax()))
    return tokens[sample["prompt_len"]:]


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsed_with_its_own_family(monkeypatch, tmp_path, trace):
    import jax

    from benchmark import run

    man = copy.deepcopy(manifest.load())
    for c in man["configs"]:
        c["file"] = TINY
    real = manifest.traffic_of
    monkeypatch.setattr(
        manifest, "traffic_of",
        lambda cell: {**rehearsal.shrink(real(cell)), "lead_in_s": 1.0})
    result = run.run_cell(man, CELL, 2 ** 31 + 5, 1.5, trace,
                          jax.devices()[:1], rehearsal.PEAKS, str(tmp_path),
                          process_start=time.perf_counter())
    json.dumps(result)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    got = result["metrics"]
    if not trace:
        assert {"setup_s", "serve_tokens_per_s"} <= set(got)
        return
    # 2 of 8 experts held: a quarter of the chosen pairs, within sampling
    assert 10.0 < got["moe_held_share_pct.longgen"]["value"] < 45.0
    assert got["moe_load_max_over_mean.longgen"]["value"] >= 1.0
    assert got["latent_live_positions_mean.longgen"]["value"] > 1.0
    assert 0.0 < got["prefill_share_pct.longgen"]["value"] < 100.0
    assert got["window_compiles_serve.batch"]["value"] == 0.0
    # device-trace readers find no TPU plane on the CPU and report nothing
    assert "decode_hbm_roofline.longgen" not in got


def test_required_work_of_the_new_kernel_and_step():
    ops, moved = opcount_mla.flash_fwd_mixed(16, 4096, 4096, 192, 128, True,
                                             4)
    pairs = opcount.causal_pairs(4096, 4096)
    assert ops == 2 * 16 * pairs * (192 + 128)
    assert moved == 4 * 16 * 4096 * (2 * 192 + 2 * 128) + 4 * 16 * 4096
    # equal widths: the count the accepted reader uses
    assert opcount_mla.flash_fwd_mixed(8, 512, 512, 128, 128, True, 2) == \
        opcount.flash_fwd(8, 512, 512, 128, True, 2)
    man = manifest.load()
    cfg = family.model_config(manifest.config_of(man,
                                                 manifest.cell(man, CELL)))
    need = opcount_mla.decode_step_bytes(cfg, 32)
    assert need["per_expert_cell"] == 4 * 3 * 2048 * 1408
    assert need["per_live_position"] == 4 * 9 * 576
    # 13.76 M attention a layer, the dense layer, 17.4 M shared and router
    # an expert layer, 335.5 M of head: 0.50 + 0.28 + 0.56 + 1.34 GB
    assert 2.6e9 < need["fixed"] < 2.8e9
