"""The gated short-convolution, grouped-query, sparse-expert family in the
harness, on the CPU at a toy size: the cell rehearsed with the family's
own toy configuration (its engine, its reference, its counters and
readers), the configuration and the traffic mix as stated, the fp8 control
failing the cell's limits, and the counts of required work."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmark import checks, manifest, trafficgen, weights
from benchmark.families import lfm2_moe as family
from benchmark.reference import lfm2_moe_check
from benchmark.reference import lfm2_moe_ref as ref
from benchmark.reference.transformer_ref import FP8
from benchmark.tests import rehearsal
from benchmark.trace import opcount, opcount_lfm2_moe

CELL = "lfm2_serve_turns"
TINY = os.path.join("benchmark", "tests", "data", "tiny-lfm2-config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cfg():
    with open(os.path.join(manifest.ROOT, TINY)) as f:
        return family.model_config(json.load(f))


def test_manifest_resolves_the_cell_to_this_family():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    conf = manifest.config_of(man, cell)
    assert cell["chips"] == 1 and conf["family"] == "lfm2_moe"
    assert manifest.kind_of(manifest.traffic_of(cell)).run
    cfg = family.model_config(conf)
    # every width as published, the cut as the file states it
    pub = conf["published"]
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"],
            cfg["moe_d_ff"], cfg["top_k"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["conv_l_cache"], cfg["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["num_experts_per_tok"],
        pub["num_experts"], pub["vocab_size"], pub["conv_L_cache"],
        pub["rope_theta"])
    for key, value in pub.items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers"]
    assert cfg["held_experts"] == list(range(32)) and cfg["n_layers"] == 8
    # layers 0-7: two whole periods, both dense layers, six expert layers
    assert cfg["layer_types"] == pub["layer_types"][:8] == \
        conf["serve"]["layer_types"]
    assert cfg["layer_types"].count("conv") == 6
    assert cfg["num_dense_layers"] == 2
    names = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", [])}
    assert {"moe_load_max_over_mean.turns", "decode_hbm_roofline.turns",
            "flash_fwd_roofline.turns", "moe_experts_hit_mean.turns",
            "kv_live_positions_mean.turns"} <= names


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["source"] == row["source_url"]
    conf = manifest.config_of(man, manifest.cell(man, CELL))
    assert conf["published"] == row["config"]
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == {"num_hidden_layers"} == set(entry["reduced"])


def test_turns_draws_the_stated_lengths_in_blocks_alike():
    traffic = manifest.traffic_of(manifest.cell(manifest.load(), CELL))
    assert (traffic["clients"], traffic["requests"], traffic["block"]) == (
        64, 256, 64)
    assert traffic["lead_in_s"] == 10.0
    a = trafficgen.requests(traffic, 256, 65536, 5)
    b = trafficgen.requests(traffic, 256, 65536, 2 ** 31 + 9)
    for reqs in (a, b):
        prompts = [len(r["prompt"]) for r in reqs]
        outputs = [r["max_new"] for r in reqs]
        assert 128 <= min(prompts) and max(prompts) <= 768
        assert 320 <= min(outputs) and max(outputs) <= 448
        assert abs(np.mean(prompts) - 448) < 1 and \
            abs(np.mean(outputs) - 384) < 1
        assert max(p + o for p, o in zip(prompts, outputs)) <= 1280
        assert max(int(r["prompt"].max()) for r in reqs) > 65000
        # every block of 64 holds one request of each 64-quantile of the
        # output lengths: the same work whichever stretch a window reaches
        edges = sorted(outputs)[::4]
        for lo in range(0, 256, 64):
            block = sorted(outputs[lo:lo + 64])
            assert all(e <= v for e, v in zip(edges, block))
            assert abs(sum(block) - 64 * 384) <= 64 * 6
    # every seed offers the same lengths, each in an order of its own
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]


def greedy(cfg, key, sample, n=6):
    """The reference's own greedy continuation of a sample's prompt."""
    tokens = list(sample["tokens"][:sample["prompt_len"]])
    params = family.tree(key, cfg)
    conf = family.reference_config(cfg)
    for _ in range(n):
        z = ref.forward(params, np.asarray(tokens), conf)
        tokens.append(int(np.asarray(z[-1]).argmax()))
    return tokens[sample["prompt_len"]:]


def test_fp8_control_fails_the_cells_limits():
    cfg = tiny_cfg()
    limits = checks.limits_for(CELL)
    failures = 0
    for seed in (1, 2 ** 31 + 2, 3):
        rng = np.random.default_rng(seed)
        samples = [{"tokens": rng.integers(0, cfg["vocab_size"], 60).tolist(),
                    "prompt_len": 12} for _ in range(4)]
        key = weights.base_key(seed)
        same = lfm2_moe_check.served_numbers(cfg, key, [
            {**s, "tokens": s["tokens"][:12] + greedy(cfg, key, s)}
            for s in samples[:1]])
        assert same["logit_gap_max"] == 0.0
        got = lfm2_moe_check.control_numbers(cfg, key, samples, FP8)
        assert got["positions"] == 4 * 48
        failures += any(got[name] > limits[name] for name in limits)
    assert failures == 3


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 6])
def test_control_mode_of_the_check_is_not_correct(monkeypatch, capsys, seed):
    """``python3 -m benchmark.reference.lfm2_moe_check``: the fp8 control
    at the cell's (here: the toy's) sizes through the cell's limits."""
    real_traffic = manifest.traffic_of
    with open(os.path.join(manifest.ROOT, TINY)) as f:
        tiny = json.load(f)

    def toy_traffic(cell):
        # replies long enough for a share of flipped tokens to be read
        t = rehearsal.shrink(real_traffic(cell))
        t["classes"][0].update(
            prompt_len={"dist": "uniform", "min": 3, "max": 20},
            output_len={"dist": "uniform", "min": 20, "max": 40})
        return t

    monkeypatch.setattr(manifest, "config_of", lambda man, cell: tiny)
    monkeypatch.setattr(manifest, "traffic_of", toy_traffic)
    assert lfm2_moe_check.main(["--workload", CELL, "--seed", str(seed)]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["control"] == "fp8"
    assert last["positions"] == 40 + 30    # the longest and the middle reply


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
    assert got["moe_load_max_over_mean.turns"]["value"] >= 1.0
    # of 6 expert layers x 8 experts
    assert 0.0 < got["moe_experts_hit_mean.turns"]["value"] <= 48.0
    assert got["kv_live_positions_mean.turns"]["value"] > 1.0
    assert 0.0 < got["prefill_share_pct.turns"]["value"] < 100.0
    assert got["window_compiles_serve.batch"]["value"] == 0.0
    # device-trace readers find no TPU plane on the CPU and report nothing
    assert "decode_hbm_roofline.turns" not in got


def test_required_work_of_the_grouped_flash_and_the_step():
    ops, moved = opcount_lfm2_moe.flash_fwd_grouped(32, 8, 768, 768, 64,
                                                    True, 4)
    pairs = opcount.causal_pairs(768, 768)
    assert ops == 2 * 2 * 32 * pairs * 64
    assert moved == 4 * 64 * (2 * 32 * 768 + 2 * 8 * 768) + 4 * 32 * 768
    # as many K/V heads as query heads: the count the accepted reader uses
    assert opcount_lfm2_moe.flash_fwd_grouped(8, 8, 512, 512, 128, True, 2) \
        == opcount.flash_fwd(8, 512, 512, 128, True, 2)
    man = manifest.load()
    cfg = family.model_config(manifest.config_of(man,
                                                 manifest.cell(man, CELL)))
    need = opcount_lfm2_moe.decode_step_bytes(cfg, 64)
    assert need["per_expert_cell"] == 4 * 3 * 2048 * 1792
    assert need["per_live_position"] == 4 * 2 * 2 * 8 * 64
    # 6 convolutions 0.40, 2 attentions 0.08, 2 dense layers 0.35, the
    # tied table as the head 0.54 GB, routers, norms, 64 state rows 6 MB
    assert 1.37e9 < need["fixed"] < 1.40e9
    # with every (layer, expert) cell reached: the 9.8 GB of the weights
    assert 9.8e9 < need["fixed"] + 192 * need["per_expert_cell"] < 9.9e9
