"""The gated-delta-rule, full-attention family in the harness, on the CPU
at a toy size: the cell rehearsed with the family's own toy configuration
(its engine with the configuration's state rows, its reference, its
counters and readers), the configuration and the traffic mix as stated,
the fp8 control failing the cell's limits, and the counts of required
work."""

import copy
import json
import os
import time

import numpy as np
import pytest

from benchmark import checks, manifest, trafficgen, weights
from benchmark.families import olmo_hybrid as family
from benchmark.reference import olmo_hybrid_check
from benchmark.reference import olmo_hybrid_ref as ref
from benchmark.reference.transformer_ref import FP8
from benchmark.tests import rehearsal
from benchmark.trace import opcount_olmo_hybrid

CELL = "olmohybrid_serve_threads"
TINY = os.path.join("benchmark", "tests", "data",
                    "tiny-olmo-hybrid-config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cfg():
    with open(os.path.join(manifest.ROOT, TINY)) as f:
        return family.model_config(json.load(f))


def test_manifest_resolves_the_cell_to_this_family():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    conf = manifest.config_of(man, cell)
    assert cell["chips"] == 1 and conf["family"] == "olmo_hybrid"
    assert manifest.kind_of(manifest.traffic_of(cell)).run
    cfg = family.model_config(conf)
    # every width as published, the cut as the file states it
    pub = conf["published"]
    assert (cfg["d_model"], cfg["n_heads"], cfg["d_ff"], cfg["vocab_size"],
            cfg["linear_heads"], cfg["linear_key_dim"],
            cfg["linear_value_dim"], cfg["conv_kernel"],
            cfg["allow_neg_eigval"], cfg["rms_norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["intermediate_size"], pub["vocab_size"],
        pub["linear_num_value_heads"], pub["linear_key_head_dim"],
        pub["linear_value_head_dim"], pub["linear_conv_kernel_dim"],
        pub["linear_allow_neg_eigval"], pub["rms_norm_eps"])
    assert (3840, 30, 96, 192, 4, 11008, 100352) == (
        cfg["d_model"], cfg["linear_heads"], cfg["linear_key_dim"],
        cfg["linear_value_dim"], cfg["conv_kernel"], cfg["d_ff"],
        cfg["vocab_size"])
    for key, value in pub.items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers"]
    # layers 0-7: two whole periods, six linear layers and two full ones
    assert cfg["n_layers"] == 8
    assert cfg["layer_types"] == pub["layer_types"][:8] == \
        conf["serve"]["layer_types"]
    assert cfg["layer_types"].count("linear_attention") == 6
    serve = conf["serve"]
    assert (serve["state_rows"], serve["max_queue"], serve["positions"],
            serve["decode_ladder"]) == (40, 64, 1536, [8, 32])
    assert len(serve["prefill_ladder"]) <= 4 and \
        serve["prefill_ladder"][-1] == 1024
    names = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", [])}
    assert {"decode_hbm_roofline.threads", "flash_share_pct_serve.batch",
            "state_live_rows_mean.threads", "kv_live_positions_mean.threads",
            "scan_padding_share_pct.threads",
            "state_step_roofline.threads"} <= names


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["source"] == row["source_url"]
    conf = manifest.config_of(man, manifest.cell(man, CELL))
    assert conf["published"] == row["config"]
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == {"num_hidden_layers"} == set(entry["reduced"])


def test_threads_draws_the_stated_lengths_in_blocks_alike():
    traffic = manifest.traffic_of(manifest.cell(manifest.load(), CELL))
    assert (traffic["clients"], traffic["requests"], traffic["block"]) == (
        32, 128, 32)
    assert traffic["lead_in_s"] == 10.0
    a = trafficgen.requests(traffic, 128, 100352, 5)
    b = trafficgen.requests(traffic, 128, 100352, 2 ** 31 + 9)
    for reqs in (a, b):
        prompts = [len(r["prompt"]) for r in reqs]
        outputs = [r["max_new"] for r in reqs]
        assert 256 <= min(prompts) and max(prompts) <= 1024
        assert 352 <= min(outputs) and max(outputs) <= 416
        assert abs(np.mean(prompts) - 640) < 1 and \
            abs(np.mean(outputs) - 384) < 1
        assert max(p + o for p, o in zip(prompts, outputs)) <= 1536
        assert max(int(r["prompt"].max()) for r in reqs) > 99000
        for lo in range(0, 128, 32):
            block = sorted(outputs[lo:lo + 32])
            assert abs(sum(block) - 32 * 384) <= 32 * 6
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
        same = olmo_hybrid_check.served_numbers(cfg, key, [
            {**s, "tokens": s["tokens"][:12] + greedy(cfg, key, s)}
            for s in samples[:1]])
        assert same["logit_gap_max"] == 0.0
        got = olmo_hybrid_check.control_numbers(cfg, key, samples, FP8)
        assert got["positions"] == 4 * 48
        failures += any(got[name] > limits[name] for name in limits)
    assert failures == 3


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 6])
def test_control_mode_of_the_check_is_not_correct(monkeypatch, capsys, seed):
    """``python3 -m benchmark.reference.olmo_hybrid_check``: the fp8
    control at the cell's (here: the toy's) sizes through the cell's
    limits."""
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
    assert olmo_hybrid_check.main(
        ["--workload", CELL, "--seed", str(seed)]) == 0
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
    assert got["kv_live_positions_mean.threads"]["value"] > 1.0
    # the toy's top decode rung is 8 slots
    assert 0.0 < got["state_live_rows_mean.threads"]["value"] <= 8.0
    # prompts of 3-28 tokens in rungs of 16 and 32: most of a rung is padding
    assert got["scan_padding_share_pct.threads"]["value"] > 10.0
    assert 0.0 < got["prefill_share_pct.threads"]["value"] < 100.0
    assert got["window_compiles_serve.batch"]["value"] == 0.0
    # device-trace readers find no TPU plane on the CPU and report nothing
    assert "decode_hbm_roofline.threads" not in got
    assert "state_step_roofline.threads" not in got


def test_required_bytes_of_the_step_and_its_kernels():
    man = manifest.load()
    cfg = family.model_config(manifest.config_of(man,
                                                 manifest.cell(man, CELL)))
    need = opcount_olmo_hybrid.decode_step_bytes(cfg, 32)
    # K and V of 30 heads of 128 in the 2 attention layers
    assert need["per_live_position"] == 61440
    # 6 layers x (552,960 + 34,560) values, read and written
    assert need["per_live_row"] == 2 * 4 * 6 * (552960 + 34560)
    # 6.66 GB of layers and 1.54 GB of head: everything but the embedding
    assert 8.19e9 < need["fixed"] < 8.21e9
    units = opcount_olmo_hybrid.kernel_unit_bytes(cfg)
    assert units["latent_decode"] == ("decode.kv.live_positions", 30720)
    hist, row = units["gdn_state_step"]
    assert hist == "decode.state.live_rows"
    assert 2 * 4 * 552960 < row < 2 * 4 * 552960 * 1.02
