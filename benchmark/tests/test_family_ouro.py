"""The looped family in the harness, on the CPU at a toy size: the
configuration as the catalog's row gives it and the cut as the file
states it, the traffic mix, the cell rehearsed with the family's own toy
configuration (its engine, its reference, its counters and readers: four
passes a step, the head reading the fourth), and the control mode of its
check."""

import copy
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import manifest, trafficgen, weights
from benchmark.families import ouro as family
from benchmark.reference import ouro_check
from benchmark.reference import ouro_ref as ref
from benchmark.tests import rehearsal

CELL = "ouro_serve_reason"
TINY = os.path.join("benchmark", "tests", "data", "tiny-ouro-config.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_conf():
    with open(os.path.join(manifest.ROOT, TINY)) as f:
        return json.load(f)


def tiny_cfg():
    return family.model_config(tiny_conf())


def test_manifest_resolves_the_cell_to_this_family():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    conf = manifest.config_of(man, cell)
    assert cell["chips"] == 1 and conf["family"] == "ouro"
    assert manifest.traffic_of(cell)["kind"] == "serve_closed_family"
    assert manifest.kind_of(manifest.traffic_of(cell)).run
    cfg = family.model_config(conf)
    # every width as published, the loop as published, the cut as stated
    pub = conf["published"]
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"],
            cfg["ut_steps"], cfg["early_exit_threshold"], cfg["rope_theta"],
            cfg["rms_norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"], pub["vocab_size"], pub["total_ut_steps"],
        pub["early_exit_threshold"], pub["rope_theta"], pub["rms_norm_eps"])
    assert (2048, 16, 16, 128, 5632, 49152, 4, 1.0) == (
        cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
        cfg["d_ff"], cfg["vocab_size"], cfg["ut_steps"],
        cfg["early_exit_threshold"])
    for key, value in pub.items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers"]
    assert cfg["n_layers"] == 12 and pub["num_hidden_layers"] == 48
    serve = conf["serve"]
    assert (serve["positions"], serve["decode_ladder"][-1]) == (768, 16)
    assert serve["prefill_ladder"][0] >= 96 and \
        serve["prefill_ladder"][-1] == 288
    assert serve["positions"] % serve["page_size"] == 0
    for key in ("reduced_why", "assumed", "precision", "deployment"):
        assert conf[key], key
    names = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", [])}
    own = {n for n in names if n.endswith(".reason")}
    assert own == {"decode_hbm_roofline.reason",
                   "kv_live_positions_mean.reason",
                   "loop_passes_mean.reason", "exit_pass_mean.reason",
                   "prefill_share_pct.reason"} and len(own) <= 16
    # a step yields one token a slot: the shared entries that reckon so
    assert {"sched_slots_mean.batch", "decode_step_p50_ms.batch",
            "gap_p99_ms.batch", "kv_read_roofline.threads",
            "flash_fwd_prefill_roofline", "steps_fed_share.batch"} <= names
    assert len(man["per_layer"]) <= 128
    # what needs no trace stands beside every untraced run's rate
    beside = {e["name"] for e, _, _ in manifest.metrics_for(
        man, CELL, "per_layer", manifest.PROGRAM_SOURCES)}
    assert {"loop_passes_mean.reason", "exit_pass_mean.reason",
            "kv_live_positions_mean.reason"} <= beside


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    man = manifest.load()
    entry = next(c for c in man["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == row["source_url"]
    conf = manifest.config_of(man, manifest.cell(man, CELL))
    assert conf["published"] == row["config"]
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == {"num_hidden_layers"} == set(entry["reduced"])


def test_reason_draws_the_stated_lengths_in_blocks_alike():
    traffic = manifest.traffic_of(manifest.cell(manifest.load(), CELL))
    assert (traffic["clients"], traffic["requests"], traffic["block"]) == (
        16, 64, 16)
    assert traffic["lead_in_s"] == 10.0 and traffic["stagger_start"]
    assert (traffic["sub_windows"], traffic["check_requests"],
            traffic["trace_seconds"]) == (9, 2, 4.0)
    lo, hi = (traffic["classes"][0]["output_len"][k] for k in ("min", "max"))
    assert (lo, hi) in ((416, 480), (432, 464))    # the mix or its fallback
    a = trafficgen.requests(traffic, 64, 49152, 5)
    b = trafficgen.requests(traffic, 64, 49152, 2 ** 31 + 9)
    for reqs in (a, b):
        prompts = [len(r["prompt"]) for r in reqs]
        outputs = [r["max_new"] for r in reqs]
        assert 96 <= min(prompts) and max(prompts) <= 288
        assert lo <= min(outputs) and max(outputs) <= hi
        assert abs(np.mean(prompts) - 192) < 1 and \
            abs(np.mean(outputs) - 448) < 1
        # the longest request fills a slot's positions and no more
        assert max(p + o for p, o in zip(prompts, outputs)) <= 768
        assert max(int(r["prompt"].max()) for r in reqs) > 48000
        for at in range(0, 64, 16):
            assert abs(sum(outputs[at:at + 16]) - 16 * 448) <= 16 * 4
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]
    # caller i's first reply is cut to ceil(length x (i + 1) / 16)
    first = list(zip(range(16), trafficgen.closed_order(traffic, a, 16)))
    assert [r["max_new"] for _, r in first] == [
        -(-a[i]["max_new"] * (i + 1) // 16) for i in range(16)]


def test_weights_are_a_function_of_seed_layer_and_leaf():
    cfg = tiny_cfg()
    key = weights.base_key(2 ** 31 + 3)
    whole = family.tree(key, cfg)
    again = family.layer_maker(cfg)(key, 2)
    for a, b in zip(jax.tree.leaves(whole["blocks"][2]),
                    jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)
    other = family.tree(weights.base_key(2 ** 31 + 4), cfg)
    assert not np.array_equal(whole["head"], other["head"])
    # the gate: a Glorot d -> 1 product, its bias zero
    d = cfg["d_model"]
    assert np.abs(whole["gate"]["w"]).max() <= np.sqrt(6.0 / (d + 1))
    assert float(whole["gate"]["b"]) == 0.0


def test_a_served_request_of_the_references_own_tokens_reads_zero():
    cfg = tiny_cfg()
    key = weights.base_key(7)
    params = family.tree(key, cfg)
    conf = family.reference_config(cfg)
    tokens = np.random.default_rng(7).integers(
        0, cfg["vocab_size"], 12).tolist()
    for _ in range(6):
        z = ref.forward(params, np.asarray(tokens), conf)
        tokens.append(int(np.asarray(z[-1]).argmax()))
    got = ouro_check.served_numbers(cfg, key, [
        {"tokens": tokens, "prompt_len": 12}])
    assert got["positions"] == 6 and got["logit_gap_max"] == 0.0
    wrong = list(tokens)
    wrong[14] = (wrong[14] + 1) % cfg["vocab_size"]
    bad = ouro_check.served_numbers(cfg, key, [
        {"tokens": wrong, "prompt_len": 12}])
    assert bad["logit_gap_max"] > 0.01 and bad["flip_share"] > 0


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 6])
def test_control_mode_of_the_check_is_not_correct(monkeypatch, capsys, seed):
    """``python3 -m benchmark.reference.ouro_check``: the fp8 control at
    the cell's (here: the toy's) sizes through the cell's limits."""
    real_traffic = manifest.traffic_of
    tiny = tiny_conf()

    def toy_traffic(cell):
        # replies long enough for a share of flipped tokens to be read
        t = rehearsal.shrink(real_traffic(cell))
        t["classes"][0].update(
            prompt_len={"dist": "uniform", "min": 3, "max": 20},
            output_len={"dist": "uniform", "min": 20, "max": 40})
        return t

    monkeypatch.setattr(manifest, "config_of", lambda man, cell: tiny)
    monkeypatch.setattr(manifest, "traffic_of", toy_traffic)
    assert ouro_check.main(["--workload", CELL, "--seed", str(seed)]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["control"] == "fp8"
    assert last["positions"] == 40 + 30    # the longest and the middle reply


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsed_with_its_own_family(monkeypatch, tmp_path, trace):
    from benchmark import run

    man = copy.deepcopy(manifest.load())
    for c in man["configs"]:
        c["file"] = TINY
    real = manifest.traffic_of
    monkeypatch.setattr(
        manifest, "traffic_of",
        lambda cell: {**rehearsal.shrink(real(cell), outputs=(8, 20)),
                      "lead_in_s": 1.0})
    result = run.run_cell(man, CELL, 2 ** 31 + 5, 1.5, trace,
                          jax.devices()[:1], rehearsal.PEAKS, str(tmp_path),
                          process_start=time.perf_counter())
    json.dumps(result)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["compared"]) == {"logit_gap_mean", "flip_share",
                                       "failed_requests"}
    got = result["metrics"]
    if not trace:
        assert {"setup_s", "serve_tokens_per_s"} <= set(got)
        got = result["per_layer"]
    # the loop: four passes every step, the head reading the fourth
    assert got["loop_passes_mean.reason"]["value"] == 4.0
    assert got["exit_pass_mean.reason"]["value"] == 4.0
    assert got["kv_live_positions_mean.reason"]["value"] > 1.0
    assert 0.0 < got["prefill_share_pct.reason"]["value"] < 100.0
    if not trace:
        return
    # the toy's top decode rung is 8 slots, a token a slot a step
    assert 0.0 < got["sched_slots_mean.batch"]["value"] <= 8.0
    assert got["window_compiles_serve.batch"]["value"] == 0.0
    # device-trace readers find no TPU plane on the CPU and report nothing
    assert "decode_hbm_roofline.reason" not in got
    assert "kv_read_roofline.threads" not in got
