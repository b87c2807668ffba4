"""The block-diffusion, grouped-query, sparse-expert family in the
harness, on the CPU at a toy size: the configuration as the catalog's row
gives it and the cut as the file states it, the traffic mix, weights that
are a function of (seed, layer, leaf), the trajectory check (a served
request reads zero, the fp8 control fails the cell's limits, the bfloat16
one passes), the cell rehearsed with the family's own toy configuration,
and the counts of required work."""

import copy
import json
import os
import time

import numpy as np
import pytest

import jax

from benchmark import checks, manifest, trafficgen, weights
from benchmark.families import sdar_moe as family
from benchmark.reference import sdar_moe_check as check
from benchmark.reference import sdar_moe_ref as ref
from benchmark.tests import rehearsal
from benchmark.trace import opcount_sdar_moe

CELL = "sdar_serve_blocks"
TINY = os.path.join("benchmark", "tests", "data", "tiny-sdar-config.json")
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
    assert cell["chips"] == 1 and conf["family"] == "sdar_moe"
    assert manifest.traffic_of(cell)["kind"] == "serve_closed_blocks"
    assert manifest.kind_of(manifest.traffic_of(cell)).run
    cfg = family.model_config(conf)
    # every width as published, the cut as the file states it
    pub = conf["published"]
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["head_dim"], cfg["moe_d_ff"], cfg["top_k"],
            cfg["n_routed_experts"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["moe_intermediate_size"], pub["num_experts_per_tok"],
        pub["num_experts"], pub["vocab_size"], pub["rope_theta"],
        pub["rms_norm_eps"])
    for key, value in pub.items():
        if key not in conf["reduced"]:
            assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers", "num_experts"]
    assert conf["num_experts"] == 16 == len(cfg["held_experts"])
    assert conf["num_experts_published"] == 128
    assert cfg["held_experts"] == list(range(16)) and cfg["n_layers"] == 16
    # the generation procedure as the file states it
    assert (cfg["block_length"], cfg["denoising_steps"],
            cfg["mask_token_id"]) == (4, 4, 151669)
    assert conf["generation"]["remasking_strategy"] == \
        "low_confidence_static"
    serve = conf["serve"]
    assert serve["positions"] == cfg["seq_len"] == 1536
    assert serve["page_size"] % 4 == 0 and serve["decode_ladder"] == [8, 32]
    for key in ("reduced_why", "assumed", "precision", "deployment"):
        assert conf[key], key
    names = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", [])}
    assert {"block_pass_p50_ms.blocks", "passes_per_block_mean.blocks",
            "tokens_per_pass_mean.blocks", "commit_share_pct.blocks",
            "decode_hbm_roofline.blocks", "kv_read_roofline.threads",
            "flash_fwd_roofline.blocks", "steps_fed_share.batch"} <= names
    # what assumes a token a slot a step is not joined
    assert not {"sched_slots_mean.batch", "decode_step_p50_ms.batch",
                "gap_p99_ms.batch"} & names


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_configuration_holds_the_catalogs_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    man = manifest.load()
    entry = next(c for c in man["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["source"] == row["source_url"]
    conf = manifest.config_of(man, manifest.cell(man, CELL))
    assert conf["published"] == row["config"]
    changed = {k for k, v in row["config"].items() if conf[k] != v}
    assert changed == {"num_hidden_layers", "num_experts"} \
        == set(entry["reduced"])


def test_blocks_draws_the_stated_lengths_below_the_mask_id():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    traffic = manifest.traffic_of(cell)
    cfg = family.model_config(manifest.config_of(man, cell))
    assert (traffic["clients"], traffic["requests"], traffic["block"]) == (
        32, 128, 32)
    assert traffic["lead_in_s"] == 10.0 and traffic["stagger_start"] is True
    assert (traffic["sub_windows"], traffic["check_requests"],
            traffic["trace_seconds"]) == (9, 2, 4.0)
    vocab = family.vocab(cfg)
    assert vocab == cfg["mask_token_id"] < cfg["vocab_size"]
    a = trafficgen.requests(traffic, 128, vocab, 5)
    b = trafficgen.requests(traffic, 128, vocab, 2 ** 31 + 9)
    for reqs in (a, b):
        prompts = [len(r["prompt"]) for r in reqs]
        outputs = [r["max_new"] for r in reqs]
        assert 256 <= min(prompts) and max(prompts) <= 1024
        assert 352 <= min(outputs) and max(outputs) <= 416
        assert abs(np.mean(prompts) - 640) < 1 and \
            abs(np.mean(outputs) - 384) < 1
        # a slot's positions hold every request, rounded up to its blocks
        assert max(-(-(p + o) // 4) * 4
                   for p, o in zip(prompts, outputs)) <= cfg["seq_len"]
        top = max(int(r["prompt"].max()) for r in reqs)
        assert 151000 < top < cfg["mask_token_id"]
        # the stratified lengths are 256 + 6 (i + 1/2), all odd: a prompt
        # leaves a tail of 1 or 3 tokens to its open block, never none
        assert {p % 4 for p in prompts} == {1, 3}
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]


def test_weights_are_a_function_of_seed_layer_and_leaf():
    cfg = tiny_cfg()
    key = weights.base_key(2 ** 31 + 3)
    whole = family.tree(key, cfg)
    again = family.layer(key, cfg, 1)
    for a, b in zip(jax.tree.leaves(whole["blocks"][1]),
                    jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)
    made = family.layer_maker(cfg)(key, 2)
    for a, b in zip(jax.tree.leaves(whole["blocks"][2]),
                    jax.tree.leaves(made)):
        np.testing.assert_array_equal(a, b)
    other = family.layer(weights.base_key(4), cfg, 1)
    assert not np.array_equal(other["attn"]["wq"], again["attn"]["wq"])
    assert not np.array_equal(whole["blocks"][0]["attn"]["wq"],
                              whole["blocks"][1]["attn"]["wq"])
    # the router over all the published experts, the weights of the held
    assert again["moe"]["router"].shape == (64, 16)
    assert again["moe"]["experts"]["w_down"].shape == (4, 48, 64)
    outer = family.outer(key, cfg)
    assert outer["head"].shape == (64, 256) and \
        outer["embed"].shape == (256, 64)
    spec = json.loads(family.ModelSpec(cfg, whole).to_json())
    assert spec == {"class_name": "SdarMoeDecoder", "config": cfg}


def served(cfg, key, sizes, seed=0):
    """Requests served by the program itself, as the kind samples them."""
    from dist_keras_tpu.serving import DecodeEngine

    rng = np.random.default_rng(seed)
    out = []
    with jax.default_matmul_precision("highest"):
        with DecodeEngine(family.ModelSpec(cfg, family.tree(key, cfg)),
                          replicas=1, prefill_ladder=(16, 32),
                          decode_ladder=(1, 4), page_size=4) as eng:
            for prompt, new in sizes:
                doc = eng.generate(
                    rng.integers(0, family.vocab(cfg), prompt).tolist(),
                    max_new_tokens=new, timeout_s=600)
                out.append({"tokens": doc["tokens"], "prompt_len": prompt,
                            "passes": doc["passes"]})
    return out


def test_a_served_trajectory_reads_zero_and_a_moved_one_does_not(
        monkeypatch):
    """The program's own trajectory is the reference's (every number 0 on
    the CPU at "highest"); a token swapped, or two passes of a block
    swapped, is seen; only whole blocks are read, the first among them."""
    monkeypatch.setattr(check, "BLOCKS", 3)
    cfg = tiny_cfg()
    seed = 2 ** 31 + 9
    key = weights.base_key(seed)
    samples = served(cfg, key, [(13, 22), (8, 17)])
    assert check.sampled_blocks(samples[0], cfg, seed)[0] == 3
    assert len(check.sampled_blocks(samples[0], cfg, seed)) == 3
    # 13 + 22 = 35 tokens: blocks 3..7 are whole, block 8 was trimmed
    assert max(check.sampled_blocks(samples[0], cfg, seed)) <= 7
    got = check.served_numbers(cfg, key, samples, seed)
    assert (got["logit_gap_mean"], got["confidence_gap_mean"],
            got["flip_share"]) == (0.0, 0.0, 0.0)
    # 13 % 4 leaves 3 masks to the first block: 3 passes, then 2 x 4; the
    # prompt of 8 leaves none: 3 x 4
    assert got["passes"] == 3 + 2 * 4 + 3 * 4
    wrong = copy.deepcopy(samples)
    block = check.sampled_blocks(wrong[1], cfg, seed)[1]
    at = 4 * block
    wrong[1]["tokens"][at] = (wrong[1]["tokens"][at] + 1) % 250
    moved = check.served_numbers(cfg, key, wrong, seed)
    assert moved["logit_gap_mean"] > 0 and moved["flip_share"] > 0
    order = copy.deepcopy(samples)
    passes = order[1]["passes"]
    first = at - order[1]["prompt_len"]
    i, j = (passes[first:first + 4].index(p) + first for p in (0, 3))
    passes[i], passes[j] = passes[j], passes[i]
    swapped = check.served_numbers(cfg, key, order, seed)
    assert swapped["confidence_gap_mean"] > 0 and swapped["flip_share"] > 0
    nothing = [{"tokens": [1, 2, 3, 4, 5, 6, 7], "prompt_len": 5,
                "passes": [0, 1]}]          # no whole block behind block 1
    assert check.served_numbers(cfg, key, nothing, seed)["flip_share"] \
        == float("inf")


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 6])
def test_control_mode_of_the_check_is_not_correct(monkeypatch, capsys, seed):
    """``python3 -m benchmark.reference.sdar_moe_check``: the fp8 control
    at the cell's (here: the toy's) sizes through the cell's limits."""
    real_traffic = manifest.traffic_of
    tiny = tiny_conf()

    def toy_traffic(cell):
        t = rehearsal.shrink(real_traffic(cell))
        t["classes"][0].update(
            prompt_len={"dist": "uniform", "min": 3, "max": 20},
            output_len={"dist": "uniform", "min": 20, "max": 40})
        return t

    monkeypatch.setattr(check, "BLOCKS", 6)
    monkeypatch.setattr(manifest, "config_of", lambda man, cell: tiny)
    monkeypatch.setattr(manifest, "traffic_of", toy_traffic)
    assert check.main(["--workload", CELL, "--seed", str(seed)]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["control"] == "fp8"
    # six blocks of each of the two requests, four passes a block (fewer
    # in a first block that opens holding a prompt's tail)
    assert 2 * (1 + 5 * 4) <= last["passes"] <= 2 * 6 * 4


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsed_with_its_own_family(monkeypatch, tmp_path, trace):
    from benchmark import run

    man = copy.deepcopy(manifest.load())
    for c in man["configs"]:
        c["file"] = TINY
    real = manifest.traffic_of
    monkeypatch.setattr(
        manifest, "traffic_of",
        lambda cell: {**rehearsal.shrink(real(cell), (8, 20)),
                      "lead_in_s": 1.0})
    result = run.run_cell(man, CELL, 2 ** 31 + 5, 2.0, trace,
                          jax.devices()[:1], rehearsal.PEAKS, str(tmp_path),
                          process_start=time.perf_counter())
    json.dumps(result)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    # the one number that separates float8 from the stated precision is
    # held by a limit; the other two are read and printed
    assert set(result["compared"]) == {"confidence_gap_mean",
                                       "failed_requests"}
    got = result["metrics"] if trace else result["per_layer"]
    if not trace:
        assert {"setup_s", "serve_tokens_per_s"} <= set(result["metrics"])
    slots = got["block_slots_mean.blocks"]["value"]
    fixed = got["tokens_per_pass_mean.blocks"]["value"]
    # four tokens a five passes, fewer passes where a prompt's tail stands
    assert 0.7 < fixed / slots < 0.85
    assert 4.0 < got["passes_per_block_mean.blocks"]["value"] <= 5.0
    assert 15.0 < got["commit_share_pct.blocks"]["value"] < 30.0
    assert got["block_pass_p50_ms.blocks"]["value"] > 0
    assert got["kv_live_positions_mean.blocks"]["value"] > 1.0
    assert got["moe_load_max_over_mean.blocks"]["value"] >= 1.0
    # experts 4-7 of 16 held: about a quarter of the chosen pairs
    assert 15.0 < got["moe_held_share_pct.blocks"]["value"] < 35.0
    assert 0.0 < got["prefill_share_pct.blocks"]["value"] < 100.0
    assert got["window_compiles_serve.batch"]["value"] == 0.0
    # device-trace readers find no TPU plane on the CPU and report nothing
    assert "decode_hbm_roofline.blocks" not in got
    assert "flash_fwd_roofline.blocks" not in got


def test_block_causal_flash_reader_charges_each_event_its_own_shapes(capsys):
    from benchmark.readers import flash_roofline_blocks as reader

    class Ctx:
        peaks = rehearsal.PEAKS

    def event(seconds, t):
        return (seconds, f"%flash_fwd.1 = (f32[32,{t},128]{{2,1,0}}, "
                f"f32[32,{t},1]{{2,1,0}}) custom-call(f32[32,{t},128]{{2,1,0}}"
                f" %q, f32[4,{t},128]{{2,1,0}} %k, f32[4,{t},128]{{2,1,0}} "
                f'%v), custom_call_target="tpu_custom_call", '
                f'metadata={{op_name="jit(_packed_prefill_fn)/attend/'
                f'flash_fwd"}}')

    assert reader.read({"trace": None}, Ctx, "^flash_fwd", 4) is None
    assert reader.read({"trace": {"events": [(1.0, "%x = f32[2]{0} add()")]}},
                       Ctx, "^flash_fwd", 4) is None
    outcome = {"trace": {"events": [event(1e-3, 1024), event(4e-4, 512)]}}
    got = reader.read(outcome, Ctx, "^flash_fwd", 4)
    least = 0.0
    for t in (1024, 512):
        ops, moved = opcount_sdar_moe.flash_fwd_block_causal(
            32, 4, t, t, 128, 4, 4)
        least += max(ops / 197e12, moved / 819e9)
    assert got == pytest.approx(100.0 * least / 1.4e-3)
    assert "flash_roofline_blocks" in capsys.readouterr().out
