"""The comparison that decides ``correct`` for a served looped decoder:
``serve_check``'s, with this family's reference.

A seeded sample of the requests the window finished (the longest among
them) is teacher-forced, prompt plus served tokens, through the plain
float32 reference's full forward (``ouro_ref``: no cache, no loop
primitive, the stack of layers applied pass after pass to the whole
sequence, each pass attending over what it computed itself), and at every
served position the number read is how far the served token's logit lies
below the reference's best.  Prefill (the passes as a loop of the
program, a row written in every (pass, layer) entry, the flash forward,
the gate and the exit rule at the prompt's last position) and decoding
(the paged read of each entry through a traced index) are thereby both
held to the full forward.

The reference's weights are made again from the seed after the engine
has been freed: the served layers once, 2.47 GB at the published widths
(every pass needs every layer, and beside nothing they fit), each used in
all the passes.

The control is a mode of this check: ``python3 -m
benchmark.reference.ouro_check --workload <cell> --seed <n>`` puts the
reference carried in a lower precision (float8_e4m3 operands by default)
in the program's place, at the cell's own sizes, and holds it to the
cell's limits, which it has to fail: the last line says ``"correct":
false`` and the exit code is 0 when it does.
"""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np

from benchmark import checks, weights
from benchmark.families import ouro as family
from benchmark.reference import ouro_ref as ref
from benchmark.reference import serve_check
from benchmark.reference.transformer_ref import FLOAT32, FP8, Precision

CONTROLS = {"fp8": FP8, "bfloat16": Precision("bfloat16", jnp.bfloat16, False)}


def reference_logits(cfg, key, samples, prec=FLOAT32):
    """Teacher-forced logits at the served positions of each sample ->
    list of float32 arrays (served tokens, vocab), on the device."""
    import jax

    conf = family.reference_config(cfg)
    outer = jax.jit(lambda k: family.outer(k, cfg))(key)
    make = family.layer_maker(cfg)
    blocks = [make(key, index) for index in range(cfg["n_layers"])]
    # every sample padded to a slot's positions: one compiled shape for
    # every seed and length (the padding lies behind every served
    # position, and the model is causal)
    padded = cfg["seq_len"]
    embed = jax.jit(lambda o, t: ref.embed(o, t, prec))
    apply_layer = jax.jit(lambda b, x: ref.layer(b, x, conf, prec))
    end_of_pass = jax.jit(lambda o, x: ref.end_of_pass(o, x, conf))
    head = jax.jit(lambda o, hs, gs, p: ref.lm_logits(
        o, hs, gs, p, conf, prec)[0])
    out = []
    for s in samples:
        tokens = np.zeros((padded,), np.int32)
        tokens[:len(s["tokens"]) - 1] = s["tokens"][:-1]
        x = embed(outer, jnp.asarray(tokens))
        n_prompt = s["prompt_len"]
        served = len(s["tokens"]) - n_prompt
        # the logits at position p choose the token at p + 1
        positions = jnp.arange(n_prompt - 1, n_prompt - 1 + served)
        states, gates = [], []
        for _ in range(conf["total_ut_steps"]):
            for blk in blocks:
                x = apply_layer(blk, x)
            x, g = end_of_pass(outer, x)
            states.append(x[positions])
            gates.append(g[positions])
        span = jnp.arange(served)
        out.append(head(outer, states, gates, span))
    return out


def served_numbers(cfg, key, samples):
    reference = reference_logits(cfg, key, samples)
    return serve_check.numbers(reference, [s["tokens"][s["prompt_len"]:]
                                           for s in samples])


def control_numbers(cfg, key, samples, prec):
    """The control: the reference carried in a lower precision, put in
    the program's place: at each position of the same prompts and tokens,
    the token the lower precision puts first."""
    reference = reference_logits(cfg, key, samples)
    low = reference_logits(cfg, key, samples, prec)
    return serve_check.numbers(
        reference, [np.asarray(z).argmax(axis=1) for z in low])


def compare(ctx, cfg, samples):
    """-> checks, one per number with a limit in the cell's limits file."""
    if not samples:
        return [checks.limit("finished_requests_sampled", 1, 0)]
    got = served_numbers(cfg, weights.base_key(ctx.seed), samples)
    print(f"ouro_check: {len(samples)} requests compared "
          f"{json.dumps(got)}", flush=True)
    bounds = checks.limits_for(ctx.cell["name"])
    return [checks.limit(name, got[name], bounds[name]) for name in bounds]


def control(cell_name, seed, prec):
    """The control at the cell's sizes: two requests of the mix's longest
    and middle lengths, tokens from the seed -> (numbers, checks)."""
    from benchmark import manifest

    man = manifest.load()
    entry = manifest.cell(man, cell_name)
    cfg = family.model_config(manifest.config_of(man, entry))
    cls = manifest.traffic_of(entry)["classes"][0]
    sizes = [(cls["prompt_len"][k], cls["output_len"][k])
             for k in ("max", "min")]
    sizes[1] = tuple((a + b) // 2 for a, b in zip(*sizes))
    rng = np.random.default_rng([int(seed), 5])
    samples = [{"tokens": rng.integers(0, cfg["vocab_size"], n + m).tolist(),
                "prompt_len": n} for n, m in sizes]
    got = control_numbers(cfg, weights.base_key(seed), samples, prec)
    bounds = checks.limits_for(cell_name)
    return got, [checks.limit(name, got[name], bounds[name])
                 for name in bounds]


def main(argv=None):
    import argparse

    from dist_keras_tpu.utils import compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[-1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS), default="fp8")
    args = ap.parse_args(argv)
    compile_cache.enable()
    got, compared = control(args.workload, args.seed, CONTROLS[args.control])
    for c in compared:
        print("check", json.dumps(c), flush=True)
    correct = all(c["ok"] for c in compared)
    print(json.dumps({"correct": correct, "control": args.control,
                      "seed": args.seed, **got}), flush=True)
    return 0 if not correct else 1


if __name__ == "__main__":
    raise SystemExit(main())
