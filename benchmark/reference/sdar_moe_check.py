"""The comparison that decides ``correct`` for a served block-diffusion
decoder: the served TRAJECTORY against the plain reference.

A served request is a prompt, its generated tokens and, for each of them,
the pass of its block that fixed it.  That is the whole trajectory: before
pass ``p`` of a block, the positions fixed by passes ``< p`` (and the
prompt's tail) hold their tokens and the others the mask id, and pass
``p`` fixed the positions that say ``p``.  A seeded sample of the requests
the window finished (the longest among them) is teacher-forced through
the plain float32 reference's forward under the block-causal mask, and of
each sampled request a seeded sample of ``BLOCKS`` blocks (the first, the
one that holds the prompt's tail, always; never single passes of a block)
is read at EVERY pass: the block in its state before the pass stands
behind the request's final tokens as ``B`` more rows (at the block's own
positions; they see the final tokens before the block and one another, and
nothing sees them), so ONE forward of the request's positions plus ``B x
T`` rows a sampled block gives every pass's logits against the history the
program had: K/V a prefill wrote for the prompt and commit passes wrote
for every block since.  Three numbers:

- ``logit_gap_mean``: at each position a pass fixed, how far the served
  token's logit lies below the reference's best (the mask id left out);
- ``confidence_gap_mean``: how far the served position's confidence (the
  logarithm of its best token's probability) lies below that of the
  position the reference would fix last in that pass (0 where the served
  position is among the reference's own);
- ``flip_share``: the share of passes whose fixed (position, token) pairs
  are not the reference's own.

The reference's weights are made again from the seed, one layer at a
time, after the engine has been freed.

The control is a mode of this check: ``python3 -m
benchmark.reference.sdar_moe_check --workload <cell> --seed <n>`` puts the
reference carried in a lower precision (float8_e4m3 operands by default)
in the program's place, at the cell's own sizes: at every state of a
seeded trajectory of the mix's longest and middle request it fixes what
IT finds most confident, and is held to the cell's limits, which it has
to fail: the last line says ``"correct": false`` and the exit code is 0
when it does.
"""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np

from benchmark import checks, weights
from benchmark.families import sdar_moe as family
from benchmark.reference import sdar_moe_ref as ref
from benchmark.reference.transformer_ref import FLOAT32, FP8, Precision

Q_BLOCK = 1024
BLOCKS = 48         # blocks read of a sampled request, each at every pass
CONTROLS = {"fp8": FP8, "bfloat16": Precision("bfloat16", jnp.bfloat16, False)}


def sampled_blocks(sample, cfg, seed):
    """The blocks of one served request whose passes are read -> sorted
    block indices: whole generated blocks only (the last one's trimmed
    positions never reached the doc), the first always, ``BLOCKS`` in
    all."""
    size = cfg["block_length"]
    first = sample["prompt_len"] // size
    whole = range(first, len(sample["tokens"]) // size)
    rng = np.random.default_rng([int(seed), 6, sample["prompt_len"]])
    rest = rng.permutation(list(whole)[1:])[:BLOCKS - 1]
    return sorted([first, *(int(b) for b in rest)][:len(whole)])


def states(sample, block, cfg):
    """One block's trajectory -> for each pass ``p`` that fixed something,
    (the block's tokens before it, the positions it fixed)."""
    size, mask_id = cfg["block_length"], cfg["mask_token_id"]
    n = sample["prompt_len"]
    final = sample["tokens"][block * size:(block + 1) * size]
    # the pass that fixed each position; the prompt's tail was never masked
    fixed_at = [-1 if at < n else sample["passes"][at - n]
                for at in range(block * size, (block + 1) * size)]
    out = []
    for p in range(max(fixed_at) + 1):
        before = [t if f < p else mask_id for t, f in zip(final, fixed_at)]
        out.append((before, [b for b, f in enumerate(fixed_at) if f == p]))
    return out


def pass_logits(cfg, key, samples, seed, prec=FLOAT32):
    """The reference's logits at every pass of every sampled block -> for
    each sample a list of (block, pass, state, served positions, logits
    ``(B, vocab)`` on the host)."""
    import jax

    conf = family.reference_config(cfg)
    held = cfg["held_experts"]
    size, steps = cfg["block_length"], cfg["denoising_steps"]
    outer = jax.jit(lambda k: family.outer(k, cfg))(key)
    # every sample padded to a slot's positions, with room for every
    # sampled block's passes behind: one compiled shape for every seed
    history = cfg["seq_len"]
    extra = BLOCKS * steps * size
    total = history + extra
    embed = jax.jit(lambda o, t: ref.embed(o, t, prec))
    plans, hs, where = [], [], []
    for s in samples:
        tokens = np.zeros((total,), np.int32)
        positions = np.arange(total)
        tokens[:len(s["tokens"])] = s["tokens"]
        # final rows see block-causally; the padding behind them sees and
        # is seen by nothing that is read
        mask = np.zeros((total, total), bool)
        mask[:history, :history] = np.asarray(ref.block_causal(
            np.arange(history), np.arange(history), size))
        mask[history:, history:] = np.eye(extra, dtype=bool)
        plan, row = [], history
        for block in sampled_blocks(s, cfg, seed):
            for p, (before, served) in enumerate(states(s, block, cfg)):
                rows = slice(row, row + size)
                tokens[rows] = before
                positions[rows] = np.arange(block * size,
                                            (block + 1) * size)
                mask[rows, :block * size] = True
                mask[rows, rows] = True
                plan.append((block, p, before, served, row))
                row += size
        plans.append(plan)
        where.append((jnp.asarray(positions), jnp.asarray(mask)))
        hs.append(embed(outer, jnp.asarray(tokens)))
    apply_layer = jax.jit(lambda b, h, at, m: ref.layer(
        b, h, at, m, conf, held, prec, Q_BLOCK))
    make = family.layer_maker(cfg)
    for index in range(cfg["n_layers"]):
        blk = make(key, index)
        hs = [apply_layer(blk, h, at, m) for h, (at, m) in zip(hs, where)]
        del blk
    head = jax.jit(lambda o, h, rows: ref.lm_logits(o, h, rows, conf, prec))
    out = []
    for plan, h in zip(plans, hs):
        rows = jnp.arange(history, total)
        logits = np.asarray(head(outer, h, rows))
        out.append([(block, p, before, served,
                     logits[row - history:row - history + size])
                    for block, p, before, served, row in plan])
    return out


def numbers(readings, chosen, cfg):
    """The three numbers over every read pass: ``readings`` from
    :func:`pass_logits` at float32, ``chosen(i, j)`` -> the (positions,
    tokens) the system under comparison fixed in pass ``j`` of sample
    ``i``."""
    mask_id = cfg["mask_token_id"]
    gaps, behind, flips = [], [], []
    for i, sample in enumerate(readings):
        for j, (_, _, before, _, logits) in enumerate(sample):
            positions, tokens = chosen(i, j)
            best, c = ref.confidences(logits, mask_id)
            masked = [t == mask_id for t in before]
            own = ref.most_confident(c, masked, len(positions))
            z = logits.copy()
            z[:, mask_id] = -np.inf
            for b, t in zip(positions, tokens):
                gaps.append(float(z[b].max() - z[b, t]))
                behind.append(max(0.0, float(min(c[o] for o in own)
                                             - c[b])))
            flips.append(
                sorted(zip(positions, tokens))
                != [(b, int(best[b])) for b in own])
    if not flips:
        # nothing was read (no sampled request holds a whole block):
        # nothing is correct
        return {name: float("inf") for name in (
            "logit_gap_mean", "confidence_gap_mean", "flip_share")}
    return {"logit_gap_mean": float(np.mean(gaps)),
            "confidence_gap_mean": float(np.mean(behind)),
            "flip_share": float(np.mean(flips)),
            "logit_gap_max": float(np.max(gaps)),
            "passes": len(flips)}


def served_numbers(cfg, key, samples, seed):
    readings = pass_logits(cfg, key, samples, seed)
    size = cfg["block_length"]

    def chosen(i, j):
        block, _, _, served, _ = readings[i][j]
        final = samples[i]["tokens"][block * size:(block + 1) * size]
        return served, [final[b] for b in served]

    return numbers(readings, chosen, cfg)


def control_numbers(cfg, key, samples, seed, prec):
    """The control: the reference carried in a lower precision, put in the
    program's place.  It need not generate: at every state of the same
    trajectories, what the lower precision would fix."""
    readings = pass_logits(cfg, key, samples, seed)
    low = pass_logits(cfg, key, samples, seed, prec)
    mask_id = cfg["mask_token_id"]

    def chosen(i, j):
        _, _, before, served, logits = low[i][j]
        best, c = ref.confidences(logits, mask_id)
        own = ref.most_confident(c, [t == mask_id for t in before],
                                 len(served))
        return own, [int(best[b]) for b in own]

    return numbers(readings, chosen, cfg)


def compare(ctx, cfg, samples):
    """-> checks, one per number with a limit in the cell's limits file."""
    if not samples:
        return [checks.limit("finished_requests_sampled", 1, 0)]
    got = served_numbers(cfg, weights.base_key(ctx.seed), samples, ctx.seed)
    print(f"sdar_moe_check: {len(samples)} requests compared "
          f"{json.dumps(got)}", flush=True)
    bounds = checks.limits_for(ctx.cell["name"])
    return [checks.limit(name, got[name], bounds[name]) for name in bounds]


def seeded_trajectory(cfg, rng, prompt_len, new):
    """A request of the given sizes with a trajectory from the seed: ids
    below the mask id, every block's positions fixed in a seeded order,
    ``block_length / denoising_steps`` a pass."""
    size = cfg["block_length"]
    per = size // cfg["denoising_steps"]
    whole = -(-(prompt_len + new) // size) * size
    tokens = rng.integers(0, cfg["mask_token_id"], whole)
    passes = []
    at = prompt_len
    while at < whole:
        masked = size - at % size
        passes += [int(o) // per for o in rng.permutation(masked)]
        at += masked
    return {"tokens": tokens.tolist(), "prompt_len": prompt_len,
            "passes": passes}


def control(cell_name, seed, prec):
    """The control at the cell's sizes: two requests of the mix's longest
    and middle lengths, tokens and trajectories from the seed -> (numbers,
    checks)."""
    from benchmark import manifest

    man = manifest.load()
    entry = manifest.cell(man, cell_name)
    cfg = family.model_config(manifest.config_of(man, entry))
    cls = manifest.traffic_of(entry)["classes"][0]
    sizes = [(cls["prompt_len"][k], cls["output_len"][k])
             for k in ("max", "min")]
    sizes[1] = tuple((a + b) // 2 for a, b in zip(*sizes))
    rng = np.random.default_rng([int(seed), 5])
    samples = [seeded_trajectory(cfg, rng, n, m) for n, m in sizes]
    got = control_numbers(cfg, weights.base_key(seed), samples, seed, prec)
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
