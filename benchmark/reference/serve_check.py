"""The comparison that decides ``correct`` for a served model.

``DecodeEngine`` returns tokens, not logits, so the comparison is made
from the reference's side: a seeded sample of the requests the window
finished (the longest among them) is teacher-forced, prompt plus served
tokens, through the plain float32 reference's full forward, and at every
served position the number read is how far the served token's logit lies
below the reference's best.  Prefill-then-decode through the paged cache
is thereby held to the full forward.  Greedy tokens only.

The reference's weights are made again from the seed, one layer at a
time, after the engine has been freed.
"""

from __future__ import annotations

import numpy as np

from benchmark import checks, weights
from benchmark.reference import transformer_ref as ref


def pick(finished, count, seed):
    """A seeded sample of ``count`` finished requests, the longest in it."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 4])
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i]["tokens"]))
    rest = [i for i in range(len(finished)) if i != longest]
    chosen = [longest] + list(rng.permutation(rest)[:max(0, count - 1)])
    return [finished[int(i)] for i in chosen]


def reference_logits(cfg, key, samples, prec=ref.FLOAT32):
    """Teacher-forced logits at the served positions of each sample ->
    list of float32 arrays (served tokens, vocab), on the device."""
    import jax
    import jax.numpy as jnp

    d, heads, ff = cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    outer = jax.jit(lambda k: weights.outer(
        k, cfg["input_dim"], cfg["seq_len"], d, cfg["n_classes"]))(key)
    # every sample is padded to the model's positions: one compiled shape
    # for every seed (a length of the sample's own compiled anew for each,
    # 20-45 s a run: measured, PR 23)
    padded = cfg["seq_len"]
    embed = jax.jit(lambda o, t: ref.embed(o, t, prec))
    hs = []
    for s in samples:
        tokens = np.zeros((padded,), np.int32)
        tokens[:len(s["tokens"]) - 1] = s["tokens"][:-1]
        hs.append(embed(outer, jnp.asarray(tokens)))
    make_block = jax.jit(lambda k, i: weights.block(k, i, d, heads, ff))
    apply_block = jax.jit(lambda b, h: ref.block(b, h, prec))
    for layer in range(cfg["n_layers"]):
        blk = make_block(key, layer)
        hs = [apply_block(blk, h) for h in hs]
        del blk
    head = jax.jit(lambda o, h, p: ref.lm_logits(o, h, p, prec))
    out = []
    for s, h in zip(samples, hs):
        n_prompt = s["prompt_len"]
        served = len(s["tokens"]) - n_prompt
        # the logits at position p choose the token at p + 1
        positions = jnp.arange(n_prompt - 1, n_prompt - 1 + served)
        out.append(head(outer, h, positions))
    return out


def numbers(reference, chosen):
    """How far each chosen token's logit lies below the reference's best
    -> dict of the widest gap, the mean gap and the share of positions
    where the choice is not the reference's own."""
    gaps = []
    for z, tokens in zip(reference, chosen):
        z = np.asarray(z)
        tokens = np.asarray(tokens)
        gaps.append(z.max(axis=1) - z[np.arange(len(tokens)), tokens])
    gaps = np.concatenate(gaps)
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean()),
            "flip_share": float(np.mean(gaps > 0)),
            "positions": int(gaps.size)}


def served_numbers(cfg, key, samples):
    reference = reference_logits(cfg, key, samples)
    return numbers(reference, [s["tokens"][s["prompt_len"]:]
                               for s in samples])


def control_numbers(cfg, key, samples, prec):
    """The control: the reference carried in a lower precision, put in
    the program's place.  It need not decode: at each position of the same
    prompts and tokens, the token the lower precision puts first."""
    reference = reference_logits(cfg, key, samples)
    low = reference_logits(cfg, key, samples, prec)
    return numbers(reference, [np.asarray(z).argmax(axis=1) for z in low])


def compare(ctx, cfg, samples):
    """-> checks, one per number with a limit in the cell's limits file."""
    if not samples:
        return [checks.limit("finished_requests_sampled", 1, 0)]
    got = served_numbers(cfg, weights.base_key(ctx.seed), samples)
    print(f"serve_check: {len(samples)} requests, {got['positions']} "
          "served tokens compared", flush=True)
    bounds = checks.limits_for(ctx.cell["name"])
    return [checks.limit(name, got[name], bounds[name]) for name in bounds]
