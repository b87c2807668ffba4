#!/usr/bin/env python3
"""Chip smoke: the two hot paths, once, on the TPU this process holds.

    python3 chip_smoke.py        # from the repo root, on a TPU host

One process, one ``import jax``, no platform override, no child that
needs the chip.  Four stages, each a plain function of its sizes (so
``tests/test_chip_smoke.py`` calls A-C at toy sizes on the CPU mesh):

- **A** ``stage_trainer``: ``ADAG(mnist_cnn(), window=12, batch 2048,
  bf16)`` at the headline bench shape cut to 2 epochs, then
  ``ModelPredictor`` on a held-out slice — finite losses, accuracy above
  chance, every device holding only its worker's shard of the data.
- **B** ``stage_train_step``: ``make_tp_train_step`` at the widest model
  the repo supports (d_model 768, 6 heads of 128, seq 2048, bf16) —
  finite loss that moves, flash forward + both flash backward kernels in
  the lowered step.
- **C** ``stage_decode_server``: ``DecodeEngine`` (vocabulary 32768, one
  replica per device) behind ``ServingServer``; batched and streamed
  ``POST /generate`` over HTTP; tokens equal a full-forward greedy
  oracle (both under "highest" matmul precision), no retrace past the
  ladder bound, no leaked KV page.
- **D** ``stage_kernels``: every ``pl.pallas_call`` under ``ops/pallas/``
  compiled (``interpret=False``) at the geometry B and C use and compared
  with its ``jnp`` reference at a stated tolerance.

Without a TPU the script names the platform it found on stderr, prints no
result and exits 2.  A stage that raises ends the run non-zero.  On
success the LAST stdout line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import functools
import importlib.metadata
import json
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.data import (
    AccuracyEvaluator,
    Dataset,
    LabelIndexTransformer,
    ModelPredictor,
)
from dist_keras_tpu.models import mnist_cnn
from dist_keras_tpu.models.transformer import (
    Transformer,
    apply_block,
    kv_block_pages,
    layer_norm,
    transformer_config,
)
from dist_keras_tpu.ops.attention import attention_with_lse
from dist_keras_tpu.ops import gated_delta
from dist_keras_tpu.ops.pallas import decode_attention
from dist_keras_tpu.ops.pallas import gated_delta as pallas_gated_delta
from dist_keras_tpu.ops.pallas.flash_attention import (
    _bwd_call,
    _fwd_call,
    attention_auto,
    use_pallas,
)
from dist_keras_tpu.parallel.fsdp import place_by_specs
from dist_keras_tpu.parallel.transformer_tp import (
    make_tp_mesh,
    make_tp_train_step,
    tp_step_specs,
)
from dist_keras_tpu.serving import DecodeEngine, ServingServer
from dist_keras_tpu.trainers import ADAG
from dist_keras_tpu.utils import compile_cache, knobs
from dist_keras_tpu.utils.misc import one_hot

# the widest model the repo supports (bench_transformer_tp's config)
WIDE = dict(d_model=768, n_heads=6, n_layers=4)


class SmokeFailure(AssertionError):
    """A stage's check did not hold."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _kernel_counts(lowered_text, names):
    """How many Pallas custom calls of each kernel a lowered program has
    (``_kernel_name`` may append a span path, hence the prefix match)."""
    return {n: lowered_text.count(f'kernel_name = "{n}') for n in names}


def _check_kernels_traced(stage, counts):
    """On a TPU the kernel must be the thing that ran; elsewhere
    ``attention_auto`` traces the jnp reference and no call may appear."""
    for name, n in counts.items():
        _check((n > 0) == use_pallas(),
               f"stage {stage}: {n} {name} custom call(s) lowered with "
               f"use_pallas()={use_pallas()}")


class CompileMeter:
    """Seconds jax spent in backend compiles (or, with a warm persistent
    cache, fetching them), summed over every thread."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._EVENT:
            with self._lock:
                self.seconds += duration

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# A — the paper's headline config through the trainer
# ---------------------------------------------------------------------------
def stage_trainer(workers, batch, steps, epochs, window=12, image=28,
                  held_out=2048, seed=0):
    """ADAG on a seeded 10-class template task -> report dict.

    ``steps`` is the bench's total step count: the run trains on
    ``batch * steps`` rows dealt over ``workers``; ``held_out`` more
    rows go to the predictor."""
    rng = np.random.default_rng(seed)
    n_train = batch * steps
    y = rng.integers(0, 10, n_train + held_out)
    templates = rng.normal(size=(10, image, image, 1)).astype(np.float32)
    x = 0.5 * templates[y] + rng.normal(
        size=(len(y), image, image, 1)).astype(np.float32)
    train = Dataset({"features": x[:n_train], "label": y[:n_train],
                     "label_encoded": one_hot(y[:n_train], 10)})
    held_out = Dataset({"features": x[n_train:], "label": y[n_train:]})

    # the resident data arrays, seen from an epoch-end callback while the
    # run still holds them: every device must own exactly its worker's
    # (1, ...) shard, never the whole (workers, ...) stack
    seen = []

    def watch_shards(trainer, epoch, logs):
        for a in jax.live_arrays():
            if (a.ndim == 7 and a.shape[0] == workers
                    and a.shape[3:] == (batch, image, image, 1)):
                seen.append(sorted(
                    (s.device.id, s.data.shape[0])
                    for s in a.addressable_shards))

    def make():
        return ADAG(mnist_cnn(input_shape=(image, image, 1)),
                    num_workers=workers, communication_window=window,
                    worker_optimizer="adam", batch_size=batch,
                    num_epoch=epochs, label_col="label_encoded",
                    compute_dtype=jnp.bfloat16, callbacks=[watch_shards])

    model = make().train(train)          # compiles
    trainer = make()
    model = trainer.train(train)         # the same executables, warm
    losses = np.asarray(trainer.get_history(), np.float64)
    _check(losses.size > 0 and np.isfinite(losses).all(),
           f"stage A: non-finite ADAG losses ({losses.size} recorded)")
    _check(seen, "stage A: no resident data array seen at epoch end")
    for shards in seen:
        _check(len(shards) == workers
               and len({d for d, _ in shards}) == workers
               and all(rows == 1 for _, rows in shards),
               f"stage A: data not sharded one worker per device: {shards}")

    predicted = ModelPredictor(model, batch_size=batch).predict(held_out)
    acc = AccuracyEvaluator(label_col="label").evaluate(
        LabelIndexTransformer().transform(predicted))
    _check(acc > 0.2, f"stage A: accuracy {acc:.3f} is not above chance "
                      "(0.1) on the template task")
    return {"run_s": trainer.get_training_time(),
            "samples": int(losses.size) * batch,
            "first_loss": float(losses.reshape(-1)[0]),
            "last_loss": float(losses.reshape(-1)[-1]),
            "accuracy": acc, "data_shards": seen[-1]}


# ---------------------------------------------------------------------------
# B — the widest model, three training steps
# ---------------------------------------------------------------------------
def stage_train_step(batch, seq, d_model, n_heads, n_layers, steps=3,
                     seed=0):
    cfg = transformer_config(input_dim=32, seq_len=seq, d_model=d_model,
                             n_heads=n_heads, n_layers=n_layers,
                             n_classes=2)
    mesh = make_tp_mesh(1, 1, 1)
    step_factory, init_fn = make_tp_train_step(
        mesh, cfg, causal=True, compute_dtype=jnp.bfloat16)
    params, opt_state = init_fn(seed)
    fn = step_factory(params, opt_state)
    # placed by the step's own specs, as train_tp_transformer does: an
    # unplaced first call compiles the step a second time on call two
    pspecs, ospecs, xspec, yspec = tp_step_specs(params, opt_state)
    params = place_by_specs(mesh, params, pspecs)
    opt_state = place_by_specs(mesh, opt_state, ospecs)
    rng = np.random.default_rng(seed)
    x = place_by_specs(
        mesh, rng.normal(size=(batch, seq, 32)).astype(np.float32), xspec)
    y = place_by_specs(
        mesh, rng.integers(0, 2, batch).astype(np.int32), yspec)

    kernels = _kernel_counts(
        fn.lower(params, opt_state, x, y).as_text(),
        ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    _check_kernels_traced("B", kernels)

    losses, t_warm = [], None
    for _ in range(steps):
        params, opt_state, loss = fn(params, opt_state, x, y)
        losses.append(float(loss))       # waits for the step
        if t_warm is None:
            t_warm = time.perf_counter()  # step 1 paid the compile
    jax.block_until_ready(params)
    run_s = time.perf_counter() - t_warm
    _check(np.isfinite(losses).all(), f"stage B: losses {losses}")
    _check(len(set(losses)) == len(losses),
           f"stage B: loss did not change between steps: {losses}")
    return {"run_s": run_s, "steps_timed": steps - 1, "losses": losses,
            "kernels": kernels}


# ---------------------------------------------------------------------------
# C — the decode server over HTTP
# ---------------------------------------------------------------------------
def _post_generate(addr, body, timeout_s):
    req = urllib.request.Request(
        "http://%s:%d/generate" % addr,
        data=json.dumps(body).encode("utf-8"), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return resp.status, [json.loads(ln) for ln in
                             resp.read().decode("utf-8").splitlines() if ln]


def _make_oracle(cfg):
    """Greedy next token by a FULL forward over the padded sequence —
    the verify skill's PR-19 oracle, jitted once per padded length
    (positions past ``length`` cannot reach ``length - 1`` under the
    causal mask)."""
    @jax.jit
    def next_token(params, tokens, length):
        x = jax.nn.one_hot(tokens[None], cfg["input_dim"])
        h = x @ params["proj"] + params["pos"][None, :tokens.shape[0]]
        for blk in params["blocks"]:
            h = apply_block(blk, h, attention_auto, True)
        hs = layer_norm(params["ln_f"], h)[0, length - 1]
        return jnp.argmax(
            hs @ params["head"]["kernel"] + params["head"]["bias"])

    def generate(params, prompt, max_new, t_pad):
        toks = np.zeros((t_pad,), np.int32)
        toks[:len(prompt)] = prompt
        out = []
        for n in range(len(prompt), len(prompt) + max_new):
            nxt = int(next_token(params, jnp.asarray(toks), jnp.int32(n)))
            out.append(nxt)
            toks[n] = nxt
        return out

    return generate


def stage_decode_server(vocab, seq, d_model, n_heads, n_layers, replicas,
                        prefill_ladder, decode_ladder, max_new=8,
                        page_size=8, seed=0):
    """Both sides of the token check trace under "highest" matmul
    precision, set process-wide so the engine's worker threads see it:
    at the TPU default a bf16-pass matmul flips greedy near-ties between
    the cache path and the full forward (measured on a v5e, PR 21: 1 of
    32 prompts diverged within 254 tokens at the default, 0 of 32 at
    "highest")."""
    previous = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        return _decode_server(vocab, seq, d_model, n_heads, n_layers,
                              replicas, prefill_ladder, decode_ladder,
                              max_new, page_size, seed)
    finally:
        jax.config.update("jax_default_matmul_precision", previous)


def _decode_server(vocab, seq, d_model, n_heads, n_layers, replicas,
                   prefill_ladder, decode_ladder, max_new, page_size,
                   seed):
    cfg = transformer_config(input_dim=vocab, seq_len=seq,
                             d_model=d_model, n_heads=n_heads,
                             n_layers=n_layers, n_classes=vocab)
    model = Transformer(cfg, seed=seed)
    eng = DecodeEngine(model, replicas=replicas,
                       prefill_ladder=prefill_ladder,
                       decode_ladder=decode_ladder, page_size=page_size,
                       max_new_default=max_new)
    # the first request of each rung waits for its compile
    srv = ServingServer(eng, port=0, request_timeout_s=900.0)
    addr = srv.start()
    try:
        # each replica on its own device, pools and params included
        for rep in eng._replicas:
            on = {d for leaf in jax.tree.leaves((rep.pools, rep.params))
                  for d in leaf.devices()}
            _check(on == {rep.device},
                   f"stage C: replica {rep.index} holds arrays on "
                   f"{sorted(d.id for d in on)}, expected device "
                   f"{rep.device.id}")

        # what the jitted steps trace: flash in the prefill; the decode
        # step reads its ``v | k`` pool through the latent_decode kernel
        rep0 = eng._replicas[0]
        vec = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)  # noqa: E731
        top, slots = prefill_ladder[-1], decode_ladder[-1]
        # a dispatch hands over ONE packed int32 array; a decode step also
        # takes the output of the step before it, which never left the
        # device (serving/decode.py)
        prefill_text = eng._prefill_jit.lower(
            rep0.params, *rep0.pools, vec(3 * top + 1)).as_text()
        decode_text = eng._decode_jit.lower(
            rep0.params, *rep0.pools, rep0.no_tokens,
            vec(slots * (eng.max_pages_per_seq + 5))).as_text()
        kernels = _kernel_counts(prefill_text, ("flash_fwd",))
        _check_kernels_traced("C", kernels)
        kernels.update(_kernel_counts(decode_text, ("latent_decode",)))

        # traffic: per replica one short and one long prompt (the long
        # ones reach the top prefill rung), sent concurrently
        rng = np.random.default_rng(seed)
        short_max = prefill_ladder[0] - max_new
        long_lo = (prefill_ladder[-2] + 1 if len(prefill_ladder) > 1
                   else 1)
        long_hi = min(prefill_ladder[-1], seq - max_new)
        prompts = []
        for _ in range(replicas):
            prompts.append(rng.integers(
                0, vocab, rng.integers(2, short_max + 1)).tolist())
            prompts.append(rng.integers(
                0, vocab, rng.integers(long_lo, long_hi + 1)).tolist())

        def round_trip():
            out = [None] * len(prompts)

            def one(i):
                out[i] = _post_generate(
                    addr, {"tokens": prompts[i],
                           "max_new_tokens": max_new}, 900)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            _check(all(o is not None for o in out),
                   "stage C: a /generate request did not return")
            return out

        # every (rung, replica) pair compiles once, and which pairs a
        # round hits is the scheduler's choice: repeat until a round
        # compiled nothing — that one is what a client waits for
        meter, rounds = CompileMeter(), 0
        while True:
            c0, t0 = meter.seconds, time.perf_counter()
            replies = round_trip()
            run_s = time.perf_counter() - t0
            rounds += 1
            if meter.seconds == c0 or rounds == 8:
                break
        meter.close()
        status, lines = _post_generate(
            addr, {"tokens": prompts[0], "max_new_tokens": max_new,
                   "stream": True}, 900)
        _check(status == 200 and lines[-1].get("done") is True
               and "error" not in lines[-1],
               f"stage C: stream ended {status} {lines[-1:]}")
        streamed = [ln["token"] for ln in lines[:-1]]

        # the oracle, on the chip, from the same weights
        oracle = _make_oracle(cfg)
        pad_short = prefill_ladder[0]
        pad_long = min(seq, -(-(long_hi + max_new) // 128) * 128)
        want = [oracle(model.params, p, max_new,
                       pad_short if len(p) <= short_max else pad_long)
                for p in prompts]
        for i, (status, docs) in enumerate(replies):
            _check(status == 200, f"stage C: request {i} -> {status}")
            _check(docs[0]["generated"] == want[i],
                   f"stage C: request {i} (prompt {len(prompts[i])}) "
                   f"generated {docs[0]['generated']}, oracle {want[i]}")
        _check(streamed == want[0],
               f"stage C: streamed {streamed}, oracle {want[0]}")

        srv.drain()
        stats = eng.stats()
        _check(stats["retrace_count"] <= stats["retrace_bound"],
               f"stage C: retraced {stats['shapes_dispatched']}")
        _check(stats["kv"]["used_pages"] == 0,
               f"stage C: {stats['kv']['used_pages']} KV pages in use "
               "after the drain")
        eng.assert_no_leaks()
        _check(stats["errors"] == 0 and stats["completed"]
               == rounds * len(prompts) + 1, f"stage C: {stats}")
        served = [r["peak_pages"] for r in stats["kv"]["replicas"]]
        _check(len(served) == replicas and all(served),
               f"stage C: per-replica peak KV pages {served}: a replica "
               "served nothing")
        return {"run_s": run_s, "requests_timed": len(prompts),
                "tokens_timed": len(prompts) * max_new, "rounds": rounds,
                "completed": stats["completed"],
                "shapes_dispatched": stats["shapes_dispatched"],
                "retrace_bound": stats["retrace_bound"],
                "replica_devices": [r.device.id for r in eng._replicas],
                "replica_peak_pages": served, "kernels": kernels,
                "decode_attention": ("latent_decode kernel"
                                     if kernels["latent_decode"]
                                     else "latent_attention_reference"),
                "matmul_precision":
                    jax.config.jax_default_matmul_precision}
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# D — every Pallas kernel, compiled, against its jnp reference
# ---------------------------------------------------------------------------
# max |kernel - reference| / max |reference|, the reference computed in
# float32 at "highest" matmul precision from the same inputs.  One bound
# for both dtypes: Mosaic feeds the MXU bf16 passes for float32 tiles too
# (like XLA's default precision on this chip), and the bf16 kernels round
# their probability tiles to 8 mantissa bits.  Measured on a v5e (PR 21):
# 2.0e-3 .. 5.4e-3.
TOLERANCE = 2e-2


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def _bh(x):        # (BH, T, D) -> (1, T, BH, D), the reference's layout
    return jnp.transpose(x, (1, 0, 2))[None]


def _flash_case(bh, t, d, block, dtype):
    """Inputs + the three flash entry points + their jnp references for
    one (BH, T, D) causal geometry."""
    rng = np.random.default_rng(0)
    q, k, v, do = (jnp.asarray(rng.normal(size=(bh, t, d)) * 0.3, dtype)
                   for _ in range(4))
    scale = d ** -0.5
    geom = (True, scale, block, block, 0, 0, False)  # interpret=False

    def fwd(q, k, v):
        return _fwd_call(q, k, v, *geom)

    def residuals(q, k, v, do):
        out, lse = fwd(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        return lse, -delta

    def bwd(q, k, v, do):
        return _bwd_call(q, k, v, do, *residuals(q, k, v, do), *geom)

    def ref(q, k, v):
        out, _ = attention_with_lse(_bh(q), _bh(k), _bh(v), causal=True,
                                    scale=scale)
        return jnp.transpose(out[0], (1, 0, 2))

    @jax.jit
    def ref_chunk(*qkv_do):
        q, k, v, do = (a.astype(jnp.float32) for a in qkv_do)
        out, vjp = jax.vjp(ref, q, k, v)
        return out, vjp(do)

    def ref_both(q, k, v, do):
        """-> (out, (dq, dk, dv)) in float32, a few heads at a time: the
        (BH, T, T) logits of the whole batch need not fit at once."""
        step = min(bh, 12)
        parts = [ref_chunk(*(a[i:i + step] for a in (q, k, v, do)))
                 for i in range(0, bh, step)]
        cat = lambda xs: jnp.concatenate(xs, axis=0)  # noqa: E731
        return (cat([o for o, _ in parts]),
                tuple(cat([g[j] for _, g in parts]) for j in range(3)))

    return {"args": (q, k, v, do), "fwd": fwd, "bwd": bwd,
            "ref_both": ref_both}


def _case_lengths(slots, page_size, n_pages):
    """A padding slot, one position (a partial page), a page boundary, the
    full extent."""
    return jnp.asarray([(0, 1, page_size, n_pages * page_size)[i % 4]
                        for i in range(slots)], jnp.int32)


# the latent pool's read at the published widths (rank 512 + rope 64 in a
# row of 640 lanes, scores scaled for 128 + 64 wide keys)
_LATENT = {"rank": 512, "scale": 192 ** -0.5}


def _latent_case(slots, heads, page_size, n_pages, width=640, used=576):
    """float32 rows of whole lanes, like the engine's latent pool, two
    layers of it flat with the page ids offset to the second; lengths of
    :func:`_case_lengths`, the extent past the kernel's first block."""
    rng = np.random.default_rng(1)
    per_layer = slots * n_pages + 1
    live = np.arange(width) < used
    q = jnp.asarray(rng.normal(size=(slots, heads, width)) * live,
                    jnp.float32)
    pool = jnp.asarray(
        rng.normal(size=(2 * per_layer, page_size, width)) * live,
        jnp.float32)
    table = jnp.asarray(per_layer + rng.permutation(per_layer)[
        :slots * n_pages].reshape(slots, n_pages), jnp.int32)
    return (q, pool, table, _case_lengths(slots, page_size, n_pages))


# the transformer family's read at the galactica row: ``v | k`` of 32
# heads of 128, 8,192 lanes
_KV_ROWS = {"rank": 32 * 128, "scale": 128 ** -0.5}


def _kv_rows_case(slots, page_size, n_pages, heads=32, d=128):
    """float32 rows ``v | k`` of ``2 x heads x d`` lanes, two layers flat
    with the page ids offset to the second, and the queries as
    ``attend_rows`` lays them out (head h's in the lanes of its own keys,
    zeros elsewhere); lengths of :func:`_case_lengths`."""
    rng = np.random.default_rng(3)
    per_layer = slots * n_pages + 1
    width = heads * d
    q = np.zeros((slots, heads, 2 * width), np.float32)
    for h in range(heads):
        q[:, h, width + h * d:width + (h + 1) * d] = rng.standard_normal(
            (slots, d), np.float32)
    pool = rng.standard_normal((2 * per_layer, page_size, 2 * width),
                               np.float32)
    table = jnp.asarray(per_layer + rng.permutation(per_layer)[
        :slots * n_pages].reshape(slots, n_pages), jnp.int32)
    return (jnp.asarray(q), jnp.asarray(pool), table,
            _case_lengths(slots, page_size, n_pages))


def _state_step_case(slots, heads, dk=96, dv=192):
    """A flat pool of two layers' per-sequence matrices (``slots + 1`` rows
    a layer, the published key and value widths), the slots' rows of the
    second layer in another order, and one position's q, k, v, g, beta."""
    rng = np.random.default_rng(2)
    rows = slots + 1

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    k = normal(slots, heads, dk)
    return (normal(2 * rows, heads, dk, dv),
            jnp.asarray(rows + rng.permutation(slots), jnp.int32),
            normal(slots, heads, dk) * dk ** -0.5,
            k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            normal(slots, heads, dv),
            -jnp.abs(normal(slots, heads)),
            2.0 * jax.nn.sigmoid(normal(slots, heads)))


def kernel_cases(batch, seq, n_heads, head_dim, prefill, slots, page_size):
    """name -> (fn, args): every ``pl.pallas_call`` in ``ops/pallas/`` at
    the geometry stages B and C dispatch.  The one table stage D runs on
    the chip and tier-1 lowers for TPU from the CPU."""
    cases = {}
    flash = {"bf16_train": _flash_case(batch * n_heads, seq, head_dim,
                                       min(1024, seq), jnp.bfloat16),
             "f32_prefill": _flash_case(n_heads, prefill, head_dim,
                                        min(1024, prefill), jnp.float32)}
    for tag, c in flash.items():
        q, k, v, do = c["args"]
        cases[f"flash_fwd/{tag}"] = (c["fwd"], (q, k, v))
        cases[f"flash_bwd/{tag}"] = (c["bwd"], (q, k, v, do))
    cases["latent_decode/f32"] = (
        functools.partial(decode_attention.latent_attention_kernel,
                          **_LATENT),
        _latent_case(slots, n_heads, page_size, -(-seq // page_size)))
    # 256 positions a slot: four of the read's blocks of 64
    cases["latent_decode/kv_rows_f32"] = (
        functools.partial(decode_attention.latent_attention_kernel,
                          block_pages=kv_block_pages(page_size), **_KV_ROWS),
        _kv_rows_case(slots, page_size, 256 // page_size))
    cases["gdn_state_step/f32"] = (
        pallas_gated_delta.state_step_kernel,
        _state_step_case(slots, n_heads))
    return cases, flash


def stage_kernels(batch, seq, n_heads, head_dim, prefill, slots,
                  page_size):
    cases, flash = kernel_cases(batch, seq, n_heads, head_dim, prefill,
                                slots, page_size)
    report, run_s = {}, 0.0

    def run(name):
        nonlocal run_s
        fn, args = cases[name]
        jitted = jax.jit(fn)
        jax.block_until_ready(jitted(*args))    # compiles
        t0 = time.perf_counter()
        out = jax.block_until_ready(jitted(*args))
        run_s += time.perf_counter() - t0
        return out

    with jax.default_matmul_precision("highest"):
        refs = {tag: c["ref_both"](*c["args"]) for tag, c in flash.items()}
        latent_ref = decode_attention.latent_attention_reference(
            *cases["latent_decode/f32"][1], **_LATENT)
        rows_ref = decode_attention.latent_attention_reference(
            *cases["latent_decode/kv_rows_f32"][1], **_KV_ROWS)
        states, rows, *position = cases["gdn_state_step/f32"][1]
        step_o, step_rows = gated_delta.gated_delta_step(states[rows],
                                                         *position)
        step_ref = (step_o, states.at[rows].set(step_rows))

    for tag, c in flash.items():
        ref_out, ref_grads = refs[tag]
        out, _ = run(f"flash_fwd/{tag}")
        report[f"flash_fwd/{tag}"] = (_rel_err(out, ref_out), TOLERANCE)
        grads = run(f"flash_bwd/{tag}")
        report[f"flash_bwd/{tag}"] = (
            max(_rel_err(g, r) for g, r in zip(grads, ref_grads)),
            TOLERANCE)
    report["latent_decode/f32"] = (
        _rel_err(run("latent_decode/f32"), latent_ref), TOLERANCE)
    report["latent_decode/kv_rows_f32"] = (
        _rel_err(run("latent_decode/kv_rows_f32"), rows_ref), TOLERANCE)
    # the output and the WHOLE pool: the rows no slot names are untouched
    report["gdn_state_step/f32"] = (
        max(_rel_err(g, r)
            for g, r in zip(run("gdn_state_step/f32"), step_ref)),
        TOLERANCE)
    for name, (err, tol) in report.items():
        _check(np.isfinite(err) and err <= tol,
               f"stage D: {name} rel err {err:.3g} > tolerance {tol}")
    return {"run_s": run_s, "rel_err_and_tolerance": report}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def _peak_bytes():
    stats = [d.memory_stats() for d in jax.devices()]
    return [s["peak_bytes_in_use"] if s else None for s in stats]


def run_stage(meter, name, fn, **sizes):
    print(f"[stage {name}] {fn.__name__}({sizes})", flush=True)
    c0, t0 = meter.seconds, time.perf_counter()
    report = fn(**sizes)
    wall = time.perf_counter() - t0
    print(f"[stage {name}] compile_s={meter.seconds - c0:.2f} "
          f"run_s={report.pop('run_s'):.3f} wall_s={wall:.2f} "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)
    print(f"[stage {name}] {json.dumps(report, default=str)}", flush=True)
    return report


def main():
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind!r}, {len(devices)} "
              "device(s))", file=sys.stderr)
        return 2
    n = len(devices)
    cache_dir = compile_cache.enable()
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={n} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache_dir}", flush=True)
    print(f"knobs: DK_COMM_OVERLAP={knobs.get('DK_COMM_OVERLAP')!r}",
          flush=True)
    meter = CompileMeter()
    head_dim = WIDE["d_model"] // WIDE["n_heads"]

    run_stage(meter, "A", stage_trainer, workers=min(4, n), batch=2048,
              steps=48, epochs=2)
    if n > 1:
        peaks = _peak_bytes()
        _check(max(peaks) <= 1.25 * min(peaks),
               f"stage A: uneven per-device peak_bytes_in_use {peaks}")
    run_stage(meter, "B", stage_train_step, batch=16, seq=2048, **WIDE)
    run_stage(meter, "C", stage_decode_server, vocab=32768, seq=2048,
              replicas=n, prefill_ladder=(128, 1024),
              decode_ladder=(1, 8), **WIDE)
    run_stage(meter, "D", stage_kernels, batch=16, seq=2048,
              n_heads=WIDE["n_heads"], head_dim=head_dim, prefill=1024,
              slots=8, page_size=8)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
