"""A compiled training step, driven free-running.

Set-up builds ONE object (the program's compiled step with its state, made
on the device from the seed), drives it through its first three steps by
the window's own call and feed, and hands that same object to the window.
The window enqueues steps without waiting for them: the host only ever
waits for the step ``inflight`` back, which has long finished, so the
device always has its next step queued and no more than ``inflight`` + 1
generations of state are alive (enqueueing without any bound fills the
whole HBM with queued outputs: measured, PR 23).  Each of its equal
sub-windows of whole steps ends in a loss fetch, as a loop that logs
every N steps does.  The rate reported end to end is every step of the
window over all of its time; the sub-windows' rates stand beside it.
After the window the state is freed and the plain reference follows the
first three steps.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import meter, trafficgen, weights
from benchmark import checks
from benchmark.reference import train_check
from benchmark.trace import opcount

CHECK_STEPS = 3


class Step:
    """The program's compiled step with its state: the object set-up
    builds and the window drives."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding

        from dist_keras_tpu.models.transformer import transformer_config
        from dist_keras_tpu.parallel.transformer_tp import (
            make_tp_mesh,
            make_tp_train_step,
            tp_step_specs,
        )

        conf, tr = ctx.config, ctx.traffic
        train = conf["train"]
        self.cfg = transformer_config(
            input_dim=train["input_dim"], seq_len=tr["seq_len"],
            d_model=conf["hidden_size"],
            n_heads=conf["num_attention_heads"],
            n_layers=conf["num_hidden_layers"]["train"],
            d_ff=conf["ffn_dim"], n_classes=train["n_classes"])
        self.batch = int(tr["batch"])
        tx = optax.adam(float(train["learning_rate"]))
        mesh = make_tp_mesh(1, 1, 1, devices=list(ctx.devices))
        factory, _ = make_tp_train_step(
            mesh, self.cfg, optimizer=tx, causal=bool(tr["causal"]),
            compute_dtype=jnp.dtype(train["compute_dtype"]),
            remat=train["remat"])
        key = weights.base_key(ctx.seed)
        self.key = key

        def make_state(k):
            params = weights.transformer(k, self.cfg)
            return params, tx.init(params)

        shapes = jax.eval_shape(make_state, key)
        pspecs, ospecs, xspec, yspec = tp_step_specs(*shapes)

        def shard(spec):
            return NamedSharding(mesh, spec)

        is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
        out = (jax.tree.map(shard, pspecs, is_leaf=is_spec),
               jax.tree.map(shard, ospecs, is_leaf=is_spec))
        # weights and optimizer state: one jitted call, on the device
        self.params, self.opt_state = jax.jit(
            make_state, out_shardings=out)(key)
        xs, ys = jax.jit(
            lambda k: trafficgen.train_batches(
                tr, train["input_dim"], train["n_classes"], k))(key)
        n = xs.shape[0]
        self.feed = [(jax.device_put(xs[i], shard(xspec)),
                      jax.device_put(ys[i], shard(yspec)))
                     for i in range(n)]
        self.fn = factory(self.params, self.opt_state)
        self.steps = 0

    def __call__(self):
        """One step through the program's compiled function -> the loss,
        still on the device."""
        x, y = self.feed[self.steps % len(self.feed)]
        self.params, self.opt_state, loss = self.fn(
            self.params, self.opt_state, x, y)
        self.steps += 1
        return loss

    def free(self):
        self.params = self.opt_state = self.feed = None


def drive(step, seconds, per_sub, spans, inflight):
    """Sub-windows of ``per_sub`` steps until ``seconds`` have passed ->
    (list of (steps, seconds) per sub-window, every step's loss on the
    device, seconds from the first step's dispatch to the last loss
    fetched)."""
    subs, losses = [], []
    begin = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with spans("bench.train_subwindow"):
            for _ in range(per_sub):
                if len(losses) >= inflight:
                    losses[-inflight].block_until_ready()
                loss = step()
                losses.append(loss)
            with spans("bench.fetch_loss"):
                float(loss)
        t1 = time.perf_counter()
        subs.append((per_sub, t1 - t0))
        if t1 - begin + 0.5 * (t1 - t0) >= seconds:
            return subs, losses, t1 - begin


def run(ctx):
    import jax
    import jax.numpy as jnp

    tr = ctx.traffic
    spans = meter.Spans()
    compiles = meter.CompileCounter()
    ctx.mark("imports done")
    step = Step(ctx)
    probes = train_check.Probes(step.params, ctx.seed, step.cfg)
    ctx.mark("state and feed on the device")

    # the first steps: the window's own call and feed, rows that all differ
    first_losses, grad_probe = [], None
    for i in range(CHECK_STEPS):
        first_losses.append(float(step()))
        if i == 0:
            grad_probe = probes.first_gradient(step.opt_state)
    delta_probe = probes.change(step.params, step.key)
    ctx.mark("first steps driven and probed")

    # a timing of a few warm steps, one at a time, sizes the sub-windows
    t0 = time.perf_counter()
    for _ in range(3):
        float(step())
    step_s = (time.perf_counter() - t0) / 3
    per_sub = max(1, int(round(ctx.seconds / tr["sub_windows"] / step_s)))
    print(f"train_step: layers={step.cfg['n_layers']} warm step "
          f"{step_s * 1e3:.1f} ms, {per_sub} steps a sub-window",
          flush=True)

    # what one generation of state, its successor and a step's temporaries
    # need: read while steps still ran one at a time.  Free-running keeps
    # ``inflight`` + 1 generations alive and reads near the whole HBM.
    step_peak = meter.memory_peak_bytes(ctx.devices)
    inflight = int(tr["inflight"])
    reduced = None
    compiles.reset()
    ctx.setup_done()
    if ctx.trace:
        # a traced lead-in of a few sub-windows, then the window itself
        # with the profiler stopped: stopping it takes seconds of host time
        profiler = meter.Profiler(os.path.join(ctx.scratch, "trace"), spans)
        profiler.start()
        drive(step, float(tr["trace_seconds"]), per_sub, spans, inflight)
        profiler.close_window()
        reduced = profiler.finish()
        ctx.mark("traced lead-in reduced, window begins")
    subs, losses, window_s = drive(step, ctx.seconds, per_sub, spans,
                                   inflight)
    in_window = compiles.count
    compiles.close()
    window_losses = np.asarray(jax.device_get(jnp.stack(losses)))
    peak = meter.memory_peak_bytes(ctx.devices)
    step.free()
    ctx.mark("window closed, state freed; the reference follows")

    compared = train_check.compare(
        ctx, step.cfg, first_losses, grad_probe, delta_probe, probes,
        CHECK_STEPS)
    compared.append(checks.limit(
        "window_loss_nonfinite_steps",
        int(np.sum(~np.isfinite(window_losses))), 0))
    ctx.mark("compared")
    rates = [n * step.batch / s for n, s in subs]
    steps = sum(n for n, _ in subs)
    return {
        "attempted": len(window_losses),
        "failed": int(np.sum(~np.isfinite(window_losses))),
        "checks": compared,
        "trace": reduced,
        "series": {
            "subwindow_samples_per_s": rates,
        },
        "counters": {
            # all the work over all the time of the window
            "window_samples_per_s": steps * step.batch / window_s,
            "window_step_ms": 1e3 * window_s / steps,
            "memory_peak_bytes": peak,
            "step_peak_bytes": step_peak,
            "window_compiles": in_window,
            "required_ops_per_sample": opcount.train_step_per_sample(
                tr["seq_len"], step.cfg["n_layers"], step.cfg["d_model"],
                step.cfg["d_ff"], step.cfg["input_dim"],
                bool(tr["causal"])),
            "window_loss_first": float(window_losses[0]),
            "window_loss_last": float(window_losses[-1]),
        },
    }
