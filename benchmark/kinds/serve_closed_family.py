"""A closed loop over the engine of the configuration's own block family:
``clients`` callers, each sending its next request when the last one
returned (``serving.closed_loop``).  When the window closes, what is still
in the engine is cut there.

The family (``benchmark/families``) builds the engine from the
configuration and compares with its own plain reference; the loop, the
records and their reduction are ``serving``'s, as they are.
"""

from __future__ import annotations

import gc
import os
import threading
import time

from benchmark import checks, families, meter, serving, trafficgen

PAIRS = ("decode.moe.pairs_held", "decode.moe.pairs_total")


def run(ctx):
    from dist_keras_tpu.observability import metrics

    family = families.of(ctx.config)
    spans = meter.Spans()
    compiles = meter.CompileCounter()
    ctx.mark("imports done")
    engine, cfg = family.build_engine(ctx)
    ctx.mark("engine built")
    vocab = family.vocab(cfg)
    # the pool reserves a slot's worst case for every request at the door:
    # a caller past the engine's slots would be refused there, not queued
    clients = min(int(ctx.traffic["clients"]), engine.max_slots)
    profiler = None
    if ctx.trace:
        profiler = meter.Profiler(os.path.join(ctx.scratch, "trace"), spans)
    step_hist = metrics.histogram("decode.step_s")
    state = {}

    def pairs():
        return [metrics.counter(name).value for name in PAIRS]

    def begin():
        """End of set-up: counters to zero, the profiler open."""
        step_hist.reset()
        state["before"] = engine.stats()
        state["pairs"] = pairs()
        compiles.reset()
        if profiler is not None:
            profiler.start()
            state["trace_until"] = time.perf_counter() + float(
                ctx.traffic["trace_seconds"])
        ctx.setup_done()

    def tick():
        """Closes the traced segment once it has run its length; a helper
        thread stops the profiler while the load goes on, and the trace is
        reduced once the window has closed."""
        if profiler is not None and "stopper" not in state \
                and time.perf_counter() >= state["trace_until"]:
            profiler.close_window()
            state["stopper"] = threading.Thread(target=profiler.stop)
            state["stopper"].start()

    try:
        pool = trafficgen.requests(ctx.traffic, int(ctx.traffic["requests"]),
                                   vocab, ctx.seed)
        print("serve_closed_family: drawn", trafficgen.describe(pool),
              flush=True)
        serving.warm(engine, pool, vocab)
        ctx.mark("warm")
        records, t0 = serving.closed_loop(ctx, engine, pool, clients, spans,
                                          begin, tick)
        if profiler is not None:
            state["trace_until"] = 0.0
            tick()
            state["stopper"].join()
            state["trace"] = profiler.reduced()
        in_window = compiles.count
        after = engine.stats()
        held, total = (b - a for a, b in zip(state["pairs"], pairs()))
        steps_ms, steps, steps_from_s = serving.window_steps(
            step_hist, t0, ctx.seconds)
        peak = meter.memory_peak_bytes(ctx.devices)
        counters = family.counters(engine, cfg)
    finally:
        compiles.close()
        engine.close(drain=False)
    del engine
    gc.collect()
    ctx.mark("window closed, engine freed; the reference follows")

    series, reduced = serving.reduce_records(
        records, t0, ctx.seconds, int(ctx.traffic.get("sub_windows", 0)))
    counters.update(reduced)
    series["decode_step_ms"] = steps_ms
    prefills = [v for at, v in metrics.histogram(
        "decode.prefill_s").samples_between(t0, t0 + ctx.seconds)[0]]
    print(f"serving: {steps} decode steps of mean "
          f"{sum(steps_ms) / max(1, len(steps_ms)):.3f} ms, "
          f"{len(prefills)} prefills of {sum(prefills):.3f} s together, in "
          f"the window", flush=True)
    serving.log_regions(t0, ctx.seconds)
    print(f"serving: {counters['requests_finished']} of {len(records)} "
          f"requests finished, {counters['requests_cut_at_close']} cut at "
          f"the window's close", flush=True)
    before = state["before"]
    # every admitted request's first token comes from its prefill, the
    # rest from decode steps: tokens a step is the mean of slots in use
    stepped = (after["tokens"] - before["tokens"]) \
        - (after["admitted"] - before["admitted"])
    counters.update({
        "memory_peak_bytes": peak,
        "window_compiles": in_window,
        "slots_mean": stepped / steps if steps else None,
        "held_pairs_pct": 100.0 * held / total if total else None,
    })
    compared = family.compare(ctx, cfg, serving.check_samples(ctx, records))
    ctx.mark("compared")
    failed = sum(1 for r in records if r.doc is None)
    compared.append(checks.limit("failed_requests", failed, 0))
    return {
        "attempted": len(records),
        "failed": failed,
        "checks": compared,
        "trace": state.get("trace"),
        "series": series,
        "series_from_s": steps_from_s,
        "counters": counters,
    }
