"""A closed loop over an engine whose family generates a BLOCK at a time:
``clients`` callers, each sending its next request when the last one
returned (``serving.closed_loop``), and when the window closes what is
still in the engine is cut there: ``serve_closed_family``'s loop, records
and reduction, for a step that is a PASS over every slot's open block.

What differs, and why this is a kind of its own (``serving.check_samples``
hands a family the tokens alone, and ``serve_closed_family`` reckons
tokens a step from tokens less admissions): a pass fixes 0 to a block's
length of tokens a slot, a block's tokens come out together at its commit,
a prefill yields none, and ``correct`` compares the served TRAJECTORY, so a
sample carries for each generated token the pass of its block that fixed
it (the result doc's ``passes``).  Passes a block, tokens a pass and the
share of passes that commit are the program's own stamped histograms,
which the cell's metric files name; no counter here assumes a token a slot
a step.  The series ``prefill_ms`` holds the program's own prefill times
(``decode.prefill_s``): the reconstruction from token times
(``serving.reduce_records``) counts on a first token that comes from the
prefill.
"""

from __future__ import annotations

import gc
import os
import threading
import time

from benchmark import checks, families, meter, serving, trafficgen
from benchmark.reference import serve_check

PAIRS = ("decode.moe.pairs_held", "decode.moe.pairs_total")


def check_samples(ctx, records):
    """The finished requests ``correct`` compares with the reference, each
    with its trajectory: ``serving.check_samples``' seeded sample (the
    longest among them; a first reply cut by ``stagger_start`` is not one
    of the mix's)."""
    served = [{"tokens": r.doc["tokens"], "prompt_len": r.doc["prompt_len"],
               "passes": r.doc["passes"]}
              for r in records
              if serving.finished(r) and not r.req.get("staggered")]
    return serve_check.pick(served, int(ctx.traffic["check_requests"]),
                            ctx.seed)


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
        print("serve_closed_blocks: drawn", trafficgen.describe(pool),
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
        passes_ms, passes, _ = serving.window_steps(step_hist, t0,
                                                    ctx.seconds)
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
    prefills = [v for at, v in metrics.histogram(
        "decode.prefill_s").samples_between(t0, t0 + ctx.seconds)[0]]
    series["prefill_ms"] = [1e3 * v for v in prefills]
    before = state["before"]
    print(f"serving: {passes} passes of mean "
          f"{sum(passes_ms) / max(1, len(passes_ms)):.3f} ms "
          f"({after['steps'] - before['steps']} by the engine's count), "
          f"{after['tokens'] - before['tokens']} tokens emitted, "
          f"{len(prefills)} prefills of {sum(prefills):.3f} s together, in "
          f"the window", flush=True)
    serving.log_regions(t0, ctx.seconds)
    print(f"serving: {counters['requests_finished']} of {len(records)} "
          f"requests finished, {counters['requests_cut_at_close']} cut at "
          f"the window's close", flush=True)
    counters.update({
        "memory_peak_bytes": peak,
        "window_compiles": in_window,
        "held_pairs_pct": 100.0 * held / total if total else None,
    })
    compared = family.compare(ctx, cfg, check_samples(ctx, records))
    ctx.mark("compared")
    failed = sum(1 for r in records if r.doc is None)
    compared.append(checks.limit("failed_requests", failed, 0))
    return {
        "attempted": len(records),
        "failed": failed,
        "checks": compared,
        "trace": state.get("trace"),
        "series": series,
        "counters": counters,
    }
