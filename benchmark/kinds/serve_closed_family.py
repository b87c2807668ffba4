"""A closed loop over the engine of the configuration's own block family:
``clients`` callers, each sending its next request when the last one
returned.  The callers may start ``lead_in_s`` seconds before the window
opens, so that the window samples a server that is running and not one
prefilling every caller's first request at once; tokens that came out
before the window count nowhere and those seconds fall to set-up.  When
the window closes, what is still in the engine is cut there.

The family (``benchmark/families``) builds the engine from the
configuration and compares with its own plain reference; the loop, the
records and their reduction are ``serving``'s, as they are.
"""

from __future__ import annotations

import gc
import os
import queue
import threading
import time

from benchmark import checks, families, meter, serving, trafficgen
from benchmark.reference import serve_check

PER_REQUEST = ("ttft_ms", "generator_late_ms", "queue_wait_ms",
               "prefill_ms")
PAIRS = ("decode.moe.pairs_held", "decode.moe.pairs_total")
# the worker's own regions, whose window means go to the log: they tell
# one process's steps from another's in an untraced run
REGIONS = ("decode.sched", "decode.step.build", "decode.step.dispatch",
           "decode.step.wait", "decode.step.emit", "decode.prefill.build",
           "decode.prefill.dispatch", "decode.prefill.wait")


def reduce_window(records, t0, seconds, sub_windows):
    """``serving.reduce_records`` over a window that opened on a running
    loop: a token from before ``t0`` counts nowhere, and a request sent
    before ``t0`` has no time to its first token."""
    for r in records:
        r.times = [t for t in r.times if t >= t0]
    series, counters = serving.reduce_records(records, t0, seconds,
                                              sub_windows)
    close = t0 + seconds
    seen = [r for r in records
            if r.doc is not None and any(t <= close for t in r.times)]
    for name in PER_REQUEST:
        series[name] = [v for v, r in zip(series[name], seen)
                        if r.sent >= t0]
    return series, counters


def run(ctx):
    from dist_keras_tpu.observability import metrics

    family = families.of(ctx.config)
    lead_in = float(ctx.traffic.get("lead_in_s", 0.0))
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
        thread stops the profiler while the load goes on."""
        if profiler is not None and "stopper" not in state \
                and time.perf_counter() >= state["trace_until"]:
            profiler.close_window()
            state["stopper"] = threading.Thread(
                target=lambda: state.update(trace=profiler.finish()))
            state["stopper"].start()

    try:
        pool = trafficgen.requests(ctx.traffic, int(ctx.traffic["requests"]),
                                   vocab, ctx.seed)
        print("serve_closed_family: drawn", trafficgen.describe(pool),
              flush=True)
        serving.warm(engine, pool, vocab)
        ctx.mark("warm")
        replies = queue.Queue()
        records = []

        def send():
            req = pool[len(records) % len(pool)]
            rec = serving.Record(req, time.perf_counter())
            with spans("bench.submit"):
                serving.submit(engine, rec, on_done=replies.put)
            records.append(rec)

        started = time.perf_counter()
        for _ in range(clients):
            send()
        t0 = None
        while t0 is None or time.perf_counter() - t0 < ctx.seconds:
            if t0 is None and time.perf_counter() - started >= lead_in:
                begin()
                t0 = time.perf_counter()
            with spans("bench.wait_reply"):
                try:
                    replies.get(timeout=0.05)
                    replied = True
                except queue.Empty:
                    replied = False
            if t0 is not None:
                tick()
            # every reply taken is answered by the caller's next request,
            # on either side of the window's opening (a caller dropped
            # there would leave its slot empty for the whole window);
            # after the window's close nothing is sent
            if replied and (t0 is None
                            or time.perf_counter() - t0 < ctx.seconds):
                send()
        serving.close_window(engine, records)
        if profiler is not None:
            state["trace_until"] = 0.0
            tick()
            state["stopper"].join()
        in_window = compiles.count
        after = engine.stats()
        held, total = (b - a for a, b in zip(state["pairs"], pairs()))
        steps_ms = [1e3 * s for s in step_hist.samples]
        peak = meter.memory_peak_bytes(ctx.devices)
        counters = family.counters(engine, cfg)
    finally:
        compiles.close()
        engine.close(drain=False)
    del engine
    gc.collect()
    ctx.mark("window closed, engine freed; the reference follows")

    series, reduced = reduce_window(
        records, t0, ctx.seconds, int(ctx.traffic.get("sub_windows", 0)))
    counters.update(reduced)
    series["decode_step_ms"] = steps_ms
    prefills = [v for at, v in metrics.histogram(
        "decode.prefill_s").samples_between(t0, t0 + ctx.seconds)[0]]
    print(f"serving: {len(steps_ms)} decode steps of mean "
          f"{sum(steps_ms) / max(1, len(steps_ms)):.3f} ms, "
          f"{len(prefills)} prefills of {sum(prefills):.3f} s together, in "
          f"the window", flush=True)
    for region in REGIONS:
        inside, cut = metrics.histogram(
            "perf.phase." + region).samples_between(t0, t0 + ctx.seconds)
        inside = [v for at, v in inside]
        if inside and not cut:
            print(f"serving: {region} {len(inside)} times, mean "
                  f"{1e3 * sum(inside) / len(inside):.3f} ms", flush=True)
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
        "slots_mean": stepped / len(steps_ms) if steps_ms else None,
        "held_pairs_pct": 100.0 * held / total if total else None,
    })
    served = [{"tokens": r.doc["tokens"], "prompt_len": r.doc["prompt_len"]}
              for r in records if serving.finished(r)]
    samples = serve_check.pick(served, int(ctx.traffic["check_requests"]),
                               ctx.seed)
    compared = family.compare(ctx, cfg, samples)
    ctx.mark("compared")
    failed = sum(1 for r in records if r.doc is None)
    compared.append(checks.limit("failed_requests", failed, 0))
    return {
        "attempted": len(records),
        "failed": failed,
        "checks": compared,
        "trace": state.get("trace"),
        "shapes": {},
        "series": series,
        "counters": counters,
    }
