"""What the serving kinds share: the engine built from the configuration,
warm-up of the cell's shapes, per-request records stamped by the
benchmark's own callbacks, and the reduction of those records to series.
"""

from __future__ import annotations

import bisect
import gc
import json
import os
import queue
import threading
import time

import numpy as np

from benchmark import checks, meter, trafficgen, weights
from benchmark.reference import serve_check


class ModelSpec:
    """What the engine's serialization layer round-trips to a
    ``Transformer``: the architecture as JSON and the weights as a list of
    leaves.  The device copy is let go leaf by leaf as the host copy is
    made, so the two never sit on the device together with the engine's."""

    def __init__(self, cfg, params):
        self.cfg = cfg
        self._params = params

    def to_json(self):
        return json.dumps({"class_name": "Transformer", "config": self.cfg})

    def get_weights(self):
        import jax

        leaves = jax.tree.leaves(self._params)
        self._params = None
        out = []
        while leaves:
            out.append(np.asarray(leaves.pop(0)))
        return out


def model_config(conf):
    from dist_keras_tpu.models.transformer import transformer_config

    return transformer_config(
        input_dim=conf["vocab_size"],
        seq_len=conf["max_position_embeddings"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_layers=conf["num_hidden_layers"]["serve"], d_ff=conf["ffn_dim"],
        n_classes=conf["vocab_size"])


def build_engine(ctx):
    """Weights on the device from the seed in one jitted call, then the
    program's ``DecodeEngine`` on one replica -> (engine, model cfg)."""
    import jax

    from dist_keras_tpu.serving.decode import DecodeEngine

    cfg = model_config(ctx.config)
    serve = ctx.config["serve"]
    # the spec owns the only reference to the device copy and lets it go
    # while the engine takes its own
    spec = ModelSpec(cfg, jax.jit(lambda k: weights.transformer(k, cfg))(
        weights.base_key(ctx.seed)))
    engine = DecodeEngine(
        spec, replicas=1,
        prefill_ladder=tuple(serve["prefill_ladder"]),
        decode_ladder=tuple(serve["decode_ladder"]),
        page_size=serve["page_size"], max_queue=serve["max_queue"],
        devices=list(ctx.devices[:1]))
    return engine, cfg


def warm(engine, reqs, vocab):
    """Run every prefill rung the requests will hit and every decode rung
    once, so that nothing compiles inside the window."""
    rungs = sorted({min(b for b in engine.prefill_ladder
                        if len(r["prompt"]) <= b) for r in reqs})
    for rung in rungs:
        # as long as the rung allows and a slot's positions hold
        engine.generate([1] * min(rung, engine.seq_len - 2),
                        max_new_tokens=2, timeout_s=1100)
    short = [2] * min(rungs[0], 8)
    for slots in engine.decode_ladder:
        for _ in range(4):
            gens = [engine.submit_generate(short, max_new_tokens=8)
                    for _ in range(slots)]
            for g in gens:
                g.result(timeout=1100)
            if ["decode", slots] in [list(s) for s in
                                     engine.stats()["shapes_dispatched"]]:
                break
        else:
            raise RuntimeError(f"warm-up never ran {slots} slots together")


class Record:
    """One request as the benchmark saw it."""

    __slots__ = ("req", "due", "sent", "gen", "times", "doc", "error",
                 "replied")

    def __init__(self, req, due):
        self.req = req
        self.due = due
        self.sent = None
        self.gen = None
        self.times = []
        self.doc = None
        self.error = None
        self.replied = threading.Event()

    def on_token(self, _token):
        self.times.append(time.perf_counter())


def submit(engine, rec, on_done=None):
    """Send one request; a refusal at the door is a failed request."""
    from dist_keras_tpu.serving.engine import Overloaded

    rec.sent = time.perf_counter()
    try:
        rec.gen = engine.submit_generate(
            rec.req["prompt"].tolist(), max_new_tokens=rec.req["max_new"],
            on_token=rec.on_token)
    except Overloaded as e:
        reply(rec, on_done, error=e)
        return
    rec.gen.future.add_done_callback(lambda fut: reply(
        rec, on_done, doc=None if fut.exception() else fut.result(),
        error=fut.exception()))


def reply(rec, on_done, doc=None, error=None):
    rec.doc, rec.error = doc, error
    rec.replied.set()
    if on_done is not None:
        on_done(rec)


def close_window(engine, records, timeout_s=60.0):
    """The window has closed: requests still in the engine are cut there
    (cancelled: they resolve with the tokens they have, and are neither
    finished nor failed).  One that does not resolve is a failed request."""
    for rec in records:
        if not rec.replied.is_set() and rec.gen is not None:
            engine.cancel(rec.gen)
    deadline = time.perf_counter() + timeout_s
    for rec in records:
        if not rec.replied.wait(max(0.0, deadline - time.perf_counter())):
            rec.error = TimeoutError("no reply to a cancel")


def finished(rec):
    """The request ran to its last token inside the window."""
    return rec.doc is not None and rec.doc["finish"] != "cancelled"


def closed_loop(ctx, engine, pool, clients, spans, begin, tick):
    """``clients`` callers, each sending its next request of ``pool`` (in
    ``trafficgen.closed_order``) when the last one returned, until the
    window closes -> (records, t0).  The callers start ``lead_in_s``
    seconds before the window opens (``begin()``, the end of set-up), so
    that the window samples a server that is running and not one that
    prefills every caller's first request back to back and then steps
    them in lockstep; tokens that came out before the window count
    nowhere (``reduce_records``) and those seconds fall to set-up.  What
    is still in the engine at the window's close is cut there."""
    lead_in = float(ctx.traffic.get("lead_in_s", 0.0))
    order = trafficgen.closed_order(ctx.traffic, pool, clients)
    replies = queue.Queue()
    records = []

    def send():
        rec = Record(next(order), time.perf_counter())
        with spans("bench.submit"):
            submit(engine, rec, on_done=replies.put)
        records.append(rec)

    t0 = None
    started = time.perf_counter()
    if lead_in <= 0.0:
        begin()
        t0 = time.perf_counter()
    for _ in range(clients):
        send()
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
        # every reply taken is answered by the caller's next request, on
        # either side of the window's opening (a caller dropped there
        # would leave its slot empty for the whole window); after the
        # window's close nothing is sent
        if replied and (t0 is None
                        or time.perf_counter() - t0 < ctx.seconds):
            send()
    close_window(engine, records)
    return records, t0


def check_samples(ctx, records):
    """The finished requests ``correct`` compares with the reference: a
    sample of ``check_requests`` drawn from the seed, the longest among
    them, of those the mix drew whole (a first reply cut by
    ``stagger_start`` is not one of the mix's)."""
    served = [{"tokens": r.doc["tokens"], "prompt_len": r.doc["prompt_len"]}
              for r in records
              if finished(r) and not r.req.get("staggered")]
    return serve_check.pick(served, int(ctx.traffic["check_requests"]),
                            ctx.seed)


BURST_GAP_S = 0.002


def burst_rates(events, lo, hi, parts):
    """Tokens a second over ``parts`` sub-windows of whole engine
    iterations.  One iteration's tokens come out together (a burst, its
    callbacks microseconds apart); a sub-window runs from the end of one
    burst to the end of a later one, the bursts dealt evenly, so that no
    edge cuts through an iteration: with edges on the clock a sub-window's
    count jumps by a whole slot set (1.1% here, PR 23)."""
    inside = [t for t in events if lo <= t <= hi]
    ends, counts = [], []          # each burst's last time, tokens so far
    for i, t in enumerate(inside):
        if i + 1 == len(inside) or inside[i + 1] - t > BURST_GAP_S:
            ends.append(t)
            counts.append(i + 1)
    if len(ends) <= parts:
        return []
    cuts = [round(k * (len(ends) - 1) / parts) for k in range(parts + 1)]
    return [(counts[b] - counts[a]) / (ends[b] - ends[a])
            for a, b in zip(cuts, cuts[1:])]


def reduce_records(records, t0, seconds, sub_windows=0):
    """Per-request records -> (series, counters), times in ms.  Only what
    happened inside the window counts: a token that came out before it
    opened (a closed loop's lead-in) or after its close belongs to no
    series, and a request sent before it opened has no time to its first
    token, no queue and no prefill."""
    close = t0 + seconds
    seen = []
    for r in records:
        times = [t for t in r.times if t0 <= t <= close]
        if r.doc is not None and times:
            seen.append((r, times))
    events = sorted(t for _, times in seen for t in times)
    ttft, gaps, late, waited, prefill = [], [], [], [], []
    for r, times in seen:
        gaps.extend(1e3 * (b - a) for a, b in zip(times, times[1:]))
        if r.sent < t0:
            continue
        first = times[0]
        ttft.append(1e3 * (first - r.due))
        late.append(1e3 * (r.sent - r.due))
        # the engine's worker is one loop: this prefill began when the
        # loop's previous event (a step's or a prefill's token) was out,
        # or when the request arrived, whichever is later
        i = bisect.bisect_left(events, first)
        before = events[i - 1] if i > 0 else r.sent
        began = max(before, r.sent)
        prefill.append(1e3 * (first - began))
        waited.append(1e3 * (began - r.due))
    series = {"ttft_ms": ttft, "gap_ms": gaps, "generator_late_ms": late,
              "queue_wait_ms": waited, "prefill_ms": prefill}
    if sub_windows:
        series["subwindow_tokens_per_s"] = burst_rates(
            events, t0, close, sub_windows)
    counters = {
        # all the tokens that came out in the window over all of its time
        "window_tokens_per_s": len(events) / seconds,
        "requests_finished": sum(1 for r in records if finished(r)),
        "requests_cut_at_close": sum(
            1 for r in records if r.doc is not None and not finished(r)),
    }
    return series, counters


def window_steps(step_hist, t0, seconds):
    """The window's decode steps -> (each one's ms, their exact count,
    ``series_from_s``).  The program's histogram keeps its most recent
    4,096 samples and counts them all: where the window held more, the
    list is its tail, and ``series_from_s`` says from which second."""
    pairs, cut = step_hist.samples_between(t0, t0 + seconds)
    tail = {"decode_step_ms": pairs[0][0] - t0} if cut and pairs else {}
    return ([1e3 * v for _, v in pairs], step_hist.totals()["count"], tail)


# the worker's own regions, whose window means go to the log: they tell
# one process's steps from another's in an untraced run
REGIONS = ("decode.sched", "decode.step.build", "decode.step.dispatch",
           "decode.step.wait", "decode.step.emit", "decode.prefill.build",
           "decode.prefill.dispatch", "decode.prefill.wait")


def log_regions(t0, seconds):
    from dist_keras_tpu.observability import metrics

    for region in REGIONS:
        inside, cut = metrics.histogram(
            "perf.phase." + region).samples_between(t0, t0 + seconds)
        if inside and not cut:
            mean = sum(v for _, v in inside) / len(inside)
            print(f"serving: {region} {len(inside)} times, mean "
                  f"{1e3 * mean:.3f} ms", flush=True)


def measure(ctx, drive):
    """The part of a run both loops share.  ``drive(engine, vocab, spans)``
    -> (records, t0): it calls ``begin()`` itself, right before the first
    request, and returns when the window has closed and every request
    still in the engine has been cut (``close_window``)."""
    from dist_keras_tpu.observability import metrics

    spans = meter.Spans()
    compiles = meter.CompileCounter()
    ctx.mark("imports done")
    engine, cfg = build_engine(ctx)
    ctx.mark("engine built")
    profiler = None
    if ctx.trace:
        profiler = meter.Profiler(os.path.join(ctx.scratch, "trace"), spans)
    step_hist = metrics.histogram("decode.step_s")
    state = {}

    def begin():
        """End of set-up: counters to zero, the profiler open."""
        step_hist.reset()
        state["before"] = engine.stats()
        compiles.reset()
        if profiler is not None:
            profiler.start()
            state["trace_until"] = time.perf_counter() + float(
                ctx.traffic["trace_seconds"])
        ctx.setup_done()

    def tick():
        """Called by the loop between its own actions: closes the traced
        segment once it has run its length.  Stopping the profiler takes
        seconds, so a helper thread does it while the load goes on; the
        trace is reduced once the window has closed."""
        if profiler is not None and "stopper" not in state \
                and time.perf_counter() >= state["trace_until"]:
            profiler.close_window()
            state["stopper"] = threading.Thread(target=profiler.stop)
            state["stopper"].start()

    try:
        records, t0 = drive(engine, cfg["n_classes"], spans, begin, tick)
        if profiler is not None:
            state["trace_until"] = 0.0
            tick()
            state["stopper"].join()
            state["trace"] = profiler.reduced()
        in_window = compiles.count
        after = engine.stats()
        steps_ms, steps, steps_from_s = window_steps(step_hist, t0,
                                                     ctx.seconds)
        peak = meter.memory_peak_bytes(ctx.devices)
    finally:
        compiles.close()
        engine.close(drain=False)
    del engine
    gc.collect()
    ctx.mark("window closed, engine freed; the reference follows")

    series, counters = reduce_records(
        records, t0, ctx.seconds, int(ctx.traffic.get("sub_windows", 0)))
    series["decode_step_ms"] = steps_ms
    log_regions(t0, ctx.seconds)
    worst = max(records, key=lambda r: r.sent - r.due)
    print(f"serving: the generator sent {len(records)} requests, the one "
          f"due at {worst.due - t0:.3f} s the latest, by "
          f"{1e3 * (worst.sent - worst.due):.3f} ms", flush=True)
    print(f"serving: {counters['requests_finished']} of {len(records)} "
          f"requests finished in the window, "
          f"{counters['requests_cut_at_close']} cut at its close",
          flush=True)
    before = state["before"]
    # every admitted request's first token comes from its prefill, the
    # rest from decode steps: tokens a step is the mean of slots in use
    stepped = (after["tokens"] - before["tokens"]) \
        - (after["admitted"] - before["admitted"])
    counters.update({
        "memory_peak_bytes": peak,
        "window_compiles": in_window,
        "slots_mean": stepped / steps if steps else None,
    })
    compared = serve_check.compare(ctx, cfg, check_samples(ctx, records))
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
