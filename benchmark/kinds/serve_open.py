"""An open loop: requests are sent when they are due, whatever the engine
is doing, and every time is taken from when the request was due.  When
the window closes, what is still in the engine is cut there."""

from __future__ import annotations

import time

from benchmark import serving, trafficgen


def wait(spans, name, until, tick):
    """Sleep until the clock reads ``until``, in slices of 20 ms, each
    under a span of its own: a span still open when the traced segment
    closes never reaches the trace, and a wait can outlast the segment."""
    while True:
        with spans(name):
            tick()
            left = until - time.perf_counter()
            if left > 0:
                time.sleep(min(left, 0.02))
        if left <= 0:
            return


def run(ctx):
    def drive(engine, vocab, spans, begin, tick):
        reqs = trafficgen.open_schedule(ctx.traffic, ctx.seconds, vocab,
                                        ctx.seed)
        print("serve_open: drawn", trafficgen.describe(reqs), flush=True)
        serving.warm(engine, reqs, vocab)
        begin()
        t0 = time.perf_counter()
        records = []
        for req in reqs:
            rec = serving.Record(req, t0 + req["due"])
            wait(spans, "bench.wait_due", rec.due, tick)
            with spans("bench.submit"):
                serving.submit(engine, rec)
            records.append(rec)
        wait(spans, "bench.wait_close", t0 + ctx.seconds, tick)
        serving.close_window(engine, records)
        return records, t0

    return serving.measure(ctx, drive)
