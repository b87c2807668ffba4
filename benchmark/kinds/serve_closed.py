"""A closed loop: ``clients`` callers, each sending its next request when
the last one returned (``serving.closed_loop``), until the window closes;
what is then still in the engine is cut there."""

from __future__ import annotations

from benchmark import serving, trafficgen


def run(ctx):
    def drive(engine, vocab, spans, begin, tick):
        pool = trafficgen.requests(ctx.traffic, int(ctx.traffic["requests"]),
                                   vocab, ctx.seed)
        print("serve_closed: drawn", trafficgen.describe(pool), flush=True)
        serving.warm(engine, pool, vocab)
        return serving.closed_loop(ctx, engine, pool,
                                   int(ctx.traffic["clients"]), spans,
                                   begin, tick)

    return serving.measure(ctx, drive)
