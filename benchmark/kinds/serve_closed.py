"""A closed loop: ``clients`` callers, each sending its next request when
the last one returned, until the window closes; what is then still in
the engine is cut there."""

from __future__ import annotations

import queue
import time

from benchmark import serving, trafficgen


def run(ctx):
    clients = int(ctx.traffic["clients"])

    def drive(engine, vocab, spans, begin, tick):
        pool = trafficgen.requests(ctx.traffic, int(ctx.traffic["requests"]),
                                   vocab, ctx.seed)
        print("serve_closed: drawn", trafficgen.describe(pool), flush=True)
        serving.warm(engine, pool, vocab)
        replies = queue.Queue()
        records = []

        def send():
            req = pool[len(records) % len(pool)]
            rec = serving.Record(req, time.perf_counter())
            with spans("bench.submit"):
                serving.submit(engine, rec, on_done=replies.put)
            records.append(rec)

        begin()
        t0 = time.perf_counter()
        for _ in range(clients):
            send()
        while time.perf_counter() - t0 < ctx.seconds:
            with spans("bench.wait_reply"):
                try:
                    replies.get(timeout=0.05)
                except queue.Empty:
                    tick()
                    continue
            tick()
            if time.perf_counter() - t0 < ctx.seconds:
                send()
        serving.close_window(engine, records)
        return records, t0

    return serving.measure(ctx, drive)
