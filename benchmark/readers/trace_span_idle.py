"""Share of the traced segment in which chip 0 was idle while the program's
host region ``span`` was the innermost of its kind open, in percent.

The run's ``.xplane.pb`` is opened again and reduced over the program's
regions (``perf.decode.*``: the decode worker's own thread, one replica a
cell) in place of the benchmark's ``bench.*`` spans, so that an idle gap goes to
what the engine's worker was doing, not to where the load generator slept.
The whole table, every region and ``_no_span_``, goes to the log.  A
program that opens no such region reads nothing."""

import os

from benchmark import meter
from benchmark.trace import reduce

PREFIX = "perf.decode."


def table(reduced, path):
    """-> ({region or ``_no_span_``: idle seconds of chip 0}, seconds of
    the traced segment); worked out and printed once a run, and kept with
    the run's reduced trace."""
    if "region_idle" not in reduced:
        window = meter.Profiler.WINDOW
        trace = reduce.Trace.from_file(path, span_prefix="")
        own = [s for s in trace.host_spans if s[2].startswith(PREFIX)]
        trace.host_spans = own + [s for s in trace.host_spans
                                  if s[2] == window]
        again = trace.reduce(window_span=window)
        idle = again["idle_gaps"] if own else {}
        reduced["region_idle"] = (idle, again["window_s"])
        total = sum(idle.values())
        for name, seconds in sorted(idle.items(), key=lambda kv: -kv[1]):
            print(f"reader trace_span_idle: {name} {seconds:.6f} s idle, "
                  f"{100.0 * seconds / total:.1f}% of the idle time")
    return reduced["region_idle"]


def read(outcome, ctx, span):
    reduced = outcome.get("trace")
    if not reduced:
        return None
    idle, window_s = table(reduced, reduce.find_xplane(
        os.path.join(ctx.scratch, "trace")))
    if not idle or window_s <= 0:
        return None
    return 100.0 * idle.get(PREFIX + span, 0.0) / window_s
