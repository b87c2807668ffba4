"""A decode step's share of its memory roofline for a dense family, whose
step's bytes depend on the live positions alone: ``decode_roofline``'s
reckoning without its expert cells.  The bytes a step MUST read
(``outcome["counters"]["decode_bytes"]``, from the family's ``opcount``
file: ``fixed``, the weights as often as the step applies them and what
else every step reads, and ``per_live_position``, a cached position's keys
and values in every cache entry) over the step's time on the device times
the HBM's peak.

Bytes: the window's mean step, from the program's stamped histogram of
the live positions.  Time: the mean duration of the step's program on chip
0 in the traced segment.  A program without the histogram, or a trace
without such a module, reads nothing."""

import os

from benchmark import meter
from benchmark.readers import decode_roofline, program_window
from benchmark.trace import reduce


def read(outcome, ctx, module, positions_histogram):
    need = outcome["counters"].get("decode_bytes")
    if not outcome.get("trace") or not need:
        return None
    positions = program_window.window_samples(ctx, positions_histogram)
    if not positions:
        return None
    steps = decode_roofline.module_seconds(
        reduce.find_xplane(os.path.join(ctx.scratch, "trace")), module,
        meter.Profiler.WINDOW)
    if not steps:
        return None
    required = (need["fixed"]
                + need["per_live_position"] * sum(positions) / len(positions))
    taken = sum(steps) / len(steps)
    print(f"reader decode_roofline_positions: {required / 1e9:.3f} GB a "
          f"step required, {1e3 * taken:.3f} ms on the device over "
          f"{len(steps)} traced steps")
    return 100.0 * required / ctx.peaks["hbm_bytes_per_s"] / taken
