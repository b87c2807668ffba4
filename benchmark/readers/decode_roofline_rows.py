"""A decode step's share of its memory roofline for a family that keeps a
state a SEQUENCE beside its paged entries: ``decode_roofline``'s
reckoning with a per-sequence term in the place of its expert cells.  The
bytes a step MUST move (``outcome["counters"]["decode_bytes"]``, from
``trace/opcount_olmo_hybrid.py``: the weights outside the embedding, a
cached position's keys and values for each live position, a sequence's
state read and written for each live row) over the step's time on the
device times the HBM's peak.

Bytes: the window's mean step, from the program's stamped histograms (the
live positions and the live rows of each step).  Time: the mean duration
of the step's program on chip 0 in the traced segment.  A program without
those histograms, or a trace without such a module, reads nothing."""

import os

from benchmark import meter
from benchmark.readers import decode_roofline, program_window
from benchmark.trace import reduce


def read(outcome, ctx, module, positions_histogram, rows_histogram):
    need = outcome["counters"].get("decode_bytes")
    if not outcome.get("trace") or not need:
        return None
    positions = program_window.window_samples(ctx, positions_histogram)
    rows = program_window.window_samples(ctx, rows_histogram)
    if not positions or not rows:
        return None
    steps = decode_roofline.module_seconds(
        reduce.find_xplane(os.path.join(ctx.scratch, "trace")), module,
        meter.Profiler.WINDOW)
    if not steps:
        return None
    required = (need["fixed"]
                + need["per_live_position"] * sum(positions) / len(positions)
                + need["per_live_row"] * sum(rows) / len(rows))
    taken = sum(steps) / len(steps)
    print(f"reader decode_roofline_rows: {required / 1e9:.3f} GB a step "
          f"required, {1e3 * taken:.3f} ms on the device over "
          f"{len(steps)} traced steps")
    return 100.0 * required / ctx.peaks["hbm_bytes_per_s"] / taken
