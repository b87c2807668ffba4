"""A decode step's share of its memory roofline: the bytes a step MUST
read (``outcome["counters"]["decode_bytes"]``, from
``trace/opcount_mla.py``: weights outside the routed experts and the
head, a held expert's weights for each (layer, expert) cell a token
reached, the latent rows of live positions only) over the step's time on
the device times the HBM's peak.

Bytes: the window's mean step, from the program's stamped histograms (the
cells hit and the live positions of each step).  Time: the mean duration
of the step's program on chip 0 in the traced segment (the trace's ``XLA
Modules`` line, events named by ``module``).  A program without those
histograms, or a trace without such a module, reads nothing."""

import os
import re

from benchmark import meter
from benchmark.readers import program_window
from benchmark.trace import reduce

MODULES_LINE = "XLA Modules"


def module_seconds(path, pattern, window_span):
    """Durations (s) of chip 0's programs whose name matches ``pattern``
    inside the traced window."""
    from jax.profiler import ProfileData

    want = re.compile(pattern)
    lo, hi = reduce.Trace.from_file(path).window(window_span)
    out = []
    for plane in ProfileData.from_file(path).planes:
        m = reduce.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != 0:
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                if want.search(ev.name) and lo <= t0 \
                        and t0 + ev.duration_ns * 1e-9 <= hi:
                    out.append(ev.duration_ns * 1e-9)
    return out


def read(outcome, ctx, module, cells_histogram, positions_histogram):
    need = outcome["counters"].get("decode_bytes")
    if not outcome.get("trace") or not need:
        return None
    cells = program_window.window_samples(ctx, cells_histogram)
    positions = program_window.window_samples(ctx, positions_histogram)
    if not cells or not positions:
        return None
    steps = module_seconds(
        reduce.find_xplane(os.path.join(ctx.scratch, "trace")), module,
        meter.Profiler.WINDOW)
    if not steps:
        return None
    required = (need["fixed"]
                + need["per_expert_cell"] * sum(cells) / len(cells)
                + need["per_live_position"] * sum(positions)
                / len(positions))
    taken = sum(steps) / len(steps)
    print(f"reader decode_roofline: {required / 1e9:.3f} GB a step "
          f"required, {1e3 * taken:.3f} ms on the device over "
          f"{len(steps)} traced steps")
    return 100.0 * required / ctx.peaks["hbm_bytes_per_s"] / taken
