"""The seconds a loop's long iterations cost a window, whatever made them
long: over the samples of ``histogram`` inside the measured window that
overlap no sample of ``apart_from`` and are longer than ``factor`` times
the median of those, the sum of (sample - median).  Both histograms stamp
a sample with its START and hold its length in seconds, on one clock, so
a sample is the interval ``[stamp, stamp + value)``.

Why the second histogram: an iteration that waited for another piece of
work (a decode step launched behind a prefill waits for it on the device)
holds that work's time, hundreds of times a window; left in, those would
bury the few iterations that a stalled host made long.  What a stall
costs while such work runs is therefore not in this number.

The statistic needs the whole window of both histograms
(``window_total``'s rules: a program without either, or one whose
bounded memory dropped a part of the window, reads nothing); a window
with samples and none of them long reads 0.0."""

import bisect
import statistics

from benchmark.readers import window_total

# two samples that touch (one ends where the next begins, each end a sum
# of one clock's readings) do not overlap
TOUCH_S = 1e-6


def _union(pairs):
    """Intervals ``(start, length)`` -> their union as sorted, disjoint
    ``[start, end]`` lists."""
    out = []
    for at, v in sorted(pairs):
        if out and at <= out[-1][1]:
            out[-1][1] = max(out[-1][1], at + v)
        else:
            out.append([at, at + v])
    return out


def apart(pairs, others):
    """The values of ``pairs`` whose interval overlaps none of ``others``."""
    busy = _union(others)
    starts = [a for a, _ in busy]
    kept = []
    for at, v in pairs:
        # the last busy interval that begins before this sample ends
        # overlaps it or none does
        i = bisect.bisect_left(starts, at + v - TOUCH_S) - 1
        if i >= 0 and busy[i][1] > at + TOUCH_S:
            continue
        kept.append(v)
    return kept


def read(outcome, ctx, histogram, apart_from, factor):
    pairs = window_total.window_pairs(ctx, histogram, "window_excess")
    others = window_total.window_pairs(ctx, apart_from, "window_excess")
    if not pairs or others is None:
        return None
    values = apart(pairs, others)
    if not values:
        return None
    median = statistics.median(values)
    long = [v for v in values if v > factor * median]
    print(f"reader window_excess: {histogram}: {len(values)} of "
          f"{len(pairs)} samples apart from {apart_from}'s {len(others)}, "
          f"median {1e3 * median:.3f} ms, {len(long)} longer than "
          f"{factor} times that")
    return float(sum(v - median for v in long))
