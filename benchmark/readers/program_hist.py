"""A percentile of the program's own registry histograms over the measured
window, in ms: the samples stamped (on ``time.perf_counter()``, the
region's start) between the window's start and its end, whatever the
program observed before or after (``program_window``'s rules, its
bounded memory among them).  Several ``histograms`` give the sum of the
statistic of each (regions that follow one another in an iteration: the
sum of their medians, not the median of per-iteration sums)."""

from benchmark import stats
from benchmark.readers import program_window


def read(outcome, ctx, histograms, stat):
    if not stat.startswith("p"):
        raise ValueError(f"unknown statistic {stat!r}")
    total = 0.0
    for name in histograms:
        values = program_window.window_samples(ctx, name)
        if not values:
            return None
        total += 1e3 * stats.percentile(values, float(stat[1:]))
    return total
