"""A statistic of the program's own registry histograms over the measured
window, in ms: the samples stamped (on ``time.perf_counter()``, the
region's start) between the window's start and its end, whatever the
program observed before or after.  Several ``histograms`` give the sum of
the statistic of each (regions that follow one another in an iteration:
the sum of their medians, not the median of per-iteration sums).  A
program whose histograms carry no stamps, or a window of which the
histogram's bounded memory has already dropped a part, reads nothing."""

from benchmark import stats


def read(outcome, ctx, histograms, stat):
    from dist_keras_tpu.observability import metrics

    if not stat.startswith("p"):
        raise ValueError(f"unknown statistic {stat!r}")
    lo = ctx.process_start + ctx.setup_s
    hi = lo + ctx.seconds
    total = 0.0
    for name in histograms:
        between = getattr(metrics.histogram(name), "samples_between", None)
        if between is None:
            return None
        pairs, truncated = between(lo, hi)
        if truncated:
            print(f"reader program_hist: {name} no longer holds the whole "
                  f"window ({len(pairs)} samples left): not reported")
            return None
        if not pairs:
            return None
        total += 1e3 * stats.percentile([v for _, v in pairs],
                                        float(stat[1:]))
    return total
