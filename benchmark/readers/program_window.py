"""A statistic of one of the program's own registry histograms over the
measured window, in the histogram's own unit (``program_hist`` reads
milliseconds of seconds): ``mean``, ``p<q>``, or ``sum_pct`` — the
samples' sum as a share of the window's seconds, in percent (regions that
observe their seconds).  Samples are those stamped inside the window; a
program without the histogram, without stamps, or whose bounded memory
has dropped part of the window reads nothing."""

from benchmark import stats


def window_samples(ctx, name):
    """-> the values stamped inside the window, or None."""
    from dist_keras_tpu.observability import metrics

    between = getattr(metrics.histogram(name), "samples_between", None)
    if between is None:
        return None
    lo = ctx.process_start + ctx.setup_s
    pairs, truncated = between(lo, lo + ctx.seconds)
    if truncated:
        print(f"reader program_window: {name} no longer holds the whole "
              f"window ({len(pairs)} samples left): not reported")
        return None
    return [v for _, v in pairs]


def read(outcome, ctx, histogram, stat):
    values = window_samples(ctx, histogram)
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "sum_pct":
        return 100.0 * sum(values) / ctx.seconds
    if stat.startswith("p"):
        return stats.percentile(values, float(stat[1:]))
    raise ValueError(f"unknown statistic {stat!r}")
