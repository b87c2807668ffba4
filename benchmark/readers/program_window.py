"""A statistic of one of the program's own registry histograms over the
measured window, in the histogram's own unit (``program_hist`` reads
milliseconds of seconds): ``mean``, ``p<q>``, or ``sum_pct`` — the
samples' sum as a share of the window's seconds, in percent (regions that
observe their seconds).  Samples are those stamped inside the window; a
program without the histogram or without stamps reads nothing.  The
program's histograms keep their most recent samples (16,384 since PR 39,
a whole 51 s window of 3.1 ms steps): where a window holds more, a mean or
a percentile is of the window's LAST samples, as many as are kept, and is
never reported as if it were the whole window's: the reader leaves the
second it runs from in ``ctx.tails``, and the result line carries it
beside the value (``window_from_s``, ``run._read``); a sum needs the whole
window and reads nothing then."""

from benchmark import stats


def window_samples(ctx, name, whole=False):
    """-> the values stamped inside the window (``whole``: or None where
    the histogram's bounded memory has dropped a part of it), or None."""
    from dist_keras_tpu.observability import metrics

    between = getattr(metrics.histogram(name), "samples_between", None)
    if between is None:
        return None
    lo = ctx.process_start + ctx.setup_s
    pairs, truncated = between(lo, lo + ctx.seconds)
    if truncated and (whole or not pairs):
        print(f"reader program_window: {name} no longer holds the whole "
              f"window ({len(pairs)} samples left): not reported")
        return None
    if truncated:
        ctx.tails.append(pairs[0][0] - lo)
        print(f"reader program_window: {name} holds the window's last "
              f"{len(pairs)} samples, from {pairs[0][0] - lo:.1f} s into "
              f"it: the statistic is of those")
    return [v for _, v in pairs]


def read(outcome, ctx, histogram, stat):
    values = window_samples(ctx, histogram, whole=stat == "sum_pct")
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "sum_pct":
        return 100.0 * sum(values) / ctx.seconds
    if stat.startswith("p"):
        return stats.percentile(values, float(stat[1:]))
    raise ValueError(f"unknown statistic {stat!r}")
