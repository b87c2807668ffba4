"""What one of the program's stamped histograms gathered inside the
measured window, taken whole: ``sum`` (the samples' sum, in the
histogram's own unit) or ``count`` (how many samples the window holds).
For a histogram that observes what is rare (a host stall): a window with
none reads 0.0, which says something, where a program WITHOUT the
histogram reads nothing.  So the name is looked up and never created
(``metrics.histogram`` makes what it does not find; the registry's
snapshot lists what exists).  A window the histogram's bounded memory
has dropped a part of reads nothing: a total of a tail is no total."""


def window_pairs(ctx, name, reader="window_total"):
    """-> ``[(stamp, value), ...]`` of the window, or None where the
    program has no such histogram, no stamps, or not the whole window
    (said in the log under the asking ``reader``'s name)."""
    from dist_keras_tpu.observability import metrics

    if name not in metrics.snapshot(percentiles=False)["histograms"]:
        return None
    between = getattr(metrics.histogram(name), "samples_between", None)
    if between is None:
        return None
    lo = ctx.process_start + ctx.setup_s
    pairs, truncated = between(lo, lo + ctx.seconds)
    if truncated:
        print(f"reader {reader}: {name} no longer holds the whole "
              f"window ({len(pairs)} samples left): not reported")
        return None
    return pairs


def read(outcome, ctx, histogram, stat):
    if stat not in ("sum", "count"):
        raise ValueError(f"unknown statistic {stat!r}")
    pairs = window_pairs(ctx, histogram)
    if pairs is None:
        return None
    total = float(sum(v for _, v in pairs))
    print(f"reader window_total: {histogram}: {len(pairs)} samples in the "
          f"window, {total:.4f} in all"
          + "".join(f", {v:.4f} at {at - ctx.process_start - ctx.setup_s:.2f}"
                    f" s" for at, v in pairs[:8]))
    return float(len(pairs)) if stat == "count" else total
