"""A statistic of one of the run's series (a list of host-clock or
program-histogram readings): ``median``, or ``p<q>`` with at least
``min_beyond`` samples beyond it.  A series that holds only the window's
tail (``series_from_s``: the program's histograms keep their most recent
samples) is marked as ``program_window`` marks one."""

import statistics

from benchmark import stats


def read(outcome, ctx, series, stat, min_beyond=0):
    values = outcome["series"].get(series)
    if not values:
        return None
    if series in outcome.get("series_from_s", {}):
        ctx.tails.append(outcome["series_from_s"][series])
    if stat == "median":
        return statistics.median(values)
    if not stat.startswith("p"):
        raise ValueError(f"unknown statistic {stat!r}")
    q = float(stat[1:])
    if stats.samples_beyond(len(values), q) < min_beyond:
        print(f"reader series_stat: {series} has {len(values)} samples, "
              f"fewer than {min_beyond} beyond p{q:g}: not reported")
        return None
    return stats.percentile(values, q)
