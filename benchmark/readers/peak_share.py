"""A rate of required operations as a share of the chips' peak: the
counter ``rate_counter`` (units of work a second over the whole window,
all chips) times the counter ``ops_counter`` (operations one unit
requires, from shapes, no recomputation) over chips times the peak of
``dtype``."""


def read(outcome, ctx, rate_counter, ops_counter, dtype):
    rate = outcome["counters"].get(rate_counter)
    ops = outcome["counters"].get(ops_counter)
    if rate is None or ops is None:
        return None
    peak = ctx.peaks["flops_per_s"][dtype] * len(ctx.devices)
    return 100.0 * rate * ops / peak
