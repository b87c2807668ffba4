"""One of the run's counters (a count or a single reading), scaled."""


def read(outcome, ctx, counter, scale=1.0):
    value = outcome["counters"].get(counter)
    return None if value is None else scale * value
