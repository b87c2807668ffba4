"""The sum of one of the program's stamped histograms over the sum of
another, inside the measured window, less one, in percent: how much more
of something was done than was asked for (a prefill's rung over its
prompt's length).  ``program_window``'s rules: a program without either
histogram, or whose bounded memory dropped part of the window, reads
nothing."""

from benchmark.readers import program_window


def read(outcome, ctx, over, under):
    top = program_window.window_samples(ctx, over, whole=True)
    bottom = program_window.window_samples(ctx, under, whole=True)
    if not top or not bottom or sum(bottom) <= 0:
        return None
    return 100.0 * (sum(top) / sum(bottom) - 1.0)
