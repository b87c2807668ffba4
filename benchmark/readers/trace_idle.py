"""Share of the traced steady window in which no operation ran on the
device, in percent."""


def read(outcome, ctx):
    reduced = outcome.get("trace")
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
