"""Device: share of the traced window in which no operation ran on the
chip, in percent."""


def read(red, rec, ctx):
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
