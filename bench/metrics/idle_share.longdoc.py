"""Device: share of the traced window with no operation running on the
chip, % (long-document cells)."""

from kvbench import measure


def read(ctx):
    return measure.idle_share(ctx)
