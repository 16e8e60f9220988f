"""Device: share of the traced window with no operation running on the
chip, % (chat cell)."""

from kvbench import measure


def read(ctx):
    return measure.idle_share(ctx)
