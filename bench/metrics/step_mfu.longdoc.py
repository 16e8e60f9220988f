"""Model step: model operations of the window over the chips' peak, %
(long-document cells)."""

from kvbench import measure


def read(ctx):
    return measure.step_mfu(ctx)
