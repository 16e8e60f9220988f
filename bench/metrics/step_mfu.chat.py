"""Model step: model operations of the window over the chip's peak, %
(chat cell)."""

from kvbench import measure


def read(ctx):
    return measure.step_mfu(ctx)
