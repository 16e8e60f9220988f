"""Load generator: how late requests due in the window were submitted,
95th percentile over all of them, ms (host clock; open loop only)."""

from kvbench import stats


def read(ctx):
    due = ctx.due_in_window()
    if not due:
        return None
    return 1e3 * stats.percentile([r.submit - r.due for r in due], 95)
