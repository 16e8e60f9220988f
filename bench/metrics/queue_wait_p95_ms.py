"""Scheduler: time from a request's due time until its first prefill
work, 95th percentile over requests due in the window, ms (the generator's
lag plus the program's ``Result.queue_wait_s``)."""

from kvbench import stats


def read(ctx):
    due = ctx.due_in_window()
    if not due:
        return None
    return 1e3 * stats.percentile(
        [r.submit - r.due + r.result.queue_wait_s for r in due], 95)
