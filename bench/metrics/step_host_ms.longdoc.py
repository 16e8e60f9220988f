"""Scheduler: host time per decode step outside the step's two syncs, ms
(long-document cells).  The program's ``step`` counters over the window:
the steps' wall time less the decode step's token transfer
(``token_sync``) and the finishing prefill chunks' (``prefill_sync``),
over the decode steps.  The loop is synchronous -- once the tokens reach
the host nothing is queued on the chip until the next dispatch -- so this
is the host work the device waits for on each step.  A program without
these counters reports nothing."""


def read(ctx):
    if "step" not in ctx.stats_open or "step" not in ctx.stats_close:
        return None
    steps = ctx.counter("step", "decode_steps")
    if not steps:
        return None
    host = (ctx.counter("step", "wall_s")
            - ctx.counter("step", "token_sync_s")
            - ctx.counter("step", "prefill_sync_s"))
    return 1e3 * host / steps
