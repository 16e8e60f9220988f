"""Pool / prefix cache: prompt tokens served from cached pages over all
prompt tokens of the traffic's requests admitted up to the window's close,
% (the program's ``prefix`` counters ``reused_tokens`` and
``prefill_tokens``; counted from the first request of the traffic, after
the documents and the warm-up requests of set-up, because in a closed loop
of long answers few requests are admitted inside the window itself)."""


def read(ctx):
    if "prefix" not in ctx.stats_close:
        return None
    reused = ctx.counter("prefix", "reused_tokens", since="admit")
    total = reused + ctx.counter("prefix", "prefill_tokens", since="admit")
    return 100.0 * reused / total if total else None
