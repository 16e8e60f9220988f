"""Model step: device time of the chunked-prefill programs per prompt
chunk prefilled in the window, ms (trace; the program's ``prefill.chunks``
counter)."""

from kvbench import measure


def read(ctx):
    chunks = ctx.counter("prefill", "chunks")
    if ctx.trace is None or not chunks:
        return None
    t = sum(ctx.trace.op_seconds(program=p) for p in measure.CHUNK_PROGRAMS)
    return 1e3 * t / ctx.trace.n_devices / chunks if t else None
