"""Arithmetic the per-layer metric readers share: the model operations of
the window, the roofline of the fused decode attention kernel, and the
device time of the program's jitted closures.

What one token, one prefilled chunk or one decode step's attention costs
is the architecture's to count (``bench/archs/<name>.py``: ``token_flops``,
``prefill_flops``, ``decode_attn_work``); this module sums those counts
over what the window served.  None of them depends on how the kernel is
written.
"""

from __future__ import annotations

# Names the program's jitted serving closures carry in a device trace
# (``jax.jit`` of ``Server._decode`` and of the paged chunked-prefill
# closures), each followed by ``(<hash>)``.
DECODE_PROGRAM = "jit__decode("
CHUNK_PROGRAMS = ("jit__chunk_paged(", "jit__chunk_paged_scan(",
                  "jit__finish_paged(")


def window_flops(ctx) -> float:
    """Model operations the window did: every decoded token at its context,
    every prefilled prompt token and the prefill attention pairs the
    program counted."""
    model, a = ctx.cell.architecture, ctx.arch
    flops = float(sum(model.token_flops(a, int(c))
                      for c in ctx.decode_contexts()))
    pairs = (ctx.counter("prefix", "prefill_attn_pairs")
             if "prefix" in ctx.stats_close else 0)
    return flops + model.prefill_flops(
        a, ctx.counter("prefill", "prefill_tokens"),
        ctx.counter("prefill", "chunks"), pairs)


def step_mfu(ctx) -> float:
    """Model operations per second of the window over the chips' peak, %."""
    return 100.0 * window_flops(ctx) / ctx.window_s / (
        ctx.chips * ctx.peaks["flops_per_s"])


def decode_attn_work(ctx) -> tuple[float, float]:
    """(operations, bytes) the decode steps' attention needed over the
    window, all layers."""
    return ctx.cell.architecture.decode_attn_work(
        ctx.arch, ctx.decode_contexts(), ctx.kv)


def kernel_seconds(ctx, program: str) -> float | None:
    """Device seconds, summed over devices, of the Pallas kernel's
    operations inside ``program``; None when the trace has none."""
    s = ctx.trace.op_seconds(program=program, op=KERNEL_OP)
    return s if s > 0 else None


# The fused kernel's operation in a TPU trace: the Pallas custom call the
# program names ``fused_attention`` (its ``kvcomp/fused_attention`` scope).
KERNEL_OP = "%fused_attention"


def attn_roofline(ctx) -> float | None:
    """Least time the chip needs for the decode attention's operations and
    bytes, over the kernel's device time, %."""
    if ctx.trace is None:
        return None
    t = kernel_seconds(ctx, DECODE_PROGRAM)
    if t is None:
        return None
    flops, nbytes = decode_attn_work(ctx)
    least = max(flops / ctx.peaks["flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    # ``least`` is one chip's time for all of it; over k chips the work
    # splits k ways, as does the kernel time summed over the devices.
    return 100.0 * least / t


def idle_share(ctx) -> float | None:
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
