"""Arithmetic the per-layer metric readers share: the model operations of
the window, the roofline of the fused decode attention kernel, and the
device time of the program's jitted closures.

The kernel's byte model is that of ``repro.launch.dryrun.decode_kv_traffic``
made exact per row: a decode step's attention reads, for each live row and
layer, the compressed store of its flushed blocks (payload and scales, at
the bytes per token the arena's shapes give), the raw append buffer of its
tail, its queries and its output.  A dead grid step reads nothing that
counts.  Its operations are a score and a weighted sum per (query head,
cached token).  Neither depends on how the kernel is written.
"""

from __future__ import annotations

import numpy as np

from kvbench.model import token_flops

# Names the program's jitted serving closures carry in a device trace
# (``jax.jit`` of ``Server._decode`` and of the paged chunked-prefill
# closures), each followed by ``(<hash>)``.
DECODE_PROGRAM = "jit__decode("
CHUNK_PROGRAMS = ("jit__chunk_paged(", "jit__chunk_paged_scan(",
                  "jit__finish_paged(")


def window_flops(ctx) -> float:
    """Model operations the window did: every decoded token at its context,
    every prefilled prompt token (the output head runs once per chunk) and
    the prefill attention pairs the program counted."""
    a = ctx.arch
    dec = ctx.decode_contexts()
    flops = float(sum(token_flops(a, int(c)) for c in dec))
    head = 2 * a.d_model * a.vocab
    pf_tokens = ctx.counter("prefill", "prefill_tokens")
    chunks = ctx.counter("prefill", "chunks")
    flops += pf_tokens * (2 * a.matmul_params() - head) + chunks * head
    if "prefix" in ctx.stats_close:
        flops += (ctx.counter("prefix", "prefill_attn_pairs")
                  * a.attn_flops_per_pair())
    return flops


def step_mfu(ctx) -> float:
    """Model operations per second of the window over the chips' peak, %."""
    return 100.0 * window_flops(ctx) / ctx.window_s / (
        ctx.chips * ctx.peaks["flops_per_s"])


def decode_attn_work(ctx) -> tuple[float, float]:
    """(operations, bytes) the decode steps' attention needed over the
    window, all layers."""
    a, kv = ctx.arch, ctx.kv
    T = kv["block"]
    c = ctx.decode_contexts().astype(np.float64)
    flushed = np.floor(c / T) * T
    per_layer_bytes = (flushed * kv["store_bytes_per_token"]
                       + (c - flushed) * kv["raw_bytes_per_token"]
                       ) / a.n_layers
    # queries read (bf16) and outputs written (f32), per row and layer
    io = a.n_heads * a.head_dim * (2 + 4)
    nbytes = float(np.sum(per_layer_bytes + io)) * a.n_layers
    flops = float(np.sum(c)) * a.attn_flops_per_pair()
    return flops, nbytes


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
