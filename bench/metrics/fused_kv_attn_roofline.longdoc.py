"""Fused decode attention kernel: least time for the live compressed
bytes and operations of the window's decode steps over the kernel's device
time in the decode program, % (long-document cells)."""

from kvbench import measure


def read(ctx):
    return measure.attn_roofline(ctx)
