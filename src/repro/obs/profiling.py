"""Kernel profiling hooks: named scopes for compression stages and opt-in
``jax.profiler`` capture (DESIGN.md §14).

The Real-TPU ROADMAP item needs device profiles that attribute time to the
*compression* stages KVComp adds — fused in-situ-decompression attention,
the ``pack_encode`` Store path, the huffman LUT decode, the blockwise span
loop — not one undifferentiated jit blob.  ``annotate(name)`` wraps a
region in ``jax.named_scope`` so the XLA ops it traces carry the name into
any profile (TensorBoard, Perfetto, ``xprof``); it is a trace-time-only
construct with zero runtime cost, safe on every hot path.  ``annotation``
is the *runtime* counterpart (``jax.profiler.TraceAnnotation``) for host
regions, and ``trace_capture(dir)`` brackets a block with
``jax.profiler.start_trace``/``stop_trace`` — the hook behind
``benchmarks/serve_throughput.py --profile-dir``.
"""

from __future__ import annotations

import contextlib

import jax

__all__ = ["annotate", "annotation", "trace_capture"]

SCOPE_PREFIX = "kvcomp"


def annotate(name: str):
    """Trace-time scope for jitted code: ops created inside carry
    ``kvcomp/<name>`` into profiles.  Usable as context manager or
    decorator (``jax.named_scope`` supports both)."""
    return jax.named_scope(f"{SCOPE_PREFIX}/{name}")


@contextlib.contextmanager
def annotation(name: str):
    """Runtime (host-side) profiler annotation around a region — shows up
    as a track slice in a captured ``jax.profiler`` trace."""
    with jax.profiler.TraceAnnotation(f"{SCOPE_PREFIX}:{name}"):
        yield


@contextlib.contextmanager
def trace_capture(log_dir: str | None):
    """Capture a ``jax.profiler`` device+host trace into ``log_dir`` for
    the duration of the block; ``None`` disables (the default path costs
    nothing).  A trace that cannot start or stop raises: a run asked to
    produce a trace must not exit 0 without one."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
