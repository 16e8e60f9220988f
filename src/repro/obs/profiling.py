"""Kernel profiling hooks: named scopes for compression stages and opt-in
``jax.profiler`` capture (DESIGN.md §14).

The Real-TPU ROADMAP item needs device profiles that attribute time to the
*compression* stages KVComp adds — fused in-situ-decompression attention,
the ``pack_encode`` Store path, the huffman LUT decode, the blockwise span
loop — not one undifferentiated jit blob.  ``annotate(name)`` wraps a
region in ``jax.named_scope`` so the XLA ops it traces carry the name into
any profile (TensorBoard, Perfetto, ``xprof``); it is a trace-time-only
construct with zero runtime cost, safe on every hot path.  ``Phases`` is
the *runtime* counterpart for host regions: each named phase of a serving
step is both a ``kvcomp:<name>`` profiler span
(``jax.profiler.TraceAnnotation``), on the same clock as the device
operations of a captured trace, and an always-on registry counter of the
phase's self time, so a run that captured no trace still reports where
the host spent its steps.  ``trace_capture(dir)`` brackets a block with
``jax.profiler.start_trace``/``stop_trace`` — the hook behind
``benchmarks/serve_throughput.py --profile-dir``.
"""

from __future__ import annotations

import contextlib
from time import perf_counter

import jax

__all__ = ["Phases", "annotate", "trace_capture"]

SCOPE_PREFIX = "kvcomp"


def annotate(name: str):
    """Trace-time scope for jitted code: ops created inside carry
    ``kvcomp/<name>`` into profiles.  Usable as context manager or
    decorator (``jax.named_scope`` supports both)."""
    return jax.named_scope(f"{SCOPE_PREFIX}/{name}")


class Phases:
    """Named host phases of one loop, each a ``kvcomp:<name>`` profiler
    span and a registry counter.

    ``with phases("admit"):`` opens ``TraceAnnotation("kvcomp:admit")`` and
    adds the phase's self time (``time.perf_counter`` seconds, less the
    time of the phases nested in it) to the counter ``<prefix>.admit_s``.
    An outermost phase also adds its whole duration to ``<prefix>.wall_s``,
    so the self times of the phases nested in it sum to ``wall_s``.  There
    is no switch: without a profiler running, a phase costs two
    ``perf_counter`` calls and one ``TraceAnnotation`` object.
    """

    def __init__(self, registry, prefix: str, names):
        self.wall = registry.counter(f"{prefix}.wall_s")
        self._phases = {
            name: _Phase(self, f"{SCOPE_PREFIX}:{name}",
                         registry.counter(f"{prefix}.{name}_s"))
            for name in names}
        self._open: list[list] = []  # one frame per open phase

    def __call__(self, name: str) -> "_Phase":
        return self._phases[name]

    def snapshot(self) -> dict:
        """``wall_s`` and each phase's self seconds, as ``<name>_s``."""
        return {"wall_s": self.wall.value,
                **{f"{name}_s": p.counter.value
                   for name, p in self._phases.items()}}


class _Phase:
    """One named phase; re-entrant, since the open phases live on the
    owner's stack."""

    __slots__ = ("_owner", "_label", "counter")

    def __init__(self, owner: Phases, label: str, counter):
        self._owner, self._label, self.counter = owner, label, counter

    def __enter__(self):
        # The span opens and closes right beside the clock readings, so a
        # profile's spans and the counters measure the same interval.
        span = jax.profiler.TraceAnnotation(self._label)
        frame = [span, 0.0, 0.0]  # span, start, seconds of nested phases
        self._owner._open.append(frame)
        span.__enter__()
        frame[1] = perf_counter()

    def __exit__(self, *exc):
        stack = self._owner._open
        frame = stack[-1]
        end = perf_counter()
        frame[0].__exit__(None, None, None)
        stack.pop()
        took = end - frame[1]
        self.counter.value += took - frame[2]
        if stack:
            stack[-1][2] += took
        else:
            self._owner.wall.value += took


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
