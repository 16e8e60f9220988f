"""Latency arithmetic, copied from ``benchmarks/serve_throughput.py``'s
``_latency_block`` so that no later change to the program moves it: a tail
is numpy's linearly interpolated percentile over every sample, never a
median of chunks and never read from a histogram."""

from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile of all ``samples`` (numpy's default linear
    interpolation)."""
    a = np.asarray(samples, np.float64)
    if a.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(a, q))


def gaps_in(token_times, lo: float, hi: float) -> np.ndarray:
    """Gaps between consecutive tokens of one request whose two stamps both
    lie in ``[lo, hi]``."""
    t = np.asarray(token_times, np.float64)
    if t.size < 2:
        return np.zeros(0)
    ok = (t[:-1] >= lo) & (t[1:] <= hi)
    return np.diff(t)[ok]
