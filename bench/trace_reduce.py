"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time as the union of operation intervals,
the idle share, device time by program and by operation, the collective
time no compute overlaps, and the longest idle gaps, each named by what
the host was doing in it.

Device planes are ``/device:<KIND>:<n>``; on each, the ``XLA Modules``
line holds one event per program execution and the ``XLA Ops`` line one per
operation.  An operation belongs to the program execution whose interval
holds its start.  Operations nest (a ``while`` holds its body's
operations), so time by operation is self time: an operation's duration
less that of the operations inside it.  Host planes are ``/host:...``;
every event on every thread line is a host span (the harness's
``TraceAnnotation`` spans and the profiler's Python function events among
them), and a gap is named by the innermost host span that covers its
midpoint.  All times are nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass
class Device:
    ops: list = dataclasses.field(default_factory=list)      # (s, e, name)
    modules: list = dataclasses.field(default_factory=list)  # (s, e, name)


@dataclasses.dataclass
class Trace:
    devices: dict        # plane name -> Device
    host: list           # (s, e, name)


def read_xspace(path) -> Trace:
    """Device operations, program executions and host spans of a trace
    (``.xplane.pb``, or the same gzipped)."""
    from jax.profiler import ProfileData

    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path) as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                dest = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}.get(
                    line.name)
                if dest is not None:
                    dest.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events)
    return Trace(devices=devices, host=host)


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` pairs of possibly overlapping
    intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _subtract(a, b) -> float:
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def self_times(ops) -> list:
    """``(start, end, name, self_ns)`` of each operation: its duration less
    the durations of the operations directly inside it."""
    out, stack = [], []
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, name, e - s]
        if stack and e <= stack[-1][1]:
            stack[-1][3] -= e - s
        out.append(rec)
        stack.append(rec)
    return [tuple(r) for r in out]


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def _module_of(modules_sorted, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules_sorted[i][1] >= t:
        return modules_sorted[i][2]
    return ""


def _host_name(host_sorted, host_starts, t) -> str:
    """The innermost (shortest) host span covering time ``t``."""
    best = None
    i = bisect.bisect_right(host_starts, t)
    for s, e, name in reversed(host_sorted[max(0, i - 4096):i]):
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "(no host span)"


@dataclasses.dataclass
class Reduced:
    n_devices: int
    window_s: float
    busy_s: float                 # mean over devices
    op_s: dict                    # (program, op) -> self seconds, summed
    program_s: dict               # program -> seconds of its executions
    program_runs: dict            # program -> executions
    collective_s: float           # mean over devices
    collective_exposed_s: float   # mean over devices
    gaps: list                    # (host span name, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, program: str | None = None, op: str | None = None,
                   ) -> float:
        """Device seconds (summed over devices) of operations whose program
        and operation names contain the given substrings."""
        return sum(s for (p, o), s in self.op_s.items()
                   if (program is None or program in p)
                   and (op is None or op in o))

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations that took most device time, per device."""
        tot = collections.Counter()
        for (p, o), s in self.op_s.items():
            tot[f"{p}/{o}" if p else o] += s / max(self.n_devices, 1)
        return [[k, v] for k, v in tot.most_common(n)]

    def top_gaps(self, n: int = 10) -> list:
        """Idle time per host activity, the ``n`` largest."""
        tot = collections.Counter()
        for name, s in self.gaps:
            tot[name] += s
        return [[k, v] for k, v in tot.most_common(n)]


def reduce(trace: Trace, window: tuple | None = None,
           devices: list | None = None) -> Reduced:
    """Reduce ``trace`` over ``window`` (``(start_ns, end_ns)``; default:
    the span of all device operations) and the device planes named in
    ``devices`` (default: every device plane with operations)."""
    names = devices if devices is not None else sorted(
        n for n, d in trace.devices.items() if d.ops)
    if not names:
        raise ValueError("the trace has no device operations")
    if window is None:
        window = (min(s for n in names for s, _, _ in trace.devices[n].ops),
                  max(e for n in names for _, e, _ in trace.devices[n].ops))
    lo, hi = window
    host = sorted(_clip(trace.host, lo, hi))
    host_starts = [h[0] for h in host]
    busy = coll = exposed = 0.0
    op_s, program_s, runs = (collections.Counter(), collections.Counter(),
                             collections.Counter())
    gaps = []
    for n in names:
        dev = trace.devices[n]
        mods = sorted(dev.modules)
        starts = [m[0] for m in mods]
        for s, e, name in _clip(mods, lo, hi):
            program_s[name] += (e - s) / 1e9
            runs[name] += 1
        ops = _clip(dev.ops, lo, hi)
        for s, e, name, own in self_times(ops):
            op_s[(_module_of(mods, starts, s), name)] += own / 1e9
        merged = union((s, e) for s, e, _ in ops)
        busy += _length(merged)
        c = union((s, e) for s, e, name in ops if is_collective(name))
        other = union((s, e) for s, e, name in ops if not is_collective(name))
        coll += _length(c)
        exposed += _subtract(c, other)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((_host_name(host, host_starts, (gs + ge) / 2),
                             (ge - gs) / 1e9 / len(names)))
    k = len(names)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(n_devices=k, window_s=(hi - lo) / 1e9,
                   busy_s=busy / k / 1e9, op_s=dict(op_s),
                   program_s=dict(program_s), program_runs=dict(runs),
                   collective_s=coll / k / 1e9,
                   collective_exposed_s=exposed / k / 1e9, gaps=gaps)


def host_span(trace: Trace, name: str) -> tuple | None:
    """``(start_ns, end_ns)`` of the first host span called ``name``."""
    for s, e, n in sorted(trace.host):
        if n == name:
            return s, e
    return None
