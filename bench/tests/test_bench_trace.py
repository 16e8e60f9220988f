"""The trace reduction on hand-made intervals and on a trace recorded on a
TPU v5e: a 0.5 s traced window of the ``yi_6b.longdoc`` cell
(``python3 bench/run.py --workload yi_6b.longdoc --seed 302 --seconds 0.5
--trace 1 --keep-trace DIR``, gzipped), kept in ``bench/tests/data``."""

from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import tinycell  # noqa: E402,F401  (puts bench/ on sys.path)

import trace_reduce as tr  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def _trace():
    """One device, a 100 ms window:

    * program ``jit__decode`` runs 0-40 ms with ops kernel 0-30 and
      fusion 20-40 (overlapping 20-30), program ``jit__chunk`` runs
      50-70 ms with a while 50-70 holding one op 55-65;
    * an all-gather 80-90 ms, half of it under a fusion 85-95;
    * host spans: ``bench:step`` 0-100, ``PjitFunction(_decode)`` 40-50
      inside it, nothing narrower over 70-80 or 95-100.
    """
    dev = tr.Device(
        ops=[(0, 30 * MS, "%fused_attention.6 = custom-call()"),
             (20 * MS, 40 * MS, "fusion.1"),
             (50 * MS, 70 * MS, "while.2"),
             (55 * MS, 65 * MS, "fusion.2"),
             (80 * MS, 90 * MS, "all-gather.3"),
             (85 * MS, 95 * MS, "fusion.4")],
        modules=[(0, 40 * MS, "jit__decode"), (50 * MS, 70 * MS, "jit__chunk"),
                 (80 * MS, 95 * MS, "jit__gather")])
    host = [(0, 100 * MS, "bench:step"),
            (40 * MS, 50 * MS, "PjitFunction(_decode)")]
    return tr.Trace(devices={"/device:TPU:0": dev}, host=host)


def test_busy_is_the_union_of_op_intervals():
    r = tr.reduce(_trace(), (0, 100 * MS))
    # busy: 0-40, 50-70, 80-95 = 75 ms of 100
    assert r.busy_s == pytest.approx(0.075)
    assert r.window_s == pytest.approx(0.1)
    assert r.idle_share == pytest.approx(0.25)


def test_time_by_program_and_by_op():
    r = tr.reduce(_trace(), (0, 100 * MS))
    assert r.program_s["jit__decode"] == pytest.approx(0.040)
    assert r.program_runs == {"jit__decode": 1, "jit__chunk": 1,
                              "jit__gather": 1}
    assert r.op_seconds(program="jit__decode") == pytest.approx(0.050)
    assert r.op_seconds(program="decode", op="%fused_attention") == \
        pytest.approx(0.030)
    # self time: the while holds fusion.2, so the two split its 20 ms
    assert r.op_seconds(program="jit__chunk") == pytest.approx(0.020)
    assert r.op_seconds(op="while") == pytest.approx(0.010)
    assert r.top_ops(1) == [["jit__decode/%fused_attention.6 = custom-call()",
                             pytest.approx(0.030)]]


def test_exposed_collective_time():
    r = tr.reduce(_trace(), (0, 100 * MS))
    assert r.collective_s == pytest.approx(0.010)
    assert r.collective_exposed_s == pytest.approx(0.005)


def test_gaps_are_named_by_the_innermost_host_span():
    r = tr.reduce(_trace(), (0, 100 * MS))
    gaps = dict((n, pytest.approx(s)) for n, s in r.top_gaps())
    # 40-50 under PjitFunction(_decode); 70-80 and 95-100 under bench:step
    assert gaps == {"PjitFunction(_decode)": 0.010, "bench:step": 0.015}


def test_window_clips_everything():
    r = tr.reduce(_trace(), (25 * MS, 60 * MS))
    # busy 25-40 and 50-60 of 35 ms
    assert r.busy_s == pytest.approx(0.025)
    assert r.op_seconds(op="%fused_attention") == pytest.approx(0.005)
    assert tr.host_span(_trace(), "bench:step") == (0, 100 * MS)
    assert tr.host_span(_trace(), "nope") is None


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr._subtract([[0, 10]], [[2, 3], [5, 20]]) == 2 + 2
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(devices={}, host=[]))


RECORDED = sorted(DATA.glob("*.xplane.pb.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_chip_trace(path):
    """A window traced on one TPU v5e: the device plane, its programs and
    the kernel are found, the host spans the harness writes are there, and
    the reduced numbers hang together."""
    t = tr.read_xspace(path)
    assert list(t.devices) == ["/device:TPU:0"]
    window = tr.host_span(t, "bench:window")
    assert window is not None
    r = tr.reduce(t, window)
    assert 0 < r.busy_s <= r.window_s
    from kvbench import measure

    assert sum(n for p, n in r.program_runs.items()
               if measure.DECODE_PROGRAM in p) >= 1
    kernel = r.op_seconds(program=measure.DECODE_PROGRAM, op=measure.KERNEL_OP)
    assert 0 < kernel < r.busy_s
    assert sum(s for _, s in r.gaps) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    # every gap lies under a host span: the harness's or a Python function
    assert r.gaps and all(n != "(no host span)" for n, _ in r.gaps)
