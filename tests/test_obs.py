"""Serving observability (DESIGN.md §14): metrics registry, scheduler event
trace, and the stats()/export surfaces built on them.

The load-bearing contracts:

* ``Server.stats()`` is ONE schema — the key tree depends only on
  (cache_mode, prefix_cache), never on the mesh (the sharded leg runs in a
  subprocess with a forced 4-device count and must produce the identical
  tree).
* Trace-reconstructed per-request timings equal the ``Result`` fields
  EXACTLY (float-for-float): token events reuse the same monotonic stamps.
* ``trace="off"`` records nothing and adds no device dispatches — greedy
  outputs are bit-identical to a traced run.
* The always-on step phases partition ``Server.step``: their
  ``serve.step.*`` self times sum to its wall time and agree with the
  ``kvcomp:*`` spans of a ``jax.profiler`` trace, whose clock the Chrome
  export shares.
"""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import EventTrace
from repro.models import model as M
from repro.models import registry
from repro.serve.scheduler import STEP_PHASES, Request, Server, ServerConfig

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
BENCH = ROOT / "bench"

LENS = (7, 13, 19, 26)
NEWS = (3, 6, 4, 5)


# ---------------------------------------------------------------------------
# Metrics primitives (pure host, no jax)
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    c.inc(0.25)  # seconds accumulate as floats
    assert c.value == 5.25
    g = Gauge()
    g.set(3.0)
    g.set_max(2.0)
    assert g.value == 3.0
    g.set_max(7.5)
    assert g.value == 7.5


def test_histogram_observe_and_quantiles():
    h = Histogram()
    vals = [0.001, 0.002, 0.004, 0.008, 0.016, 0.032]
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.min == min(vals) and h.max == max(vals)
    # quantiles: monotone, clamped to the observed range
    q = [h.quantile(p) for p in (0.0, 0.25, 0.5, 0.9, 1.0)]
    assert all(a <= b for a, b in zip(q, q[1:]))
    assert min(vals) <= q[0] and q[-1] <= max(vals)
    snap = h.snapshot()
    assert set(snap) == {"count", "sum", "mean", "min", "max", "p50", "p99"}
    assert snap["mean"] == pytest.approx(sum(vals) / len(vals))
    # empty histogram: all-zero snapshot, no division blowups
    assert Histogram().snapshot()["count"] == 0
    assert Histogram().quantile(0.5) == 0.0


def test_registry_snapshot_nesting_and_types():
    reg = MetricsRegistry()
    reg.counter("serve.preemptions").inc(2)
    reg.gauge("pool.shard0.high_water_pages").set(7)
    reg.histogram("serve.ttft_s").observe(0.01)
    snap = reg.snapshot()
    assert snap["serve"]["preemptions"] == 2
    assert snap["pool"]["shard0"]["high_water_pages"] == 7
    assert snap["serve"]["ttft_s"]["count"] == 1
    # same name + same type returns the same object; a type clash raises
    assert reg.counter("serve.preemptions").value == 2
    with pytest.raises(TypeError):
        reg.gauge("serve.preemptions")
    assert "serve.preemptions" in reg
    assert reg.get("nope") is None


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("serve.preemptions").inc()
    reg.histogram("serve.ttft_s").observe(0.01)
    text = reg.prometheus_text()
    assert "# TYPE kvcomp_serve_preemptions counter" in text
    assert "kvcomp_serve_preemptions 1" in text
    assert "# TYPE kvcomp_serve_ttft_s histogram" in text
    assert 'kvcomp_serve_ttft_s_bucket{le="+Inf"} 1' in text
    assert "kvcomp_serve_ttft_s_count 1" in text


# ---------------------------------------------------------------------------
# EventTrace primitives
# ---------------------------------------------------------------------------


def test_trace_levels_and_ring_drop():
    with pytest.raises(ValueError):
        EventTrace("verbose")
    with pytest.raises(ValueError):
        EventTrace("full")  # retired: the step phases time each step
    tr = EventTrace("events", capacity=4)
    assert tr.enabled
    for i in range(10):
        tr.emit("token", req=0, t=float(i), index=i)
    assert len(tr.events) == 4
    assert tr.emitted == 10 and tr.dropped == 6
    off = EventTrace("off")
    assert not off.enabled


def test_request_timings_reconstruction_synthetic():
    tr = EventTrace("events")
    tr.emit("submit", req=3, t=1.0)
    tr.emit("prefill_start", req=3, t=1.5, row=0)
    tr.emit("token", req=3, t=2.0, index=0)
    tr.emit("token", req=3, t=2.25, index=1)
    tr.emit("token", req=3, t=2.25, index=1)  # replay: same index ignored
    tr.emit("retire", req=3, t=2.3, reason="length")
    tim = tr.request_timings()[3]
    assert tim["submit"] == 1.0 and tim["first_work"] == 1.5
    assert tim["token_times"] == (2.0, 2.25)
    assert tim["ttft_s"] == 1.0
    assert tim["retired"] and tim["reason"] == "length"


def test_chrome_export_structure_synthetic():
    tr = EventTrace("events")
    tr.emit("submit", req=0, t=1.0)
    tr.emit("prefill_start", req=0, t=1.2, row=0)
    tr.emit("prefill_chunk", req=0, t=1.25, dur=0.05, row=0, pos=0, tokens=8)
    tr.emit("token", req=0, t=1.5, index=0)
    tr.emit("retire", req=0, t=1.6, reason="length")
    before = (time.time_ns() - time.monotonic_ns()) / 1e3
    doc = tr.to_chrome()
    after = (time.time_ns() - time.monotonic_ns()) / 1e3
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i"}
    names = {e["name"] for e in evs}
    # metadata tracks + raw events + synthesized queue/decode spans
    assert {"process_name", "thread_name", "prefill_chunk",
            "queue", "decode"} <= names
    track = [e for e in evs if e["name"] == "thread_name"
             and e["tid"] == 1][0]
    assert track["args"]["name"] == "req 0"
    # Every ts is the monotonic stamp moved onto the profiler's (wall)
    # clock by the one offset the export records.
    offset = doc["otherData"]["clock_offset_us"]
    assert before - 1e3 <= offset <= after + 1e3
    queue = [e for e in evs if e["name"] == "queue"][0]
    assert queue["ts"] == pytest.approx(1.0e6 + offset, abs=1.0)
    assert queue["dur"] == pytest.approx(0.2e6)
    chunk = [e for e in evs if e["name"] == "prefill_chunk"][0]
    assert chunk["ts"] == pytest.approx(1.25e6 + offset, abs=1.0)
    assert chunk["dur"] == pytest.approx(0.05e6)
    json.dumps(doc)  # must be serializable as-is


# ---------------------------------------------------------------------------
# Server integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_smoke_config("yi_6b")
    cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.integers(0, cfg.vocab_size, L).astype(np.int32)])
        for L in LENS]
    return cfg, params, prompts


def _run(cfg, params, prompts, **kw):
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=128, **kw),
                    q_chunk=32, kv_chunk=32)
    handles = [server.submit(Request(prompt=p, max_new_tokens=n))
               for p, n in zip(prompts, NEWS)]
    server.run()
    return server, handles, [h.result() for h in handles]


@pytest.fixture(scope="module")
def traced(setup):
    """One paged + prefix-sharing server run under trace='events'."""
    cfg, params, prompts = setup
    return _run(cfg, params, prompts, cache_mode="paged", prefix_cache="on",
                trace="events")


def key_tree(d):
    """Shape of a stats tree: nested keys with list lengths normalized (a
    per_shard list of 1 and of 4 have the same schema)."""
    if isinstance(d, dict):
        return {k: key_tree(v) for k, v in sorted(d.items())}
    if isinstance(d, list):
        return [key_tree(d[0])] if d else []
    return "."


LAT_KEYS = {"count", "sum", "mean", "min", "max", "p50", "p99"}
STEP_KEYS = ({"steps", "decode_steps", "wall_s", "attn_live_blocks",
              "attn_block_slots"}
             | {f"{p}_s" for p in STEP_PHASES})


def test_stats_schema_across_modes(setup):
    """The documented tree: key structure is a pure function of
    (cache_mode, prefix_cache) — latency/trace/shards always present,
    pool (aggregate + per_shard) in paged mode, prefix when enabled."""
    cfg, params, prompts = setup
    combos = [("dense", "off"), ("paged", "off"),
              ("paged", "on"), ("paged", "noshare")]
    stats = {}
    for mode, pfx in combos:
        server, _, results = _run(cfg, params, prompts, cache_mode=mode,
                                  prefix_cache=pfx)
        assert all(len(r.tokens) for r in results)
        stats[(mode, pfx)] = server.stats()
    for (mode, pfx), st in stats.items():
        base = {"cache_mode", "active", "pending", "preemptions",
                "prefill", "latency", "trace", "step", "shards",
                "lifecycle"}
        want = base | ({"pool"} if mode == "paged" else set())
        want |= {"prefix"} if pfx != "off" else set()
        assert set(st) == want, (mode, pfx)
        assert st["cache_mode"] == mode
        for h in ("ttft_s", "itl_s", "queue_wait_s"):
            assert set(st["latency"][h]) == LAT_KEYS
        assert st["latency"]["ttft_s"]["count"] == len(prompts)
        assert set(st["trace"]) == {"level", "events", "dropped"}
        assert set(st["step"]) == STEP_KEYS
        assert st["step"]["decode_steps"] <= st["step"]["steps"]
        assert st["step"]["wall_s"] > 0
        sh = st["shards"]
        assert sh["n_data"] == 1 and len(sh["per_shard"]) == 1
        for p in sh["per_shard"]:
            want_sh = {"preemptions"} | (
                {"pages_live", "pages_free", "high_water_pages"}
                if mode == "paged" else set())
            assert set(p) == want_sh
        if mode == "paged":
            pl = st["pool"]
            assert {"pages_total", "pages_live", "pages_free",
                    "high_water_pages", "alloc_pages", "freed_pages",
                    "per_shard"} <= set(pl)
            assert len(pl["per_shard"]) == 1
    # identical paged trees whether sharing is on or merely accounted
    t_on = key_tree(stats[("paged", "on")])
    t_no = key_tree(stats[("paged", "noshare")])
    t_on["prefix"].pop("index")  # noshare keeps no radix index
    assert t_on == t_no


def test_stats_schema_sharded_equals_unsharded(setup):
    """Mesh-invariance: a 4-device paged server's stats() has the IDENTICAL
    key tree as the single-device paged server (per_shard just gets more
    entries).  The subprocess forces a fake 4-device CPU count."""
    cfg, params, prompts = setup
    server, _, _ = _run(cfg, params, prompts, cache_mode="paged")
    local = key_tree(server.stats())
    prog = textwrap.dedent("""
        import dataclasses, json
        import jax, numpy as np
        from repro.launch.mesh import make_serve_mesh
        from repro.models import model as M
        from repro.models import registry
        from repro.serve.scheduler import Request, Server, ServerConfig

        def key_tree(d):
            if isinstance(d, dict):
                return {k: key_tree(v) for k, v in sorted(d.items())}
            if isinstance(d, list):
                return [key_tree(d[0])] if d else []
            return "."

        LENS, NEWS = (7, 13, 19, 26), (3, 6, 4, 5)
        cfg = registry.get_smoke_config("yi_6b")
        cfg = dataclasses.replace(cfg, cache_layout="packed", cache_block=8)
        params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        prompts = [np.concatenate([
            shared, rng.integers(0, cfg.vocab_size, L).astype(np.int32)])
            for L in LENS]
        server = Server(cfg, params,
                        ServerConfig(max_slots=4, max_seq=128,
                                     cache_mode="paged",
                                     mesh=make_serve_mesh("4,1")),
                        q_chunk=32, kv_chunk=32)
        for p, n in zip(prompts, NEWS):
            server.submit(Request(prompt=p, max_new_tokens=n))
        server.run()
        st = server.stats()
        print(json.dumps({"tree": key_tree(st),
                          "n_data": st["shards"]["n_data"],
                          "n_per_shard": len(st["shards"]["per_shard"]),
                          "n_pool_shards": len(st["pool"]["per_shard"])}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_data"] == 4
    assert res["n_per_shard"] == 4 and res["n_pool_shards"] == 4
    assert res["tree"] == local


def test_trace_timings_equal_results_exactly(traced):
    """The identity contract: reconstructed token_times / TTFT are the SAME
    floats Result carries — not approximately, exactly."""
    server, handles, results = traced
    tim = server.trace.request_timings()
    assert server.stats()["trace"]["dropped"] == 0
    for h, r in zip(handles, results):
        t = tim[h.id]
        assert t["token_times"] == r.token_times
        assert t["ttft_s"] == r.ttft_s
        assert t["retired"] and t["reason"] == r.finish_reason


def test_trace_full_records_scheduler_vocabulary(traced):
    server, handles, results = traced
    kinds = {e.kind for e in server.trace.events}
    assert {"submit", "prefill_start", "prefill_chunk", "prefill_finish",
            "token", "retire", "page_assign", "prefix_hit"} <= kinds
    # every token of every result is in the ring (small run, no wrap)
    n_tok = sum(1 for e in server.trace.events if e.kind == "token")
    assert n_tok == sum(len(r.tokens) for r in results)
    # The decode dispatches are the step counters' business, not events:
    # every phase of this paged prefix-sharing run took time, and each
    # decode step produced the first or a later token of some request.
    st = server.stats()["step"]
    assert all(st[f"{p}_s"] > 0 for p in STEP_PHASES)
    assert 0 < st["decode_steps"] <= st["steps"]
    n_decoded = sum(max(len(r.tokens) - 1, 0) for r in results)
    assert st["decode_steps"] <= n_decoded


def test_chrome_export_from_server(traced, tmp_path):
    server, handles, _ = traced
    path = tmp_path / "trace.json"
    server.trace.write_chrome(path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    track_names = {e["args"]["name"] for e in evs
                   if e["name"] == "thread_name"}
    assert {"scheduler"} | {f"req {h.id}" for h in handles} <= track_names
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] in ("X", "i"):
            assert "ts" in e


def test_shutdown_writes_exports(traced, tmp_path):
    server, _, _ = traced
    mpath, tpath = tmp_path / "metrics.json", tmp_path / "trace.json"
    snap = server.shutdown(metrics_out=mpath, trace_out=tpath)
    disk = json.loads(mpath.read_text())
    assert set(disk) == set(snap) == {"stats", "metrics"}
    assert disk["stats"]["cache_mode"] == "paged"
    assert disk["metrics"]["serve"]["ttft_s"]["count"] > 0
    prom = mpath.with_suffix(".prom").read_text()
    assert "# TYPE kvcomp_serve_preemptions counter" in prom
    assert json.loads(tpath.read_text())["traceEvents"]


def test_bench_columns_schema(traced):
    server, _, _ = traced
    cols = obs.bench_columns(server)
    assert tuple(cols) == obs.BENCH_COLUMNS
    assert cols["ttft_p50_s"] > 0 and cols["itl_p50_s"] >= 0


def test_format_snapshot_renders_all_sections(traced):
    server, _, _ = traced
    text = obs.format_snapshot(server.stats())
    for frag in ("serve[paged]", "lifecycle:", "prefill[", "latency:",
                 "pool:", "shards:", "prefix[on]", "step:", "attn_blocks=",
                 "trace[events]"):
        assert frag in text, frag


@pytest.mark.parametrize("backend", ["fused", "xla"])
def test_attn_block_counters(setup, backend):
    """serve.attn.live_blocks / block_slots against hand counts: two rows
    (prompts 14 and 21, block 8, 4 blocks per row, so 4 pages per compute
    block) decode at positions 14-17 and 21-23; the kernel attends
    min((pos + 1) // 8, 4) blocks per row: 1+2+2+2 and 2+2+3, each rounded
    up to one 4-page compute block.  Through xla both stay 0."""
    cfg, params, _ = setup
    rng = np.random.default_rng(5)
    server = Server(cfg, params, ServerConfig(
        max_slots=2, max_seq=32, cache_mode="paged", attn_backend=backend),
        q_chunk=32, kv_chunk=32)
    for L, n in ((14, 5), (21, 4)):
        server.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
            max_new_tokens=n))
    server.run()
    st = server.stats()["step"]
    snap = server.metrics.snapshot()["serve"]["attn"]
    if backend == "xla":
        assert st["attn_live_blocks"] == st["attn_block_slots"] == 0
        return
    assert server._attn_geom == (8, 4, 4)
    assert st["attn_live_blocks"] == snap["live_blocks"] == 7 + 7
    assert st["attn_block_slots"] == snap["block_slots"] == 4 * 4 + 3 * 4
    assert st["attn_live_blocks"] <= st["attn_block_slots"]
    assert "kvcomp_serve_attn_live_blocks 14" in server.metrics.prometheus_text()


# The Server's jitted device entry points — everything a step can dispatch.
DISPATCH_ATTRS = ("_prefill", "_decode", "_insert", "_assign", "_clear",
                  "_chunk", "_chunk_scan", "_fresh", "_chunk_paged",
                  "_chunk_paged_scan", "_finish_paged", "_gather")


def _count_dispatches(server) -> dict:
    counts = {"n": 0}
    for name in DISPATCH_ATTRS:
        fn = getattr(server, name, None)
        if fn is None or not callable(fn):
            continue

        def wrap(f):
            def g(*a, **k):
                counts["n"] += 1
                return f(*a, **k)
            return g

        setattr(server, name, wrap(fn))
    return counts


def test_trace_off_zero_events_zero_extra_dispatches(setup):
    """trace='off' must cost nothing: no events, the same number of device
    dispatches as a traced run, and bit-identical greedy tokens (the
    always-on step phases dispatch nothing either way)."""
    cfg, params, prompts = setup
    runs = {}
    for level in ("off", "events"):
        server = Server(cfg, params,
                        ServerConfig(max_slots=2, max_seq=128,
                                     cache_mode="paged", prefix_cache="on",
                                     trace=level),
                        q_chunk=32, kv_chunk=32)
        counts = _count_dispatches(server)
        handles = [server.submit(Request(prompt=p, max_new_tokens=n))
                   for p, n in zip(prompts, NEWS)]
        server.run()
        runs[level] = (server, counts["n"],
                       [h.result().tokens.tolist() for h in handles])
    off_server, off_n, off_toks = runs["off"]
    ev_server, ev_n, ev_toks = runs["events"]
    assert len(off_server.trace.events) == 0
    assert off_server.trace.emitted == 0
    assert off_server.stats()["trace"] == {"level": "off", "events": 0,
                                           "dropped": 0}
    assert len(ev_server.trace.events) > 0
    assert off_n == ev_n
    assert off_toks == ev_toks
    assert (off_server.stats()["step"]["decode_steps"]
            == ev_server.stats()["step"]["decode_steps"])


def test_trace_capture_raises_when_the_trace_cannot_start(monkeypatch, tmp_path):
    """A run asked for a profile must not exit 0 without one."""
    def refuse(_):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with obs.trace_capture(str(tmp_path)):
            pass
    with obs.trace_capture(None):  # off: no profiler call at all
        pass


def test_trace_capture_raises_when_the_trace_cannot_stop(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", calls.append)

    def refuse():
        raise RuntimeError("stop failed")

    monkeypatch.setattr(jax.profiler, "stop_trace", refuse)
    with pytest.raises(RuntimeError, match="stop failed"):
        with obs.trace_capture(str(tmp_path)):
            pass
    assert calls == [str(tmp_path)]


# ---------------------------------------------------------------------------
# Step phases: profiler spans and self-time counters
# ---------------------------------------------------------------------------


def _trace_reduce():
    """``bench/trace_reduce.py``, the benchmark's own trace reader."""
    spec = importlib.util.spec_from_file_location(
        "kvcomp_trace_reduce", BENCH / "trace_reduce.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_phases_self_times_partition_the_outermost():
    reg = MetricsRegistry()
    ph = obs.Phases(reg, "loop", ("outer", "inner"))
    assert ph.snapshot() == {"wall_s": 0, "outer_s": 0, "inner_s": 0}
    for _ in range(3):
        with ph("outer"):
            time.sleep(0.002)
            with ph("inner"):
                time.sleep(0.004)
                with ph("inner"):  # re-entrant: nested self time is its own
                    time.sleep(0.001)
    snap = ph.snapshot()
    assert snap["inner_s"] >= 3 * 0.005 and snap["outer_s"] >= 3 * 0.002
    assert snap["outer_s"] + snap["inner_s"] == pytest.approx(snap["wall_s"])
    # The counters live in the registry, so the exporters see them.
    assert reg.snapshot()["loop"]["inner_s"] == snap["inner_s"]
    assert "# TYPE kvcomp_loop_wall_s counter" in reg.prometheus_text()
    with pytest.raises(KeyError):
        with ph("outer"):
            raise KeyError("a phase that raises still closes")
    assert ph._open == []


@pytest.fixture(scope="module")
def profiled(setup, tmp_path_factory):
    """A paged + prefix-sharing run (trace='events') under a
    ``jax.profiler`` trace: the server, its results, the trace's host spans
    on the profiler's absolute clock (ns), and the Chrome export."""
    cfg, params, prompts = setup
    server = Server(cfg, params,
                    ServerConfig(max_slots=2, max_seq=128,
                                 cache_mode="paged", prefix_cache="on",
                                 trace="events"),
                    q_chunk=32, kv_chunk=32)
    # Compile outside the trace, so the traced steps are steady ones.
    server.submit(Request(prompt=prompts[0], max_new_tokens=2))
    server.run()
    before = server.stats()["step"]
    log_dir = tmp_path_factory.mktemp("profile")
    # Host spans only: the profiler's Python tracer hooks every call,
    # including those between a span's edge and the phase's clock reading,
    # which would time the tracer rather than the phases.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        handles = [server.submit(Request(prompt=p, max_new_tokens=n))
                   for p, n in zip(prompts, NEWS)]
        server.run()
    after = server.stats()["step"]
    chrome = server.trace.to_chrome()
    tr = _trace_reduce()
    path = sorted(log_dir.rglob("*.xplane.pb"))[-1]
    # The trace's times are ns since the profile started, on the wall clock.
    env = jax.profiler.ProfileData.from_file(str(path)).find_plane_with_name(
        "Task Environment")
    start = dict(env.stats)["profile_start_time"]
    spans = [(s + start, e + start, n) for s, e, n in tr.read_xspace(path).host
             if n.startswith("kvcomp:")]
    delta = {k: after[k] - before[k] for k in after}
    return server, handles, spans, delta, chrome


def _self_seconds(spans) -> dict:
    """Self time of each ``kvcomp:`` span name: its duration less that of
    the spans directly inside it (all on one thread)."""
    out: dict = {}
    stack: list = []
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][2]] = out.get(stack[-1][2], 0.0) - (e - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((s, e, name))
    return {n: v / 1e9 for n, v in out.items()}


def test_every_phase_span_nests_inside_a_step_span(profiled):
    _, _, spans, _, _ = profiled
    names = {n for _, _, n in spans}
    assert names == {f"kvcomp:{p}" for p in STEP_PHASES}
    steps = sorted((s, e) for s, e, n in spans if n == "kvcomp:step")
    assert steps
    for s, e, name in spans:
        if name != "kvcomp:step":
            assert any(a <= s and e <= b for a, b in steps), name


def test_phase_self_times_sum_to_the_step_wall(profiled):
    server, _, _, delta, _ = profiled
    total = sum(delta[f"{p}_s"] for p in STEP_PHASES)
    assert total == pytest.approx(delta["wall_s"], rel=0.01)
    st = server.stats()["step"]
    assert sum(st[f"{p}_s"] for p in STEP_PHASES) == pytest.approx(
        st["wall_s"], rel=0.01)


def test_phase_counters_agree_with_their_spans(profiled):
    _, _, spans, delta, _ = profiled
    by_span = _self_seconds(spans)
    for p in STEP_PHASES:
        counted, seen = delta[f"{p}_s"], by_span[f"kvcomp:{p}"]
        assert abs(counted - seen) <= max(0.05 * counted, 2e-4), (
            p, counted, seen)
    n_steps = sum(1 for _, _, n in spans if n == "kvcomp:step")
    assert n_steps == delta["steps"]


def test_chrome_export_lines_up_with_the_profiler(profiled):
    """Shifted onto the profiler's clock, a decode token's ``ts`` falls in
    (or within 2 ms of) a ``kvcomp:retire`` span, and a first token's in a
    ``kvcomp:prefill_sync`` one: the phases that push them."""
    _, handles, spans, _, chrome = profiled
    near = {n: [(s, e) for s, e, m in spans if m == n]
            for n in ("kvcomp:retire", "kvcomp:prefill_sync")}
    ids = {h.id for h in handles}
    tokens = [e for e in chrome["traceEvents"]
              if e["name"] == "token" and e["tid"] - 1 in ids]
    assert len(tokens) == sum(len(h.result().tokens) for h in handles)
    for ev in tokens:
        t = ev["ts"] * 1e3  # us -> ns
        phase = ("kvcomp:prefill_sync" if ev["args"]["index"] == 0
                 else "kvcomp:retire")
        gap = min(max(s - t, t - e, 0.0) for s, e in near[phase])
        assert gap <= 2e6, (ev, phase, gap)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "kvcomp_metric_" + name.replace(".", "_"),
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_step_host_ms_reader_synthetic():
    """``step_host_ms.longdoc``: (wall - token_sync - prefill_sync) over the
    window's decode steps, in ms; nothing where the program has no
    ``step`` counters (a program from before them)."""
    sys.path.insert(0, str(BENCH))
    try:
        from kvbench.cell import Context
    finally:
        sys.path.remove(str(BENCH))

    def step(steps, decode, wall, token_sync, prefill_sync):
        return {"step": {"steps": steps, "decode_steps": decode,
                         "wall_s": wall, "token_sync_s": token_sync,
                         "prefill_sync_s": prefill_sync, "admit_s": 0.5}}

    def ctx(a, b):
        return Context(cell=None, arch=None, chips=1, peaks={}, t_open=0.0,
                       t_close=51.0, records=[], stats_open=a, stats_close=b,
                       stats_admit=a, kv={})

    read = _reader("step_host_ms.longdoc")
    a = step(100, 90, 10.0, 8.0, 0.5)
    b = step(550, 540, 61.0, 56.0, 1.1)
    # 51 s of wall, 48 s of token sync, 0.6 s of prefill sync, 450 steps
    assert read(ctx(a, b)) == pytest.approx(1e3 * 2.4 / 450)
    assert read(ctx(a, a)) is None  # no decode step in the window
    assert read(ctx({"prefix": {}}, {"prefix": {}})) is None
