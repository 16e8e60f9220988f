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
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import EventTrace
from repro.models import model as M
from repro.models import registry
from repro.serve.scheduler import Request, Server, ServerConfig

SRC = str(Path(__file__).resolve().parents[1] / "src")

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
    tr = EventTrace("events", capacity=4)
    assert tr.enabled and not tr.full
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
    tr = EventTrace("full")
    tr.emit("submit", req=0, t=1.0)
    tr.emit("prefill_start", req=0, t=1.2, row=0)
    tr.emit("prefill_chunk", req=0, t=1.25, dur=0.05, row=0, pos=0, tokens=8)
    tr.emit("token", req=0, t=1.5, index=0)
    tr.emit("retire", req=0, t=1.6, reason="length")
    tr.emit("decode_step", t=1.4, dur=0.01, rows=1)
    doc = tr.to_chrome()
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i"}
    names = {e["name"] for e in evs}
    # metadata tracks + raw events + synthesized queue/decode spans
    assert {"process_name", "thread_name", "prefill_chunk", "decode_step",
            "queue", "decode"} <= names
    track = [e for e in evs if e["name"] == "thread_name"
             and e["tid"] == 1][0]
    assert track["args"]["name"] == "req 0"
    queue = [e for e in evs if e["name"] == "queue"][0]
    assert queue["ts"] == pytest.approx(1.0e6)
    assert queue["dur"] == pytest.approx(0.2e6)
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
    """One paged + prefix-sharing server run under trace='full'."""
    cfg, params, prompts = setup
    return _run(cfg, params, prompts, cache_mode="paged", prefix_cache="on",
                trace="full")


def key_tree(d):
    """Shape of a stats tree: nested keys with list lengths normalized (a
    per_shard list of 1 and of 4 have the same schema)."""
    if isinstance(d, dict):
        return {k: key_tree(v) for k, v in sorted(d.items())}
    if isinstance(d, list):
        return [key_tree(d[0])] if d else []
    return "."


LAT_KEYS = {"count", "sum", "mean", "min", "max", "p50", "p99"}


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
                "prefill", "latency", "trace", "shards", "lifecycle"}
        want = base | ({"pool"} if mode == "paged" else set())
        want |= {"prefix"} if pfx != "off" else set()
        assert set(st) == want, (mode, pfx)
        assert st["cache_mode"] == mode
        for h in ("ttft_s", "itl_s", "queue_wait_s"):
            assert set(st["latency"][h]) == LAT_KEYS
        assert st["latency"]["ttft_s"]["count"] == len(prompts)
        assert set(st["trace"]) == {"level", "events", "dropped"}
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
            "token", "retire", "page_assign", "prefix_hit",
            "decode_step"} <= kinds
    # every token of every result is in the ring (small run, no wrap)
    n_tok = sum(1 for e in server.trace.events if e.kind == "token")
    assert n_tok == sum(len(r.tokens) for r in results)


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
                 "pool:", "shards:", "prefix[on]", "trace[full]"):
        assert frag in text, frag


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
    dispatches as a fully traced run, and bit-identical greedy tokens."""
    cfg, params, prompts = setup
    runs = {}
    for level in ("off", "full"):
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
    full_server, full_n, full_toks = runs["full"]
    assert len(off_server.trace.events) == 0
    assert off_server.trace.emitted == 0
    assert off_server.stats()["trace"] == {"level": "off", "events": 0,
                                           "dropped": 0}
    assert len(full_server.trace.events) > 0
    assert off_n == full_n
    assert off_toks == full_toks


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
