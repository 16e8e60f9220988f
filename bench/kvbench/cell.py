"""One run of one cell: set-up, the measured window, the check, the line.

The window drives the program's normal serving path: ``repro.api.serve``
-> ``Server.submit`` / ``Server.step`` with the cell's configuration, paged
compressed pool, prefix cache and chunked prefill, backend ``auto``.
What belongs to one architecture -- its sizes, weights, the program's
``ModelConfig``, its operation counts and the cache's bytes -- comes from
the cell's architecture module (``spec.load_arch``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from kvbench import check, spec, stats, traffic as traffic_mod


@dataclasses.dataclass(eq=False)
class Record:
    """One request as the harness saw it (host clock, ``time.monotonic``)."""
    req: traffic_mod.Req
    due: float
    submit: float
    handle: object
    result: object = None


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cell: spec.Cell
    arch: object         # the cell's ``architecture.Arch``
    chips: int
    peaks: dict
    t_open: float
    t_close: float
    records: list
    stats_open: dict
    stats_close: dict
    stats_admit: dict    # before the traffic's first request was submitted
    kv: dict             # arena bytes per token: stored, raw (arena_bytes)
    trace: object = None  # trace_reduce.Reduced of the window, when traced

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def counter(self, *path, since: str = "open") -> float:
        """Growth of a ``Server.stats()`` counter over the window (or, with
        ``since="admit"``, since the traffic's first request)."""
        a = self.stats_admit if since == "admit" else self.stats_open
        b = self.stats_close
        for k in path:
            a, b = a[k], b[k]
        return b - a

    def due_in_window(self) -> list:
        return [r for r in self.records
                if self.t_open <= r.due < self.t_close]

    def decode_contexts(self) -> np.ndarray:
        """Context length of every token a decode step produced in the
        window: token ``i >= 1`` of a request attends its prompt and ``i``
        generated tokens."""
        out = []
        for r in self.records:
            t = np.asarray(r.result.token_times)
            i = np.nonzero((t >= self.t_open) & (t <= self.t_close))[0]
            i = i[i >= 1]
            out.append(len(r.req.prompt) + i)
        return np.concatenate(out) if out else np.zeros(0, np.int64)


class Fail(RuntimeError):
    """A run that cannot produce a result line."""


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise Fail(f"no TPU: JAX found {d.platform} devices; the benchmark "
                   "runs on the chip only")
    if len(devs) < chips:
        raise Fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak(chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def make_server(config: dict, params, architecture):
    from repro import api

    s = config["serving"]
    mesh = None
    if s.get("mesh"):
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(s["mesh"])
    return api.serve(architecture.program_config(config), params,
                     max_slots=s["max_slots"], max_seq=s["max_seq"],
                     cache_mode=s["cache_mode"],
                     pool_hbm_bytes=s["pool_hbm_bytes"],
                     prefix_cache=s["prefix_cache"],
                     prefill_chunk_tokens=s.get("prefill_chunk_tokens"),
                     mesh=mesh)


class CompileCounter:
    """Counts traces and backend compilations while ``armed``."""

    def __init__(self):
        self.armed = False
        self.traces = self.compiles = 0
        self._names = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                       "/jax/core/compile/backend_compile_duration":
                           "compiles"}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        what = self._names.get(event)
        if self.armed and what:
            setattr(self, what, getattr(self, what) + 1)


def _span(tracing: bool, name: str):
    return (jax.profiler.TraceAnnotation(name) if tracing
            else contextlib.nullcontext())


def drain(server, handles, tracing=False, limit_s=600.0):
    t_end = time.monotonic() + limit_s
    while not all(h.done for h in handles):
        if time.monotonic() > t_end:
            raise Fail("set-up requests did not finish")
        with _span(tracing, "bench:step"):
            server.step()


def warm_shapes(server, reqs, block: int, tracing=False):
    """Compile every program the window will use: one short request per
    distinct prompt length of the traffic (the finishing prefill chunk is
    compiled per tail length, the multi-chunk scans per trip count), the
    decode step, and -- through the request that reaches a block boundary
    soonest, decoded across it -- the page-assignment step."""
    from repro import api

    lengths = sorted({len(r.prompt): r for r in reqs}.items())
    # decode steps until the cache flushes a block: (pos + 1) % block == 0
    until_flush = {n: (block - 1 - n) % block for n, _ in lengths}
    cross = min(until_flush, key=until_flush.get)
    warm = [server.submit(api.Request(
        prompt=r.prompt,
        max_new_tokens=until_flush[n] + 2 if n == cross else 2))
        for n, r in lengths]
    drain(server, warm, tracing)


class Runner:
    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 tracing: bool, t_start: float, require_tpu: bool = True,
                 peaks: dict | None = None, keep_trace: str | None = None):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.keep_trace = keep_trace
        self.tracing, self.t_start = tracing, t_start
        self.device = device_info(cell.chips, require_tpu)
        self.peaks = (peaks if peaks is not None else
                      spec.load_peaks(self.device["kind"], cell.bench_dir))
        self.arch = cell.architecture.Arch.from_config(cell.config)
        self.compiles = CompileCounter()
        self.records: list[Record] = []

    # -- set-up ---------------------------------------------------------------
    def _phase(self, name):
        now = time.monotonic()
        self.phases.append((name, now - self._t_phase))
        self._t_phase = now

    def setup(self):
        cfg, architecture = self.cell.config, self.cell.architecture
        self.phases, self._t_phase = [], self.t_start
        self.traffic = traffic_mod.generate(self.cell.traffic, self.arch.vocab,
                                            self.seed, self.seconds)
        self._phase("start and traffic")
        self.params = jax.block_until_ready(
            architecture.make_weights(self.arch, self.seed))
        self._phase("weights")
        self.server = make_server(cfg, self.params, architecture)
        self.kv = architecture.arena_bytes(self.server, cfg)
        self._phase("server")
        from repro import api

        if self.traffic.documents:
            docs = [self.server.submit(api.Request(prompt=d, max_new_tokens=2))
                    for d in self.traffic.documents]
            drain(self.server, docs)
            self._phase("documents")
        warm_shapes(self.server, self.traffic.requests,
                    cfg["serving"]["cache_block"])
        self._phase("programs")
        self.stats_admit = self.server.stats()

    # -- window ---------------------------------------------------------------
    def _submit(self, req, due):
        from repro import api

        with _span(self.tracing, "bench:submit"):
            t = time.monotonic()
            h = self.server.submit(api.Request(
                prompt=req.prompt, max_new_tokens=req.max_new_tokens))
        rec = Record(req=req, due=due, submit=t, handle=h)
        self.records.append(rec)
        return rec

    def _step(self):
        with _span(self.tracing, "bench:step"):
            self.server.step()

    def run_closed(self):
        """Closed loop: each client sends its next request when the last
        completes; the window opens once every client is decoding."""
        tr = self.traffic
        queues = [[r for r in tr.requests if r.client == c]
                  for c in range(tr.clients)]
        inflight = {}
        for c, q in enumerate(queues):
            inflight[c] = self._submit(q.pop(0), time.monotonic())
        srv = self.server
        while srv.active < tr.clients or srv.pending or srv.prefilling:
            self._step()
            if any(r.handle.done for r in inflight.values()):
                raise Fail("a request finished before every client was "
                           "decoding; the window could not open")
        self._open()
        while time.monotonic() < self.t_close:
            self._step()
            for c, rec in list(inflight.items()):
                if rec.handle.done:
                    if queues[c]:
                        inflight[c] = self._submit(queues[c].pop(0),
                                                   time.monotonic())
                    else:
                        del inflight[c]
        self._close()

    def run_open(self):
        """Open loop: requests are submitted when due, whatever the server
        is doing; the schedule starts ``warm_s`` before the window.  After
        the window closes nothing more is submitted, and the server runs on
        (up to a minute) until every request due in the window has its
        first token, so that a request's whole wait counts."""
        sched = sorted(self.traffic.requests, key=lambda r: r.due_s)
        self.t_open = time.monotonic() + self.traffic.warm_s
        srv = self.server
        i = 0

        def serve_until(t_end):
            nonlocal i
            while (now := time.monotonic()) < t_end:
                while i < len(sched) and self.t_open + sched[i].due_s <= now:
                    self._submit(sched[i], self.t_open + sched[i].due_s)
                    i += 1
                if srv.active or srv.pending or srv.prefilling:
                    self._step()
                    continue
                nxt = self.t_open + sched[i].due_s if i < len(sched) else t_end
                with _span(self.tracing, "bench:wait"):
                    time.sleep(max(0.0, min(nxt, t_end) - now))

        serve_until(self.t_open)
        self._open(self.t_open)
        serve_until(self.t_close)
        self._close()
        t_end = time.monotonic() + 60.0
        while (srv.pending or srv.prefilling) and time.monotonic() < t_end:
            srv.step()

    def _open(self, t=None):
        self.stats_open = self.server.stats()
        self.t_open = time.monotonic() if t is None else t
        self._phase("traffic warm-up")
        print("set-up: " + ", ".join(f"{n} {s:.1f}s" for n, s in self.phases),
              file=sys.stderr)
        self.t_close = self.t_open + self.seconds
        self.setup_s = self.t_open - self.t_start
        self.compiles.armed = True
        if self.tracing:
            self.trace_dir = tempfile.mkdtemp(prefix="kvbench_trace_")
            jax.profiler.start_trace(self.trace_dir)
            self._window_span = jax.profiler.TraceAnnotation("bench:window")
            self._window_span.__enter__()

    def _close(self):
        self.compiles.armed = False
        if self.tracing:
            self._window_span.__exit__(None, None, None)
            jax.block_until_ready(self.server.state)
            jax.profiler.stop_trace()
        self.stats_close = self.server.stats()

    def collect(self):
        """Results of every request; those still running are cancelled."""
        for rec in self.records:
            if not rec.handle.done:
                rec.handle.cancel()
            rec.result = rec.handle.result()

    # -- the whole run --------------------------------------------------------
    def serve_window(self):
        """Set-up, the measured window, and every request's result."""
        self.setup()
        if self.traffic.loop == "closed":
            self.run_closed()
        else:
            self.run_open()
        self.collect()

    def free(self):
        """Drop the program's state, so the reference has the memory (a
        handle holds its server)."""
        for rec in self.records:
            rec.handle = None
        del self.server
        gc.collect()

    def run(self) -> dict:
        self.serve_window()
        peak = memory_peak(self.cell.chips)
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        print(f"memory: peak {peak} of {limit} bytes", file=sys.stderr)
        ctx = Context(cell=self.cell, arch=self.arch, chips=self.cell.chips,
                      peaks=self.peaks, t_open=self.t_open,
                      t_close=self.t_close, records=self.records,
                      stats_open=self.stats_open,
                      stats_close=self.stats_close,
                      stats_admit=self.stats_admit, kv=self.kv)
        reduced = None
        if self.tracing:
            reduced = self._reduce_trace()
            ctx.trace = reduced
        end_to_end = self.end_to_end(ctx)
        per_layer = self.per_layer(ctx) if self.tracing else {}
        # Closed loop: every request sent; open loop: those due in the window.
        attempted = (len(ctx.due_in_window()) if self.traffic.loop == "open"
                     else len(self.records))
        failed = sum(r.result.finish_reason == "error" for r in self.records)
        self.free()
        t_check = time.monotonic()
        verdict = check.check(self.cell, self.arch, self.params, self.records,
                              self.seed, failed)
        print(f"check: reference over the sample in "
              f"{time.monotonic() - t_check:.1f}s", file=sys.stderr)
        device = dict(self.device, memory_peak_bytes=peak)
        if reduced is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        metrics = per_layer if self.tracing else end_to_end
        line = {"correct": verdict.correct, "attempted": attempted,
                "failed": failed, "metrics": metrics, "device": device}
        if reduced is not None:
            line["breakdown"] = {"device_ops": reduced.top_ops(10),
                                 "idle_gaps": reduced.top_gaps(10)}
        print(f"window: {self.compiles.traces} traces, "
              f"{self.compiles.compiles} compilations", file=sys.stderr)
        line["check"] = verdict.numbers
        return line

    def _reduce_trace(self):
        sys.path.insert(0, str(self.cell.bench_dir))
        import trace_reduce

        paths = sorted(pathlib.Path(self.trace_dir).rglob("*.xplane.pb"))
        if not paths:
            raise Fail("the profiler wrote no trace")
        if self.keep_trace:
            pathlib.Path(self.keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(paths[-1], self.keep_trace)
        tr = trace_reduce.read_xspace(paths[-1])
        window = trace_reduce.host_span(tr, "bench:window")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        names = sorted(n for n, d in tr.devices.items() if d.ops)
        if not names:
            raise Fail("the trace holds no device operations")
        return trace_reduce.reduce(tr, window, names[:self.cell.chips])

    # -- metrics --------------------------------------------------------------
    def end_to_end(self, ctx: Context) -> dict:
        out = {}
        for m in self.cell.end_to_end:
            value = E2E[m["name"]](self, ctx)
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def per_layer(self, ctx: Context) -> dict:
        out = {}
        for m in self.cell.per_layer:
            value = spec.metric_reader(m["name"], self.cell.bench_dir)(ctx)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


def _out_tok_s(run, ctx):
    n = sum(int(np.sum((np.asarray(r.result.token_times) >= ctx.t_open)
                       & (np.asarray(r.result.token_times) <= ctx.t_close)))
            for r in ctx.records)
    return n / ctx.window_s


def _itl_p95_ms(run, ctx):
    gaps = np.concatenate([stats.gaps_in(r.result.token_times, ctx.t_open,
                                         ctx.t_close) for r in ctx.records])
    return 1e3 * stats.percentile(gaps, 95)


def _ttft_p95_ms(run, ctx):
    ttft = [(r.result.token_times[0] - r.due) if r.result.token_times
            else float("inf") for r in ctx.due_in_window()]
    return 1e3 * stats.percentile(ttft, 95)


def _setup_s(run, ctx):
    return run.setup_s


# End-to-end metrics are taken by the harness itself, on the host clock.
E2E = {"out_tok_s": _out_tok_s, "itl_p95_ms": _itl_p95_ms,
       "ttft_p95_ms": _ttft_p95_ms, "setup_s": _setup_s}
