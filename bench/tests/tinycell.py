"""A CPU-sized copy of the benchmark's data for the tests: the repository's
``BENCHMARK.json`` and data files, plus a tiny configuration and two tiny
cells (closed and open loop) that run on the CPU in seconds."""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Peaks for the CPU: the harness never reads a CPU number as a device one;
# the tests only need the arithmetic to run.
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CELLS = ("tiny.longdoc", "tiny.chat")


def load_run_module():
    """``bench/run.py`` under a name of its own."""
    spec = importlib.util.spec_from_file_location("kvbench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric(name, unit, better, workloads=None):
    m = {"name": name, "unit": unit, "better": better, "bound": 0.25,
         "source": "host_clock"}
    if workloads is not None:
        m["workloads"] = workloads
    return m


def make_bench(tmp: pathlib.Path, limit: float = 1.0) -> pathlib.Path:
    """A checkout-like tree under ``tmp`` whose ``bench/`` holds the
    repository's data files and the tiny ones; returns its ``bench``."""
    bench = tmp / "bench"
    for sub in ("configs", "archs", "traffic", "workloads", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    cfg = json.loads((BENCH / "configs" / "yi_6b.json").read_text())
    cfg["name"] = "tiny"
    cfg["architecture"] = "llama_gqa"
    cfg["model"].update(hidden_size=64, intermediate_size=128,
                        num_attention_heads=4, num_key_value_heads=2,
                        num_hidden_layers=2, vocab_size=256, head_dim=16)
    cfg["serving"].update(cache_block=8, max_slots=4, max_seq=256,
                          pool_hbm_bytes=2_000_000)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny_closed.json").write_text(json.dumps({
        "loop": "closed", "clients": 4, "requests_per_client": 6,
        "documents": {"count": 2, "tokens": 32},
        "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24,
                          "quantum": 8},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 24}}))
    (bench / "traffic" / "tiny_open.json").write_text(json.dumps({
        "loop": "open", "arrival": "poisson", "rate_per_s": 20.0,
        "warm_s": 0.5,
        "prompt_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.7,
                          "min": 8, "max": 48, "quantum": 8},
        "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 2, "max": 12}}))
    for c in CELLS:
        (bench / "workloads" / f"{c}.json").write_text(json.dumps(
            {"check": {"requests": 3, "mean_logit_gap": limit}}))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU size"})
    b["workloads"] += [
        {"name": "tiny.longdoc", "config": "tiny", "traffic": "tiny_closed",
         "chips": 1, "why": "CPU size"},
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny_open",
         "chips": 1, "why": "CPU size"}]
    # The tiny cells' own metrics, whatever cells BENCHMARK.json holds.
    b["end_to_end"] = [
        _metric("out_tok_s", "tokens/s", "higher", ["tiny.longdoc"]),
        _metric("itl_p95_ms", "ms", "lower"),
        _metric("ttft_p95_ms", "ms", "lower", ["tiny.chat"]),
        _metric("setup_s", "s", "lower")]
    b["per_layer"] = [
        dict(_metric("kv_bytes_per_tok", "B", "lower"),
             layer="layout codecs", moves="itl_p95_ms"),
        dict(_metric("prefix_hit_share", "%", "higher", ["tiny.longdoc"]),
             layer="pool and prefix cache", moves="out_tok_s")]
    (tmp / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return bench


def run_cell(bench: pathlib.Path, workload: str, seed: int = 5,
             seconds: float = 1.0, trace: int = 0) -> dict:
    """One run of a cell of ``bench`` on the CPU; returns its line."""
    run = load_run_module()
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return run.run(args, require_tpu=False, peaks=CPU_PEAKS, bench_dir=bench)
