"""Find a cell's files by name: ``BENCHMARK.json`` names the cell's
configuration and traffic mix; each lives in a file of its own under
``bench/``, the configuration's architecture in a module of its own under
``bench/archs/``, and each per-layer metric in a reader of its own.
Adding a configuration, an architecture, a traffic mix or a metric
therefore adds files and entries and edits no code."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]

# What an architecture module (``bench/archs/<name>.py``) defines:
#   Arch                 frozen, hashable sizes; ``Arch.from_config(config)``;
#                        at least vocab, d_model, n_layers, n_heads, head_dim
#   make_weights(arch, seed, dtype=bfloat16)     the weights, on the device
#   program_config(config)                       the program's ModelConfig
#   logits_at(params, arch, tokens, at, precision="f32", kv=None)
#                        the plain float32 reference and its controls
#   token_flops(arch, context)                   operations of a decoded token
#   prefill_flops(arch, tokens, chunks, attn_pairs)   of prefilled tokens
#   decode_attn_work(arch, contexts, kv)         (operations, bytes) of decode
#                                                attention, layer by layer
#   arena_bytes(server, config)                  the cache's bytes per token
ARCH_NAMES = ("Arch", "make_weights", "program_config", "logits_at",
              "token_flops", "prefill_flops", "decode_attn_work",
              "arena_bytes")


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found or
    does not hold what the harness needs."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict        # bench/configs/<config>.json, as loaded
    architecture: object  # bench/archs/<config["architecture"]>.py, loaded
    traffic: dict       # bench/traffic/<traffic>.json, as loaded
    end_to_end: tuple   # BENCHMARK.json metric entries this cell reports
    per_layer: tuple
    bench_dir: pathlib.Path


def _load_json(path: pathlib.Path, what: str) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{what}: no file {path}") from None


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: pathlib.Path = BENCH_DIR,
              root: pathlib.Path | None = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files.

    A per-layer metric without a ``workloads`` key is reported in every
    cell that reports the end-to-end metric it moves."""
    root = root if root is not None else bench_dir.parent
    bench = _load_json(root / "BENCHMARK.json", "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _load_json(bench_dir / "configs" / f"{w['config']}.json",
                        f"config {w['config']!r}")
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']!r}")
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    moved = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if m["moves"] in moved and _reports(m, name))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                architecture=load_arch(config, bench_dir), traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def load_arch(config: dict, bench_dir: pathlib.Path = BENCH_DIR):
    """The module ``bench/archs/<config["architecture"]>.py``, imported by
    path once per file, with every name of ``ARCH_NAMES``."""
    known = sorted(p.stem for p in (bench_dir / "archs").glob("*.py"))
    name = config.get("architecture")
    if name not in known:
        raise SpecError(f"config {config.get('name')!r}: architecture "
                        f"{name!r} is not one of bench/archs/ {known}")
    path = bench_dir / "archs" / f"{name}.py"
    # Registered in sys.modules before it runs, as dataclasses need; a
    # module already loaded from this file is reused, so the reference's
    # jitted functions are compiled once per process.
    mod_name = "kvbench_arch_" + name.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(mod_name)
    if mod is None or mod.__file__ != str(path):
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    missing = [n for n in ARCH_NAMES if not callable(getattr(mod, n, None))]
    if missing:
        raise SpecError(f"architecture {name!r}: {path} lacks {missing} "
                        f"(architectures: {known})")
    return mod


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"metric {name!r}: no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "kvbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric {name!r}: {path} has no read(ctx)")
    return mod.read


def load_peaks(kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip of ``kind`` (JAX's
    ``device_kind``).  A kind missing from the table is an error."""
    table = _load_json(bench_dir / "peaks.json", "peaks")["devices"]
    if kind not in table:
        raise SpecError(f"no peaks for device kind {kind!r} in peaks.json "
                        f"(known: {sorted(table)})")
    return table[kind]
