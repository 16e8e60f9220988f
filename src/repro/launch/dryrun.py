import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and derive the roofline terms.

Per cell this produces two kinds of compiles:

1. **full** — the real step (scan-over-layers, flash-chunked attention, full
   depth) is lowered and compiled; success is the deliverable gate, and
   ``memory_analysis()`` proves per-device fit.

2. **analysis** — XLA's CPU cost model counts loop bodies ONCE (verified in
   EXPERIMENTS.md §Dry-run notes), so FLOPs/bytes/collective bytes come from
   two loop-free compiles at reduced depth (layers unrolled, attention/SSD
   chunk scans unrolled) and are linearly extrapolated:
       per_layer = c(d2) − c(d1);  total = c(d1) + (L − d1)·per_layer.
   Collective bytes are parsed from the post-SPMD HLO (all-gather /
   all-reduce / reduce-scatter / all-to-all / collective-permute operand
   sizes).

Usage:
    python -m repro.launch.dryrun --arch yi_6b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all          # every cell, both meshes
    python -m repro.launch.dryrun --all --mesh multipod --no-analysis
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models import registry
from repro.models.config import ModelConfig
from repro.distributed import sharding as shd
from repro.train import step as step_lib

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# TPU v5e hardware constants (per chip).
HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "u16": 2,
               "s16": 2, "c64": 8, "c128": 16}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def skip_reason(cfg: ModelConfig, shape_name: str) -> str | None:
    info = SHAPES[shape_name]
    if info["kind"] == "decode" and cfg.encoder_only:
        return "encoder-only: no autoregressive decode"
    if shape_name == "long_500k":
        if cfg.encoder_only:
            return "encoder-only: no decode"
        if not cfg.supports_long_context_decode:
            return "pure full-attention arch: long_500k needs sub-quadratic attention"
    return None


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape_name: str) -> dict:
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    if info["kind"] in ("train", "prefill"):
        if cfg.input_mode == "tokens":
            specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        else:
            specs = {"embeddings": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)}
        if info["kind"] == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        return specs
    # decode: one new token against a seq-long cache
    if cfg.input_mode == "tokens":
        return {"tokens": jax.ShapeDtypeStruct((B,), jnp.int32)}
    return {"tokens": jax.ShapeDtypeStruct((B, cfg.d_model), jnp.bfloat16)}


# ---------------------------------------------------------------------------
# cell builders: (fn, example_args, in_shardings)
# ---------------------------------------------------------------------------


def build_train_cell(cfg: ModelConfig, shape_name: str, mesh, *,
                     q_chunk=2048, kv_chunk=2048, unroll=False, microbatches=8):
    scfg = step_lib.TrainStepConfig(
        remat=True, q_chunk=q_chunk, kv_chunk=kv_chunk, unroll=unroll,
        microbatches=microbatches)
    bspecs = input_specs(cfg, shape_name)
    step, state_shapes, in_sh, out_sh = step_lib.build_train_artifacts(
        cfg, mesh, scfg, bspecs)
    state_shapes = tuple(state_shapes[:2]) + (None,)
    in_sh = ((in_sh[0][0], in_sh[0][1], None), in_sh[1])
    return step, (state_shapes, bspecs), (in_sh, out_sh)


def build_prefill_cell(cfg: ModelConfig, shape_name: str, mesh, *,
                       q_chunk=2048, kv_chunk=2048, unroll=False):
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    bspecs = input_specs(cfg, shape_name)
    rules = shd.serve_rules(cfg, mesh)
    shd.set_ambient_mesh(mesh)
    pshapes, axes = step_lib.shapes_and_axes(cfg)
    pshard = shd.make_param_shardings(axes, pshapes, rules, mesh)
    bshard = {k: shd.batch_sharding(mesh, v) for k, v in bspecs.items()}

    if cfg.encoder_only:
        def fn(params, batch):
            logits, _ = M.forward(params, cfg, batch,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk, unroll=unroll)
            return logits
    else:
        def fn(params, batch):
            logits, state = M.prefill(params, cfg, batch, max_seq=S,
                                      q_chunk=q_chunk, kv_chunk=kv_chunk,
                                      unroll=unroll)
            return logits[:, -1], state

    return fn, ((pshapes, bspecs),), ((pshard, bshard), None)


def build_decode_cell(cfg: ModelConfig, shape_name: str, mesh, *, unroll=False, **_kw):
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    bspecs = input_specs(cfg, shape_name)
    rules = shd.serve_rules(cfg, mesh)
    shd.set_ambient_mesh(mesh)
    pshapes, axes = step_lib.shapes_and_axes(cfg)
    pshard = shd.make_param_shardings(axes, pshapes, rules, mesh)
    state_shapes = jax.eval_shape(lambda: M.init_decode_state(cfg, B, S))
    sshard = shd.cache_shardings(state_shapes, mesh)
    tok_sh = shd.batch_sharding(mesh, bspecs["tokens"])
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, tokens, position, state):
        return M.decode_step(params, cfg, tokens, position, state, unroll=unroll)

    args = (pshapes, bspecs["tokens"], pos, state_shapes)
    in_sh = (pshard, tok_sh, shd.replicated(mesh), sshard)
    return fn, (args,), (in_sh, None)


def build_cell(cfg, shape_name, mesh, **kw):
    kind = SHAPES[shape_name]["kind"]
    if kind == "train":
        fn, (st, bs), (in_sh, out_sh) = build_train_cell(cfg, shape_name, mesh, **kw)
        return fn, (st, bs), (in_sh, out_sh)
    if kind == "prefill":
        fn, (args,), sh = build_prefill_cell(cfg, shape_name, mesh, **kw)
        return fn, args, sh
    fn, (args,), sh = build_decode_cell(cfg, shape_name, mesh, **kw)
    return fn, args, sh


# ---------------------------------------------------------------------------
# HLO accounting
# ---------------------------------------------------------------------------


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(tok_dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * DTYPE_BYTES.get(tok_dtype, 4)


def parse_collective_bytes(hlo: str) -> dict:
    """Sum operand bytes of every collective op in (post-SPMD) HLO text."""
    out = {c: 0 for c in COLLECTIVES}
    for line in hlo.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = ", ls)
        if not m:
            continue
        rhs = ls[m.end():]
        opm = re.match(r"(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*) ([a-z\-]+)\(", rhs)
        if not opm or opm.group(1) not in COLLECTIVES:
            continue
        op = opm.group(1)
        # operand list inside the call parens: count operand shapes
        call = rhs[rhs.index("(") + 1:]
        # operands are %name references; their shapes appear in the def lines,
        # but HLO also inlines shapes for constants. Use the op RESULT shape
        # as the moved-bytes proxy for single-operand collectives (operand
        # size == result size for all-reduce/permute/all-to-all; for
        # all-gather the operand is result/axis, for reduce-scatter the
        # operand is result*axis — we take max(operand,result) conservatism
        # by recording the RESULT bytes and correcting all-gather below).
        shapes = _SHAPE_RE.findall(rhs[: rhs.index("(")])
        nbytes = sum(_shape_bytes(d, dims) for d, dims in shapes)
        out[op] += nbytes
    out["total"] = sum(out[c] for c in COLLECTIVES)
    return out


def cost_numbers(compiled) -> dict:
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def memory_numbers(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }


# ---------------------------------------------------------------------------
# per-cell driver
# ---------------------------------------------------------------------------


def lower_compile(fn, args, in_sh, out_sh, donate=None):
    t0 = time.time()
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return compiled, {"lower_s": t1 - t0, "compile_s": t2 - t1}


def analysis_depths(cfg: ModelConfig) -> tuple[ModelConfig, ModelConfig, int, int]:
    """Two reduced-depth configs + their depths in 'units' (layers/periods)."""
    if cfg.family == "hybrid":
        p = cfg.hybrid_period
        c1 = dataclasses.replace(cfg, n_layers=1 * p)
        c2 = dataclasses.replace(cfg, n_layers=2 * p)
        return c1, c2, 1, 2
    c1 = dataclasses.replace(cfg, n_layers=1)
    c2 = dataclasses.replace(cfg, n_layers=2)
    return c1, c2, 1, 2


def full_units(cfg: ModelConfig) -> float:
    """Depth in the units used by analysis_depths (layers, or periods with
    the tail counted as a mamba-share fraction of a period)."""
    if cfg.family == "hybrid":
        n_periods, ppm, tail = M._hybrid_counts(cfg)
        return n_periods + (tail / ppm) * (ppm / cfg.hybrid_period)  # ≈ mamba share
    return float(cfg.n_layers)


def run_analysis(cfg: ModelConfig, shape_name: str, mesh) -> dict:
    """Loop-free reduced-depth compiles -> extrapolated flops/bytes/collectives."""
    kind = SHAPES[shape_name]["kind"]
    c1, c2, d1, d2 = analysis_depths(cfg)
    # chunks sized so the triangular causal schedule is visible in the
    # unrolled HLO (train 4k -> nq=2; prefill 32k -> nq=4) while keeping the
    # number of unrolled attention bodies bounded
    ck = 2048 if SHAPES[shape_name]["seq"] <= 4096 else 8192
    kw = dict(q_chunk=ck, kv_chunk=ck, unroll=True)
    if cfg.ssm_state:
        c1 = dataclasses.replace(c1, ssm_chunk=2048)
        c2 = dataclasses.replace(c2, ssm_chunk=2048)

    def one(c):
        if kind == "train":
            fn, (st, bs), (in_sh, out_sh) = build_train_cell(
                c, shape_name, mesh, microbatches=1, **kw)
            compiled, _ = lower_compile(fn, (st, bs), in_sh, out_sh)
        elif kind == "prefill":
            fn, args, (in_sh, out_sh) = build_cell(c, shape_name, mesh, **kw)
            compiled, _ = lower_compile(fn, args, in_sh, out_sh)
        else:
            fn, args, (in_sh, out_sh) = build_cell(c, shape_name, mesh, unroll=True)
            compiled, _ = lower_compile(fn, args, in_sh, out_sh)
        nums = cost_numbers(compiled)
        nums["collectives"] = parse_collective_bytes(compiled.as_text())
        return nums

    n1, n2 = one(c1), one(c2)
    L = full_units(cfg)

    def extrap(a, b):
        per = (b - a) / (d2 - d1)
        return max(a + (L - d1) * per, 0.0)

    coll = {}
    for k in n1["collectives"]:
        coll[k] = extrap(n1["collectives"][k], n2["collectives"][k])
    return {
        "flops": extrap(n1["flops"], n2["flops"]),
        "bytes": extrap(n1["bytes"], n2["bytes"]),
        "collectives": coll,
        "depth_points": {str(d1): n1, str(d2): n2},
    }


def _serve_shards(chips: int, batch: int) -> tuple[int, int, int]:
    """(model_n, data_n, batch_shards) of the serve mesh: TP over a 16-wide
    model axis, batch sharded over the data axes when it divides evenly."""
    model_n = 16  # single-pod mesh model axis
    data_n = max(chips // model_n, 1)
    batch_shards = data_n if batch % data_n == 0 else 1
    return model_n, data_n, batch_shards


def analytic_memory_bytes(cfg: ModelConfig, shape_name: str, chips: int) -> float:
    """Principled minimum HBM traffic per device per step (documented in
    EXPERIMENTS.md §Roofline).  The HLO 'bytes accessed' figure is a naive
    per-op sum on the CPU backend (pre-TPU-fusion), so this analytic floor
    accompanies it; hillclimbs track both.

    train  : params (fwd read + bwd read + update write, bf16) + optimizer
             moments (read+write, f32) + remat-saved layer inputs (r+w).
    prefill: params read + KV-cache write (layout-aware compressed bytes)
             + 2x activations stream.
    decode : params read (one read per step, batch-amortized) + compressed
             KV-cache read — the paper's target term.
    """
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    model_n, data_n, batch_shards = _serve_shards(chips, B)
    # train: FSDP over (data×model); serve: TP over model only (replicated
    # across data) — matches the rule tables in distributed/sharding.py.
    n_local_train = cfg.param_count() / chips
    n_local_serve = cfg.param_count() / model_n
    d = cfg.d_model

    def kv_bytes_per_token_layer() -> float:
        """Bytes per cached token per attention layer, averaged over the
        CompressionPolicy's per-layer resolved layouts (each CacheLayout
        owns its analytic size model — no layout-name branching here)."""
        if not cfg.has_attention:
            return 0.0
        from repro.core import layouts as cache_layouts

        Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
        specs = M.cache_specs(cfg, S)
        if not specs:
            return 0.0
        per_layer = [cache_layouts.get_layout(sp.layout).bytes_per_token(sp, Hkv, Dh)
                     for sp in specs]
        return sum(per_layer) / len(per_layer)

    def n_attn_layers() -> int:
        if cfg.family == "hybrid":
            return cfg.n_layers // cfg.hybrid_period
        return cfg.n_layers if cfg.has_attention else 0

    kv_pt = kv_bytes_per_token_layer()
    ctx = min(S, cfg.sliding_window) if cfg.sliding_window else S

    if info["kind"] == "train":
        tokens_local = B * S / data_n  # batch sharded on data axes
        mb_tokens = tokens_local / 8  # default microbatches=8
        act = cfg.n_layers * mb_tokens * d * 2 * 2  # saved inputs r+w
        return 3 * 2 * n_local_train + 2 * 8 * n_local_train + act
    if info["kind"] == "prefill":
        tokens_local = B * S / batch_shards
        kv_w = B * ctx * kv_pt * n_attn_layers() / (batch_shards * model_n)
        act = 2 * cfg.n_layers * tokens_local * d * 2 / model_n
        return 2 * n_local_serve + kv_w + act
    # decode
    kv_r = B * ctx * kv_pt * n_attn_layers() / (batch_shards * model_n)
    ssm_state = 0.0
    if cfg.ssm_state:
        n_mamba = cfg.n_layers - (cfg.n_layers // cfg.hybrid_period
                                  if cfg.hybrid_period else 0)
        ssm_state = (2 * B * cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim
                     * 4 * n_mamba / (batch_shards * model_n))
    return 2 * n_local_serve + kv_r + ssm_state


def decode_kv_traffic(cfg: ModelConfig, shape_name: str, chips: int) -> dict | None:
    """Per-device decode-step KV HBM traffic, fused vs materializing.

    The fused/blockwise backends (DESIGN.md §9) stream each layer's
    COMPRESSED bytes exactly once per step — `CacheLayout.bytes_per_token`
    payload+scales, no dequantized writeback.  The retired materializing
    attend reads the same compressed bytes, then writes the dequantized
    ``[B, Hkv, NB, T, D]`` K/V intermediate to HBM and reads it back for the
    matvec: + 2x the RAW cache bytes per step.  The ratio is the
    data-movement win the paper's Fetch-stage co-design claims; the roofline
    charges the production (fused) number.
    """
    info = SHAPES[shape_name]
    if info["kind"] != "decode" or not cfg.has_attention:
        return None
    from repro.core import layouts as cache_layouts

    B, S = info["batch"], info["seq"]
    model_n, _, batch_shards = _serve_shards(chips, B)
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    specs = M.cache_specs(cfg, S)
    if not specs:
        return None
    ctx = min(S, cfg.sliding_window) if cfg.sliding_window else S
    shard = batch_shards * model_n
    comp_pt = sum(cache_layouts.get_layout(sp.layout).bytes_per_token(sp, Hkv, Dh)
                  for sp in specs) / len(specs)
    raw_pt = 2.0 * Hkv * Dh * 2  # K+V bf16 — the dequantized intermediate
    n_layers = len(specs)
    fused = B * ctx * comp_pt * n_layers / shard
    materialized = fused + 2.0 * B * ctx * raw_pt * n_layers / shard
    return {
        "fused_bytes": fused,
        "materialized_bytes": materialized,
        "traffic_ratio": materialized / max(fused, 1.0),
        "fused_s": fused / HW["hbm_bw"],
        "materialized_s": materialized / HW["hbm_bw"],
    }


def roofline_terms(analysis: dict, chips: int,
                   analytic_bytes: float | None = None) -> dict:
    # cost_analysis numbers come from the per-device partitioned module, so
    # global = per_device * chips and the prescribed terms
    #   term = global_quantity / (chips * per_chip_rate)
    # reduce to per_device_quantity / per_chip_rate.
    comp = analysis["flops"] / HW["peak_flops"]
    mem = analysis["bytes"] / HW["hbm_bw"]
    coll = analysis["collectives"]["total"] / HW["ici_bw"]
    out = {"compute_s": comp, "memory_s": mem, "collective_s": coll}
    if analytic_bytes is not None:
        out["memory_analytic_s"] = analytic_bytes / HW["hbm_bw"]
        dom = max(("compute", comp), ("memory", out["memory_analytic_s"]),
                  ("collective", coll), key=lambda kv: kv[1])
    else:
        dom = max(("compute", comp), ("memory", mem), ("collective", coll),
                  key=lambda kv: kv[1])
    out["bottleneck"] = dom[0]
    return out


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    info = SHAPES[shape_name]
    D = info["batch"] * (info["seq"] if info["kind"] != "decode" else 1)
    n = cfg.active_param_count()
    mult = 6 if info["kind"] == "train" else 2
    return float(mult * n * D)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             analysis: bool = True, force: bool = False) -> dict:
    cfg = registry.get_config(arch)
    out_path = ARTIFACTS / mesh_kind / f"{arch}__{shape_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    out_path.parent.mkdir(parents=True, exist_ok=True)

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "status": "pending", "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}
    reason = skip_reason(cfg, shape_name)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh.devices.size
    try:
        fn, args, (in_sh, out_sh) = build_cell(cfg, shape_name, mesh)
        kind = SHAPES[shape_name]["kind"]
        donate = (0,) if kind == "train" else ((3,) if kind == "decode" else None)
        compiled, times = lower_compile(
            fn, args if isinstance(args, tuple) else (args,), in_sh, out_sh,
            donate=donate)
        rec.update(times)
        rec["memory"] = memory_numbers(compiled)
        rec["cost_raw"] = cost_numbers(compiled)  # loop bodies counted once
        rec["status"] = "ok"
        if analysis and mesh_kind == "pod":
            rec["analysis"] = run_analysis(cfg, shape_name, mesh)
            rec["analytic_memory_bytes"] = analytic_memory_bytes(cfg, shape_name, chips)
            rec["roofline"] = roofline_terms(rec["analysis"], chips,
                                             rec["analytic_memory_bytes"])
            traffic = decode_kv_traffic(cfg, shape_name, chips)
            if traffic is not None:
                rec["decode_kv_traffic"] = traffic
            rec["model_flops"] = model_flops(cfg, shape_name)
            hlo_global = rec["analysis"]["flops"] * chips  # cost_analysis is per device
            rec["hlo_flops_global"] = hlo_global
            rec["useful_flops_ratio"] = (rec["model_flops"] / hlo_global) if hlo_global else None
    except Exception as e:  # noqa: BLE001 — record the failure, don't hide it
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-analysis", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = registry.ASSIGNED if (args.all or not args.arch) else [registry.canonical(args.arch)]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_fail = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                t0 = time.time()
                rec = run_cell(arch, shape_name, mesh_kind,
                               analysis=not args.no_analysis, force=args.force)
                dt = time.time() - t0
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_fail += rec["status"] == "failed"
                extra = ""
                if rec["status"] == "ok" and "roofline" in rec:
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s"
                             f" ma={r.get('memory_analytic_s', 0):.3f}s"
                             f" x={r['collective_s']:.3f}s")
                    if "decode_kv_traffic" in rec:
                        extra += (" kv_fused/mat="
                                  f"1/{rec['decode_kv_traffic']['traffic_ratio']:.1f}x")
                if rec["status"] == "failed":
                    extra = " " + rec["error"][:120]
                print(f"[{mesh_kind}] {arch:22s} {shape_name:12s} "
                      f"{rec['status']:8s} ({dt:.1f}s){extra}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} failed={n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
