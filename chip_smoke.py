"""Bring-up smoke run on a TPU: serve yi_6b at its published widths.

    python chip_smoke.py             # one chip: every layout, dense and paged
    python chip_smoke.py --chips 4   # the sharded path only, on a 2x2 mesh

It drives the same code path as ``python -m repro.launch.serve --size
published``: ``launch.serve``'s model sizing, weights and workload, then
``api.serve`` -> ``Server``.  yi_6b keeps every published width (d_model 4096,
32 q / 4 kv heads, head_dim 128, d_ff 11008, vocab 64000, cache block 64);
only the depth is cut, and the weights are random bf16 from a fixed seed.

One chip, in three phases:

1. Kernels against their references at yi_6b cache widths: the compiled
   fused kernel against its XLA oracle for raw/packed/kivi, dense and over
   a permuted paged arena, within the backend-parity tolerance of
   ``tests/test_backends.py``; huffman's entropy decode must return exactly
   packed's codes (it is lossless over the same quantization).
2. A few heterogeneous requests through ``api.serve`` for the raw, packed,
   kivi and huffman layouts in dense and paged modes, backend ``auto``.
   Every request must end in eos/length with tokens, each layout must
   resolve to its expected decode backend (the compiled fused Pallas kernel
   for raw/packed/kivi, the blockwise XLA scan for huffman — DESIGN.md §9),
   the served decode program must hold the kernel or not accordingly, and
   kernels must run compiled, not interpreted.
3. ``auto`` (fused kernel) and ``xla`` give the same greedy tokens for the
   raw layout.  The two paths sum in different orders, so a stream may part
   only where the model itself is at a near-tie: the step where it parts is
   re-scored by a full forward pass, and both picks must lie within
   ``TIE_LOGITS`` of each other and of the best logit.

``--chips 4``: the same requests through ``api.serve(mesh=2x2)`` and
through a single-device server in this process; greedy tokens must be
bit-identical (DESIGN.md §12) and the paged arena must be spread over all
four devices.

Everything runs in this one process, which holds the chip(s).  The figures
printed on the way (compile-inclusive first-run seconds, cache bytes, peak
device memory) are smoke figures, not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

ARCH = "yi_6b"
MAX_SLOTS = 4
MAX_SEQ = 2048
N_REQUESTS = 4
PROMPT_LEN = 160  # 80..160 tokens: one to two compressed blocks plus a tail
NEW_TOKENS = 24
LAYOUTS = ("raw", "packed", "kivi", "huffman")
# auto's resolution on a TPU (DESIGN.md §9): huffman's in-kernel LUT decode
# does not lower under Mosaic, so it is served by the blockwise scan.
EXPECTED_BACKEND = {"raw": "fused", "packed": "fused", "kivi": "fused",
                    "huffman": "xla"}
# Kernel vs oracle: the backend-parity tolerance of tests/test_backends.py.
KERNEL_ATOL = 5e-3
KERNEL_CONTEXT = 1000  # tokens: 15 compressed blocks of 64 plus a 40-token tail
# Largest logit gap between two greedy picks that still counts as a tie the
# paths' rounding may break either way: a few bf16 ulps at logit scale.
TIE_LOGITS = 0.125


class SmokeFailure(Exception):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(n_chips: int) -> dict:
    from repro.launch.serve import device_info

    device = device_info()
    check(device["platform"] == "tpu",
          f"no TPU: JAX found {device['platform']} devices; this run needs the chip")
    check(device["count"] >= n_chips,
          f"needs {n_chips} TPU chips, found {device['count']}")
    return device


def require_compiled_kernels():
    from repro.kernels import runtime

    check(runtime.resolve_interpret("auto") is False,
          "Pallas kernels would run in interpret mode")
    check(runtime.resolve_impl("auto") == "pallas", "kernel impl is not pallas")


def serve_once(cfg, params, requests, **kw):
    """One server, the workload submitted up front and run to completion.
    Returns (server, results, seconds)."""
    from repro import api

    server = api.serve(cfg, params, max_slots=MAX_SLOTS, max_seq=MAX_SEQ, **kw)
    t0 = time.monotonic()
    handles = [server.submit(r) for r in requests]
    server.run()
    results = [h.result() for h in handles]
    return server, results, time.monotonic() - t0


def check_results(results, what: str):
    for i, r in enumerate(results):
        check(r.finish_reason in ("eos", "length"),
              f"{what}: request {i} ended {r.finish_reason!r} ({r.error})")
        check(len(r.tokens) > 0, f"{what}: request {i} produced no tokens")


def tokens_of(results):
    return [r.tokens.tolist() for r in results]


def decode_holds_kernel(server) -> bool:
    """Whether the server's jitted decode step lowers with a Mosaic kernel."""
    import jax.numpy as jnp

    zeros = jnp.zeros((MAX_SLOTS,), jnp.int32)
    text = server._decode.lower(server.params, zeros, zeros, server.state).as_text()
    return "tpu_custom_call" in text


def kernels_vs_reference(cfg):
    """Phase 1: the compiled kernels against their XLA references on one
    seeded cache per layout at the served model's cache widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cache as C
    from repro.core import pool
    from repro.kernels import ops
    from repro.models import model as M

    B, Hkv, D = MAX_SLOTS, cfg.n_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.normal(size=(B, Hkv, KERNEL_CONTEXT, D)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, cfg.n_heads, D)), jnp.bfloat16)
    attend = jax.jit(ops.cache_decode_attention, static_argnames=("impl",))
    caches = {}
    for layout in LAYOUTS:
        spec = M.cache_spec(dataclasses.replace(cfg, cache_layout=layout), MAX_SEQ)
        caches[layout] = jax.jit(lambda k, v, spec=spec: C.prefill(spec, k, v))(k, v)
    for layout in ("raw", "packed", "kivi"):
        dense = caches[layout]
        NB = dense.spec.n_blocks
        pages = rng.permutation(B * NB).astype(np.int32).reshape(B, NB)
        paged = pool.from_dense(dense, B * NB, pages)
        want = attend(dense, q, impl="xla")
        for mode, cache in (("dense", dense), ("paged", paged)):
            got = attend(cache, q, impl="pallas")
            check(got.shape == q.shape and bool(jnp.all(jnp.isfinite(got))),
                  f"kernel {layout}/{mode}: output {got.shape} not finite "
                  f"of shape {q.shape}")
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            check(err <= KERNEL_ATOL,
                  f"kernel {layout}/{mode}: max |fused - oracle| {err} > "
                  f"{KERNEL_ATOL}")
            print(f"kernel {layout}/{mode}: max |fused - xla oracle| = {err} "
                  f"over {KERNEL_CONTEXT} tokens", flush=True)
    hl, pl = caches["huffman"], caches["packed"]
    for t in ("k", "v"):
        store = lambda c: c.k_store if t == "k" else c.v_store
        h = jax.jit(lambda s: hl.spec.impl.decode_codes(hl.spec, s, D, t))(store(hl))
        p = jax.jit(lambda s: pl.spec.impl.decode_codes(pl.spec, s, D, t))(store(pl))
        check(bool(jnp.array_equal(h.astype(jnp.int32), p.astype(jnp.int32))),
              f"huffman {t.upper()} codes differ from packed's")
    print("kernel huffman: entropy-decoded K/V codes equal packed's exactly",
          flush=True)


def first_parting(a, b):
    """Index of the first token where two streams differ, or None."""
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


def check_greedy_agreement(cfg, params, requests, got, want, what):
    """``got`` and ``want`` must be the same greedy streams, except that a
    stream may part at a near-tie of the model (see the module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    width = PROMPT_LEN + NEW_TOKENS  # one padded length: one compile
    logits_at = jax.jit(lambda p, t, i: M.forward(p, cfg, {"tokens": t})[0][0, i]
                        .astype(jnp.float32))
    for i, (req, a, b) in enumerate(zip(requests, got, want)):
        d = first_parting(a, b)
        if d is None:
            continue
        check(d < min(len(a), len(b)), f"{what}: request {i} streams differ "
              f"in length ({len(a)} vs {len(b)}) with no token parted")
        ctx = np.zeros((1, width), np.int32)
        seq = np.concatenate([req.prompt, np.asarray(b[:d], np.int32)])
        ctx[0, :len(seq)] = seq
        logits = np.asarray(logits_at(params, ctx, len(seq) - 1))
        gap = abs(float(logits[a[d]] - logits[b[d]]))
        below = float(logits.max() - min(logits[a[d]], logits[b[d]]))
        check(gap <= TIE_LOGITS and below <= TIE_LOGITS,
              f"{what}: request {i} parts at token {d} ({a[d]} vs {b[d]}) "
              f"where the model is not at a tie: logit gap {gap}, "
              f"{below} below the best")
        print(f"{what}: request {i} parts at token {d} of {len(b)} on a "
              f"near-tie (logit gap {gap}, {below} below the best)", flush=True)


def one_chip(cfg, params, requests):
    from repro.core import layouts
    from repro.kernels import ops

    require_compiled_kernels()
    kernels_vs_reference(cfg)
    tokens = {}
    for layout in LAYOUTS:
        backend = ops.resolve_backend("auto", layouts.get_layout(layout))
        check(backend == EXPECTED_BACKEND[layout],
              f"{layout}: auto resolved to {backend!r}, expected "
              f"{EXPECTED_BACKEND[layout]!r}")
        lcfg = dataclasses.replace(cfg, cache_layout=layout)
        for mode in ("dense", "paged"):
            what = f"{layout}/{mode}"
            server, res, secs = serve_once(lcfg, params, requests,
                                           cache_mode=mode, attn_backend="auto")
            check_results(res, what)
            kernel = decode_holds_kernel(server)
            check(kernel == (backend == "fused"),
                  f"{what}: decode program {'holds' if kernel else 'lacks'} "
                  f"a Pallas kernel but the backend is {backend!r}")
            tokens[what] = tokens_of(res)
            n_tok = sum(len(r.tokens) for r in res)
            print(f"{what}: backend={backend} pallas_kernel_in_decode={kernel} "
                  f"kv_cache_bytes={server.memory_report()['kv_bytes']} "
                  f"tokens={n_tok} first_run_s_incl_compile={secs:.1f} "
                  f"finish={[r.finish_reason for r in res]}", flush=True)
            del server
        print(f"{layout}: paged tokens == dense tokens: "
              f"{tokens[layout + '/paged'] == tokens[layout + '/dense']}",
              flush=True)
    rcfg = dataclasses.replace(cfg, cache_layout="raw")
    _, res, _ = serve_once(rcfg, params, requests, cache_mode="dense",
                           attn_backend="xla")
    check_results(res, "raw/dense/xla")
    check_greedy_agreement(rcfg, params, requests, tokens["raw/dense"],
                           tokens_of(res), "raw/dense auto vs xla")
    same = tokens_of(res) == tokens["raw/dense"]
    print(f"raw/dense: auto and xla greedy tokens "
          f"{'identical' if same else 'agree up to near-ties'} "
          f"({sum(len(t) for t in tokens['raw/dense'])} tokens)", flush=True)


def four_chips(cfg, params, requests):
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh("2,2")
    check(mesh is not None and mesh.devices.size == 4, "no 2x2 serving mesh")
    pcfg = dataclasses.replace(cfg, cache_layout="packed")
    _, single, s1 = serve_once(pcfg, params, requests, cache_mode="paged")
    check_results(single, "single-device")
    server, sharded, s4 = serve_once(pcfg, params, requests,
                                     cache_mode="paged", mesh=mesh)
    check_results(sharded, "sharded")
    check(tokens_of(sharded) == tokens_of(single),
          "sharded greedy tokens differ from the single-device server")
    k_store = server.state["kv"].k_store
    devices = {s.device for s in k_store.addressable_shards}
    shard_shape = k_store.addressable_shards[0].data.shape
    check(len(devices) == 4, f"paged arena on {len(devices)} devices, not 4")
    check(shard_shape != k_store.shape,
          f"paged arena is replicated, not spread: shard {shard_shape} "
          f"== global {k_store.shape}")
    print(f"packed/paged on mesh 2x2: tokens bit-identical to single device "
          f"({sum(len(r.tokens) for r in sharded)} tokens); k_store "
          f"{k_store.shape} spread over {len(devices)} devices as {shard_shape} "
          f"shards; first_run_s_incl_compile single={s1:.1f} sharded={s4:.1f}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path and its comparison")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is missing ({SRC}); run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    from repro.launch import serve as launch
    from repro.launch.compile_cache import enable_compile_cache

    try:
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        device = require_tpu(args.chips)
        print(f"device: {device}", flush=True)
        cfg = launch.model_config(ARCH, "published")
        print(launch.describe_cut(ARCH, cfg), flush=True)
        t0 = time.monotonic()
        params = jax.block_until_ready(launch.init_params(cfg))
        n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
        print(f"weights: {n_bytes / 2**30:.2f} GiB in "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        requests = launch.workload(cfg, N_REQUESTS, PROMPT_LEN, NEW_TOKENS)
        print(f"requests: prompt lens {[len(r.prompt) for r in requests]}, "
              f"budgets {[r.max_new_tokens for r in requests]}", flush=True)
        if args.chips == 4:
            four_chips(cfg, params, requests)
        else:
            one_chip(cfg, params, requests)
        stats = jax.devices()[0].memory_stats() or {}
        print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
