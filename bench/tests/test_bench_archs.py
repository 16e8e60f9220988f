"""Architectures are plug-ins of the harness: ``bench/archs/<name>.py``,
named by a configuration's ``architecture``.  The dense one, ``llama_gqa``,
gives the very sizes, weights, reference logits, counts and cache bytes
that the harness computed before it moved there (the literals below were
read from the harness of that time); a new architecture is a file the
harness finds and runs without an edit; an unknown one is an error."""

from __future__ import annotations

import hashlib
import json
import pathlib
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import tinycell  # noqa: E402

from kvbench import measure, spec  # noqa: E402
from kvbench.cell import Context  # noqa: E402

LLAMA = spec.load_arch({"architecture": "llama_gqa"})
CONFIGS = ("yi_6b", "ministral_8b")


def _config(name):
    return json.loads((tinycell.BENCH / "configs" / f"{name}.json")
                      .read_text())


def test_configurations_name_their_architecture():
    for name in CONFIGS:
        assert spec.load_cell(f"{name}.longdoc").architecture is LLAMA


def _model_config(**kw):
    from repro.models.config import ModelConfig

    return ModelConfig(
        family="dense", n_layers=8, d_model=4096, head_dim=128,
        qk_norm=False, encoder_only=False, causal=True, tie_embeddings=False,
        input_mode="tokens", norm_eps=1e-05, n_experts=0, top_k=0,
        d_ff_expert=0, router_aux_coef=0.01, capacity_factor=1.25,
        ssm_state=0, ssm_conv=4, ssm_expand=2, ssm_head_dim=64, ssm_groups=1,
        ssm_chunk=128, hybrid_period=0, cache_layout="packed",
        cache_block=64, rel_scale_k=0.05, rel_scale_v=0.15, kivi_bits=2,
        cache_overrides=(), cache_mode="paged", attn_backend="auto",
        cache_span_tokens=None, cache_unroll_max=None, dtype="bfloat16", **kw)


PROGRAM_CONFIG = {
    "yi_6b": dict(name="yi_6b", vocab_size=64000, n_heads=32, n_kv_heads=4,
                  sliding_window=None, rope_theta=5000000.0, d_ff=11008),
    "ministral_8b": dict(name="ministral_8b", vocab_size=131072, n_heads=32,
                         n_kv_heads=8, sliding_window=32768,
                         rope_theta=100000000.0, d_ff=12288),
}
TOKEN_FLOPS = {"yi_6b": {1: 3292659712, 3072: 3695181824,
                         16384: 5440012288},
               "ministral_8b": {1: 4160880640, 3072: 4563402752,
                                16384: 6308233216}}
# (window_flops, decode attention operations, decode attention bytes) of
# the fake window below
WINDOW = {"yi_6b": (11534223409152.0, 6450577408.0, 249217024.0),
          "ministral_8b": (12850366644224.0, 6450577408.0, 497057792.0)}
KV = {"yi_6b": {"store_bytes_per_token": 4992.0,
                "raw_bytes_per_token": 16384.0, "block": 64},
      "ministral_8b": {"store_bytes_per_token": 9984.0,
                       "raw_bytes_per_token": 32768.0, "block": 64}}


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_is_the_harness_of_before(name):
    assert LLAMA.program_config(_config(name)) == _model_config(
        **PROGRAM_CONFIG[name])


def _fake_window(name):
    """Three requests (prompts of 3,000, 4,100 and 16,000 tokens) with
    tokens stamped around a 2 s window, and prefill counters."""
    ns = types.SimpleNamespace
    records = [ns(req=ns(prompt=np.zeros(n, np.int32)),
                  result=ns(token_times=t))
               for n, t in ((3000, (9.5, 10.2, 10.4, 11.9, 12.5)),
                            (4100, (10.1, 10.6, 11.0)),
                            (16000, (9.0, 9.9, 10.0, 11.99)))]
    cell = spec.load_cell(f"{name}.longdoc")
    return Context(
        cell=cell, arch=LLAMA.Arch.from_config(cell.config), chips=1,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        t_open=10.0, t_close=12.0, records=records,
        stats_open={"prefill": {"prefill_tokens": 100, "chunks": 3},
                    "prefix": {"prefill_attn_pairs": 1000}},
        stats_close={"prefill": {"prefill_tokens": 4196, "chunks": 11},
                     "prefix": {"prefill_attn_pairs": 1235567}},
        stats_admit={}, kv=KV[name])


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_the_harness_of_before(name):
    arch = LLAMA.Arch.from_config(_config(name))
    for context, flops in TOKEN_FLOPS[name].items():
        assert LLAMA.token_flops(arch, context) == flops
    ctx = _fake_window(name)
    assert ctx.decode_contexts().tolist() == [3001, 3002, 3003, 4101, 4102,
                                              16002, 16003]
    assert (measure.window_flops(ctx), *measure.decode_attn_work(ctx)) \
        == WINDOW[name]


def test_a_window_caps_the_counts():
    """A layer reads at most its window: past it, a token costs what one
    at the window's length does, and reads the store and tail it holds."""
    import dataclasses

    arch = dataclasses.replace(LLAMA.Arch.from_config(_config("yi_6b")),
                               window=4096)
    assert LLAMA.token_flops(arch, 16384) == LLAMA.token_flops(arch, 4096)
    assert LLAMA.token_flops(arch, 3072) == TOKEN_FLOPS["yi_6b"][3072]
    kv = KV["yi_6b"]
    flops, nbytes = LLAMA.decode_attn_work(arch, np.array([16003]), kv)
    # 4,096 keys: the 3-token tail raw, 4,093 from the store; queries and
    # outputs in 8 layers
    assert flops == 4096 * arch.attn_flops_per_pair()
    assert nbytes == (4093 * kv["store_bytes_per_token"]
                      + 3 * kv["raw_bytes_per_token"]
                      + 32 * 128 * 6 * 8)


class _Array:
    def __init__(self, shape, itemsize):
        self.shape, self.nbytes = shape, int(np.prod(shape)) * itemsize


def _fake_server():
    """An arena of yi_6b's shapes: 8 layers, 7,825 pages of 64 tokens, 4 kv
    heads of 128, 11 payload words for K and 7 for V, 64 slots."""
    L, P, Hkv, D, T, B = 8, 7825, 4, 128, 64, 64
    kv = types.SimpleNamespace(
        k_store=_Array((L, 1, Hkv, P, 11, D), 4),
        k_min=_Array((L, 1, Hkv, P, 1, D), 2),
        k_step=_Array((L, 1, Hkv, P, 1, D), 2),
        v_store=_Array((L, 1, Hkv, P, 7, D), 4),
        v_min=_Array((L, 1, Hkv, P, 1, T), 2),
        v_step=_Array((L, 1, Hkv, P, 1, T), 2),
        k_buf=_Array((L, B, T, Hkv, D), 2),
        v_buf=_Array((L, B, T, Hkv, D), 2))
    return types.SimpleNamespace(state={"kv": kv})


@pytest.mark.parametrize("name,raw", [("yi_6b", 16384.0),
                                      ("ministral_8b", 32768.0)])
def test_arena_bytes_are_the_harness_of_before(name, raw):
    assert LLAMA.arena_bytes(_fake_server(), _config(name)) == {
        "store_bytes_per_token": 4992.0, "raw_bytes_per_token": raw,
        "block": 64}


# llama_gqa at tiny widths (as test_bench_parts' forward test): the sha256
# of make_weights(seed=7)'s leaves in tree order, and the reference's
# logits of a fixed 300-token sequence at 4 positions, exact and with the
# KV cache (block 64).  Logits are compared to float32 rounding (rtol 1e-6:
# a change to the computation moves them by far more).
TINY_WEIGHTS_SHA256 = ("f1df5b4977933ce63cbb303c801ed4b062e14b81fe88e7df"
                       "53436a07d80326c6")
TINY_LOGITS = {  # sum, sum of |x|, x[0,0], x[1,17], x[2,100], x[3,255]
    None: (-108.74474564834964, 829.8416251764866, -0.8468137383460999,
           -0.982545018196106, -1.2647521495819092, 1.548084020614624),
    (64, 0.05, 0.15): (-111.90394990907225, 830.6901813725271,
                       -0.8468137383460999, -0.982545018196106,
                       -1.358079195022583, 1.5602777004241943),
}


def _tiny_arch():
    cfg = _config("yi_6b")
    cfg["model"].update(hidden_size=64, intermediate_size=128,
                        num_attention_heads=4, num_key_value_heads=2,
                        num_hidden_layers=2, vocab_size=256, head_dim=16,
                        rope_theta=10000.0)
    return LLAMA.Arch.from_config(cfg)


def test_weights_and_reference_are_the_harness_of_before():
    import jax

    arch = _tiny_arch()
    params = LLAMA.make_weights(arch, 7)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256
    toks = np.random.default_rng(0).integers(0, 256, 300).astype(np.int32)
    at = np.array([0, 5, 150, 299])
    for kv, want in TINY_LOGITS.items():
        lg = np.asarray(LLAMA.logits_at(params, arch, toks, at, kv=kv))
        got = (lg.astype(np.float64).sum(), np.abs(lg).astype(np.float64)
               .sum(), lg[0, 0], lg[1, 17], lg[2, 100], lg[3, 255])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


# A tiny GQA decoder with tied embeddings, as a later PR would add it: the
# output head is the embedding's transpose, and the program is told so.
# It takes what it shares with llama_gqa from there.
TIED_GQA = '''"""GQA decoder with tied embeddings: llama_gqa with the output head
``embed.T`` and no ``unembed``."""

import dataclasses
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from kvbench import spec
from kvbench.reference import AT_PAD, PAD, PRECISIONS, _linear, _rms

base = spec.load_arch({"architecture": "llama_gqa"},
                      pathlib.Path(__file__).resolve().parents[1])
Arch = base.Arch
token_flops, prefill_flops = base.token_flops, base.prefill_flops
decode_attn_work, arena_bytes = base.decode_attn_work, base.arena_bytes


def program_config(config):
    return dataclasses.replace(base.program_config(config),
                               tie_embeddings=True)


def make_weights(arch, seed, dtype=jnp.bfloat16):
    params = base.make_weights(arch, seed, dtype)
    return dict(params, emb={"embed": params["emb"]["embed"]})


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _head(x, at, params, arch, precision):
    h = _rms(x[at], params["ln_f"], arch.norm_eps)
    return _linear(h, params["emb"]["embed"].T, precision)


def logits_at(params, arch, tokens, at, precision="f32", kv=None):
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    buf = np.zeros(-(-len(tokens) // PAD) * PAD, np.int32)
    buf[:len(tokens)] = tokens
    x = base._embed(jnp.asarray(buf), params, arch)
    for layer in range(arch.n_layers):
        x = base._layer(x, params["blocks"], jnp.int32(layer), arch,
                        precision, kv)
    at_buf = np.zeros(-(-len(at) // AT_PAD) * AT_PAD, np.int32)
    at_buf[:len(at)] = at
    return _head(x, jnp.asarray(at_buf), params, arch, precision)[:len(at)]
'''


def test_an_architecture_is_a_file(tmp_path):
    """Files and entries only: the module, a configuration naming it, a
    cell and its check; ``run.py`` runs the cell and serves it correctly."""
    bench = tinycell.make_bench(tmp_path, limit=0.025)
    (bench / "archs" / "tied_gqa.py").write_text(TIED_GQA)
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg.update(name="tiny_tied", architecture="tied_gqa")
    cfg["model"]["tie_word_embeddings"] = True
    (bench / "configs" / "tiny_tied.json").write_text(json.dumps(cfg))
    (bench / "workloads" / "tiny_tied.longdoc.json").write_text(json.dumps(
        {"check": {"requests": 3, "mean_logit_gap": 0.025}}))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny_tied", "source": "test",
                         "file": "bench/configs/tiny_tied.json",
                         "reduced": [], "why": "CPU size"})
    b["workloads"].append({"name": "tiny_tied.longdoc", "config": "tiny_tied",
                           "traffic": "tiny_closed", "chips": 1,
                           "why": "CPU size"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.load_cell("tiny_tied.longdoc", bench)
    assert c.architecture.program_config(c.config).tie_embeddings
    line = tinycell.run_cell(bench, "tiny_tied.longdoc", seed=3, seconds=2.0)
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"itl_p95_ms", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("case", ["unknown", "unnamed", "incomplete"])
def test_an_architecture_that_cannot_be_loaded_is_an_error(tmp_path, case):
    bench = tinycell.make_bench(tmp_path)
    path = bench / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    if case == "unknown":
        cfg["architecture"] = "no_such_arch"
    elif case == "unnamed":
        del cfg["architecture"]
    else:
        (bench / "archs" / "half_gqa.py").write_text(
            "from kvbench.model import _fold_seed  # noqa: F401\n"
            "Arch = make_weights = program_config = logits_at = None\n")
        cfg["architecture"] = "half_gqa"
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="llama_gqa"):
        spec.load_cell("tiny.longdoc", bench)
