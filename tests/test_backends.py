"""Attention-backend parity suite (DESIGN.md §9).

Every decode-attention backend must agree: the blockwise lazily-dequantized
scan (``xla``), the fused in-situ-decompression kernel (``fused``, pallas and
its vmapped oracle), and the retired materializing oracle, against
``reference_attend`` — across GQA ratios, odd head dims, sliding-window ring
wraparound, and heterogeneous per-row ``nb_valid``/``buf_len`` like the
continuous-batching scheduler produces.  Greedy decode through the full model
must emit bit-identical tokens whichever backend serves it.
"""

import dataclasses
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as C
from repro.core import layouts
from repro.kernels import ops

LAYOUTS = ["raw", "packed", "kivi", "huffman"]


def _mk(rng, B, Hkv, G, S, D):
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)).astype(np.float32))
    return k, v, q


def _all_backends(cache, q):
    """Every decode path's output for one cache, keyed by name."""
    outs = {
        "blockwise": C.attend_blockwise(cache, q),
        "materialized": C.attend_materialized(cache, q),
    }
    if cache.spec.impl.supports_fused:
        outs["fused_pallas"] = ops.cache_decode_attention(cache, q, impl="pallas")
        outs["fused_oracle"] = ops.cache_decode_attention(cache, q, impl="xla")
    return outs


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("G", [1, 4, 8])
def test_backend_parity_gqa(layout, G, rng):
    k, v, q = _mk(rng, 2, 2, G, 72, 16)
    spec = C.CacheSpec(layout=layout, block_size=16, max_seq=128,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    cache = C.prefill(spec, k, v)  # 4 blocks + 8 buffered
    outs = _all_backends(cache, q)
    ref = C.reference_attend(k, v, q)
    tol = 0.4 if layout == "kivi" else 0.06
    for name, out in outs.items():
        assert float(jnp.max(jnp.abs(out - ref))) < tol, name
        np.testing.assert_allclose(np.asarray(out), np.asarray(outs["blockwise"]),
                                   atol=5e-3, err_msg=name)


@pytest.mark.parametrize("layout", ["packed", "huffman"])
@pytest.mark.parametrize("D", [80, 112, 160])
def test_backend_parity_odd_head_dims(D, layout, rng):
    """Odd head dims from the assigned archs (zamba2 80, chameleon 112, 160)."""
    k, v, q = _mk(rng, 2, 2, 4, 48, D)
    spec = C.CacheSpec(layout=layout, block_size=16, max_seq=64,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    cache = C.prefill(spec, k, v)
    outs = _all_backends(cache, q)
    for name, out in outs.items():
        np.testing.assert_allclose(np.asarray(out), np.asarray(outs["blockwise"]),
                                   atol=5e-3, err_msg=name)
    # quantization error vs the exact oracle accumulates ~sqrt(D)
    assert float(jnp.max(jnp.abs(outs["blockwise"] - C.reference_attend(k, v, q)))) < 0.2


@pytest.mark.parametrize("layout", ["packed", "raw", "huffman"])
def test_backend_parity_sliding_window_wraparound(layout, rng):
    """Ring eviction: appends past the window wrap slots; every backend must
    agree with windowed exact attention."""
    k, v, q = _mk(rng, 2, 2, 2, 32, 16)
    spec = C.CacheSpec(layout=layout, block_size=16, max_seq=512, window=32,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    cache = C.prefill(spec, k, v)
    app = jax.jit(C.append)
    extra_k = rng.normal(size=(40, 2, 2, 16)).astype(np.float32)
    extra_v = rng.normal(size=(40, 2, 2, 16)).astype(np.float32)
    for t in range(40):
        cache = app(cache, jnp.asarray(extra_k[t]), jnp.asarray(extra_v[t]))
    assert int(cache.n_flushed[0]) > spec.n_blocks  # the ring has wrapped
    k_all = jnp.concatenate([k, jnp.asarray(extra_k).transpose(1, 2, 0, 3)], 2)
    v_all = jnp.concatenate([v, jnp.asarray(extra_v).transpose(1, 2, 0, 3)], 2)
    # Block-aligned eviction retains >= window: the full ring plus whatever
    # sits in the raw buffer (here 2 blocks + 8 buffered = 40 tokens).
    visible = spec.n_blocks * spec.block_size + int(cache.buf_len[0])
    ref = C.reference_attend(k_all, v_all, q, window=visible)
    for name, out in _all_backends(cache, q).items():
        assert float(jnp.max(jnp.abs(out - ref))) < 0.06, name


@pytest.mark.parametrize("layout", ["packed", "raw", "huffman"])
def test_backend_parity_heterogeneous_rows(layout, rng):
    """Rows at different positions (the scheduler's contract): per-row
    nb_valid/buf_len flow into every backend; each row must match its solo
    run bit-for-bit per backend."""
    spec = C.CacheSpec(layout=layout, block_size=16, max_seq=256)
    k, v, q = _mk(rng, 2, 2, 2, 96, 16)
    c40 = C.prefill(spec, k[:, :, :40], v[:, :, :40])  # 2 blocks + 8 buffered
    c96 = C.prefill(spec, k, v)                        # 6 blocks + 0 buffered
    mixed = jax.tree.map(lambda a, b: jnp.stack([a[0], b[1]]), c40, c96)
    solo0 = jax.tree.map(lambda x: x[:1], c40)
    solo1 = jax.tree.map(lambda x: x[1:], c96)
    mixed_outs = _all_backends(mixed, q)
    solo0_outs = _all_backends(solo0, q[:1])
    solo1_outs = _all_backends(solo1, q[1:])
    for name in mixed_outs:
        np.testing.assert_array_equal(np.asarray(mixed_outs[name][:1]),
                                      np.asarray(solo0_outs[name]), err_msg=name)
        np.testing.assert_array_equal(np.asarray(mixed_outs[name][1:]),
                                      np.asarray(solo1_outs[name]), err_msg=name)


def test_backend_parity_empty_store_and_empty_buffer(rng):
    """nb_valid == 0 (all in buffer) and buf_len == 0 (all in store)."""
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=64,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    k, v, q = _mk(rng, 1, 2, 2, 5, 16)
    cache = C.prefill(spec, k, v)
    assert int(cache.n_flushed[0]) == 0
    ref = C.reference_attend(k, v, q)
    for name, out in _all_backends(cache, q).items():
        assert float(jnp.max(jnp.abs(out - ref))) < 5e-3, name
    k2, v2, q2 = _mk(rng, 1, 2, 2, 32, 16)
    cache2 = C.prefill(spec, k2, v2)
    assert int(cache2.buf_len[0]) == 0
    ref2 = C.reference_attend(k2, v2, q2)
    for name, out in _all_backends(cache2, q2).items():
        assert float(jnp.max(jnp.abs(out - ref2))) < 0.06, name


# ---------------------------------------------------------------------------
# dispatch / registry
# ---------------------------------------------------------------------------


def test_resolve_backend_auto_off_tpu():
    env = {k: v for k, v in os.environ.items() if k != ops.ENV_BACKEND}
    with mock.patch.dict(os.environ, env, clear=True):
        for layout in LAYOUTS:
            assert ops.resolve_backend("auto", layouts.get_layout(layout)) == "xla"


def test_resolve_backend_every_builtin_layout_is_fused_capable():
    """Off-TPU every built-in layout serves through the fused backend when
    asked, huffman's in-kernel LUT decode included (interpret mode); on a
    TPU huffman is the exception (see the huffman test below)."""
    for layout in LAYOUTS:
        assert ops.resolve_backend("fused", layouts.get_layout(layout)) == "fused"


def test_non_fused_layout_has_no_tile_spec_and_kernel_entry_rejects(rng):
    """supports_fused=False is authoritative even when a layout inherits a
    fused-capable base's _tile_decode (a custom layout subclassing packed
    with a different slot encoding): the tile spec must be None, auto must
    pick the blockwise floor, an explicit fused request and the direct
    kernel entry must raise — not silently serve another path or unpack
    the slots with the packed decoder."""

    class _Ragged(layouts.PackedLayout):
        supports_fused = False

    layouts.register_layout("_test_ragged")(_Ragged)
    try:
        lay = layouts.get_layout("_test_ragged")
        assert ops.resolve_backend("auto", lay) == "xla"
        with mock.patch.dict(os.environ, {ops.ENV_BACKEND: "fused"}):
            assert ops.resolve_backend("auto", lay) == "xla"
        with pytest.raises(ValueError, match="cannot serve layout"):
            ops.resolve_backend("fused", lay)
        spec = C.CacheSpec(layout="_test_ragged", block_size=16, max_seq=64)
        assert spec.impl.tile_decode(spec, 16) is None
        k, v, q = _mk(rng, 1, 2, 2, 32, 16)
        cache = C.prefill(spec, k, v)
        with pytest.raises(ValueError, match="fused-capable layout"):
            ops.cache_decode_attention(cache, q)
    finally:
        layouts._REGISTRY.pop("_test_ragged", None)


def test_resolve_backend_env_override_replaces_auto_only():
    lay = layouts.get_layout("packed")
    with mock.patch.dict(os.environ, {ops.ENV_BACKEND: "fused"}):
        assert ops.resolve_backend("auto", lay) == "fused"
        assert ops.resolve_backend(None, lay) == "fused"
        assert ops.resolve_backend("xla", lay) == "xla"  # explicit wins


def test_resolve_backend_unknown_errors():
    with pytest.raises(ValueError, match="unknown attention backend"):
        ops.resolve_backend("mps", layouts.get_layout("packed"))


def test_register_backend_is_dispatchable(rng):
    calls = []

    @ops.register_backend("_test_probe")
    def _probe(cache, q, scale=None):
        calls.append(cache.spec.layout)
        return C.attend_blockwise(cache, q, scale)

    try:
        spec = C.CacheSpec(layout="packed", block_size=16, max_seq=64,
                           attn_backend="_test_probe")
        k, v, q = _mk(rng, 1, 2, 2, 32, 16)
        cache = C.prefill(spec, k, v)
        out = C.attend(cache, q)
        assert calls == ["packed"]
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(C.attend_blockwise(cache, q)))
    finally:
        ops._BACKENDS.pop("_test_probe", None)


def test_attn_backend_threads_config_to_spec():
    from repro.models.config import ModelConfig
    from repro.core.policy import LayerOverride

    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                      vocab_size=64, n_heads=2, n_kv_heads=2,
                      attn_backend="xla",
                      cache_overrides=(LayerOverride(layers=(2,),
                                                     attn_backend="fused"),))
    pol = cfg.compression_policy()
    assert pol.spec_for_layer(0, max_seq=64).attn_backend == "xla"
    assert pol.spec_for_layer(2, max_seq=64).attn_backend == "fused"


# ---------------------------------------------------------------------------
# blockwise span/unroll knobs
# ---------------------------------------------------------------------------


def test_blockwise_knobs_precedence(monkeypatch):
    """Spec field > REPRO_BLOCKWISE_* env var > module default."""
    monkeypatch.delenv(C.ENV_SPAN_TOKENS, raising=False)
    monkeypatch.delenv(C.ENV_UNROLL_MAX, raising=False)
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=64)
    assert C.blockwise_knobs(spec) == (C.BLOCKWISE_SPAN_TOKENS,
                                       C.BLOCKWISE_UNROLL_MAX)
    monkeypatch.setenv(C.ENV_SPAN_TOKENS, "128")
    monkeypatch.setenv(C.ENV_UNROLL_MAX, "3")
    assert C.blockwise_knobs(spec) == (128, 3)
    pinned = C.CacheSpec(layout="packed", block_size=16, max_seq=64,
                         span_tokens=32, unroll_max=7)
    assert C.blockwise_knobs(pinned) == (32, 7)  # explicit spec wins
    with pytest.raises(ValueError, match="span_tokens"):
        C.CacheSpec(layout="packed", span_tokens=0)
    # env values get the same validation as spec fields, with a clear error
    monkeypatch.setenv(C.ENV_UNROLL_MAX, "0")
    with pytest.raises(ValueError, match=C.ENV_UNROLL_MAX):
        C.blockwise_knobs(spec)
    monkeypatch.setenv(C.ENV_UNROLL_MAX, "3")
    monkeypatch.setenv(C.ENV_SPAN_TOKENS, "1k")
    with pytest.raises(ValueError, match="not an integer"):
        C.blockwise_knobs(spec)


def test_blockwise_output_invariant_to_span_and_unroll(rng):
    """Any span size / unroll-vs-scan choice computes the same attention
    (the knob only trades peak temps for per-step overhead)."""
    k, v, q = _mk(rng, 2, 2, 2, 96, 16)
    base = C.attend_blockwise(
        C.prefill(C.CacheSpec(layout="packed", block_size=16, max_seq=128),
                  k, v), q)
    for span_tokens, unroll_max in [(16, 64), (48, 64), (16, 1)]:
        spec = C.CacheSpec(layout="packed", block_size=16, max_seq=128,
                           span_tokens=span_tokens, unroll_max=unroll_max)
        out = C.attend_blockwise(C.prefill(spec, k, v), q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-5, err_msg=f"{span_tokens}/{unroll_max}")


def test_span_knobs_thread_config_to_spec():
    from repro.models.config import ModelConfig
    from repro.core.policy import LayerOverride

    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=32,
                      vocab_size=64, n_heads=2, n_kv_heads=2,
                      cache_span_tokens=256, cache_unroll_max=4,
                      cache_overrides=(LayerOverride(layers=(2,),
                                                     span_tokens=64,
                                                     unroll_max=1),))
    pol = cfg.compression_policy()
    s0 = pol.spec_for_layer(0, max_seq=64)
    assert (s0.span_tokens, s0.unroll_max) == (256, 4)
    s2 = pol.spec_for_layer(2, max_seq=64)
    assert (s2.span_tokens, s2.unroll_max) == (64, 1)


# ---------------------------------------------------------------------------
# greedy decode bit-identity across backends (the tentpole contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["packed", "raw", "huffman"])
def test_greedy_decode_tokens_bit_identical_across_backends(layout, rng):
    import dataclasses as dc

    from repro.models import model as M
    from repro.models.config import ModelConfig

    base = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                       vocab_size=97, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, cache_block=8, cache_layout=layout)
    params, _ = M.init_params(base, jax.random.PRNGKey(0))
    prompt = jnp.asarray(rng.integers(0, 97, size=(2, 21)).astype(np.int32))

    def run(backend):
        cfg = dc.replace(base, attn_backend=backend)
        logits, state = jax.jit(
            lambda p, t: M.prefill(p, cfg, {"tokens": t}, 64))(params, prompt)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        pos = jnp.full((2,), prompt.shape[1], jnp.int32)
        toks = [tok]
        step = jax.jit(lambda p, t, po, st: M.decode_step(p, cfg, t, po, st))
        for _ in range(12):
            logits, state = step(params, tok, pos, state)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            toks.append(tok)
            pos = pos + 1
        return np.stack([np.asarray(t) for t in toks])

    t_xla = run("xla")
    t_fused = run("fused")
    np.testing.assert_array_equal(t_xla, t_fused)


def test_huffman_tile_decode_bit_exact_vs_blockwise_decode(rng):
    """Losslessness of the fused path: the in-kernel chunked-LUT tile decode
    must reproduce the layout's blockwise entropy decode bit-for-bit (same
    codes, same dequant ops) for every slot — the kernel/oracle/blockwise
    paths then differ only in softmax accumulation order."""
    from repro.kernels import ref

    spec = C.CacheSpec(layout="huffman", block_size=16, max_seq=128,
                       rel_scale_k=0.02, rel_scale_v=0.05)
    k, v, q = _mk(rng, 2, 2, 2, 64, 24)
    cache = C.prefill(spec, k, v)
    lay, D = spec.impl, cache.head_dim
    tile = lay.tile_decode(spec, D)
    assert tile is not None and len(tile.aux) == 2
    aux = tuple(jnp.asarray(a) for a in tile.aux)
    k_codes = lay.decode_codes(spec, cache.k_store, D, "k")
    v_codes = lay.decode_codes(spec, cache.v_store, D, "v")
    for b in range(2):
        for h in range(2):
            for n in range(4):
                kd = tile.decode_k(cache.k_store[b, h, n], cache.k_min[b, h, n],
                                   cache.k_step[b, h, n], *aux)
                vd = tile.decode_v(cache.v_store[b, h, n], cache.v_min[b, h, n],
                                   cache.v_step[b, h, n], *aux)
                np.testing.assert_array_equal(
                    np.asarray(kd),
                    np.asarray(ref.dequant_k(k_codes[b, h, n],
                                             cache.k_min[b, h, n],
                                             cache.k_step[b, h, n])))
                np.testing.assert_array_equal(
                    np.asarray(vd),
                    np.asarray(ref.dequant_v(v_codes[b, h, n],
                                             cache.v_min[b, h, n],
                                             cache.v_step[b, h, n])))


def test_huffman_fused_pallas_matches_oracle_bit_level(rng):
    """Kernel vs vmapped-oracle parity for the huffman ragged-payload tile
    decode, through the public jit'd entry (both impls share the same
    FusedTileSpec closures, so any drift is accumulation order only)."""
    spec = C.CacheSpec(layout="huffman", block_size=16, max_seq=128)
    k, v, q = _mk(rng, 2, 2, 4, 72, 16)
    cache = C.prefill(spec, k, v)
    o_pallas = ops.cache_decode_attention(cache, q, impl="pallas")
    o_oracle = ops.cache_decode_attention(cache, q, impl="xla")
    np.testing.assert_allclose(np.asarray(o_pallas), np.asarray(o_oracle),
                               atol=1e-5, rtol=1e-5)


def test_spec_backend_dispatch_respected(rng):
    """CacheSpec.attn_backend="fused" routes through the kernel path even on
    CPU (oracle impl), and the result still tracks the blockwise path."""
    spec = C.CacheSpec(layout="packed", block_size=16, max_seq=64,
                       attn_backend="fused")
    k, v, q = _mk(rng, 1, 2, 2, 40, 16)
    cache = C.prefill(spec, k, v)
    out = C.attend(cache, q)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ops.cache_decode_attention(cache, q)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(C.attend_blockwise(cache, q)),
                               atol=5e-3)


def test_huffman_is_not_fused_on_tpu_and_explicit_fused_raises(monkeypatch):
    """The one place the huffman/TPU decision lives: off-TPU the LUT decode
    runs in the (interpreted) kernel; on a TPU auto picks the blockwise scan
    and an explicit fused request raises instead of quietly serving xla."""
    from repro.kernels import runtime

    lay = layouts.get_layout("huffman")
    assert lay.supports_fused
    assert ops.resolve_backend("fused", lay) == "fused"
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    assert not lay.supports_fused
    assert ops.resolve_backend("auto", lay) == "xla"
    with pytest.raises(ValueError, match="cannot serve layout 'huffman'"):
        ops.resolve_backend("fused", lay)
    for name in ("raw", "packed", "kivi"):
        assert ops.resolve_backend("auto", layouts.get_layout(name)) == "fused"
