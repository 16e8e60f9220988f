"""The serving path's kernels, compiled for a described TPU v5e at yi_6b widths.

Nothing runs: each case lowers with ``interpret=False`` and compiles against a
``v5e:2x2`` topology description, which raises exactly what the chip's
compiler would (Mosaic tiling, unsupported primitives, VMEM or HBM overflow).
Each case checks that the Pallas kernel is in the compiled module
(``tpu_custom_call``) and prints ``memory_analysis()``.

huffman is absent on purpose: its in-kernel LUT decode gathers per lane,
which Mosaic does not lower, so a TPU serves it through the blockwise XLA
scan (DESIGN.md §9).

The topology is described inside a module fixture only — never at import —
so every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cache as C
from repro.kernels.fused_kv_attn import fused_cache_attention_pallas
from repro.kernels.pack_encode import quant_pack_pallas
from repro.models import model as M
from repro.models import registry

# yi_6b serving widths: 4 slots, 32 q / 4 kv heads, head_dim 128, block 64,
# a 2k-token cache.
B, HQ, HKV, D, T, MAX_SEQ = 4, 32, 4, 128, 64, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip; keep these out of any cache.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("mode", ["dense", "paged"])
@pytest.mark.parametrize("layout", ["raw", "packed", "kivi"])
def test_fused_kernel_compiles(layout, mode, one_chip, no_compile_cache):
    kw = dict(mode="paged", pool_pages=B * MAX_SEQ // T) if mode == "paged" else {}
    spec = C.CacheSpec(layout=layout, block_size=T, max_seq=MAX_SEQ, **kw)
    cache = _abstract(jax.eval_shape(lambda: C.init_layer_cache(spec, B, HKV, D)),
                      one_chip)
    args = [jax.ShapeDtypeStruct((B, HQ, D), jnp.bfloat16, sharding=one_chip),
            cache.k_store, cache.k_min, cache.k_step,
            cache.v_store, cache.v_min, cache.v_step,
            cache.k_buf, cache.v_buf, cache.n_flushed, cache.buf_len]
    if mode == "paged":
        args.append(cache.page_tab)
    tile = spec.impl.tile_decode(spec, D)
    _compile(lambda *a: fused_cache_attention_pallas(
        *a, tile=tile, block_size=T, interpret=False), *args)


@pytest.mark.parametrize("B,HKV_,G,NB,P", [
    (64, 4, 8, 64, 7825),    # yi_6b.longdoc: 4k context, 64 slots
    (32, 8, 4, 512, 3756),   # ministral_8b.longdoc: 32k context, 32 slots
], ids=["yi_6b", "ministral_8b"])
def test_fused_kernel_compiles_at_cell_shapes(B, HKV_, G, NB, P, one_chip,
                                              no_compile_cache):
    """The paged packed kernel at the benchmark cells' own batch, heads,
    context and arena: its page stages and compute blocks must fit VMEM."""
    spec = C.CacheSpec(layout="packed", block_size=T, max_seq=NB * T,
                       mode="paged", pool_pages=P)
    cache = _abstract(jax.eval_shape(
        lambda: C.init_layer_cache(spec, B, HKV_, D)), one_chip)
    args = [jax.ShapeDtypeStruct((B, HKV_ * G, D), jnp.bfloat16,
                                 sharding=one_chip),
            cache.k_store, cache.k_min, cache.k_step,
            cache.v_store, cache.v_min, cache.v_step,
            cache.k_buf, cache.v_buf, cache.n_flushed, cache.buf_len,
            cache.page_tab]
    tile = spec.impl.tile_decode(spec, D)
    _compile(lambda *a: fused_cache_attention_pallas(
        *a, tile=tile, block_size=T, interpret=False), *args)


@pytest.mark.parametrize("token_wise,bits", [(False, 5), (True, 3), (False, 2)])
def test_quant_pack_compiles(token_wise, bits, one_chip, no_compile_cache):
    x = jax.ShapeDtypeStruct((B * HKV * 8, T, D), jnp.bfloat16, sharding=one_chip)
    _compile(lambda a: quant_pack_pallas(a, 0.05, bits, token_wise,
                                         interpret=False), x)


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_decode_step_compiles(mode, one_chip, no_compile_cache, monkeypatch):
    """One serving decode step (decode + greedy pick) of a 2-layer yi_6b at
    its published widths, with the fused backend forced: the kernel must
    sit inside the whole step program, not only compile alone."""
    from repro.kernels import runtime

    # Steer the platform checks to the chip this compiles for (the process
    # itself runs on the CPU): impl -> pallas, interpret -> False.
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    cfg = dataclasses.replace(registry.get_config("yi_6b"), n_layers=2,
                              cache_layout="packed", attn_backend="fused",
                              cache_mode=mode)
    assert cfg.cache_block == T
    pool_pages = B * MAX_SEQ // T if mode == "paged" else 0
    params = _abstract(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)[0]), one_chip)
    state = _abstract(jax.eval_shape(
        lambda: M.init_decode_state(cfg, B, MAX_SEQ, pool_pages=pool_pages)), one_chip)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def step(p, t, pos, st):
        logits, st = M.decode_step(p, cfg, t, pos, st)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), st

    _compile(step, params, tok, tok, state)
