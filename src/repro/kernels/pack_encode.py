"""Pallas kernel: Store-stage quantize + bit-pack (paper §3.2.2).

One grid step compresses one 2D block: the raw [T, D] tile streams HBM→VMEM,
min/max reduction, error-bounded quantization, and the planar pack all
run in VMEM, and only the packed u32 words + fp scales go back to HBM —
the Store-stage mirror of cache-resident decompression.  The paper's
inclusive-scan + atomic-offset machinery is unnecessary here because uniform
per-block widths make every output offset affine in the block index
(DESIGN.md §2).

K blocks use BlockQuant units (min/max over the T tokens, per channel);
V blocks use TokenQuant units (min/max over D, per token).

The serving path quantizes through the layouts' XLA ``write_blocks``; this
kernel is the Store stage's Pallas form, checked against
``ref.quant_pack_ref`` and compiled for the chip in
``tests/test_tpu_compile.py``.  It writes the same planar words and
``[1, U]`` unit rows the cache layouts store.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitpack
from repro.kernels.runtime import resolve_interpret

Array = jax.Array


def _kernel(x_ref, words_ref, mn_ref, st_ref, *, rel_scale, bits, token_wise):
    x = x_ref[0].astype(jnp.float32)  # [T, D]
    axis = 1 if token_wise else 0
    mn = jnp.min(x, axis=axis, keepdims=True)  # [T, 1] token-wise, else [1, D]
    mx = jnp.max(x, axis=axis, keepdims=True)
    step = rel_scale * (mx - mn)
    safe = jnp.where(step > 0, step, 1.0)
    # f32 -> i32 -> u32: Mosaic has no direct float -> unsigned convert.
    codes = jnp.clip(jnp.round((x - mn) / safe), 0, 2**bits - 1).astype(jnp.int32)
    words_ref[0] = bitpack.pack_planar(codes, bits)
    row = (lambda a: a.T) if token_wise else (lambda a: a)  # units as [1, U]
    mn_ref[0] = row(mn)
    st_ref[0] = row(step)


def quant_pack_pallas(
    x: Array,  # [NBLK, T, D] raw KV blocks
    rel_scale: float,
    bits: int,
    token_wise: bool,
    interpret: bool | str = "auto",
):
    """Returns (words u32 [NBLK, R, D] planar, mn [NBLK, 1, U], step
    [NBLK, 1, U]) where U = T for token_wise (V) else D (K) — the cache
    layouts' stored shapes, each block a whole-trailing-dims tile."""
    NBLK, T, D = x.shape
    R = bitpack.planar_rows(T, bits)
    U = T if token_wise else D
    kernel = functools.partial(
        _kernel, rel_scale=rel_scale, bits=bits, token_wise=token_wise)
    return pl.pallas_call(
        kernel,
        grid=(NBLK,),
        in_specs=[pl.BlockSpec((1, T, D), lambda n: (n, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, R, D), lambda n: (n, 0, 0)),
            pl.BlockSpec((1, 1, U), lambda n: (n, 0, 0)),
            pl.BlockSpec((1, 1, U), lambda n: (n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((NBLK, R, D), jnp.uint32),
            jax.ShapeDtypeStruct((NBLK, 1, U), jnp.float32),
            jax.ShapeDtypeStruct((NBLK, 1, U), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x)
