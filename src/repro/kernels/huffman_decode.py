"""Pallas kernel: the paper's branch-divergence-free Huffman decode (§3.3.1),
optionally fused with the K-score dot product ("single kernel").

This is the *faithful* port: one VPU lane plays one CUDA thread, walking the
array-based Huffman tree one bit per iteration with the paper's branchless
updates —

    idx    = children[idx, bit]
    out[w] = symbols[idx]              (position advances only at leaves)
    w     += is_symbol[idx]
    idx   *= 1 - is_symbol[idx]        (≡ idx &= ~(-is_symbol) reset-to-root)

Every lane executes the identical instruction sequence; there is no data-
dependent control flow anywhere in the loop, exactly as in the paper.

DESIGN.md §2 records the hardware caveat: the per-lane gathers
(``children[idx, bit]``, the masked output scatter, and ``q[w]`` in the fused
variant) vectorize in interpret mode but are VPU-hostile on real TPU hardware;
the production bandwidth path is ``fused_kv_attn`` over the planar
no-straddle layout.  This kernel exists to validate the algorithm end-to-end and to
measure the faithful single-kernel-vs-multi-kernel comparison (paper Fig. 9).

Layout: one grid step decodes one 2D block — ``S`` streams (rows of
``head_dim`` symbols) packed tightly in stream order inside the block's
payload slot, with per-stream bit counts (the paper's u16 thread metadata).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

Array = jax.Array


def _walk(payload, nbits, children, is_symbol, symbols, n_per_stream, max_bits, S):
    """The branchless lockstep walk shared by both kernel variants.

    The walk body lives in ``repro.core.huffman.walk_decode_jax`` — the
    SAME kernel-safe function the jnp oracle runs, so kernel and oracle
    cannot drift.  Returns decoded codes [S, n_per_stream] float32.
    """
    from repro.core import huffman  # kernels import core; cycle-free

    del S
    return huffman.walk_decode_jax(
        payload, nbits, children, is_symbol, symbols, n_per_stream, max_bits)


def _decode_kernel(payload_ref, nbits_ref, ch_ref, isym_ref, sym_ref, out_ref,
                   *, n_per_stream, max_bits, S):
    codes = _walk(
        payload_ref[0], nbits_ref[0], ch_ref[...], isym_ref[...], sym_ref[...],
        n_per_stream, max_bits, S,
    )
    out_ref[0] = codes.astype(jnp.uint8)


def _fused_scores_kernel(payload_ref, nbits_ref, ch_ref, isym_ref, sym_ref,
                         kmn_ref, kst_ref, q_ref, out_ref,
                         *, n_per_stream, max_bits, S, scale):
    codes = _walk(
        payload_ref[0], nbits_ref[0], ch_ref[...], isym_ref[...], sym_ref[...],
        n_per_stream, max_bits, S,
    )
    # Cache-resident consumption: dequantize + dot in VMEM, emit scores only.
    kd = kmn_ref[0][None, :] + codes * kst_ref[0][None, :]  # [S, D]
    q = q_ref[...].astype(jnp.float32)  # [D]
    out_ref[0] = (kd @ q) * scale


def huffman_decode_pallas(
    payload: Array,   # u32 [NBLK, Wslot] — per-block payload slots
    nbits: Array,     # u16 [NBLK, S]
    children: Array,  # i32 [MAXN, 2]
    is_symbol: Array, # i32 [MAXN]
    symbols: Array,   # i32 [MAXN]
    n_per_stream: int,
    max_bits: int,
    interpret: bool | str = "auto",
) -> Array:
    """Decode every block -> uint8 [NBLK, S, n_per_stream]."""
    NBLK, Wslot = payload.shape
    S = nbits.shape[1]
    MAXN = children.shape[0]
    kernel = functools.partial(
        _decode_kernel, n_per_stream=n_per_stream, max_bits=max_bits, S=S)
    return pl.pallas_call(
        kernel,
        grid=(NBLK,),
        in_specs=[
            pl.BlockSpec((1, Wslot), lambda n: (n, 0)),
            pl.BlockSpec((1, S), lambda n: (n, 0)),
            pl.BlockSpec((MAXN, 2), lambda n: (0, 0)),
            pl.BlockSpec((MAXN,), lambda n: (0,)),
            pl.BlockSpec((MAXN,), lambda n: (0,)),
        ],
        out_specs=pl.BlockSpec((1, S, n_per_stream), lambda n: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((NBLK, S, n_per_stream), jnp.uint8),
        interpret=resolve_interpret(interpret),
    )(payload, nbits, children, is_symbol, symbols)


def huffman_attn_scores_pallas(
    payload: Array, nbits: Array,
    children: Array, is_symbol: Array, symbols: Array,
    k_min: Array,   # [NBLK, D]
    k_step: Array,  # [NBLK, D]
    q: Array,       # [D]
    max_bits: int,
    scale: float = 1.0,
    interpret: bool | str = "auto",
) -> Array:
    """Fused single kernel: Huffman decode + dequant + K·q scores [NBLK, S]."""
    NBLK, Wslot = payload.shape
    S = nbits.shape[1]
    D = q.shape[0]
    MAXN = children.shape[0]
    kernel = functools.partial(
        _fused_scores_kernel, n_per_stream=D, max_bits=max_bits, S=S, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(NBLK,),
        in_specs=[
            pl.BlockSpec((1, Wslot), lambda n: (n, 0)),
            pl.BlockSpec((1, S), lambda n: (n, 0)),
            pl.BlockSpec((MAXN, 2), lambda n: (0, 0)),
            pl.BlockSpec((MAXN,), lambda n: (0,)),
            pl.BlockSpec((MAXN,), lambda n: (0,)),
            pl.BlockSpec((1, D), lambda n: (n, 0)),
            pl.BlockSpec((1, D), lambda n: (n, 0)),
            pl.BlockSpec((D,), lambda n: (0,)),
        ],
        out_specs=pl.BlockSpec((1, S), lambda n: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((NBLK, S), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(payload, nbits, children, is_symbol, symbols, k_min, k_step, q)
