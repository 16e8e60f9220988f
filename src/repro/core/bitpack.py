"""Adaptive fixed-length bit-packing — the TPU-native entropy path.

DESIGN.md §2: symbol-serial Huffman decode does not vectorize on a TPU VPU,
so the performance path preserves the paper's entropy adaptivity at *block*
granularity instead of *symbol* granularity: each 2D block stores its codes
with ``b = ceil(log2(max_code + 1))`` bits.  Because the quantized KV code
histogram is tightly concentrated (paper Fig. 3), most blocks need only a few
bits, and unpacking is pure shift/mask — fully vectorizable and fusable with
the attention matvec.

Layouts
-------
* ``pack_bits`` / ``unpack_bits`` — static bit-width b ∈ [1, 8]; codes are
  packed LSB-first into little-endian u32 words along the last axis.  Static
  shapes; straddling words is handled (b need not divide 32).
* ``pack_planar`` / ``unpack_planar`` — the cache layouts' block format:
  static width, no code straddles a word, one column per word (below).
* ``choose_bits`` — per-block adaptive width (pow2-rounded option for the
  Pallas kernel's lax.switch dispatch).
* ``pack_adaptive`` / ``unpack_adaptive`` — ragged multi-block container with
  deterministic cumsum offsets (the atomic-free Block Offsets Array).

All functions are jnp and jit-safe unless suffixed ``_np``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def packed_words(n_codes: int, bits: int) -> int:
    """Number of u32 words to hold n_codes values at `bits` bits each."""
    return (n_codes * bits + 31) // 32


def pack_bits(codes: Array, bits: int) -> Array:
    """Pack uint8 codes (< 2**bits) along the last axis into u32 words.

    codes: [..., L]  ->  [..., packed_words(L, bits)] uint32.
    Works for any static 1 <= bits <= 8 (values straddling a word boundary
    contribute to two words; contributions are bitwise disjoint so
    scatter-add ≡ or).
    """
    if not (1 <= bits <= 8):
        raise ValueError(f"bits must be in [1, 8], got {bits}")
    *lead, L = codes.shape
    W = packed_words(L, bits)
    c = codes.astype(jnp.uint32) & jnp.uint32((1 << bits) - 1)
    j = np.arange(L)
    word_idx = jnp.asarray((j * bits) >> 5)
    bit_in = jnp.asarray((j * bits) & 31, dtype=np.uint32)
    keep = jnp.uint32(32) - bit_in
    mask_low = jnp.where(keep >= 32, jnp.uint32(0xFFFFFFFF), (jnp.uint32(1) << keep) - 1)
    low = (c & mask_low) << bit_in
    high = (c >> (jnp.uint32(31) - bit_in)) >> 1
    flat = c.reshape(-1, L)
    out = jnp.zeros((flat.shape[0], W), jnp.uint32)
    low = low.reshape(-1, L)
    high = high.reshape(-1, L)
    rows = jnp.arange(flat.shape[0])[:, None]
    out = out.at[rows, word_idx[None, :]].add(low, mode="drop")
    out = out.at[rows, word_idx[None, :] + 1].add(high, mode="drop")
    return out.reshape(*lead, W)


def unpack_bits(words: Array, bits: int, n_codes: int) -> Array:
    """Inverse of pack_bits: [..., W] uint32 -> [..., n_codes] uint8.

    Gather indices are computed at trace time (static), so the lowered HLO is
    a regular gather + shift + mask — the shape the MXU/VPU wants.
    """
    if not (1 <= bits <= 8):
        raise ValueError(f"bits must be in [1, 8], got {bits}")
    j = np.arange(n_codes)
    word_idx = jnp.asarray((j * bits) >> 5)
    bit_in = jnp.asarray((j * bits) & 31, dtype=np.uint32)
    w0 = jnp.take(words, word_idx, axis=-1)
    low = w0 >> bit_in
    # Bits spilling from the next word (index clamped; masked out when unused).
    word_next = jnp.minimum(word_idx + 1, words.shape[-1] - 1)
    w1 = jnp.take(words, word_next, axis=-1)
    spill = (w1 << (jnp.uint32(31) - bit_in)) << 1
    has_spill = (bit_in + jnp.uint32(bits) > 32).astype(jnp.uint32)
    val = (low | (spill * has_spill)) & jnp.uint32((1 << bits) - 1)
    return val.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Planar no-straddle layout: each u32 word holds floor(32/bits) whole codes
# of ONE column.  A [T, D] block packs into [R, D] words, R = ceil(T / cpw):
# word[r, d] carries code[i*R + r, d] at bit i*bits for every plane i.
#
# Wastes (32 mod bits) pad bits per word plus the rows that round T up to
# cpw*R, but unpacking is one shift + mask per plane and a row concatenation
# — lane-dense [R, D] vector ops with no gather and no lane reshape, which is
# what Mosaic lowers inside the fused kernel and what XLA vectorizes
# outside it.  The same functions run in both places.
# ---------------------------------------------------------------------------


def codes_per_word(bits: int) -> int:
    return 32 // bits


def planar_rows(n_rows: int, bits: int) -> int:
    """Word rows that hold ``n_rows`` code rows at ``bits`` bits each."""
    return (n_rows + codes_per_word(bits) - 1) // codes_per_word(bits)


def pack_planar(codes: Array, bits: int) -> Array:
    """[..., T, D] codes (< 2**bits) -> [..., planar_rows(T, bits), D] u32."""
    if not (1 <= bits <= 16):
        raise ValueError(f"bits must be in [1, 16], got {bits}")
    *lead, T, D = codes.shape
    R = planar_rows(T, bits)
    n_planes = -(-T // R)
    c = codes.astype(jnp.uint32) & jnp.uint32((1 << bits) - 1)
    if n_planes * R > T:  # zero rows complete the last plane
        c = jnp.concatenate(
            [c, jnp.zeros((*lead, n_planes * R - T, D), jnp.uint32)], axis=-2)
    words = c[..., :R, :]
    for i in range(1, n_planes):
        words = words | (c[..., i * R:(i + 1) * R, :] << jnp.uint32(i * bits))
    return words


def unpack_planar(words: Array, bits: int, n_rows: int) -> Array:
    """Inverse of ``pack_planar``: [..., R, D] u32 -> [..., n_rows, D] int32.

    int32 (not uint32) so callers convert to float with a cast Mosaic
    lowers; kernel-safe as written (no captured arrays, no gathers).
    """
    R = words.shape[-2]
    mask = jnp.uint32((1 << bits) - 1)
    planes = [((words >> jnp.uint32(i * bits)) & mask).astype(jnp.int32)
              for i in range(-(-n_rows // R))]
    out = planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=-2)
    return out[..., :n_rows, :]


def choose_bits(codes: Array, axes: tuple[int, ...], pow2: bool = False) -> Array:
    """Per-block bit width: ceil(log2(max+1)), min 1; optionally rounded up
    to {1,2,4,8} so a kernel can lax.switch over four unpack variants."""
    mx = jnp.max(codes.astype(jnp.int32), axis=axes)
    b = jnp.ceil(jnp.log2(jnp.maximum(mx, 1).astype(jnp.float32) + 1.0)).astype(jnp.int32)
    b = jnp.maximum(b, 1)
    if pow2:
        b = jnp.int32(1) << jnp.ceil(jnp.log2(b.astype(jnp.float32))).astype(jnp.int32)
    return b


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AdaptivePacked:
    """Ragged container: per-block adaptive widths, deterministic offsets.

    payload : uint32 [capacity_words] — blocks packed back to back.
    offsets : int32 [n_blocks] — word offset of each block (exclusive cumsum
        of per-block word counts: the atomic-free Block Offsets Array).
    bits    : int32 [n_blocks] — width used by each block.
    nwords  : int32 [n_blocks] — words used by each block.
    """

    payload: Array
    offsets: Array
    bits: Array
    nwords: Array
    block_codes: int  # static: codes per block

    def tree_flatten(self):
        return (self.payload, self.offsets, self.bits, self.nwords), self.block_codes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, block_codes=aux)

    @property
    def payload_bits(self) -> Array:
        return jnp.sum(self.nwords) * 32

    @property
    def meta_bits(self) -> int:
        # u32 offset + u8 width per block.
        return int(self.offsets.shape[0]) * (32 + 8)


def pack_adaptive(codes: Array, capacity_words: int, pow2: bool = False) -> AdaptivePacked:
    """Pack [n_blocks, block_codes] codes with per-block adaptive widths.

    Strategy (vectorized, no data-dependent shapes): pack every block at each
    candidate width, then for each block scatter the words of its chosen
    width into the flat payload at its cumsum offset.
    """
    n_blocks, L = codes.shape
    widths = (1, 2, 4, 8) if pow2 else tuple(range(1, 9))
    bits = choose_bits(codes, axes=(1,), pow2=pow2)  # [n_blocks]
    per_block_words = (L * bits + 31) // 32
    offsets = jnp.cumsum(per_block_words) - per_block_words
    payload = jnp.zeros((capacity_words,), jnp.uint32)
    for b in widths:
        Wb = packed_words(L, b)
        pk = pack_bits(codes, b)  # [n_blocks, Wb]
        sel = (bits == b)
        # Scatter only selected blocks' words; unselected scatter to a dump slot.
        tgt = jnp.where(sel[:, None], offsets[:, None] + jnp.arange(Wb)[None, :], capacity_words)
        payload = payload.at[tgt.reshape(-1)].add(
            jnp.where(sel[:, None], pk, 0).reshape(-1), mode="drop"
        )
    return AdaptivePacked(
        payload=payload,
        offsets=offsets.astype(jnp.int32),
        bits=bits.astype(jnp.int32),
        nwords=per_block_words.astype(jnp.int32),
        block_codes=L,
    )


def unpack_adaptive(packed: AdaptivePacked) -> Array:
    """Inverse of pack_adaptive -> uint8 [n_blocks, block_codes]."""
    L = packed.block_codes
    n_blocks = packed.offsets.shape[0]
    widths = tuple(range(1, 9))
    out = jnp.zeros((n_blocks, L), jnp.uint8)
    for b in widths:
        Wb = packed_words(L, b)
        idx = packed.offsets[:, None] + jnp.arange(Wb)[None, :]
        idx = jnp.minimum(idx, packed.payload.shape[0] - 1)
        words = packed.payload[idx]  # [n_blocks, Wb]
        vals = unpack_bits(words, b, L)
        out = jnp.where((packed.bits == b)[:, None], vals, out)
    return out


# ---------------------------------------------------------------------------
# Numpy oracle (for kernel/property tests)
# ---------------------------------------------------------------------------


def pack_bits_np(codes: np.ndarray, bits: int) -> np.ndarray:
    codes = np.asarray(codes, np.uint32) & ((1 << bits) - 1)
    *lead, L = codes.shape
    W = packed_words(L, bits)
    out = np.zeros((*lead, W), np.uint32)
    flat_c = codes.reshape(-1, L)
    flat_o = out.reshape(-1, W)
    for j in range(L):
        pos = j * bits
        w, s = pos >> 5, pos & 31
        flat_o[:, w] |= (flat_c[:, j] << s) & 0xFFFFFFFF
        if s + bits > 32:
            flat_o[:, w + 1] |= flat_c[:, j] >> (32 - s)
    return out


def unpack_bits_np(words: np.ndarray, bits: int, n_codes: int) -> np.ndarray:
    words = np.asarray(words, np.uint64)
    *lead, W = words.shape
    flat_w = words.reshape(-1, W)
    out = np.zeros((flat_w.shape[0], n_codes), np.uint8)
    mask = (1 << bits) - 1
    for j in range(n_codes):
        pos = j * bits
        w, s = pos >> 5, pos & 31
        v = flat_w[:, w] >> s
        if s + bits > 32 and w + 1 < W:
            v |= flat_w[:, w + 1] << (32 - s)
        out[:, j] = (v & mask).astype(np.uint8)
    return out.reshape(*lead, n_codes)
