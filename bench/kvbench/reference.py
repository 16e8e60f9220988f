"""Shared blocks of the plain references: float32 ``jax.numpy`` with no
cache, no kernels and no batching.  Each architecture in ``bench/archs/``
builds its reference (``logits_at``) from these; none imports the program.

``_rms`` is RMSNorm; ``_rope`` the rotary embedding on (first half, second
half) pairs with ``theta ** (-i / (D/2))`` frequencies; ``_attention``
causal softmax attention (within a window when one is given) in blocks of
query rows, reading the configuration's quantised KV cache when ``kv`` is
given (``_kv_cache``, ``_quantize``).  A reference runs one sequence at a
time, layer by layer, padded to a multiple of ``PAD`` tokens, so that a
32k-token sequence fits beside the weights on one chip.  Every product runs
at ``Precision.HIGHEST`` (on a TPU a float32 product is otherwise computed
in bfloat16 passes).

``precision`` selects the control of the correctness check instead:
``"int8"`` quantises both operands of every product ``_linear`` makes to
symmetric int8 (per token for activations, per output channel for weights)
before the product; ``"fp8"`` rounds them to float8 e4m3 under the same
scales.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "int8", "fp8")
Q_BLOCK = 256   # query rows per attention block
PAD = 512       # sequences are padded to a multiple of this, and the MLP
                # runs in blocks of this many tokens (one compiled program
                # per padded length)
AT_PAD = 256    # scored positions are padded to a multiple of this


def _fake_quant(x, axis, precision):
    """Round ``x`` to the control's grid along the scale ``axis``."""
    if precision == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if precision == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, precision):
    """x [N, K] f32 @ w [K, M] (any float dtype) in float32."""
    w = w.astype(jnp.float32)
    x = _fake_quant(x, -1, precision)
    w = _fake_quant(w, 0, precision)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, D]; pos [S]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _quantize(x, rel_scale, axis):
    """The configuration's error-bounded quantiser: over each unit (the
    ``axis`` of ``x``), ``step = rel_scale * (max - min)``, codes
    ``round((x - min) / step)``, read back as ``min + code * step`` with
    ``min`` and ``step`` stored in bfloat16."""
    mn = jnp.min(x, axis=axis, keepdims=True)
    step = rel_scale * (jnp.max(x, axis=axis, keepdims=True) - mn)
    code = jnp.round((x - mn) / jnp.where(step > 0, step, 1.0))
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    return bf(mn) + code * bf(step)


def _kv_cache(k, v, kv):
    """K quantised per (block of ``block`` tokens, head, channel), V per
    (token, head): the stored form of every token in a complete block."""
    if kv is None:
        return k, v
    block, rel_k, rel_v = kv
    S, Hkv, D = k.shape
    kq = _quantize(k.reshape(S // block, block, Hkv, D), rel_k, 1)
    return kq.reshape(S, Hkv, D), _quantize(v, rel_v, -1)


def _attention(q, k, v, window, kv):
    """q [S, H, D], k/v [S, Hkv, D] -> [S, H, D], causal, in query blocks.
    With ``kv``, a query reads the stored (quantised) form of every key in
    a block that is complete at its position, and the rest exactly."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    nq = min(Q_BLOCK, S)
    qb = q.reshape(S // nq, nq, Hkv, G, D)
    kpos = jnp.arange(S)
    kq, vq = _kv_cache(k, v, kv)

    def block(args):
        qc, i = args
        qpos = i * nq + jnp.arange(nq)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        stored = jnp.zeros_like(ok)
        if kv is not None:
            blk = kv[0]
            stored = kpos[None, :] // blk < (qpos[:, None] + 1) // blk
        s_raw = jnp.einsum("qhgd,khd->hgqk", qc, k, precision=HIGHEST)
        s_q = jnp.einsum("qhgd,khd->hgqk", qc, kq, precision=HIGHEST)
        s = jnp.where(stored, s_q, s_raw) / np.sqrt(D)
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p_q = jnp.where(stored, p, 0.0)
        return (jnp.einsum("hgqk,khd->qhgd", p - p_q, v, precision=HIGHEST)
                + jnp.einsum("hgqk,khd->qhgd", p_q, vq, precision=HIGHEST))

    out = jax.lax.map(block, (qb, jnp.arange(S // nq)))
    return out.reshape(S, H, D)
