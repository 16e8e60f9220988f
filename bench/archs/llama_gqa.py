"""Llama-style dense GQA decoder: the architecture of configurations whose
file says ``"architecture": "llama_gqa"`` (yi_6b, ministral_8b).

Per layer: RMSNorm, Q/K/V projections, rotary embedding on (first half,
second half) pairs with ``theta ** (-i / (D/2))`` frequencies, causal
softmax attention (within ``sliding_window`` when the configuration has
one), output projection and residual; RMSNorm, SwiGLU MLP and residual.
Then the final RMSNorm and an untied output head.

The weights are the benchmark's, not the program's: they are made here on
the device, in the served dtype, in one jitted call, in the parameter tree
the program's dense family takes (``emb``/``ln_f``/``blocks`` with stacked
layers).  The program only receives them; the plain reference below reads
the same arrays, so it takes nothing the program made.

The reference runs one sequence at a time, layer by layer, with the shared
blocks of ``kvbench.reference`` (attention in blocks of query rows, the MLP
in blocks of tokens), so that a 32k-token sequence fits beside the weights
on one chip.  It imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from kvbench.model import _fold_seed
from kvbench.reference import (AT_PAD, PAD, PRECISIONS, _attention, _linear,
                               _rms, _rope)


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes of a dense GQA decoder, as a configuration file states
    them (Hugging Face ``config.json`` keys)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    window: int | None

    @classmethod
    def from_config(cls, config: dict) -> "Arch":
        m = config["model"]
        H = m["num_attention_heads"]
        return cls(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   n_heads=H, n_kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or m["hidden_size"] // H,
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   rope_theta=float(m["rope_theta"]),
                   norm_eps=float(m["rms_norm_eps"]),
                   window=m.get("sliding_window"))

    def matmul_params(self) -> int:
        """Weights that take part in a matrix product per token: every
        layer's projections and MLP, and the output head (the embedding
        lookup multiplies nothing)."""
        d, H, Hkv, Dh, F = (self.d_model, self.n_heads, self.n_kv_heads,
                            self.head_dim, self.d_ff)
        per_layer = d * (H + 2 * Hkv) * Dh + H * Dh * d + 3 * d * F
        return self.n_layers * per_layer + d * self.vocab

    def attn_flops_per_pair(self) -> int:
        """Operations of one (query, key) pair over all layers and heads:
        a dot product for the score and a multiply-add into the output."""
        return 4 * self.n_heads * self.head_dim * self.n_layers


# -- operation and byte counts ------------------------------------------------

def _attended(arch: Arch, context):
    """Keys a query attends at ``context`` cached tokens: every layer reads
    at most its window."""
    return context if arch.window is None else np.minimum(context,
                                                          arch.window)


def token_flops(arch: Arch, context: int) -> int:
    """Model operations of one token that attends ``context`` tokens."""
    return (2 * arch.matmul_params()
            + arch.attn_flops_per_pair() * int(_attended(arch, context)))


def prefill_flops(arch: Arch, tokens, chunks, attn_pairs):
    """Model operations of ``tokens`` prefilled prompt tokens in ``chunks``
    chunks (the output head runs once per chunk) with ``attn_pairs``
    (query, key) pairs of prefill attention, as the program counts them."""
    head = 2 * arch.d_model * arch.vocab
    return (tokens * (2 * arch.matmul_params() - head) + chunks * head
            + attn_pairs * arch.attn_flops_per_pair())


def decode_attn_work(arch: Arch, contexts: np.ndarray,
                     kv: dict) -> tuple[float, float]:
    """(operations, bytes) of the decode steps' attention, all layers, for
    one token at each of ``contexts``: the byte model of
    ``repro.launch.dryrun.decode_kv_traffic`` made exact per row.  Per row
    and layer it reads the compressed store of its flushed blocks (payload
    and scales, at the bytes per token ``kv`` gives from the arena's
    shapes) and the raw append buffer of its tail, both cut to the layer's
    window, its queries and its output; a dead grid step reads nothing
    that counts.  Its operations are a score and a weighted sum per (query
    head, attended token)."""
    T = kv["block"]
    c = np.asarray(contexts).astype(np.float64)
    n = _attended(arch, c)
    raw = np.minimum(c - np.floor(c / T) * T, n)
    per_layer_bytes = ((n - raw) * kv["store_bytes_per_token"]
                       + raw * kv["raw_bytes_per_token"]) / arch.n_layers
    # queries read (bf16) and outputs written (f32), per row and layer
    io = arch.n_heads * arch.head_dim * (2 + 4)
    nbytes = float(np.sum(per_layer_bytes + io)) * arch.n_layers
    flops = float(np.sum(n)) * arch.attn_flops_per_pair()
    return flops, nbytes


# -- the program's side -------------------------------------------------------

def program_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig

    a = Arch.from_config(config)
    s = config["serving"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=a.n_layers,
        d_model=a.d_model, vocab_size=a.vocab, n_heads=a.n_heads,
        n_kv_heads=a.n_kv_heads, head_dim=a.head_dim, d_ff=a.d_ff,
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        sliding_window=a.window, cache_layout=s["cache_layout"],
        cache_block=s["cache_block"], rel_scale_k=s["rel_scale_k"],
        rel_scale_v=s["rel_scale_v"], cache_mode=s["cache_mode"],
        attn_backend=s["attn_backend"], dtype=config["model"]["torch_dtype"])


def arena_bytes(server, config: dict) -> dict:
    """Bytes per cached token over all served layers, from the arena's
    array shapes: the compressed store (payload and scales) per page slot,
    and the raw append buffer per token."""
    kv = server.state["kv"]
    T = config["serving"]["cache_block"]
    store = sum(getattr(kv, f).nbytes for f in
                ("k_store", "k_min", "k_step", "v_store", "v_min", "v_step"))
    pages = kv.k_store.shape[-3]
    raw = kv.k_buf.nbytes + kv.v_buf.nbytes
    slots = config["serving"]["max_slots"]
    return {"store_bytes_per_token": store / (pages * T),
            "raw_bytes_per_token": raw / (slots * T), "block": T}


def make_weights(arch: Arch, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, on the device, in one program.
    Projections are N(0, 1/fan_in), the embedding N(0, 0.02^2), norm
    scales 1 + N(0, 0.1^2)."""
    d, H, Hkv, Dh, F, V, L = (arch.d_model, arch.n_heads, arch.n_kv_heads,
                              arch.head_dim, arch.d_ff, arch.vocab,
                              arch.n_layers)

    def init(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(dtype)

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dtype)

        blocks = {
            "attn": {"wq": normal((L, d, H, Dh), d ** -0.5),
                     "wk": normal((L, d, Hkv, Dh), d ** -0.5),
                     "wv": normal((L, d, Hkv, Dh), d ** -0.5),
                     "wo": normal((L, H, Dh, d), (H * Dh) ** -0.5)},
            "ln_attn": norm((L, d)),
            "mlp": {"w_gate": normal((L, d, F), d ** -0.5),
                    "w_up": normal((L, d, F), d ** -0.5),
                    "w_down": normal((L, F, d), F ** -0.5)},
            "ln_mlp": norm((L, d)),
        }
        return {"emb": {"embed": normal((V, d), 0.02),
                        "unembed": normal((d, V), d ** -0.5)},
                "ln_f": norm((d,)), "blocks": blocks}

    return jax.jit(init)(_fold_seed(seed))


# -- the plain reference ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("arch", "precision", "kv"))
def _layer(x, blocks, layer, arch: Arch, precision: str, kv):
    p = jax.tree.map(lambda a: a[layer], blocks)
    S, d = x.shape
    H, Hkv, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    pos = jnp.arange(S)
    h = _rms(x, p["ln_attn"], arch.norm_eps)
    a = p["attn"]
    q = _linear(h, a["wq"].reshape(d, H * D), precision).reshape(S, H, D)
    k = _linear(h, a["wk"].reshape(d, Hkv * D), precision).reshape(S, Hkv, D)
    v = _linear(h, a["wv"].reshape(d, Hkv * D), precision).reshape(S, Hkv, D)
    q = _rope(q, pos, arch.rope_theta)
    k = _rope(k, pos, arch.rope_theta)
    o = _attention(q, k, v, arch.window, kv).reshape(S, H * D)
    x = x + _linear(o, a["wo"].reshape(H * D, d), precision)

    m = p["mlp"]

    def mlp(xc):
        hc = _rms(xc, p["ln_mlp"], arch.norm_eps)
        g = _linear(hc, m["w_gate"], precision)
        u = _linear(hc, m["w_up"], precision)
        return xc + _linear(jax.nn.silu(g) * u, m["w_down"], precision)

    return jax.lax.map(mlp, x.reshape(S // PAD, PAD, d)).reshape(S, d)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _head(x, at, params, arch: Arch, precision: str):
    h = _rms(x[at], params["ln_f"], arch.norm_eps)
    return _linear(h, params["emb"]["unembed"], precision)


@functools.partial(jax.jit, static_argnames=("arch",))
def _embed(tokens, params, arch: Arch):
    return params["emb"]["embed"][tokens].astype(jnp.float32)


def logits_at(params, arch: Arch, tokens: np.ndarray, at: np.ndarray,
              precision: str = "f32", kv: tuple | None = None) -> jax.Array:
    """Float32 logits [len(at), vocab] of ``tokens`` at positions ``at``.
    ``kv`` = ``(block, rel_scale_k, rel_scale_v)`` applies the
    configuration's quantised KV cache (None: exact keys and values);
    ``precision`` selects a control (``kvbench.reference.PRECISIONS``)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    S = len(tokens)
    padded = -(-S // PAD) * PAD
    buf = np.zeros(padded, np.int32)
    buf[:S] = tokens
    x = _embed(jnp.asarray(buf), params, arch)
    for layer in range(arch.n_layers):
        x = _layer(x, params["blocks"], jnp.int32(layer), arch, precision,
                   kv)
    n = len(at)
    at_buf = np.zeros(-(-n // AT_PAD) * AT_PAD, np.int32)
    at_buf[:n] = at
    return _head(x, jnp.asarray(at_buf), params, arch, precision)[:n]
