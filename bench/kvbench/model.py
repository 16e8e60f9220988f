"""The benchmark's side of the model: the sizes from a configuration file,
the weights it makes from ``--seed``, and the operation counts of a token.

The weights are the benchmark's, not the program's: they are made here on
the device, in the served dtype, in one jitted call, in the parameter tree
the program's dense family takes (``emb``/``ln_f``/``blocks`` with stacked
layers).  The program only receives them; the plain reference
(``kvbench.reference``) reads the same arrays, so it takes nothing the
program made.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


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


def token_flops(arch: Arch, context: int) -> int:
    """Model operations of one token that attends ``context`` tokens."""
    return 2 * arch.matmul_params() + arch.attn_flops_per_pair() * context


def _fold_seed(seed: int):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


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
