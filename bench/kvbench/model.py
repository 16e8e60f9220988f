"""What the architectures' weight makers share.  Each architecture in
``bench/archs/`` makes its own weights from ``--seed`` (``make_weights``),
on the device, in the served dtype, in one jitted call, from the key that
``_fold_seed`` gives."""

from __future__ import annotations

import jax


def _fold_seed(seed: int):
    """A key from a seed of any size up to 63 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
