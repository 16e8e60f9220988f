"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.

Mesh creation goes through ``repro.distributed.sharding.make_mesh``, which
pins every axis to the automatic partitioner.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model); the pod axis is the
    DCN-connected outermost axis (pure DP + compressed grad all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(max_devices: int | None = None):
    """Elastic small mesh over whatever devices exist (tests, local runs)."""
    n = len(jax.devices()) if max_devices is None else min(max_devices, len(jax.devices()))
    # favor a model axis that divides n
    for m in (8, 4, 2, 1):
        if n % m == 0:
            return make_mesh((n // m, m), ("data", "model"))
    raise RuntimeError("no devices")


def make_serve_mesh(spec: str | None):
    """Build the serving mesh from a ``--mesh dp,tp`` CLI spec.

    ``dp`` shards decode slots / page tables / the paged arena's page axis
    ("data"); ``tp`` shards KV heads inside attention ("model").  Returns
    None for an empty spec or a 1x1 mesh — a single-device mesh serves
    identically to the unsharded path, so the Server treats them the same
    (DESIGN.md §12).  Raises with the ``XLA_FLAGS`` recipe when the host
    exposes fewer devices than ``dp * tp`` asks for.
    """
    if not spec:
        return None
    try:
        dp, tp = (int(p) for p in spec.split(","))
    except ValueError:
        raise ValueError(
            f"--mesh wants 'dp,tp' (two integers), got {spec!r}") from None
    if dp < 1 or tp < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {spec!r}")
    if dp * tp == 1:
        return None
    devs = jax.devices()
    if dp * tp > len(devs):
        raise RuntimeError(
            f"--mesh {spec} needs {dp * tp} devices but only {len(devs)} "
            "exist; on CPU export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={dp * tp} "
            "before starting python (it must precede jax initialization)")
    return jax.sharding.Mesh(
        np.asarray(devs[: dp * tp]).reshape(dp, tp), ("data", "model"))
