"""Dry-run record builder smoke test.

The real driver compiles every (arch × shape) cell on the production meshes
— far too heavy for tier-1 — so this runs the same ``run_cell`` record
builder end to end for one tiny decode arch on the host mesh.  It pins the
regression where ``compiled.cost_analysis()`` returns a one-dict-per-device
LIST for donated-argument decode executables (the ``--arch yi_6b``
``decode_32k`` crash: ``'list' object has no attribute 'get'``).
"""

import json

import jax
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig

TINY = ModelConfig(name="tiny-dryrun", family="dense", n_layers=2, d_model=32,
                   vocab_size=64, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                   cache_block=8)


def test_dryrun_decode_record_builder_smoke(tmp_path, monkeypatch):
    jax.devices()  # init the backend BEFORE dryrun's import-time XLA_FLAGS set
    from repro.launch import dryrun
    from repro.models import registry

    monkeypatch.setattr(dryrun, "ARTIFACTS", tmp_path)
    monkeypatch.setattr(dryrun, "make_production_mesh",
                        lambda *, multi_pod=False: make_host_mesh())
    monkeypatch.setattr(registry, "get_config", lambda name: TINY)
    monkeypatch.setitem(dryrun.SHAPES, "decode_32k",
                        dict(kind="decode", seq=64, batch=2))

    rec = dryrun.run_cell("tiny-dryrun", "decode_32k", "pod",
                          analysis=False, force=True)
    assert rec["status"] == "ok", rec.get("error")
    # cost_raw is where list-returning cost_analysis() used to crash
    assert rec["cost_raw"]["flops"] >= 0.0
    assert rec["memory"]["argument_bytes"] > 0
    on_disk = json.loads(
        (tmp_path / "pod" / "tiny-dryrun__decode_32k.json").read_text())
    assert on_disk["status"] == "ok"


def test_cost_numbers_normalizes_list_and_dict():
    """cost_numbers reads what the installed jax's cost_analysis returns,
    for a donated-argument step like the decode steps it is used on, and
    reads absent keys as 0."""
    import jax
    import jax.numpy as jnp

    from repro.launch import dryrun

    class _C:
        def __init__(self, ca):
            self._ca = ca

        def cost_analysis(self):
            return self._ca

    d = {"flops": 3.0, "bytes accessed": 7.0}
    assert dryrun.cost_numbers(_C(d)) == {"flops": 3.0, "bytes": 7.0}
    assert dryrun.cost_numbers(_C({})) == {"flops": 0.0, "bytes": 0.0}
    step = jax.jit(lambda x: x * 2.0, donate_argnums=0)
    got = dryrun.cost_numbers(step.lower(jnp.ones(1024)).compile())
    assert got["flops"] > 0 and got["bytes"] > 0
