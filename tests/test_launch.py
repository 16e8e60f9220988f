"""The serving launcher: model sizing, compile-cache placement, exit status."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import pool as blockpool
from repro.launch import compile_cache
from repro.launch import serve as launch
from repro.models import model as M
from repro.models import registry


def test_published_config_cuts_depth_only():
    full = registry.get_config("yi_6b")
    cfg = launch.model_config("yi_6b", "published")
    assert cfg.n_layers == 8
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "cache_block", "dtype"):
        assert getattr(cfg, f) == getattr(full, f), f
    assert "depth cut 32 -> 8 layers" in launch.describe_cut("yi_6b", cfg)
    assert launch.model_config("yi_6b") == registry.get_smoke_config("yi_6b")


def test_params_initialise_in_config_dtype():
    cfg = launch.model_config("yi_6b")
    shapes = jax.eval_shape(lambda: launch.init_params(cfg))
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {jnp.dtype(cfg.dtype)}


def test_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
        # Set outside: used as given, nothing else set in code.
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_serve_exits_nonzero_when_a_request_fails(capsys, monkeypatch, tmp_path):
    """Pool pressure with the victim sweep forced empty and no requeue
    budget fails a request; the launcher must not exit 0 over it."""
    # Set from outside, so the launcher leaves this process's cache alone.
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    cfg = launch.model_config("yi_6b")
    page = sum(blockpool.page_nbytes(s, cfg.n_kv_heads, cfg.resolved_head_dim)
               for s in M.cache_specs(cfg, 128))
    argv = ["--requests", "4", "--prompt-len", "11", "--new-tokens", "40",
            "--max-slots", "2", "--max-seq", "128", "--cache-mode", "paged",
            "--pool-bytes", str(6 * page), "--fault", "reclaim_sweep",
            "--max-requeues", "0"]
    with pytest.raises(SystemExit) as exc:
        launch.main(argv)
    assert exc.value.code == 1
    assert "failed: pool exhausted" in capsys.readouterr().err


def test_shard_scaling_refuses_a_chip(monkeypatch):
    """The CPU-only device-count simulation must not measure a TPU under a
    1/2/4-device label."""
    import argparse

    from benchmarks import shard_scaling

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="chip_smoke.py --chips 4"):
        shard_scaling.child_main(argparse.Namespace(child=1))
