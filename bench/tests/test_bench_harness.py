"""End-to-end runs of the harness on tiny cells on the CPU: the result line,
the refusals, and ``correct`` coming out false when the timed path is
broken underneath or the lower-precision control takes its place."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import tinycell  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "check"}
# The tiny cells' limit on the mean logit gap.  Sound CPU runs of the tiny
# longdoc cell read 0.0007-0.016 over 8 seeds (bfloat16 activations, and
# cache codes that flip where bfloat16 rounding crosses a quantisation
# step); its fp8 control reads 0.031-0.090, a token altered 2.4-2.8, a
# decode step that keeps its state 0.19-0.39.
TINY_LIMIT = 0.025
SOUND_SEEDS = {"tiny.longdoc": 3, "tiny.chat": 1}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tinycell.make_bench(tmp_path_factory.mktemp("tiny"), TINY_LIMIT)


@pytest.mark.parametrize("workload", tinycell.CELLS)
def test_line_has_the_contract_keys_and_is_correct(bench, workload):
    line = tinycell.run_cell(bench, workload, seed=SOUND_SEEDS[workload],
                             seconds=2.0)
    assert set(line) == CONTRACT_KEYS
    assert list(line)[-1] == "check"
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    names = {m for m in line["metrics"]}
    want = {"itl_p95_ms", "setup_s"} | (
        {"out_tok_s"} if workload.endswith("longdoc") else {"ttft_p95_ms"})
    assert names == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line, allow_nan=False)


def _break(monkeypatch, how):
    from repro.models import model as M

    orig = M.decode_step

    def altered(params, cfg, tokens, position, state, **kw):
        logits, st = orig(params, cfg, tokens, position, state, **kw)
        if how == "token":      # a token altered where it is produced
            import jax.numpy as jnp
            return jnp.roll(logits, 1, axis=-1), st
        return logits, state    # a step that returns its state unchanged

    monkeypatch.setattr(M, "decode_step", altered)


@pytest.mark.parametrize("how", ["token", "state"])
def test_broken_decode_is_not_correct(bench, monkeypatch, how):
    _break(monkeypatch, how)
    line = tinycell.run_cell(bench, "tiny.longdoc", seed=31)
    gap = line["check"]["mean_logit_gap"]["value"]
    assert line["correct"] is False and gap > TINY_LIMIT, gap


def test_lower_precision_control_is_not_correct(bench):
    """The reference computed in fp8, put in the program's place: at the
    positions of a fixed batch that the program served to the end, the
    tokens it ranks first read a gap over the limit on every seed."""
    from kvbench import cell as cell_mod
    from kvbench import check
    from kvbench import spec as spec_mod

    c = spec_mod.load_cell("tiny.longdoc", bench)
    kv = check.kv_of(c.config)
    gaps = []
    for seed in (1, 2, 3):
        r = cell_mod.Runner(c, seed, 1.0, False, 0.0, require_tpu=False,
                            peaks=tinycell.CPU_PEAKS)
        r.setup()
        recs = [r._submit(q, 0.0) for q in r.traffic.requests[:4]]
        cell_mod.drain(r.server, [x.handle for x in recs])
        r.collect()
        r.free()
        picks = check.sample(r.records, 3, seed)
        ref = c.architecture.logits_at
        sound = check.served_gaps(ref, r.params, r.arch, picks, kv).mean()
        assert sound <= TINY_LIMIT
        gaps.append(float(check.control_gaps(ref, r.params, r.arch, picks,
                                             "fp8", kv).mean()))
    assert min(gaps) > TINY_LIMIT, gaps


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi_6b.longdoc",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_machine_without_a_tpu():
    p = _run_script(tinycell.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_a_tree_without_the_program(tmp_path):
    shutil.copytree(tinycell.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(tinycell.ROOT / "BENCHMARK.json", tmp_path)
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
