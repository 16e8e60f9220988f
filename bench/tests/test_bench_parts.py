"""The benchmark's parts on the CPU: the traffic generator, the tail
arithmetic, finding files by name, the roofline and MFU arithmetic, the
peaks table, and the reference against the program's own forward pass."""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import tinycell  # noqa: E402

from kvbench import measure, spec, stats, traffic  # noqa: E402

MIXES = ("longdoc_4k", "longdoc_32k", "chat")
LLAMA = spec.load_arch({"architecture": "llama_gqa"})


def _mix(name):
    path = tinycell.BENCH / "traffic" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_traffic_is_a_function_of_the_seed(mix):
    a = traffic.generate(_mix(mix), 64000, 2**31 + 5, 30)
    b = traffic.generate(_mix(mix), 64000, 2**31 + 5, 30)
    c = traffic.generate(_mix(mix), 64000, 2**31 + 6, 30)
    assert len(a.requests) == len(b.requests) == len(c.requests)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a.requests, b.requests))
    # Another seed: other tokens and order, the same multiset of sizes.
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a.requests, c.requests))

    def sizes(t):
        return sorted((len(r.prompt), r.max_new_tokens) for r in t.requests)

    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in c.requests)
    assert sorted(r.max_new_tokens for r in a.requests) == \
        sorted(r.max_new_tokens for r in c.requests)
    assert sizes(a) != [] and all(0 <= r.prompt.min() and r.prompt.max()
                                  < 64000 for r in a.requests)


def test_traffic_shapes_follow_the_mix():
    m = _mix("longdoc_4k")
    t = traffic.generate(m, 64000, 9, 30)
    assert t.loop == "closed" and t.clients == 64
    assert len(t.documents) == 8
    own = [len(r.prompt) - 3072 for r in t.requests]
    assert min(own) >= 64 and max(own) <= 192
    assert all(o % 16 == 0 for o in own)
    assert all(np.array_equal(r.prompt[:3072], t.documents[r.doc])
               for r in t.requests)
    assert all(256 <= r.max_new_tokens <= 768 for r in t.requests)
    chat = traffic.generate(_mix("chat"), 64000, 9, 30)
    dues = [r.due_s for r in chat.requests]
    rate = len(dues) / (max(dues) - min(dues))
    assert abs(rate / _mix("chat")["rate_per_s"] - 1) < 0.15


def test_generator_does_not_import_the_program():
    code = ("import sys; sys.path.insert(0, 'bench'); "
            "import kvbench.traffic; "
            "assert not any(m == 'repro' or m.startswith('repro.') "
            "for m in sys.modules), 'imported the program'")
    subprocess.run([sys.executable, "-c", code], cwd=tinycell.ROOT,
                   check=True, timeout=120)


def test_tail_is_over_all_samples():
    # 90 fast gaps in one request and 10 slow ones in another: the tail
    # lies among the slow ones, which a median of per-request tails hides.
    fast = np.cumsum(np.full(91, 0.010))
    slow = 100 + np.cumsum(np.full(11, 0.500))
    gaps = np.concatenate([stats.gaps_in(t, 0, 1e9) for t in (fast, slow)])
    assert len(gaps) == 100
    assert stats.percentile(gaps, 95) == pytest.approx(
        np.percentile(gaps, 95))
    assert stats.percentile(gaps, 95) > 0.4
    assert stats.gaps_in([1.0, 2.0, 3.0, 4.0], 1.5, 4.0).tolist() == [1.0, 1.0]
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_new_config_cell_and_metric_are_found_by_name(tmp_path):
    bench = tinycell.make_bench(tmp_path)
    (bench / "configs" / "tiny2.json").write_text(
        (bench / "configs" / "tiny.json").read_text())
    (bench / "metrics" / "answer_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny2", "source": "test",
                         "file": "bench/configs/tiny2.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "tiny2.chat", "config": "tiny2",
                           "traffic": "tiny_open", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "answer_share", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "itl_p95_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.load_cell("tiny2.chat", bench)
    assert c.config["name"] == "tiny" and c.traffic["loop"] == "open"
    names = [m["name"] for m in c.per_layer]
    # A metric without ``workloads`` is reported by every cell that
    # reports the end-to-end metric it moves.
    assert "answer_share" in names and "kv_bytes_per_tok" in names
    assert "prefix_hit_share" not in names
    assert spec.metric_reader("answer_share", bench)(None) == 42.0
    with pytest.raises(spec.SpecError):
        spec.load_cell("nope.chat", bench)


def test_every_metric_of_the_benchmark_has_a_reader():
    b = json.loads((tinycell.ROOT / "BENCHMARK.json").read_text())
    for m in b["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in b["workloads"]:
        c = spec.load_cell(w["name"])
        assert c.per_layer and any(m["name"] == "setup_s"
                                   for m in c.end_to_end)
        assert (tinycell.BENCH / "workloads" / f"{w['name']}.json").is_file()


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")


YI = LLAMA.Arch(n_layers=8, d_model=4096, n_heads=32, n_kv_heads=4,
                head_dim=128, d_ff=11008, vocab=64000, rope_theta=5e6,
                norm_eps=1e-5, window=None)


class _Ctx:
    """A context with hand-made records: two requests, each with a prompt
    of 3000 tokens, producing tokens 1 and 2 inside a 1 s window."""

    def __init__(self):
        class R:
            pass
        self.arch, self.chips, self.t_open, self.t_close = YI, 1, 10.0, 11.0
        self.cell = R()
        self.cell.architecture = LLAMA
        self.peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.kv = {"store_bytes_per_token": 656.0 * 8,
                   "raw_bytes_per_token": 2048.0 * 8, "block": 64}
        self.stats_open = {"prefill": {"prefill_tokens": 0, "chunks": 0}}
        self.stats_close = {"prefill": {"prefill_tokens": 64, "chunks": 1}}
        self.records = []
        for _ in range(2):
            r = R()
            r.req = R()
            r.req.prompt = np.zeros(3000, np.int32)
            r.result = R()
            r.result.token_times = (9.5, 10.2, 10.4)
            self.records.append(r)
        self.window_s = 1.0
        self.trace = None

    def decode_contexts(self):
        from kvbench.cell import Context
        return Context.decode_contexts(self)

    def counter(self, *path):
        from kvbench.cell import Context
        return Context.counter(self, *path)


def test_model_flops_by_hand():
    # per layer: 4096*(32+8)*128 + 32*128*4096 + 3*4096*11008 = 173,015,040
    assert YI.matmul_params() == 8 * 173_015_040 + 4096 * 64000
    assert YI.attn_flops_per_pair() == 4 * 32 * 128 * 8
    assert LLAMA.token_flops(YI, 10) == 2 * YI.matmul_params() + 10 * 131072


def test_mfu_and_roofline_by_hand():
    ctx = _Ctx()
    # tokens 1 and 2 of both requests are decoded in the window at
    # contexts 3001 and 3002
    assert sorted(ctx.decode_contexts().tolist()) == [3001, 3001, 3002, 3002]
    dec = 2 * (LLAMA.token_flops(YI, 3001) + LLAMA.token_flops(YI, 3002))
    head = 2 * 4096 * 64000
    pf = 64 * (2 * YI.matmul_params() - head) + head
    assert measure.window_flops(ctx) == pytest.approx(dec + pf)
    assert measure.step_mfu(ctx) == pytest.approx(
        100 * (dec + pf) / 197e12)
    flops, nbytes = measure.decode_attn_work(ctx)
    # context 3001 = 46 flushed blocks (2944 tokens) + 57 raw tokens
    row = lambda c: (c // 64 * 64 * 656 * 8 + c % 64 * 2048 * 8  # noqa
                     + 8 * 32 * 128 * 6)
    assert nbytes == pytest.approx(2 * (row(3001) + row(3002)))
    assert flops == pytest.approx(2 * (3001 + 3002) * 131072)

    class Tr:
        n_devices = 1

        @staticmethod
        def op_seconds(program=None, op=None):
            return 1e-3 if program == measure.DECODE_PROGRAM else 0.0

    ctx.trace = Tr()
    least = nbytes / 819e9
    assert measure.attn_roofline(ctx) == pytest.approx(100 * least / 1e-3)
    assert measure.attn_roofline(ctx) < 100


def test_reference_matches_the_program_forward():
    """The float32 reference against ``models/model.py``'s forward pass on
    the same float32 weights, tiny sizes, CPU."""
    import dataclasses

    import jax.numpy as jnp

    from repro.models import model as M

    cfg = json.loads((tinycell.BENCH / "configs" / "yi_6b.json").read_text())
    cfg["model"].update(hidden_size=64, intermediate_size=128,
                        num_attention_heads=4, num_key_value_heads=2,
                        num_hidden_layers=2, vocab_size=256, head_dim=16,
                        rope_theta=10000.0)
    arch = LLAMA.Arch.from_config(cfg)
    params = LLAMA.make_weights(arch, 7, dtype=jnp.float32)
    pcfg = dataclasses.replace(LLAMA.program_config(cfg), dtype="float32")
    toks = np.random.default_rng(0).integers(0, 256, 300).astype(np.int32)
    want = M.forward(params, pcfg, {"tokens": jnp.asarray(toks)[None]},
                     q_chunk=100, kv_chunk=100)[0][0]
    at = np.array([0, 5, 150, 299])
    got = LLAMA.logits_at(params, arch, toks, at)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[at],
                               rtol=2e-4, atol=2e-4)
    # with a window, and in the control precisions, it differs
    low = LLAMA.logits_at(params, arch, toks, at, "fp8")
    assert float(jnp.max(jnp.abs(low - got))) > 1e-3
    win = LLAMA.logits_at(params, dataclasses.replace(arch, window=16),
                          toks, at)
    assert float(jnp.max(jnp.abs(win[2:] - got[2:]))) > 1e-3
    np.testing.assert_allclose(np.asarray(win[:2]), np.asarray(got[:2]),
                               rtol=1e-5, atol=1e-5)


def test_reference_cache_semantics_by_hand():
    """Keys of complete blocks are read back through the quantiser; a
    query sees its own block exactly until the block completes."""
    import jax.numpy as jnp

    from kvbench import reference

    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 1, 4)),
                    jnp.float32)
    q = reference._quantize(x.reshape(2, 4, 1, 4), 0.05, 1).reshape(8, 1, 4)
    err = np.abs(np.asarray(q - x)).reshape(2, 4, 1, 4)
    rng = np.ptp(np.asarray(x).reshape(2, 4, 1, 4), axis=1, keepdims=True)
    # half a step, plus what storing min and step in bfloat16 adds
    assert (err <= 0.05 * rng * 0.6 + 0.02).all()
    assert err.max() > 0
    qq = jnp.asarray(np.random.default_rng(2).normal(size=(8, 2, 4)),
                     jnp.float32)
    exact = reference._attention(qq, x, x, None, None)
    cached = reference._attention(qq, x, x, None, (4, 0.05, 0.15))
    diff = np.abs(np.asarray(cached - exact)).max(axis=(1, 2))
    # positions 0-2 see only their incomplete first block: exact
    assert (diff[:3] == 0).all() and (diff[3:] > 0).all()
