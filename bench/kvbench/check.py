"""Whether what the timed path served is correct.

After the window, a sample of the requests it served -- drawn from the
seed, the one with the longest sequence always in it -- is run through the
plain float32 reference (the architecture's ``logits_at``) once per
request, over its prompt and its served tokens, with the KV cache the
configuration states (keys and values of complete blocks read back through
its error-bounded quantiser, the rest exact).  At every served position
the gap is the reference's best logit less the reference logit of the
served token: 0 where the server picked the reference's argmax.

The number compared is the mean gap over all served tokens of the sample.
The widest gap was the first choice, and it does not separate: sound runs
of the program read up to 0.4 on yi_6b (bfloat16 activations move keys
across a quantisation step now and then, and the step is 5% of a block's
range), while the fp8 control reads 1.1-1.3, under three times that.  The
mean counts how often and by how much the served token leaves the
reference's argmax, and separates the two by far more (PERF.md).

The limit and the sample size are the cell's, in
``bench/workloads/<cell>.json`` under ``check``; the readings they were
set from sit beside them.  The sample prefers requests that finished in
the window and is topped up with the served tokens of requests still
running at its close.
"""

from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Verdict:
    correct: bool
    numbers: dict   # name -> {"value", "limit"}


def check_params(cell) -> dict:
    path = cell.bench_dir / "workloads" / f"{cell.name}.json"
    return json.loads(path.read_text())["check"]


def sample(records, n: int, seed: int) -> list:
    """``n`` served requests: the longest sequence, then a draw from the
    seed among finished ones, then among those cut at the window's close."""
    served = [r for r in records if len(r.result.tokens)]
    if not served:
        return []
    size = lambda r: len(r.req.prompt) + len(r.result.tokens)  # noqa: E731
    longest = max(served, key=size)
    rng = np.random.default_rng([int(seed) % 2**63, 0x636865636B])
    done = [r for r in served if r is not longest
            and r.result.finish_reason in ("eos", "length")]
    cut = [r for r in served if r is not longest and r not in done]
    picks = [longest]
    for pool in (done, cut):
        order = rng.permutation(len(pool))
        picks += [pool[i] for i in order[:n - len(picks)]]
    return picks


def served_positions(rec):
    """(tokens fed to the reference, positions scored, served tokens)."""
    toks = np.asarray(rec.result.tokens, np.int32)
    seq = np.concatenate([np.asarray(rec.req.prompt, np.int32), toks[:-1]])
    at = len(rec.req.prompt) - 1 + np.arange(len(toks))
    return seq, at, toks


def gaps(ref_logits, tokens) -> np.ndarray:
    """Per position: the reference's best logit minus that of ``tokens``."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[:, None],
                              axis=-1)[:, 0]
    return np.asarray(best - got)


def kv_of(config: dict) -> tuple | None:
    """The cache semantics the configuration states, for the reference:
    ``(block, rel_scale_k, rel_scale_v)`` for the error-bounded ``packed``
    layout, None for ``raw``."""
    s = config["serving"]
    if s["cache_layout"] == "raw":
        return None
    if s["cache_layout"] != "packed":
        raise ValueError(f"the reference has no {s['cache_layout']!r} cache")
    return s["cache_block"], s["rel_scale_k"], s["rel_scale_v"]


def served_gaps(logits_at, params, arch, picks, kv) -> np.ndarray:
    """The reference gap of every served token of ``picks``;
    ``logits_at`` is the architecture's reference."""
    out = [np.zeros(0)]
    for rec in picks:
        seq, at, toks = served_positions(rec)
        out.append(gaps(logits_at(params, arch, seq, at, kv=kv), toks))
    return np.concatenate(out)


def control_gaps(logits_at, params, arch, picks, precision: str,
                 kv) -> np.ndarray:
    """The control: the reference computed in ``precision`` put in the
    program's place.  At every served position of ``picks``, the reference
    gap of the token the lower precision ranks first."""
    out = [np.zeros(0)]
    for rec in picks:
        seq, at, _ = served_positions(rec)
        ref = logits_at(params, arch, seq, at, kv=kv)
        low = logits_at(params, arch, seq, at, precision, kv=kv)
        out.append(gaps(ref, np.asarray(jnp.argmax(low, axis=-1))))
    return np.concatenate(out)


def check(cell, arch, params, records, seed: int, failed: int) -> Verdict:
    p = check_params(cell)
    picks = sample(records, p["requests"], seed)
    g = served_gaps(cell.architecture.logits_at, params, arch, picks,
                    kv_of(cell.config))
    mean = float(g.mean()) if g.size else None
    numbers = {
        "mean_logit_gap": {"value": mean, "limit": p["mean_logit_gap"]},
        "tokens_checked": {"value": int(g.size), "limit": 1},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = (g.size >= 1 and mean <= p["mean_logit_gap"] and failed == 0)
    return Verdict(correct=bool(correct), numbers=numbers)
