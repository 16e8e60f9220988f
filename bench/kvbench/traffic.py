"""The one traffic generator: turns a traffic mix's parameters
(``bench/traffic/<name>.json``) and ``--seed`` into requests.

The program receives only what this returns.  Every seed gets the same
multiset of sizes and, in an open loop, the same multiset of inter-arrival
gaps -- stratified quantiles of each distribution -- in an order drawn from
the seed, with token ids drawn from the seed.  So two seeds do the same
amount of work in another order, and run-to-run spread measures the system
rather than the draw.

Parameters a mix may give (lengths in tokens):

* ``loop``: ``"closed"`` (``clients`` each send their next request when the
  last completes) or ``"open"`` (arrivals on a schedule, ``arrival``
  ``"poisson"`` at ``rate_per_s``, starting ``warm_s`` before the window).
* ``documents``: ``{"count", "tokens"}`` shared prefixes; each request
  starts with one, drawn evenly.
* ``prompt_tokens`` (after the document, if any) and ``output_tokens``:
  ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}``; ``quantum`` rounds a length to a
  multiple of it.
* ``requests_per_client`` (closed) -- how many requests each client has.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True, eq=False)
class Req:
    prompt: np.ndarray      # int32 [n], document + own tokens
    max_new_tokens: int
    doc: int                # index of its document, -1 without one
    due_s: float = 0.0      # open loop: due time relative to window open
    client: int = -1        # closed loop: the client that sends it


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str
    documents: tuple        # int32 arrays
    requests: tuple         # Req, in send order (per client when closed)
    clients: int
    warm_s: float


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream.encode()])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: the distribution's stratified quantiles, clipped,
    rounded to ``quantum``, in an order drawn from ``rng``."""
    q = _quantiles(n)
    kind = dist["dist"]
    if kind == "uniform":
        vals = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in q])
        vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    quantum = int(dist.get("quantum", 1))
    vals = np.round(vals / quantum) * quantum
    vals = np.clip(vals, dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(vals)


def poisson_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate``/s: stratified
    quantiles in an order drawn from ``rng``."""
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    """Requests for one run of ``seconds`` measured seconds."""
    tok = _rng(seed, "tokens")
    docs = ()
    if "documents" in mix:
        d = mix["documents"]
        docs = tuple(tok.integers(0, vocab, d["tokens"], dtype=np.int32)
                     for _ in range(d["count"]))
    loop = mix["loop"]
    if loop == "closed":
        clients = int(mix["clients"])
        n = clients * int(mix["requests_per_client"])
        warm_s = 0.0
    elif loop == "open":
        if mix.get("arrival") != "poisson":
            raise ValueError(f"unknown arrival process {mix.get('arrival')!r}")
        clients = 0
        warm_s = float(mix.get("warm_s", 0.0))
        n = math.ceil(mix["rate_per_s"] * (warm_s + seconds))
    else:
        raise ValueError(f"unknown loop {loop!r}")
    own = lengths(mix["prompt_tokens"], n, _rng(seed, "prompt_tokens"))
    out = lengths(mix["output_tokens"], n, _rng(seed, "output_tokens"))
    which = (_rng(seed, "documents").permutation(np.arange(n) % len(docs))
             if docs else np.full(n, -1))
    if loop == "open":
        due = np.cumsum(poisson_gaps(mix["rate_per_s"], n,
                                     _rng(seed, "arrivals"))) - warm_s
    reqs = []
    for i in range(n):
        body = tok.integers(0, vocab, int(own[i]), dtype=np.int32)
        prompt = (np.concatenate([docs[which[i]], body]) if which[i] >= 0
                  else body)
        reqs.append(Req(prompt=prompt, max_new_tokens=int(out[i]),
                        doc=int(which[i]),
                        due_s=float(due[i]) if loop == "open" else 0.0,
                        client=i % clients if loop == "closed" else -1))
    return Traffic(loop=loop, documents=docs, requests=tuple(reqs),
                   clients=clients, warm_s=warm_s)
