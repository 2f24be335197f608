"""The one generator of every traffic mix and training job.

A mix is a data file, ``bench/traffic/<name>.json``, of parameters that
the functions below read: lengths, rates, slots, batch and sequence.  A new
mix is a new data file; nothing here names one.

Sizes and arrival gaps are the mid-quantiles of the stated
distributions, so every seed offers the same set of requests and the same
set of gaps: the seed draws their order and the token ids.  A run's load
is then the same on every seed, while no single schedule is the one a
change could be tuned to.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    """The parameters of traffic mix ``name``."""
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator for ``seed`` (any integer) and a sub-stream."""
    return np.random.default_rng([seed % 2**64, stream])


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the mid-quantiles ``(i + 0.5) / n`` of the
    distribution ``spec`` describes, truncated to [min, max]:

    * ``{"dist": "uniform", "min": a, "max": b}``
    * ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
      (redrawn to [a, b], i.e. the lognormal conditioned on that range).
    """
    lo, hi = int(spec["min"]), int(spec["max"])
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        out = lo + np.floor(u * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
        f_lo, f_hi = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
        out = np.array([math.exp(nd.inv_cdf(f_lo + x * (f_hi - f_lo)))
                        for x in u])
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.round(out), lo, hi).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps (seconds) at the mid-quantiles of the
    exponential distribution with mean ``1 / rate``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Arrival:
    """One request of an open-loop schedule."""

    uid: int
    due: float  # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def serving_schedule(mix: dict, seed: int, seconds: float,
                     vocab: int) -> list[Arrival]:
    """The open-loop schedule of one run: ``rate_per_s * seconds`` requests
    due inside ``[0, seconds)``, their sizes and gaps in an order drawn
    from ``seed``, with token ids from ``seed``."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = rng_of(seed, 3)
    gaps = rng.permutation(exp_gaps(mix["rate_per_s"], n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    # the gaps sum to about ``seconds``; squeeze the few that spill over
    due = due * min(1.0, 0.999 * seconds / max(due[-1], 1e-9))
    prompts = rng.permutation(quantile_sizes(mix["prompt"], n))
    outs = rng.permutation(quantile_sizes(mix["output"], n))
    toks = rng_of(seed, 1)
    return [Arrival(uid=i, due=float(due[i]),
                    prompt=toks.integers(0, vocab, int(prompts[i]),
                                         dtype=np.int32),
                    max_new_tokens=int(outs[i]))
            for i in range(n)]


def zipf_text(rng: np.random.Generator, n_tokens: int, vocab: int, *,
              alpha: float = 1.2, copy_prob: float = 0.12,
              copy_span: int = 32) -> np.ndarray:
    """Zipfian unigram stream with stochastic span copying (a copy of the
    program's ``data.synthetic.zipf_text``, drawing from ``rng``)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** -alpha
    probs /= probs.sum()
    toks = rng.choice(vocab, size=n_tokens, p=probs).astype(np.int32)
    n_copies = int(n_tokens * copy_prob / copy_span)
    for _ in range(n_copies):
        if n_tokens < 4 * copy_span:
            break
        src = rng.integers(0, n_tokens - 2 * copy_span)
        dst = rng.integers(src + copy_span, n_tokens - copy_span)
        toks[dst: dst + copy_span] = toks[src: src + copy_span]
    return toks


def train_batches(mix: dict, seed: int, vocab: int) -> list[dict]:
    """``distinct_batches`` next-token batches of Zipf text, every row
    different; the window cycles through them."""
    b, n = mix["batch"], mix["seq"]
    data = mix.get("data", {})
    out = []
    for i in range(mix["distinct_batches"]):
        toks = zipf_text(rng_of(seed, 100 + i), b * (n + 1), vocab,
                         alpha=data.get("alpha", 1.2),
                         copy_prob=data.get("copy_prob", 0.12),
                         copy_span=data.get("copy_span", 32))
        toks = toks.reshape(b, n + 1)
        out.append({"inputs": toks[:, :-1].copy(),
                    "targets": toks[:, 1:].copy()})
    return out
