"""The one general traffic generator. A mix is a data file
(`traffic/<mix>.json`); what belongs to a cell alone (the fixed rate, the
batch size) comes from the cell's file.

Serving mixes are open loops. Every seed gets the SAME set of prompt
lengths, output lengths and inter-arrival gaps (the quantiles of the
mix's distributions, so the set is the distribution itself, and the
offered work in a run never depends on the seed); the seed only orders
them and draws the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(spec, n):
    """n sizes: the (i + 1/2)/n quantiles of `spec`'s distribution."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "fixed":
        v = np.full(n, float(spec["value"]))
    elif spec["dist"] == "uniform":
        v = spec["min"] + (spec["max"] - spec["min"]) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def _gaps(arrival, rate, n):
    """n inter-arrival gaps with mean 1/rate: quantiles of the arrival
    process's gap distribution (exponential for Poisson, gamma with the
    given coefficient of variation for bursts)."""
    u = (np.arange(n) + 0.5) / n
    if arrival["process"] == "poisson":
        g = -np.log1p(-u)
    elif arrival["process"] == "gamma":
        # quantiles by sorting a large fixed sample: no scipy here
        k = 1.0 / float(arrival["cv"]) ** 2
        sample = np.sort(np.random.default_rng(0).gamma(k, 1.0 / k,
                                                        200 * n))
        g = sample[(u * len(sample)).astype(int)]
    elif arrival["process"] == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    return g / g.mean() / rate


def serve_requests(mix, rate, horizon_s, vocab, seed):
    """[(due_s, prompt token ids, max_new_tokens), ...] in due order, for
    `horizon_s` seconds of arrivals at `rate` requests a second."""
    n = max(1, int(math.floor(rate * horizon_s)))
    rng = np.random.default_rng([int(seed), 0x5E44E])
    p_len = rng.permutation(_quantiles(mix["prompt_len"], n))
    o_len = rng.permutation(_quantiles(mix["output_len"], n))
    due = np.cumsum(rng.permutation(_gaps(mix["arrival"], rate, n)))
    share = mix.get("shared_prefix")
    prefixes = None
    if share:
        # `documents` prefixes of `share["len"]` tokens, each request
        # opens with one of them (asked `n / documents` times each)
        prefixes = rng.integers(1, vocab,
                                (share["documents"], share["len"]))
    out = []
    for i in range(n):
        toks = rng.integers(1, vocab, int(p_len[i]))
        if prefixes is not None:
            pre = prefixes[i % len(prefixes)][:max(0, len(toks) - 1)]
            toks[:len(pre)] = pre
        out.append((float(due[i]), toks.astype(np.int32), int(o_len[i])))
    return out


def knee(sweep, served_share=0.95):
    """Highest swept rate the system sustained. `sweep` rows:
    {"rate", "arrived", "completed", "queue_mid", "queue_end"}; a rate is
    sustained when completed >= served_share * arrived and the wait
    queue at the end is no longer than at the middle."""
    ok = [r["rate"] for r in sweep
          if r["completed"] >= served_share * r["arrived"]
          and r["queue_end"] <= r["queue_mid"]]
    return max(ok) if ok else None


def fixed_rate(knee_rate, share=0.8, step=0.5):
    """`share` of the knee, rounded down to `step` requests a second."""
    return math.floor(knee_rate * share / step) * step
