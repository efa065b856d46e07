"""The one traffic generator. A mix is a data file of parameters
(``perfbench/mixes/<traffic>.json``); this module turns it and the seed
into requests or batches.

Every seed gets the SAME set of sizes and arrival gaps (the quantiles of
the mix's distributions), so the seed never changes how much work a
window holds. Their order comes from the seed, unless the mix fixes it
with a ``schedule_seed``: then every seed replays one schedule of sizes
and arrivals and changes only the token ids (and the weights). A mix whose
window holds a few dozen long requests needs that: chat-open's six seeded
orders read a 90th percentile of 4.2 to 11.3 s (PERF.md, section 4)."""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_lengths(dist, n):
    """``n`` integer lengths: the distribution's quantiles at the
    midpoints (i + 0.5) / n, clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + np.floor(u * (dist["max"] - dist["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def rng_of(seed, stream):
    return np.random.default_rng([int(seed) % (1 << 62), stream])


def prompt_ids(vocab, seed, index, length):
    """Uniform ids over the vocabulary for request ``index``."""
    return rng_of(seed, 1000 + index).integers(
        0, vocab, int(length)).astype(np.int64)


def _order_seed(mix, seed):
    return mix.get("schedule_seed", seed)


def _requests(mix, vocab, seed, n, first=0, salt=0):
    order = rng_of(_order_seed(mix, seed), 1 + 100 * salt)
    plen = order.permutation(quantile_lengths(mix["prompt_len"], n))
    olen = order.permutation(quantile_lengths(mix["output_len"], n))
    return [{"index": first + i,
             "prompt": prompt_ids(vocab, seed, first + i, plen[i]),
             "max_new_tokens": int(olen[i])} for i in range(n)]


def open_poisson(mix, vocab, seed, seconds):
    """Requests with due times (seconds from the window's opening):
    Poisson arrivals at ``rate_per_s``, the gaps being the exponential
    distribution's quantiles in a seeded order."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng_of(_order_seed(mix, seed), 3).permutation(
        -np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    reqs = _requests(mix, vocab, seed, n)
    for r, t in zip(reqs, due):
        r["due"] = float(t)
    return [r for r in reqs if r["due"] < seconds]


def closed_clients(mix, vocab, seed):
    """The queue the clients draw from: passes over one fixed set of
    ``set_size`` requests, each pass in a new seeded order."""
    size, passes = int(mix["set_size"]), int(mix["passes"])
    out = []
    for p in range(passes):
        out += _requests(mix, vocab, seed, size, first=p * size, salt=1 + p)
    return out


def train_corpus(mix, vocab, seed):
    """[corpus_rows, seq_len + 1] int32 ids, every row different."""
    return rng_of(seed, 4).integers(
        0, vocab, (int(mix["corpus_rows"]), int(mix["seq_len"]) + 1),
        dtype=np.int32)
