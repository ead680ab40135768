"""Requests and arrival times from a seed.

One general generator reads a cell's ``traffic`` parameters; the program
receives only the generated requests.  Every seed gets the SAME multiset
of prompt lengths, output lengths and arrival gaps (quantile draws of the
stated distributions), in one fixed shuffled order
rotated to a starting point the seed chooses, with token ids of the
seed's own.  Runs with different seeds then do the same work and see
the same neighbours meet, from another point of the cycle on.  (With an
independent shuffle for each seed the 90th percentile of 42 first-token
times moved by 4% from seed to seed and by under 1% between two runs of
one seed: the order was changing the result, not the system.)
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

_NORMAL = NormalDist()


def _quantile_draws(n: int) -> list:
    """The n mid-quantiles of the unit interval."""
    return [(i + 0.5) / n for i in range(n)]


def lognormal_lengths(n: int, spec: dict) -> list:
    """n whole lengths at the quantiles of a log-normal with the given
    median and sigma, clipped to [lo, hi]."""
    mu = math.log(spec["median"])
    out = []
    for u in _quantile_draws(n):
        x = math.exp(mu + spec["sigma"] * _NORMAL.inv_cdf(u))
        out.append(int(min(max(round(x), spec["lo"]), spec["hi"])))
    return out


def exponential_gaps(n: int, total: float) -> list:
    """n gaps at the quantiles of an exponential, scaled to sum to
    `total`: a Poisson process with exactly n arrivals in the window."""
    raw = [-math.log(1.0 - u) for u in _quantile_draws(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def _rotated(n: int, traffic: dict, seed: int, total_s=None):
    """(prompt lengths, output lengths, gaps): the mix's fixed order,
    rotated by the seed."""
    order = random.Random(0)
    columns = [lognormal_lengths(n, traffic["prompt_len"]),
               lognormal_lengths(n, traffic["max_tokens"]),
               exponential_gaps(n, total_s) if total_s else [0.0] * n]
    for col in columns:
        order.shuffle(col)
    k = random.Random(seed).randrange(n)
    return [col[k:] + col[:k] for col in columns]


def _requests(prompts, outs, traffic, vocab_size, seed):
    rng = random.Random(seed + 1)
    limit = traffic["max_total_tokens"]
    return [{"prompt": [rng.randrange(vocab_size) for _ in range(p)],
             "max_tokens": min(m, limit - p)} for p, m in zip(prompts, outs)]


def make_requests(n: int, traffic: dict, vocab_size: int, seed: int) -> list:
    """n requests: ``{"prompt": [ids], "max_tokens": m}``, for a closed
    loop's clients to take in turn."""
    prompts, outs, _ = _rotated(n, traffic, seed)
    return _requests(prompts, outs, traffic, vocab_size, seed)


def fixed_requests(prompt_lens: list, max_tokens: int, vocab_size: int, seed: int) -> list:
    """One request of each given prompt length, all asking for
    `max_tokens`: the warm-up and the correctness checks of set-up."""
    rng = random.Random(seed)
    return [{"prompt": [rng.randrange(vocab_size) for _ in range(n)], "max_tokens": max_tokens}
            for n in prompt_lens]


def open_loop(traffic: dict, seconds: float, vocab_size: int, seed: int) -> list:
    """The requests of an open loop with their due times, seconds from
    the start of the window: round(rate * seconds) Poisson arrivals."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    prompts, outs, gaps = _rotated(n, traffic, seed, total_s=seconds)
    due, t = [], 0.0
    for g in gaps:
        # the first request is due half a gap in, the last half a gap
        # before the end: all n lie inside the window
        due.append(t + g / 2)
        t += g
    reqs = _requests(prompts, outs, traffic, vocab_size, seed)
    return [dict(r, due_s=d) for r, d in zip(reqs, due)]


def warmup_prompt_lengths(traffic: dict) -> list:
    """One prompt length for each power of two in the clip range of the
    mix, and its upper end: every prefill shape the window can ask for
    is compiled before it, whatever the seed."""
    lo, hi = traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]
    out, n = [], 1
    while n < lo:
        n *= 2
    while n < hi:
        out.append(n)
        n *= 2
    out.append(hi)
    return out
