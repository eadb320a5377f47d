"""The one traffic generator: a mix's data file plus a seed -> requests.

A mix (``traffic/<name>.json``) gives the arrival process and two length
distributions.  Every seed gets the same work: the lengths are the
quantiles (i + 0.5) / n of the mix's clipped lognormals and the gaps between
arrivals the same quantiles of an exponential, and the seed shuffles each
of the three lists, uniformly and independently, and draws the prompt
tokens.  So two seeds differ in which request is long and when it comes,
not in how much there is to do.  The order is not smoothed: runs of short
gaps and clusters of long outputs come as often as in independent draws.

Arrivals:
  poisson   n = round(rate x seconds) requests whose exponential gaps are
            scaled to sum to the window, so all n are due inside it, the
            first at 0: a Poisson process given its count in the window.
  backlog   n = ceil(backlog_per_s x seconds) requests, all due at 0.
"""

from __future__ import annotations

import math
import random
import statistics
from dataclasses import dataclass, field

import numpy as np

ARRIVALS = ("poisson", "backlog")


@dataclass
class Request:
    """One request as the client sends it, and what came back."""
    rid: int
    due: float                    # seconds after the window opens
    prompt: list[int]
    max_new_tokens: int
    submitted: float = -1.0       # host clock, seconds after the window opens
    prefill_start: float = -1.0
    tokens: list[int] = field(default_factory=list)
    token_times: list[float] = field(default_factory=list)
    finished: bool = False


def lognormal_quantiles(n: int, spec: dict) -> list[int]:
    nd = statistics.NormalDist()
    mu = math.log(spec["median"])
    return [int(min(spec["max"], max(spec["min"], round(math.exp(
        mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def shuffled(values: list, rng: random.Random) -> list:
    """The values in a uniformly random order drawn from ``rng``."""
    out = list(values)
    rng.shuffle(out)
    return out


def count(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "poisson":
        return max(1, round(mix["rate_per_s"] * seconds))
    if mix["arrival"] == "backlog":
        return max(1, math.ceil(mix["backlog_per_s"] * seconds))
    raise ValueError(f"unknown arrival process {mix['arrival']!r}; "
                     f"expected one of {ARRIVALS}")


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """The multiset of prompt lengths, the same for every seed."""
    return lognormal_quantiles(count(mix, seconds), mix["prompt"])


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Request]:
    n = count(mix, seconds)
    rng = random.Random(seed)
    prompts = shuffled(lognormal_quantiles(n, mix["prompt"]), rng)
    outputs = shuffled(lognormal_quantiles(n, mix["output"]), rng)
    if mix["arrival"] == "poisson":
        gaps = shuffled([-math.log(1.0 - (i + 0.5) / n)
                         for i in range(n)], rng)
        scale = seconds / sum(gaps)
        due, t = [], 0.0
        for g in gaps:
            due.append(t)
            t += g * scale
    else:
        due = [0.0] * n
    toks = np.random.default_rng(int(seed) % (1 << 64))
    return [Request(rid=i, due=due[i],
                    prompt=toks.integers(0, vocab, prompts[i]).tolist(),
                    max_new_tokens=outputs[i]) for i in range(n)]
