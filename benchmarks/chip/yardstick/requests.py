"""The one generator of serving traffic: a mix file's parameters in, a
list of requests out.

The schedule is the mix's, not the run's: ``round(rate * seconds)``
requests whose prompt lengths, output lengths and gaps between arrivals
form a fixed multiset, shuffled by the mix's ``schedule_seed``.  A run's
``--seed`` draws the prompts' token ids and the weights, never the
schedule: with the order drawn from the run's seed, the p90 sojourn of
one schedule differed from another's by a quarter, while two runs of one
schedule agreed within 5% (PERF.md, section 6).  The multiset:

* prompt lengths in the mix's shares, by largest remainder;
* output lengths log-uniform between ``output_min`` and ``output_max``,
  at the midpoints of ``n`` equal steps of probability;
* Poisson gaps: the exponential distribution's quantiles at the same
  midpoints, so the arrivals' mean rate is the mix's rate.
"""
from __future__ import annotations

import dataclasses
import math
import random


@dataclasses.dataclass(frozen=True)
class Request:
    id: int
    prompt_len: int
    output_len: int     # greedy tokens after the prefill's first one
    arrival: float      # seconds after the window opens


def _counts(n: int, weights) -> list:
    total = sum(weights)
    raw = [n * w / total for w in weights]
    counts = [int(r) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: -(raw[i] - counts[i]))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def generate(mix: dict, seconds: float) -> list:
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}")
    rate = float(mix["rate_per_s"])
    n = max(1, round(rate * seconds))
    rng = random.Random(int(mix["schedule_seed"]))
    prompts = [L for L, c in zip(mix["prompt_lengths"],
                                 _counts(n, mix["prompt_weights"]))
               for _ in range(c)]
    lo, hi = math.log(mix["output_min"]), math.log(mix["output_max"])
    mids = [(i + 0.5) / n for i in range(n)]
    outputs = [int(round(math.exp(lo + u * (hi - lo)))) for u in mids]
    gaps = [-math.log(1.0 - u) / rate for u in mids]
    for xs in (prompts, outputs, gaps):
        rng.shuffle(xs)
    out, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        out.append(Request(i, prompts[i], outputs[i], t))
    return out
