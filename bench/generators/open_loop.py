"""Open-loop requests: due on a schedule, whatever the server does.

A mix's data file states everything; this one generator reads it.

* Arrivals are Poisson.  ``rate_per_s`` gives a steady rate; ``bursts``
  (``period_s``, ``on_s``, ``on_rate_per_s``, ``off_rate_per_s``) gives a
  rate that is high for the first ``on_s`` seconds of every period and low
  (or 0) for the rest.  Arrivals are drawn at rate 1 and mapped through
  the cumulative rate, so both forms carry the same gaps in another clock.
* Requests come in ``classes``, each with a ``weight``, lognormal
  ``prompt_tokens`` and ``output_tokens`` (``median``, ``sigma``, clipped
  to ``[min, max]``) and optionally a shared prefix: ``prefix_tokens``
  tokens, one of ``prefixes`` distinct ones (default 1) drawn once from
  the seed, put before the prompt.  A mix with no ``classes`` is one class
  with the mix's own ``prompt_tokens`` and ``output_tokens``.
* The schedule starts ``ramp_s`` seconds before the measured window, so
  the slots are full when it opens.

Every seed gets the same amounts of work in another order: each run of
``stratum`` consecutive arrivals carries the same ``stratum`` quantiles of
the gaps, the same share of each class (by largest remainder) and, per
class, the same quantiles of its lengths, shuffled by the seed.  The seed
also draws the token ids.  So runs with different seeds differ in order
and content, not in load.  Where the order itself changes the work (which
arrivals share a chunk step, and so how many chunk steps fall in the
window), ``schedule_seed`` fixes it: the arrival times, classes and
lengths are then drawn from that seed, and the run's seed draws only the
token ids.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # seconds after the schedule starts
    prompt: tuple
    max_new_tokens: int
    cls: int = 0          # index of the mix's request class


def lognormal_strata(dist: dict, n: int) -> list[int]:
    """``n`` equal-probability quantiles of the clipped lognormal ``dist``."""
    z = NormalDist()
    out = []
    for i in range(n):
        x = dist["median"] * math.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def exponential_strata(rate: float, n: int) -> list[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def class_counts(weights, n: int) -> list[int]:
    """Requests of each class in a stratum of ``n``: largest remainder."""
    total = float(sum(weights))
    exact = [w / total * n for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    for i in sorted(range(len(exact)), key=lambda i: counts[i] - exact[i])[: n - sum(counts)]:
        counts[i] += 1
    return counts


def classes(mix: dict) -> list[dict]:
    return mix.get("classes") or [{"weight": 1, "prompt_tokens": mix["prompt_tokens"],
                                   "output_tokens": mix["output_tokens"]}]


def clock(mix: dict):
    """Map from rate-1 time to seconds: the inverse of the cumulative rate."""
    if "bursts" not in mix:
        rate = float(mix["rate_per_s"])
        return lambda u: u / rate
    b = mix["bursts"]
    period, on_s = float(b["period_s"]), float(b["on_s"])
    hi, lo = float(b["on_rate_per_s"]), float(b.get("off_rate_per_s", 0.0))
    on_mass = hi * on_s
    mass = on_mass + lo * (period - on_s)

    def to_s(u: float) -> float:
        k, r = divmod(u, mass)
        if r < on_mass:
            return k * period + r / hi
        return k * period + on_s + (r - on_mass) / lo

    return to_s


def make(mix: dict, seconds: float, seed: int, arch: dict) -> list[Request]:
    """Every request due from the ramp's start to the window's end."""
    rng = np.random.default_rng(seed)  # token ids
    order = rng if "schedule_seed" not in mix else np.random.default_rng(mix["schedule_seed"])
    vocab = int(arch["vocab"])
    k = int(mix["stratum"])
    cls = classes(mix)
    counts = class_counts([c["weight"] for c in cls], k)
    labels = [i for i, n in enumerate(counts) for _ in range(n)]
    strata = [(lognormal_strata(c["prompt_tokens"], n), lognormal_strata(c["output_tokens"], n))
              for c, n in zip(cls, counts)]
    prefixes = [[tuple(int(t) for t in rng.integers(0, vocab, size=int(c["prefix_tokens"])))
                 for _ in range(int(c.get("prefixes", 1)))] if c.get("prefix_tokens") else [()]
                for c in cls]
    gaps = exponential_strata(1.0, k)
    to_s = clock(mix)
    horizon = float(mix["ramp_s"]) + float(seconds)
    out, u = [], 0.0
    while True:
        drawn = [(list(order.permutation(p)), list(order.permutation(o))) for p, o in strata]
        for g, c in zip(order.permutation(gaps), order.permutation(labels)):
            u += float(g)
            t = to_s(u)
            if t >= horizon:
                return out
            p, o = drawn[c][0].pop(), drawn[c][1].pop()
            pre = prefixes[c][int(order.integers(0, len(prefixes[c])))]
            ids = rng.integers(0, vocab, size=int(p), dtype=np.int64)
            out.append(Request(t, pre + tuple(int(i) for i in ids), int(o), int(c)))
