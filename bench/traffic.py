"""The traffic generator: one general reader of the mixes in `traffic/`.

A mix file gives the loop (`open`: arrivals on a schedule at `rate_per_s`,
after `preroll_s` seconds of the same traffic; `closed`: `clients` callers
that each send their next request when the last one finishes), and the
distributions of prompt and output lengths (`uniform` or `log_uniform`
between `min` and `max`, inclusive).

Every seed gets the same multiset of sizes and of inter-arrival gaps,
taken at evenly spaced quantiles of their distributions; the seed only
orders them and draws the token ids (uniform over the vocabulary).  So two
seeds ask for the same work in a different order, and the spread between
seeds measures the system, not the luck of the draw.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# sizes in the closed loop's pool: more requests than any window finishes
CLOSED_POOL = 4096


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n of `dist`."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        out = lo + np.floor(q * (hi - lo + 1))
    elif dist["dist"] == "log_uniform":
        out = np.floor(np.exp(math.log(lo) + q * (math.log(hi + 1)
                                                  - math.log(lo))))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of the mix: when it is due (seconds after the traffic
    starts; None in a closed loop), its prompt and its output length."""
    due: float | None
    prompt: list[int]
    max_new_tokens: int


class Mix:
    """The requests of one run: `items` in the order they are sent."""

    def __init__(self, traffic: dict, seed: int, seconds: float, vocab: int):
        self.traffic = traffic
        self.loop = traffic["loop"]
        s = int(seed) % 2 ** 64
        rng = np.random.default_rng([s & 0xFFFFFFFF, s >> 32, 0x7A])
        if self.loop == "open":
            rate = float(traffic["rate_per_s"])
            self.preroll_s = float(traffic["preroll_s"])
            n = max(1, math.ceil(rate * (self.preroll_s + seconds)))
            q = (np.arange(n) + 0.5) / n
            gaps = rng.permutation(-np.log1p(-q) / rate)
            dues = np.cumsum(gaps)
        elif self.loop == "closed":
            self.clients = int(traffic["clients"])
            self.preroll_s = 0.0
            n = CLOSED_POOL
            dues = [None] * n
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        prompts = rng.permutation(quantile_lengths(traffic["prompt_tokens"], n))
        outputs = rng.permutation(quantile_lengths(traffic["output_tokens"], n))
        ids = rng.integers(0, vocab, size=int(prompts.sum()), dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(prompts)])
        self.items = [
            Item(None if d is None else float(d),
                 ids[starts[i]:starts[i + 1]].tolist(), int(outputs[i]))
            for i, d in enumerate(dues)]
