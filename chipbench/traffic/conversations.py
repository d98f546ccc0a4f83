"""Conversation traffic for a served model, generated from a mix file and
the seed.

A mix file (``traffic/<name>.json``) names this generator and gives:

- ``rate``: independent users arriving per second, open loop;
- ``prompt_tokens``: ``{"lengths": [...], "weights": [...]}``;
- ``output_tokens``: lognormal ``median`` and ``sigma``, clipped to
  ``[min, max]``;
- ``round``: conversations per round;
- ``order_seed``: the seed of the order in which each round's sizes come.

Every round holds the same sizes: prompt lengths in exact proportion to
their weights, output lengths at the lognormal's quantiles
``(i + 0.5) / round`` and inter-arrival gaps at the exponential's
quantiles, in an order shuffled once from ``order_seed``.
The seed draws only the prompt tokens, so every seed offers the same
work at the same times: a seed that reordered the sizes would move which
tokens fall inside the window, and with it the metrics, by far more than
two runs of one seed differ.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass
class Conversation:
    cid: str
    #: seconds after the window opens
    due: float
    prompt: np.ndarray  # (L,) int32
    out_tokens: int


def _proportional(weights: List[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` by ``weights``."""
    total = float(sum(weights))
    raw = [w * n / total for w in weights]
    counts = [int(math.floor(r)) for r in raw]
    by_rest = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in by_rest[: n - sum(counts)]:
        counts[i] += 1
    return counts


def round_sizes(mix: Dict) -> Dict[str, List[float]]:
    """The sizes of one round, before the seed shuffles them."""
    r = int(mix["round"])
    p = mix["prompt_tokens"]
    lengths: List[float] = []
    for length, c in zip(p["lengths"], _proportional(p["weights"], r)):
        lengths += [length] * c
    o = mix["output_tokens"]
    nd = NormalDist(math.log(o["median"]), o["sigma"])
    outs = [min(o["max"], max(o["min"], round(math.exp(nd.inv_cdf((i + 0.5) / r)))))
            for i in range(r)]
    gaps = [-math.log(1.0 - (i + 0.5) / r) / mix["rate"] for i in range(r)]
    return {"prompt": lengths, "output": outs, "gap": gaps}


def conversations(mix: Dict, seed: int, n: int, vocab: int,
                  tag: str = "c") -> List[Conversation]:
    """The first ``n`` conversations of the mix under ``seed``."""
    rng = np.random.default_rng([seed, 0x636F6E76])
    order = np.random.default_rng(int(mix["order_seed"]))
    sizes = round_sizes(mix)
    r = int(mix["round"])
    out: List[Conversation] = []
    t = 0.0
    while len(out) < n:
        lengths = order.permutation(sizes["prompt"])
        outs = order.permutation(sizes["output"])
        gaps = order.permutation(sizes["gap"])
        for i in range(min(r, n - len(out))):
            t += float(gaps[i])
            prompt = rng.integers(0, vocab, int(lengths[i]), dtype=np.int32)
            out.append(Conversation(f"{tag}{len(out):05d}", t, prompt,
                                    int(outs[i])))
    return out


def open_loop_count(mix: Dict, seconds: float) -> int:
    """Arrivals due inside a window of ``seconds`` at the mix's rate."""
    return max(1, int(round(mix["rate"] * seconds)))
