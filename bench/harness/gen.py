"""Traffic generation from a seed: token batches for training, and an
open-loop request schedule for serving.

Both read their parameters from the cell's traffic file; nothing here is
specific to one cell.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterator, List, Tuple

import numpy as np


def zipf_batches(vocab: int, batch: int, seq: int, seed: int
                 ) -> Iterator[Dict[str, np.ndarray]]:
    """Token batches with a Zipf-like marginal (p(rank r) ~ 1/r), so that
    losses behave like text; labels are the tokens shifted by one.  The
    same stream as the trainer's synthetic batches for the same seed."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = 1.0 / ranks
    probs /= probs.sum()
    rng = np.random.default_rng(seed)
    while True:
        toks = rng.choice(vocab, size=(batch, seq + 1), p=probs
                          ).astype(np.int32)
        yield {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _lognormal_grid(n: int, median: float, sigma: float, lo: int, hi: int
                    ) -> np.ndarray:
    """n lengths at the midpoint quantiles of a lognormal, clipped."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi
                   ).astype(np.int64)


def request_schedule(traffic: Dict, seconds: float, seed: int, vocab: int
                     ) -> List[Tuple[float, List[int], int]]:
    """Open-loop schedule of ``(due_s, prompt, max_new)`` over ``seconds``.

    Poisson arrivals at ``traffic["rate_per_s"]``, stratified: the gaps
    are the midpoint quantiles of the exponential, and prompt and output
    lengths those of their lognormals, each shuffled once in a fixed
    order.  The seed draws the prompts' tokens: every seed sends the same
    requests at the same times, so runs differ by the system's noise and
    not by where the longest requests fall (which moved the tails and the
    drain by 10% and more between seeds)."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])
    p, o = traffic["prompt"], traffic["output"]
    plens = _lognormal_grid(n, p["median"], p["sigma"], p["min"], p["max"])
    olens = _lognormal_grid(n, o["median"], o["sigma"], o["min"], o["max"])
    order = np.random.default_rng(0)
    gaps, plens, olens = (order.permutation(a) for a in (gaps, plens, olens))
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps) - gaps[0]          # the first request is due at 0
    scale = seconds / (due[-1] + gaps[0]) if n > 1 else 1.0
    return [(float(d * scale),
             rng.integers(0, vocab, size=int(pl)).tolist(), int(ol))
            for d, pl, ol in zip(due, plens, olens)]
