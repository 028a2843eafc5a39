"""The one traffic generator: reads a mix's parameters, makes requests.

A mix (``bench/traffic/<name>.json``) gives the prompt and output length
distributions (lognormal, by median and sigma, clipped to [min, max]) and
the arrival process: ``poisson`` (an open loop at the cell's rate) or
``backlog`` (a closed loop that keeps ``outstanding`` times the largest
batch waiting).

Every draw is stratified in blocks of ``block`` requests: each block holds
the same ``block`` quantiles of each distribution, shuffled.  The Poisson
arrival gaps are the quantiles of the exponential at the cell's rate,
shuffled the same way (the program's ``serving.workload.generate`` draws
them i.i.d.).  The shuffles are the same for every seed, so every seed
asks for the same work in the same order; the seed draws the prompts'
token ids (and, elsewhere, the weights).  Under request-level batching the
order decides which requests share a batch, so an order drawn from the
seed would move the tails from run to run for no change of the code.
``order`` picks another shuffle, for measuring how far the tails move
with it (``bench/sweep.py --orders``).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

POISSON = "poisson"
BACKLOG = "backlog"
SCHEDULE_SEED = 0       # the lengths' and gaps' order, the same for all runs


@dataclasses.dataclass
class Request:
    rid: int
    due: float              # scheduled send time, s into the window
    prompt: np.ndarray      # int32 token ids, length = prompt length
    out_len: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one stream of a run; any whole number seeds it."""
    return np.random.default_rng([seed % (1 << 64), stream])


def lognormal_quantiles(dist: Dict, n: int) -> np.ndarray:
    """The n mid-quantiles of the clipped lognormal, as whole tokens."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(vals, dist["min"], dist["max"]).astype(np.int64)


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])


def _blocks(mix: Dict, rate: float, order: int) -> Iterator[tuple]:
    """Yields (prompt_len, out_len, gap) per request, block after block."""
    n = mix["block"]
    prompts = lognormal_quantiles(mix["prompt"], n)
    outs = lognormal_quantiles(mix["output"], n)
    gaps = exponential_quantiles(rate, n) if rate else np.zeros(n)
    rng = rng_for(order, 0)
    while True:
        yield from zip(rng.permutation(prompts), rng.permutation(outs),
                       rng.permutation(gaps))


def stream(mix: Dict, seed: int, vocab: int, rate: float = 0.0,
           order: int = SCHEDULE_SEED) -> Iterator[Request]:
    """Requests in send order; ``due`` accumulates the arrival gaps.
    ``order`` shuffles the lengths and gaps; a run keeps the default."""
    tok_rng = rng_for(seed, 1)
    due = 0.0
    for rid, (p, o, gap) in enumerate(_blocks(mix, rate, order)):
        due += float(gap)
        yield Request(rid=rid, due=due, out_len=int(o),
                      prompt=tok_rng.integers(0, vocab, int(p), dtype=np.int32))


def open_loop(mix: Dict, seed: int, vocab: int, rate: float,
              seconds: float, order: int = SCHEDULE_SEED) -> List[Request]:
    """Every request due inside a window of ``seconds`` at ``rate``."""
    out = []
    for r in stream(mix, seed, vocab, rate, order):
        if r.due >= seconds:
            return out
        out.append(r)
