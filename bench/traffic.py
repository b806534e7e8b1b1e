"""The one request generator: a traffic file's parameters -> requests.

A mix is a JSON file of parameters (``traffic/<mix>.json``):

* ``arrivals``: ``"open"`` (a fixed schedule at ``rate_rps``, sent whether
  or not earlier requests finished) or ``"backlog"`` (every request due at
  once; the window (``drive.py``) keeps ``queue_per_lane`` × lanes of
  them queued);
* ``prompt_len`` (every prompt is exactly this long), ``output`` (a
  lognormal of output lengths: ``median``, ``sigma``, clipped to
  ``[min, max]``);
* ``base_share`` and ``zipf_s``: each request is for the base with that
  share; the rest go to the configuration's variants by Zipf(s) over
  their rank;
* ``tape``: how many requests one fixed tape holds.

Every seed gets the same set of inter-arrival gaps and output lengths:
the tape is the quantiles of the two distributions in one fixed shuffled
order, and the seed picks where on the tape (a rotation) its run starts.
The seed also draws the prompt tokens and which request goes to which
variant.  So a seed changes the order of the work, never its amount.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

TAPE_ORDER_SEED = 20240102      # fixes the tape's order for every run


@dataclasses.dataclass
class Request:
    index: int
    due: float                  # seconds after the window opens
    variant: str                # "__base__" or a variant name
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int


def _tape(traffic: dict) -> tuple:
    """(gaps in units of the mean gap, output lengths), both of length
    ``tape``, in the fixed tape order."""
    n = int(traffic["tape"])
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)                       # Exp(1) quantiles
    out = traffic["output"]
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(float(x)) for x in q])
    lengths = np.exp(math.log(out["median"]) + out["sigma"] * z)
    lengths = np.clip(np.rint(lengths), out["min"], out["max"]).astype(int)
    order = np.random.default_rng(TAPE_ORDER_SEED)
    return gaps[order.permutation(n)], lengths[order.permutation(n)]


def _labels(traffic: dict, variants: list, n: int,
            rng: np.random.Generator) -> list:
    """Exactly-proportioned variant labels for ``n`` requests (largest
    remainder), shuffled by the run's seed."""
    share = float(traffic["base_share"]) if variants else 1.0
    w = np.array([(i + 1) ** -float(traffic["zipf_s"])
                  for i in range(len(variants))])
    p = np.concatenate([[share], (1 - share) * w / w.sum()]) \
        if variants else np.array([1.0])
    names = ["__base__"] + list(variants)
    exact = p * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    labels = [name for name, c in zip(names, counts) for _ in range(c)]
    return [labels[i] for i in rng.permutation(n)]


def generate(traffic: dict, seed: int, *, variants: list, vocab: int,
             seconds: float) -> list:
    """The requests of one run.  Open arrivals: those due before
    ``seconds``.  Backlog: the whole tape, all due at 0."""
    gaps, lengths = _tape(traffic)
    n = len(gaps)
    rng = np.random.default_rng(seed)
    start = int(rng.integers(n))
    gaps = np.roll(gaps, -start)
    lengths = np.roll(lengths, -start)
    labels = _labels(traffic, variants, n, rng)
    open_loop = traffic["arrivals"] == "open"
    if open_loop:
        due = np.cumsum(gaps) / float(traffic["rate_rps"])
        due -= due[0]
        n = int(np.searchsorted(due, seconds))
        if n >= len(gaps):
            raise ValueError("traffic tape too short for the window: "
                             "raise 'tape'")
    else:
        due = np.zeros(n)
    plen = int(traffic["prompt_len"])
    prompts = rng.integers(1, vocab, size=(n, plen), dtype=np.int64)
    return [Request(index=i, due=float(due[i]), variant=labels[i],
                    prompt=prompts[i].astype(np.int32),
                    max_new_tokens=int(lengths[i])) for i in range(n)]
