"""The one traffic generator; every mix is a JSON file of its parameters.

Keys of a mix file:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``clients`` callers, each sending its next request when
  the previous one has finished).
* ``prompt_len``: tokens per prompt.  A recorded prefill has one prompt
  shape, so every prompt of a cell has this length.
* ``output``: ``{"median", "sigma", "min", "max"}`` of a lognormal output
  length (``max_new``), clipped to ``[min, max]``.
* open loop: ``rate_rps`` (base Poisson rate) and optional ``burst``
  ``{"every_s", "len_s", "x"}``: the rate is ``x`` times the base while
  ``t mod every_s < len_s``; ``arrival_seed`` fixes the arrival times.

Every seed gets the same work in another order.  Output lengths are the
distribution's quantiles at evenly spaced probabilities, not draws, cut
into ``STRATA`` equal strata; every run of ``STRATA`` consecutive requests
takes one length from each stratum.  The run's ``--seed`` only chooses
which member of a stratum goes to which run, the order inside each run,
and the prompt tokens, so the load at any moment is alike for all seeds.
Open-loop arrival times come from the mix's own ``arrival_seed``, with
the thinned-Poisson arithmetic of
``repro.fleet.traffic`` (draw at the burst peak, keep with probability
rate(t)/peak, a fixed number of draws per candidate).  So two seeds
differ in what is computed, not in how much.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

FIRST_ID = 3          # ids below this are special tokens in the configs
STRATA = 8


@dataclasses.dataclass(frozen=True)
class Request:
    t: float              # scheduled arrival, seconds after the window opens
    prompt: tuple
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one use of a run seed (any whole number)."""
    return np.random.default_rng([seed & (2 ** 64 - 1), *stream])


def output_lengths(out: dict, n: int) -> List[int]:
    """``n`` lognormal quantiles at (i + 0.5) / n, clipped and rounded."""
    nd = NormalDist()
    lens = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = out["median"] * math.exp(out["sigma"] * z)
        lens.append(int(min(max(round(x), out["min"]), out["max"])))
    return lens


def stratified(lens: List[int], rng: np.random.Generator) -> List[int]:
    """``lens`` (ascending) in request order, one per stratum in every run
    of ``STRATA`` consecutive requests."""
    k = -(-len(lens) // STRATA)
    strata = []
    for j in range(STRATA):
        part = lens[j * k:(j + 1) * k]
        strata.append([part[i] for i in rng.permutation(len(part))])
    out = []
    for b in range(k):
        run = [s[b] for s in strata if b < len(s)]
        out.extend(run[i] for i in rng.permutation(len(run)))
    return out


def _fixed_order(xs: list) -> list:
    """``xs`` in an order that is the same for every run and unrelated to
    its sort order."""
    return [xs[i] for i in np.random.default_rng(0).permutation(len(xs))]


def arrival_times(mix: dict, horizon_s: float) -> List[float]:
    """Open-loop arrival times in ``[0, horizon_s)``, the same for every
    run seed."""
    rate = float(mix["rate_rps"])
    burst = mix.get("burst") or {}
    every, blen, bx = burst.get("every_s"), burst.get("len_s", 0.0), \
        float(burst.get("x", 1.0))
    peak = rate * (bx if every else 1.0)
    rng = np.random.default_rng(int(mix.get("arrival_seed", 0)))
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / peak))
        u = float(rng.random())
        if t >= horizon_s:
            return out
        in_burst = bool(every) and bx > 1.0 and (t % every) < blen
        if u < (rate * bx if in_burst else rate) / peak:
            out.append(t)


def mean_rate(mix: dict) -> float:
    """The offered mean rate of an open-loop mix, bursts included."""
    burst = mix.get("burst") or {}
    if not burst.get("every_s"):
        return float(mix["rate_rps"])
    share = burst["len_s"] / burst["every_s"]
    return float(mix["rate_rps"]) * (1.0 + share * (burst["x"] - 1.0))


class Traffic:
    """One run's requests: a mix, the vocabulary ids it may use, a seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.seed = seed
        self.closed = mix["loop"] == "closed"

    def _prompt(self, rng) -> tuple:
        return tuple(int(x) for x in rng.integers(
            FIRST_ID, self.vocab, self.mix["prompt_len"]))

    def open_loop(self, seconds: float) -> List[Request]:
        """Every arrival of a window of ``seconds``, in arrival order."""
        times = arrival_times(self.mix, seconds)
        rng = rng_for(self.seed, 1)
        lens = stratified(output_lengths(self.mix["output"], len(times)),
                          rng)
        return [Request(t, self._prompt(rng), m) for t, m in zip(times, lens)]

    def closed_loop(self) -> Iterator[Request]:
        """Requests in the order the clients send them.  Each client's
        first request stands for one that was already under way when the
        window's loop began: its length is a share of one of ``clients``
        quantiles, the shares evenly spaced, (i + 0.5) / clients, and
        paired with the quantiles in a fixed order, so the first requests
        are the same work for every seed.  The seed deals them out to the
        clients.  The lengths after them cycle through one quantile set
        per ``clients`` x 4 requests, each cycle stratified afresh."""
        clients = int(self.mix["clients"])
        rng = rng_for(self.seed, 2)
        firsts = [max(1, int(math.ceil((i + 0.5) / clients * m)))
                  for i, m in zip(range(clients), _fixed_order(
                      output_lengths(self.mix["output"], clients)))]
        for j in rng.permutation(clients):
            yield Request(0.0, self._prompt(rng), firsts[j])
        lens = output_lengths(self.mix["output"], clients * 4)
        while True:
            for m in stratified(lens, rng):
                yield Request(0.0, self._prompt(rng), m)

    def warmup(self, n: int, max_new: int) -> List[Request]:
        """``n`` short requests that compile every helper before a window."""
        rng = rng_for(self.seed, 3)
        return [Request(0.0, self._prompt(rng), max_new) for _ in range(n)]
