"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and a
seed into the requests of one run.

Every seed gets the same multiset of sizes, in another order: lengths are
the mix's distribution read at the stratified quantiles ``(i + 0.5) / n``
and then permuted by the seed, and token ids are drawn from the seed.  So
two seeds do the same amount of work, and differ in which request is long.

A mix file holds:

* ``loop``: ``"closed"``: ``clients`` callers that each send their next
  request when the last one finishes, ``rounds`` requests each;
* ``prompt`` and ``output``: length distributions (see :func:`quantiles`);
* ``engine``: ``slots``, ``max_seq`` and ``block_size`` of the engine the
  mix is served by;
* ``max_burst``: the most requests the clients send in one engine step
  (so admissions stay within the burst programs set-up warms);
* optionally ``start: "stationary"``: the clients' first requests are
  caught mid-flight, as in a loop that has run a long time (see
  :func:`stationary_first_round`);
* ``source`` and ``assumed``: where the numbers come from.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent
# resolution of the length-biased law the stationary start reads
STATIONARY_GRID = 4096


@dataclass
class Spec:
    """One request as the generator makes it.  ``client`` is the caller
    that sends it; ``resumed`` is how many of the prompt's tokens stand
    for output already generated before the run (stationary start)."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    client: int
    resumed: int = 0


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """The distribution ``dist`` read at the probabilities ``u``.

    ``uniform``: ``lo``..``hi`` inclusive; ``lognormal``: ``median`` and
    ``sigma``, clipped to ``lo``..``hi``."""
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        return lo + u * (hi + 1 - lo) - 0.5
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(x, dist.get("lo", -math.inf), dist.get("hi", math.inf))
    raise ValueError(f"unknown distribution {kind!r}")


def _sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the stratified quantiles, in order."""
    x = np.rint(quantiles(dist, _stratified(n))).astype(np.int64)
    return np.maximum(x, 1)


def stationary_first_round(mix: dict):
    """Sizes of the requests in flight when a closed loop has run a long
    time: ``(prompt, output, age)`` for each client, the same for every
    seed.

    A client spends time on a request in proportion to its output length,
    so the request it is in at a random moment is drawn from the output
    law weighted by length, and has produced a uniform share of it (its
    ``age``).  Both are read at stratified quantiles; the prompts are the
    prompt law's.  The pairing is fixed, and the seed only deals the
    triples out to the clients."""
    c = mix["clients"]
    grid = _sizes(mix["output"], STATIONARY_GRID)
    cdf = np.cumsum(grid) / grid.sum()
    out = grid[np.minimum(np.searchsorted(cdf, _stratified(c)),
                          len(grid) - 1)]
    share = np.random.default_rng(0).permutation(_stratified(c))
    age = np.floor(share * out).astype(np.int64)
    prompt = np.random.default_rng(1).permutation(_sizes(mix["prompt"], c))
    return prompt, out, age


def generate(mix: dict, seed: int, vocab: int) -> List[Spec]:
    """The requests of one run, in rid order: round ``r`` holds client
    ``i``'s ``r``-th request at rid ``r * clients + i``."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng(seed)
    c, rounds = mix["clients"], mix["rounds"]
    # each round is a stratified set of its own, dealt out by the seed
    plen = [rng.permutation(_sizes(mix["prompt"], c)) for _ in range(rounds)]
    olen = [rng.permutation(_sizes(mix["output"], c)) for _ in range(rounds)]
    resumed = [np.zeros(c, np.int64)] + [None] * (rounds - 1)
    if mix.get("start") == "stationary":
        p, o, a = stationary_first_round(mix)
        deal = rng.permutation(c)
        plen[0], olen[0], resumed[0] = p[deal] + a[deal], o[deal] - a[deal], \
            a[deal]
    specs = []
    for r in range(rounds):
        for i in range(c):
            prompt = rng.integers(1, vocab, size=int(plen[r][i]))
            specs.append(Spec(rid=r * c + i, prompt=prompt.astype(np.int32),
                              max_new_tokens=int(olen[r][i]), client=i,
                              resumed=int(resumed[r][i]) if r == 0 else 0))
    return specs


def bucket_of(n: int, max_seq: int) -> int:
    """The engine's prompt bucket for ``n`` tokens: the power of two from
    16 up that holds it, capped at ``max_seq``."""
    b = 16
    while b < n:
        b *= 2
    return min(b, max_seq)


def buckets_of(mix: dict) -> List[int]:
    """The prompt buckets the mix reaches: those of its prompt lengths
    and, where the clients start mid-flight, those of a prompt with the
    output it has already produced."""
    max_seq = mix["engine"]["max_seq"]
    hi = mix["prompt"]["hi"]
    if mix.get("start") == "stationary":
        p, _, a = stationary_first_round(mix)
        hi = max(hi, int((p + a).max()))
    found = {bucket_of(n, max_seq) for n in range(mix["prompt"]["lo"], hi + 1)}
    return sorted(found)
