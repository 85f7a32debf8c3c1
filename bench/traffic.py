"""One generator for every traffic mix; a mix is a JSON file of parameters.

A mix names its arrival kind, and the kind is the module
``<bench>/traffic/<kind>.py``, found by name like everything else: it
defines ``make(mix, rng, seconds, vocab)``, which returns an
``Arrivals``.  A new kind (sessions, say) is a new module beside the
mixes; the harness asks every kind the same three things (``send``,
``next_due``, ``ended``).  This module holds what the kinds share.

Every seed gets the same work in another order.  Lengths are the
quantiles of a clipped lognormal at the midpoints ``(i + 0.5) / n`` and
open-loop gaps the quantiles of an exponential, each list shuffled by the
seed; the sampling mix is split by exact counts.  So the number of
requests in the window, and the sums of their prompt and output tokens,
are the same for every seed, and runs on different seeds differ by order
alone.  Token ids and per-request sampling seeds are drawn from the seed.

Times are on the window's clock: the measured window is ``[0, seconds)``
and warm-up traffic is due from ``-warmup_s``.
"""
from __future__ import annotations

import dataclasses
import pathlib
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.layout import BENCH, load_module

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Item:
    uid: int
    prompt: np.ndarray            # (P,) int32 token ids
    max_tokens: int
    temperature: float
    top_p: float
    seed: int                     # per-request sampling seed
    due: Optional[float] = None   # window clock; None for closed loop


class Arrivals:
    """What the harness asks of an arrival kind while it serves.

    ``send(now)`` gives the requests to submit now, each with the time it
    is due (its schedule's time, or ``now`` where a client sends it on
    feedback); ``next_due()`` the window-clock time of the next scheduled
    arrival, for the harness to sleep until when the engine is idle
    (None: none is scheduled); ``ended(item, served, now)`` tells the
    kind that a request finished (``served``: its token ids) or was
    refused (``served`` empty).  ``open_loop``: requests are due on their
    schedule whatever the engine does, so one due in the window and
    never served counts against the run.  ``items`` are the requests the
    kind was made with."""

    open_loop = True

    def __init__(self, warmup_s: float, items: List[Item]):
        self.warmup_s = warmup_s
        self.items = items

    def send(self, now: float) -> List[Tuple[Item, float]]:
        raise NotImplementedError

    def next_due(self) -> Optional[float]:
        return None

    def ended(self, item: Item, served: Sequence[int], now: float) -> None:
        pass


class OpenLoop(Arrivals):
    """Requests due on their schedule, sorted by due time."""

    def __init__(self, warmup_s: float, items: List[Item]):
        super().__init__(warmup_s, items)
        self.next = 0

    def send(self, now):
        out = []
        while self.next < len(self.items) \
                and self.items[self.next].due <= now:
            it = self.items[self.next]
            out.append((it, it.due))
            self.next += 1
        return out

    def next_due(self):
        if self.next >= len(self.items):
            return None
        return self.items[self.next].due


class ClosedLoop(Arrivals):
    """``clients`` callers; each sends the pool's next request when its
    last one ends, so the engine always has a backlog."""

    open_loop = False

    def __init__(self, warmup_s: float, items: List[Item], clients: int):
        super().__init__(warmup_s, items)
        self.clients = clients
        self.free = clients
        self.next = 0

    def send(self, now):
        out = []
        while self.free > 0:
            out.append((self.items[self.next % len(self.items)], now))
            self.next += 1
            self.free -= 1
        return out

    def ended(self, item, served, now):
        self.free += 1


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed)))


def lognormal_quantiles(p: Dict, n: int) -> np.ndarray:
    """Clipped lognormal at the midpoint quantiles, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    v = np.round(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(v, p["min"], p["max"]).astype(np.int64)


def exponential_quantiles(n: int) -> np.ndarray:
    """Unit-mean exponential at the midpoint quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def mix(sampling: List[Dict], n: int, rng) -> List[Dict]:
    """Sampling settings for n requests: exact counts by share, shuffled."""
    counts = [int(round(m["share"] * n)) for m in sampling]
    counts[-1] = n - sum(counts[:-1])
    out = [m for m, c in zip(sampling, counts) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def spaced(n: int, start: float, length: float, rng) -> np.ndarray:
    """n arrival times in ``[start, start + length)``: unit exponential
    quantiles, shuffled, scaled so that n + 1 gaps fill the span.  This
    is a Poisson process conditioned on its count, so every seed puts
    the same number of requests in the span."""
    gaps = exponential_quantiles(n + 1)[rng.permutation(n + 1)]
    gaps *= length / gaps.sum()
    return start + np.cumsum(gaps[:n])


def stratified(traffic: Dict, n: int, rng, vocab: int,
               due=None) -> List[Item]:
    """n requests whose lengths and sampling mix are stratified over n,
    with token ids and sampling seeds drawn from ``rng``."""
    plen = lognormal_quantiles(traffic["prompt_len"], n)[rng.permutation(n)]
    olen = lognormal_quantiles(traffic["output_len"], n)[rng.permutation(n)]
    sampling = mix(traffic["sampling"], n, rng)
    items = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(plen[i]), dtype=np.int64)
        items.append(Item(
            uid=i, prompt=prompt.astype(np.int32), max_tokens=int(olen[i]),
            temperature=float(sampling[i].get("temperature", 0.0)),
            top_p=float(sampling[i].get("top_p", 1.0)),
            seed=int(rng.integers(0, 2 ** 31 - 1)),
            due=None if due is None else float(due[i])))
    return items


def open_loop(traffic: Dict, spans, rng, vocab: int) -> OpenLoop:
    """Open-loop requests over ``(start, length, rate)`` spans on the
    window clock: each span holds ``round(rate * length)`` requests with
    lengths and sampling stratified within it, so every seed sends the
    same work in each span."""
    items = []
    for start, length, rate in spans:
        n = int(round(rate * length))
        if n:
            due = spaced(n, start, length, rng)
            items.extend(stratified(traffic, n, rng, vocab, due))
    return OpenLoop(float(traffic.get("warmup_s", 0.0)), renumber(items))


def renumber(items: List[Item]) -> List[Item]:
    for i, it in enumerate(items):
        it.uid = i
    return items


def generate(traffic: Dict, seed: int, seconds: float, vocab: int,
             kinds: pathlib.Path = BENCH / "traffic") -> Arrivals:
    """The requests of one run of ``seconds`` measured seconds, from the
    mix's arrival kind (``kinds/<kind>.py``)."""
    kind = traffic["arrival"]["kind"]
    path = pathlib.Path(kinds) / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown arrival kind {kind!r}: no {path}")
    mod = load_module(path, "bench_arrival_" + kind)
    return mod.make(traffic, rng_for(seed), seconds, vocab)
