"""What a run records, and the arithmetic that turns it into metrics.

Every time is the harness's own, on ``time.monotonic``, in seconds on the
window's clock: the measured window is ``[0, window_s]``.  A request is
due at its schedule's time (open loop) or when its client sent it
(closed loop); a token's time is the return of the ``Engine.step`` call
in which the request's token count grew, and every step ends in a host
sync (the sampled tokens come back to the host).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional

import numpy as np


@dataclasses.dataclass
class Rec:
    """One request as the client sees it."""
    uid: int
    due: float
    prompt_len: int
    greedy: bool
    accepted: bool = True
    sent: float = 0.0                   # when the harness submitted it
    admitted: Optional[float] = None    # end of the step that admitted it
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    req: Any = None                     # the engine's Request
    item: Any = None                    # the bench.traffic.Item sent


@dataclasses.dataclass
class Tick:
    """One ``Engine.step`` call."""
    t0: float
    t1: float
    decode_rows: int = 0    # rows that gained a token by decoding
    decode_ctx: int = 0     # their context lengths, summed (own token in)
    first_tokens: int = 0   # rows whose prefill finished (first token)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: str
    config: Dict[str, Any]
    cost: Any                       # bench.cost.Model
    peak: Dict[str, float]          # bench/peaks.json row
    rows: int                       # engine max_concurrency
    setup_s: float
    window_s: float
    drain_end: float
    recs: List[Rec]
    ticks: List[Tick]               # the steps inside the window
    trace: Optional[Dict[str, Any]] = None   # bench.xplane.reduce output
    open_loop: bool = True

    def in_window(self, t: float) -> bool:
        return 0.0 <= t <= self.window_s

    @property
    def due_in_window(self) -> List[Rec]:
        return [r for r in self.recs if 0.0 <= r.due < self.window_s]


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile over all samples (linear between ranks)."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def ttft(run: Run) -> List[float]:
    """First-token time minus due time of every request due in the
    window.  One that never got its first token counts from its due time
    to the end of the run, so it lies in the tail."""
    out = []
    for r in run.due_in_window:
        first = r.tokens[0] if (r.accepted and r.tokens) else run.drain_end
        out.append(first - r.due)
    return out


def token_gaps(run: Run) -> List[float]:
    """Every gap between consecutive output tokens of every request,
    where the later token came inside the window."""
    out = []
    for r in run.recs:
        t = r.tokens
        out.extend(b - a for a, b in zip(t, t[1:]) if run.in_window(b))
    return out


def output_tokens(run: Run) -> int:
    return sum(1 for r in run.recs for t in r.tokens if run.in_window(t))


def queue_waits(run: Run) -> List[float]:
    """Due time to the end of the step in which the request's prefill
    first advanced (its admission), for requests due in the window."""
    out = []
    for r in run.due_in_window:
        end = r.admitted if r.admitted is not None else run.drain_end
        out.append(end - r.due)
    return out


def unserved(run: Run) -> int:
    """Requests due in the window that were refused, or (open loop) never
    got a first token before the run ended.  A closed loop's requests
    still queued at the close are its clients' backlog, not late."""
    return sum(1 for r in run.due_in_window
               if not r.accepted or (run.open_loop and not r.tokens))
