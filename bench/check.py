"""Whether what the timed path served is correct.

Once the window has closed and the engine is freed, a sample of the
greedy requests the window finished, drawn from the seed and always
holding the longest, is run through the plain float32 reference
(``bench/reference.py``) over its prompt and served tokens.  For each
served token the gap is the reference's largest logit minus the
reference's logit of the token served there; a token outside the
published vocabulary has an infinite gap.  The number compared is the
widest gap over the sample, against the configuration's limit.

That covers the chunked, batched ragged prefill into pages (the first
served token), decode through the paged cache (every later one), the
hashed bank expansion, the tied LM head and the sampler's greedy choice.

Sampled requests are not compared: a logit gap judges greedy tokens
only.  Their path differs from the greedy one in the sampler's
truncation and draw alone, which this check does not cover.

The control computes the reference in float8 in the program's place: at
each served position it takes the token the float8 pass puts first and
reads that token's gap in the float32 reference.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import reference

TARGET_TOKENS = 400
MAX_REQUESTS = 12


def sample(recs, seed: int, window_s: float) -> List:
    """Greedy requests finished by the window's close: the longest, then
    others in an order drawn from the seed, until the sample holds
    ``TARGET_TOKENS`` served tokens or ``MAX_REQUESTS`` requests."""
    done = [r for r in recs if r.greedy and r.done is not None
            and r.done <= window_s and r.req is not None]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.req.tokens), r.uid))
    rest = done[1:]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 1])).permutation(len(rest))
    out, n = [done[0]], len(done[0].req.tokens)
    for i in order:
        if n >= TARGET_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].req.tokens)
    return out


def _layout(prompt: np.ndarray, served: List[int], length: int):
    """Feed = prompt + served[:-1], padded; the target at position
    ``len(prompt) - 1 + i`` is served token i."""
    p = len(prompt)
    feed = np.zeros(length, np.int32)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    feed[:len(seq)] = seq
    pos = np.arange(p - 1, p - 1 + len(served))
    return feed, pos


def gaps(params, banks, config: Dict, prompt: np.ndarray,
         served: List[int], length: int, mode: str = "program"
         ) -> np.ndarray:
    """Gap of each served token (``mode="program"``), or of the token the
    float8 control puts first at each served position
    (``mode="control"``), against the float32 reference."""
    vocab = config["vocab_size"]
    feed, pos = _layout(prompt, served, length)
    if mode == "control":
        tgt = np.zeros(length, np.int32)
        _, _, top8, _ = reference.logit_stats(params, banks, config, feed,
                                              tgt, mode="fp8")
        chosen = top8[pos]
    else:
        chosen = np.asarray(served, np.int64)
    tgt = np.zeros(length, np.int32)
    tgt[pos] = np.clip(chosen, 0, vocab - 1)
    best, at, _, _ = reference.logit_stats(params, banks, config, feed, tgt)
    g = (best[pos] - at[pos]).astype(np.float64)
    g[(chosen < 0) | (chosen >= vocab)] = np.inf
    return g


def widest_gap(params, banks, config: Dict, picked, length: int,
               mode: str = "program") -> Tuple[float, int]:
    """(widest gap, tokens compared) over the sampled requests."""
    widest, n = 0.0, 0
    for r in picked:
        g = gaps(params, banks, config, np.asarray(r.req.prompt, np.int32),
                 list(r.req.tokens), length, mode)
        widest = max(widest, float(g.max()))
        n += len(g)
    return widest, n
