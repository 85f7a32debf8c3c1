"""Fused batched sampling pipeline for the serving engine.

One jitted dispatch per decode tick covers the WHOLE decode batch, no
matter how many distinct `SamplingParams` are in flight: every knob is
a per-row array (temperature, top-k, top-p, min-p, penalties, seed,
position), so mixed greedy / top-p / penalized rows ride one XLA
program — no per-request Python branching in the hot loop.  The stages,
in order:

1. **Penalties** — repetition (HF-style divide/multiply on tokens seen
   in prompt+output) and presence (subtract on generated tokens), from
   per-row seen/generated vocab masks maintained incrementally by
   `SamplerState`.  At the default (1.0 / 0.0) the maths are exact
   identities (``x/1``, ``x*1``, ``x-0`` are bitwise x), so default
   rows see the raw logits — greedy output stays byte-identical to the
   pre-SamplingParams engine.
2. **Logprob surface** — ``log_softmax`` of the penalized,
   UN-temperature-scaled logits: the chosen token's logprob plus an
   optional top-K report (temperature-independent, for eval).
3. **Temperature → top-k → top-p → min-p** truncation.  Top-k runs the
   radix-select Pallas kernel on TPU (`kernels.topk`), the
   ``jax.lax.top_k`` full-sort fallback elsewhere.
4. **Counter-based PRNG sampling** — the Gumbel-argmax trick with a
   per-row key ``fold_in(fold_in(BASE, seed), position)`` where
   ``position`` is the index of the token being generated.  No stream
   state is consumed: the same (seed, position) always reproduces the
   same draw, so preemption-recompute and prefix-cache replay are
   bitwise token-identical for temperature > 0, and sampling a bound
   row that is later discarded (a mid-prefill row riding the batch)
   perturbs nothing.  Greedy rows (temperature 0) take ``argmax`` of
   the penalized logits instead.

`SamplerState` is the host-side row-state mirror: tiny per-row knob
vectors plus (rows, vocab) boolean seen-masks, rebound on admission
(deterministically reconstructed from prompt+tokens, so preemption
rebinds to the identical state) and advanced per committed token.
`FusedSampler` owns the dispatch surface the engine drives: the state,
the bounded menu of jitted specializations, and the sampler's
observability — dispatch counters (``sampler.dispatches.*``) and a
histogram of each dispatch's host seconds (``sampler.dispatch_s``:
inputs and dispatch, less the ``wait`` span around its results)
published into the engine's metrics registry (`repro.obs.metrics`).
The engine puts every dispatch inside its step's ``sample`` phase
(`repro.obs.trace`).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.topk import NEG, topk_mask
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import WAIT, Tracer

# Fixed base key for the per-request counter streams; per-row keys are
# fold_in(fold_in(_BASE, seed), position).  Changing this constant
# changes every sampled (temperature > 0) output.
_BASE_KEY_SEED = 20150406        # HashedNets (ICML 2015)


def apply_penalties(logits, seen, out_seen, rep_pen, pres_pen):
    """Repetition + presence penalties, rows vectorized.

    logits (B, V) fp32; seen/out_seen (B, V) bool; rep_pen/pres_pen
    (B,).  Defaults (1.0, 0.0) are exact no-ops bit-for-bit.
    """
    r = rep_pen[:, None]
    pen = jnp.where(logits > 0, logits / r, logits * r)
    x = jnp.where(seen, pen, logits)
    return x - pres_pen[:, None] * out_seen.astype(x.dtype)


def topp_mask(z, p, fill=NEG):
    """Nucleus filtering: keep the smallest descending-probability
    prefix with mass >= p (the prefix-mass rule — a token survives iff
    the mass of strictly-higher-ranked tokens is < p, which always
    keeps the top-1), then admit every token whose probability ties the
    cutoff.  ``p >= 1`` disables the row.  Same semantics as
    `kernels.ref.topp_mask_ref` (the numpy walk oracle)."""
    with jax.named_scope("top_p"):
        probs = jax.nn.softmax(z.astype(jnp.float32), axis=-1)
        srt = jnp.sort(probs, axis=-1)[:, ::-1]          # descending
        # exclusive cumsum: mass of strictly-higher-ranked tokens
        excl = jnp.concatenate(
            [jnp.zeros_like(srt[:, :1]),
             jnp.cumsum(srt, axis=-1)[:, :-1]], axis=-1)
        keep_srt = excl < p[:, None]
        cutoff = jnp.min(jnp.where(keep_srt, srt, 2.0), axis=-1)
        keep = (probs >= cutoff[:, None]) | (p >= 1.0)[:, None]
        return jnp.where(keep, z, jnp.asarray(fill, z.dtype))


def minp_mask(z, min_p, fill=NEG):
    """Drop tokens with probability < ``min_p * max_prob`` (0 disables
    the row)."""
    probs = jax.nn.softmax(z.astype(jnp.float32), axis=-1)
    mx = jnp.max(probs, axis=-1, keepdims=True)
    keep = (probs >= min_p[:, None] * mx) | (min_p <= 0.0)[:, None]
    return jnp.where(keep, z, jnp.asarray(fill, z.dtype))


def _row_key(seed, pos):
    base = jax.random.PRNGKey(_BASE_KEY_SEED)
    return jax.random.fold_in(jax.random.fold_in(base, seed), pos)


def sample_tokens(logits, state, *, logprob_k: int = 0,
                  with_sampling: bool = True,
                  with_truncation: bool = True,
                  use_pallas_topk=None, interpret=None):
    """One fused sampling dispatch for a whole batch of rows.

    logits: (B, V); state: dict of per-row arrays —
      temperature/top_p/min_p/rep_pen/pres_pen (B,) f32,
      top_k/seed/pos (B,) i32, and optionally seen/out_seen (B, V)
      bool (the penalty masks; omitting them — statically, by key —
      skips the penalty stage AND the per-tick host->device mask
      transfer, exact for batches whose rows all sit at the default
      penalties, since those are bitwise no-ops anyway).
    ``with_sampling=False`` (static) skips the truncation + Gumbel
    stages entirely — the all-greedy-batch specialization; greedy rows
    take the identical argmax in either variant, so switching variants
    between ticks never changes a token.  ``with_truncation=False``
    (static) skips just the top-k/top-p/min-p masking for
    temperature-only batches — exact, since disabled knobs (k=0, p=1,
    min_p=0) filter nothing.
    Returns {"token" (B,) i32, "logprob" (B,) f32} plus, when
    ``logprob_k > 0``, {"topk_ids" (B, K) i32, "topk_logprobs" (B, K)}.

    Pure: the counter-based keys make repeated calls with the same
    inputs bitwise identical — discarded results (inactive rows sampled
    for batching convenience) never desync anything.
    """
    x = logits.astype(jnp.float32)
    if "seen" in state:          # static: engine omits the (B, V) masks
        pen = apply_penalties(x, state["seen"], state["out_seen"],
                              state["rep_pen"], state["pres_pen"])
    else:                        # when no bound row uses penalties
        pen = x
    lp = jax.nn.log_softmax(pen, axis=-1)
    greedy_tok = jnp.argmax(pen, axis=-1)

    t = state["temperature"]
    if with_sampling:
        z = pen / jnp.maximum(t, 1e-6)[:, None]
        if with_truncation:
            z = topk_mask(z, state["top_k"], fill=NEG,
                          use_pallas=use_pallas_topk, interpret=interpret)
            z = topp_mask(z, state["top_p"])
            z = minp_mask(z, state["min_p"])

        keys = jax.vmap(_row_key)(state["seed"], state["pos"])
        g = jax.vmap(lambda k: jax.random.gumbel(
            k, (x.shape[-1],), jnp.float32))(keys)
        sampled_tok = jnp.argmax(z + g, axis=-1)
        tok = jnp.where(t <= 0.0, greedy_tok, sampled_tok)
    else:
        tok = greedy_tok
    tok = tok.astype(jnp.int32)
    out = {"token": tok,
           "logprob": jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]}
    if logprob_k > 0:
        top_lp, top_ids = jax.lax.top_k(lp, logprob_k)
        out["topk_ids"] = top_ids.astype(jnp.int32)
        out["topk_logprobs"] = top_lp
    return out


class SamplerState:
    """Host-side per-row sampling state for a fixed decode batch.

    One slot per engine row.  ``bind`` reconstructs a slot entirely
    from the request's (sampling params, prompt, tokens-so-far) — a
    pure function of request state, so a preempted request rebinds to
    the exact state it would have had uninterrupted.  ``note`` advances
    the slot one committed token.  ``batch`` materializes the array
    dict `sample_tokens` consumes (sliced for B=1 prefill dispatches).
    """

    def __init__(self, rows: int, vocab: int):
        self.rows, self.vocab = rows, vocab
        self.temperature = np.zeros((rows,), np.float32)
        self.top_k = np.zeros((rows,), np.int32)
        self.top_p = np.ones((rows,), np.float32)
        self.min_p = np.zeros((rows,), np.float32)
        self.rep_pen = np.ones((rows,), np.float32)
        self.pres_pen = np.zeros((rows,), np.float32)
        self.seed = np.zeros((rows,), np.int32)
        self.pos = np.zeros((rows,), np.int32)
        self.seen = np.zeros((rows, vocab), bool)
        self.out_seen = np.zeros((rows, vocab), bool)
        # dispatch-shaping flags (host-side, read by the engine to pick
        # the cheapest fused-sampler specialization)
        self.uses_penalties = np.zeros((rows,), bool)
        self.wants_logprobs = np.zeros((rows,), bool)
        self.is_sampled = np.zeros((rows,), bool)
        self.uses_truncation = np.zeros((rows,), bool)

    def _ids_in_vocab(self, ids) -> np.ndarray:
        ids = np.asarray(ids, np.int64).ravel()
        return ids[(ids >= 0) & (ids < self.vocab)]

    def bind(self, row: int, req) -> None:
        sp = req.sampling
        self.temperature[row] = sp.temperature
        self.top_k[row] = sp.top_k
        self.top_p[row] = sp.top_p
        self.min_p[row] = sp.min_p
        self.rep_pen[row] = sp.repetition_penalty
        self.pres_pen[row] = sp.presence_penalty
        self.seed[row] = np.int32(np.uint32(req.seed_used or 0))
        self.uses_penalties[row] = (sp.repetition_penalty != 1.0
                                    or sp.presence_penalty != 0.0)
        self.wants_logprobs[row] = sp.logprobs is not None
        self.is_sampled[row] = sp.temperature > 0.0
        self.uses_truncation[row] = (sp.top_k > 0 or sp.top_p < 1.0
                                     or sp.min_p > 0.0)
        toks = list(req.tokens or ())
        self.pos[row] = len(toks)
        self.seen[row] = False
        self.out_seen[row] = False
        self.seen[row, self._ids_in_vocab(req.prompt)] = True
        if toks:
            gen = self._ids_in_vocab(toks)
            self.seen[row, gen] = True
            self.out_seen[row, gen] = True

    def clear(self, row: int) -> None:
        self.temperature[row] = 0.0
        self.top_k[row] = 0
        self.top_p[row] = 1.0
        self.min_p[row] = 0.0
        self.rep_pen[row] = 1.0
        self.pres_pen[row] = 0.0
        self.seed[row] = 0
        self.pos[row] = 0
        self.seen[row] = False
        self.out_seen[row] = False
        self.uses_penalties[row] = False
        self.wants_logprobs[row] = False
        self.is_sampled[row] = False
        self.uses_truncation[row] = False

    def note(self, row: int, tok: int) -> None:
        """Advance one committed token: the PRNG counter moves, and the
        penalty masks absorb the new token."""
        self.pos[row] += 1
        if 0 <= tok < self.vocab:
            self.seen[row, tok] = True
            self.out_seen[row, tok] = True

    def batch(self, sl: slice = slice(None), *,
              with_masks: bool = True) -> Dict[str, np.ndarray]:
        out = {"temperature": self.temperature[sl],
               "top_k": self.top_k[sl],
               "top_p": self.top_p[sl],
               "min_p": self.min_p[sl],
               "rep_pen": self.rep_pen[sl],
               "pres_pen": self.pres_pen[sl],
               "seed": self.seed[sl],
               "pos": self.pos[sl]}
        if with_masks:
            out["seen"] = self.seen[sl]
            out["out_seen"] = self.out_seen[sl]
        return out


class FusedSampler:
    """The engine-facing fused-sampler dispatch surface.

    Holds the per-row `SamplerState`, the bounded menu of compiled
    `sample_tokens` specializations (one program, ``jit_sample_tokens``,
    keyed statically by logprob width, any-sampled-row and
    any-truncated-row), and the sampler's metrics:
    the engine dispatches the k=0 variant (no per-tick top-K) unless
    some bound row asked for logprobs, the ``with_sampling=False``
    variant (argmax only — no Gumbel field) when every bound row is
    greedy, the ``with_truncation=False`` variant (no top-k/top-p/min-p
    sorts) for temperature-only batches, and omits the penalty masks
    from the input dict (statically, by key) when no bound row uses
    penalties — sparing the (rows, vocab) host->device transfer on
    default traffic.  All variants are bitwise token-identical (greedy
    rows take argmax in every variant; disabled knobs are exact
    no-ops).
    """

    def __init__(self, rows: int, vocab: int, logprob_k: int = 8, *,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        m = metrics if metrics is not None else MetricsRegistry()
        self.state = SamplerState(rows, vocab)
        self.logprob_k = int(min(logprob_k, vocab))
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self._sample = jax.jit(sample_tokens, static_argnames=(
            "logprob_k", "with_sampling", "with_truncation"))
        self.dispatches = m.group("sampler.dispatches",
                                  keys=("prefill", "decode", "verify"))
        self._h_dispatch = m.histogram("sampler.dispatch_s")

    @property
    def time_s(self) -> float:
        """Cumulative host seconds of sampler dispatches (waits for
        their results left out)."""
        return self._h_dispatch.total

    def _dispatch(self, logits, batch, sl: slice, kind: str, t0: float
                  ) -> Dict[str, np.ndarray]:
        """Dispatch the specialization the rows of ``sl`` need, wait
        for its results, and account the dispatch begun at ``t0``."""
        st = self.state
        samp = bool(st.is_sampled[sl].any())
        out = self._sample(
            logits, batch,
            logprob_k=self.logprob_k if st.wants_logprobs[sl].any() else 0,
            with_sampling=samp,
            with_truncation=samp and bool(st.uses_truncation[sl].any()))
        with self.tracer.span(WAIT) as w:
            res = {k: np.asarray(v) for k, v in out.items()}
        self._h_dispatch.observe(time.monotonic() - t0 - w.dur_s)
        self.dispatches[kind] += 1
        return res

    def run(self, logits, sl: slice, kind: str) -> Dict[str, np.ndarray]:
        """One fused dispatch over the row slice ``sl`` of the state
        (full batch for decode ticks, the single admitted row for a
        prefill completion)."""
        t0 = time.monotonic()
        masks = bool(self.state.uses_penalties[sl].any())
        return self._dispatch(jnp.asarray(logits, jnp.float32),
                              self.state.batch(sl, with_masks=masks),
                              sl, kind, t0)

    def run_block(self, logits, sl: slice, proposals: np.ndarray,
                  kind: str = "verify") -> Dict[str, np.ndarray]:
        """One fused dispatch over an (R, S, V) logits block — the
        speculative-verify surface.

        Slot ``s`` of row ``r`` is the token the engine WOULD commit at
        generated-index ``pos[r] + s`` assuming ``proposals[r, :s]``
        were the previous ``s`` commits: the per-slot state is exactly
        what `SamplerState` would hold after ``s`` `note` calls — pos
        advanced by ``s``, seen/out_seen OR'd with the proposal one-hots
        — so every slot reproduces the baseline per-tick dispatch
        bit-for-bit (same counter-based (seed, pos) PRNG stream, same
        penalty masks, same specialization flags).  Returns flat
        (R*S,) result arrays (slot ``r*S + s``), matching what
        ``run`` returns for a batch of R*S rows.
        """
        t0 = time.monotonic()
        logits = jnp.asarray(logits, jnp.float32)
        st = self.state
        r, s_blk, vocab = logits.shape
        proposals = np.asarray(proposals, np.int32).reshape(r, s_blk - 1)
        masks = bool(st.uses_penalties[sl].any())

        exp = {key: np.repeat(v, s_blk, axis=0)
               for key, v in st.batch(sl, with_masks=False).items()}
        exp["pos"] = (st.pos[sl][:, None]
                      + np.arange(s_blk, dtype=np.int32)).reshape(-1)
        if masks:
            seen = np.repeat(st.seen[sl], s_blk, axis=0)
            out_seen = np.repeat(st.out_seen[sl], s_blk, axis=0)
            cum = np.zeros((r, vocab), bool)      # proposals committed < s
            rows = np.arange(r)
            for s in range(1, s_blk):
                t = proposals[:, s - 1]
                ok = (t >= 0) & (t < vocab)
                cum[rows[ok], t[ok]] = True
                seen[s::s_blk] |= cum
                out_seen[s::s_blk] |= cum
            exp["seen"], exp["out_seen"] = seen, out_seen

        return self._dispatch(logits.reshape(r * s_blk, vocab), exp, sl,
                              kind, t0)


def accept_counts(targets: np.ndarray, proposals: np.ndarray,
                  limits: np.ndarray) -> np.ndarray:
    """Commits per row for a verified block.

    targets (R, S): the tokens the base model commits at each slot
    (slot s valid under the hypothesis that proposals[:s] matched);
    proposals (R, S-1): the draft's k proposals; limits (R,): number
    of verify slots actually usable for the row (room/max_tokens).
    A row commits targets[0..c-1] where c = 1 + the length of the
    leading proposal prefix that matches the targets, clamped to the
    row's limit — the deterministic-verify acceptance rule, exact for
    greedy AND seeded sampling because targets ARE the baseline's
    (seed, pos)-keyed draws.
    """
    match = (targets[:, :-1] == proposals).astype(np.int64)
    run = np.cumprod(match, axis=1).sum(axis=1)
    return np.minimum(1 + run, np.asarray(limits, np.int64)).astype(np.int64)


def match_stop(tokens: List[int], stop) -> bool:
    """True when ``tokens`` ends with any of the stop sequences."""
    for seq in stop:
        n = len(seq)
        if n and len(tokens) >= n and tuple(tokens[-n:]) == tuple(seq):
            return True
    return False
