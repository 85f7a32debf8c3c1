"""Self-speculative decoding: draft k tokens with a compressed policy
variant, verify them all in one base-model dispatch.

The draft model (see `serving.draft`) shares the served model's hash
seeds and layout — HashedNets' ladder makes it a free byproduct of the
artifact.  Per scheduler tick, for every decoding row:

1. **Propose** — the draft catches its private paged KV up to the
   base's committed history (amortized 1-2 positions per tick; chunked
   on admission) and autoregressively samples k proposals
   ``d_1..d_k``, all inside ONE jitted dispatch.
2. **Verify** — the base model runs ``[t_last, d_1..d_k]`` as one
   (B, k+1) block through `Model.decode_paged_block` (bitwise equal to
   k+1 sequential decode steps) and the fused sampler's `run_block`
   computes the token the baseline engine WOULD commit at every slot,
   reusing the counter-based (seed, position) PRNG streams.
3. **Commit / rollback** — row commits the verified targets up to the
   first draft mismatch (`sampling.accept_counts`); base and draft
   caches truncate back to the commit point (`truncate_row`).

**Exactness.**  The emitted tokens ARE the base sampler's own draws —
slot s is valid precisely when the draft matched the baseline's first
s tokens, in which case its logits (and penalty masks and PRNG
counter) are bitwise the baseline's.  The draft's output distribution
never enters the acceptance rule, so every `SamplingParams` mix stays
distribution-correct and greedy/seeded decode is bitwise
token-identical to the non-speculative engine, including under
preemption, prefix cache, and chunked prefill.  This is the
deterministic-verify specialization of rejection-sampling speculative
decoding (classic accept/resample needs draft *probabilities*; with a
deterministic per-slot draw the accept test degenerates to equality
against the recomputed target — exact, and simpler).

**Isolation.**  The draft owns a private, fully-provisioned
`PagedKVCache` (its own registry: `MetricsRegistry.group` is
get-or-create, so sharing the engine's would alias the ``kv.*`` /
``prefix.*`` counters) — speculation never contends with the base
page pool and never causes extra preemptions.  When the base pool is
too tight for a row's k+1 verify writes the block just shrinks (down
to 1 == baseline) instead of preempting anyone.

Observability: ``spec.*`` counters (proposed/accepted/dispatches), an
accept-length histogram, the step phases ``spec_propose`` and
``spec_verify`` (`repro.obs.trace`), and ``spec_rollback`` instants on
the request track.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import REQUEST_PID, WAIT
from repro.serving import sampling as sampling_lib
from repro.serving.paged_cache import TRASH_PAGE, PagedKVCache

_CATCH_CHUNK = 16     # draft catch-up positions per chunk dispatch
_PROP_CATCH = 2       # catch-up slots fused into the propose dispatch
                      # (steady state needs 1, the all-accepted bonus
                      # token makes it 2; admission pre-chunks down)


class SpecDecoder:
    """Per-engine speculative-decode driver (one per Engine)."""

    # adaptive-k controller: EWMA of the per-tick draft accept rate;
    # raise k when drafts almost always land, back off when they mostly
    # roll back.  Emitted tokens are unaffected (acceptance is equality
    # against the base sampler's own draws — k only sets how far ahead
    # we *try* per tick), so the knob trades dispatch count for
    # rollback waste with zero output risk.
    _EWMA_ALPHA = 0.3
    _K_UP_AT = 0.8        # ewma above this and k < k_max -> k += 1
    _K_DOWN_AT = 0.4      # ewma below this and k > 1     -> k -= 1

    def __init__(self, engine, draft_model, draft_params, k: int = 4, *,
                 attn_impl: str = "ref", adaptive: bool = False):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1: {k}")
        self.eng = engine
        self.k_max = int(k)
        self.k = int(k)
        self.adaptive = bool(adaptive)
        self._accept_ewma: float = 0.0
        self._ewma_primed = False
        self.draft_model = draft_model
        self.draft_params = draft_params
        rows, maxp = engine.n_rows, engine.kv.maxp
        ps = engine.kv.page_size
        # fully provisioned private pool: the draft never contends with
        # the base pages and never triggers preemption
        num_pages = rows * maxp + 1
        self._kv_metrics = MetricsRegistry()
        self.kv = PagedKVCache(num_pages, ps, rows, maxp,
                               prefix_cache=False,
                               metrics=self._kv_metrics)
        self.pages = draft_model.init_paged_cache(num_pages, ps)

        m = engine.metrics
        self.counts = m.group("spec", keys=(
            "ticks", "proposed", "accepted_drafts", "rollback_tokens",
            "draft_dispatches", "verify_dispatches", "baseline_rows"))
        self._h_accept = m.histogram(
            "spec.accept_len",
            edges=tuple(float(i) for i in range(1, self.k_max + 2)))
        self._g_k = m.gauge("spec.k_current")
        self._g_k.set(self.k)
        self._g_ewma = m.gauge("spec.accept_ewma")
        self.tracer = engine.tracer

        blk = draft_model.decode_paged_block
        base_blk = engine.model.decode_paged_block
        if blk is None or base_blk is None:
            raise ValueError("speculative decoding needs "
                             "decode_paged_block (decoder, non-MoE)")
        impl = attn_impl

        def draft_catchup(dp, t, pg, tb, ln, ct):
            # pages only (XLA DCEs the LM head)
            return blk(dp, t, pg, tb, ln, ct, impl)[1]

        def verify_block(p, t, pg, tb, ln, ct):
            return base_blk(p, t, pg, tb, ln, ct, impl)

        self._catchup = jax.jit(draft_catchup, donate_argnums=(2,))
        self._verify = jax.jit(verify_block, donate_argnums=(2,))

        def draft_propose(dparams, catch_tokens, lengths, counts,
                          step_mask, pages, table, knobs, pmasks, *,
                          masks, samp, trunc, k):
            """Fused draft tick: catch-up block + k sample/decode steps.

            The sampling stages mirror the base sampler exactly — same
            `sample_tokens`, same (seed, pos) counter streams, same
            penalty-mask evolution — so an identical-logits draft (the
            equal-ratio rung) reproduces the baseline's draws bit for
            bit and accepts every slot.  ``step_mask`` zeroes KV writes
            for rows riding the batch without speculating this tick.
            """
            st = dict(knobs)
            if masks:
                st.update(pmasks)
            logits_all, pages = blk(dparams, catch_tokens, pages, table,
                                    lengths, counts, impl)
            pick = jnp.maximum(counts - 1, 0)[:, None, None]
            logits = jnp.take_along_axis(logits_all, pick, axis=1)[:, 0]
            cur = lengths + counts
            ridx = jnp.arange(catch_tokens.shape[0])
            props = []
            for j in range(k):
                r = sampling_lib.sample_tokens(
                    logits, st, logprob_k=0, with_sampling=samp,
                    with_truncation=trunc)
                d = r["token"]
                props.append(d)
                if j + 1 < k:
                    if masks:
                        st["seen"] = st["seen"].at[ridx, d].set(True)
                        st["out_seen"] = st["out_seen"].at[ridx, d] \
                            .set(True)
                    st["pos"] = st["pos"] + 1
                    logits_all, pages = blk(dparams, d[:, None], pages,
                                            table, cur, step_mask, impl)
                    logits = logits_all[:, 0]
                    cur = cur + step_mask
            return jnp.stack(props, 1), pages

        # one program per (k, masks, samp, trunc) actually used:
        # fixed-k engines only ever key k_max; the adaptive controller
        # adds a key per depth it visits
        self._propose = jax.jit(
            draft_propose, static_argnames=("masks", "samp", "trunc", "k"),
            donate_argnums=(5,))

    # ------------------------------------------------------------------
    def _history(self, i: int, upto: int) -> np.ndarray:
        req = self.eng.rows[i]
        ids = np.concatenate([np.asarray(req.prompt, np.int64).ravel(),
                              np.asarray(req.tokens or [], np.int64)])
        return ids[:upto].astype(np.int32)

    def _sampler_flags(self):
        st = self.eng._sampler_state
        masks = bool(st.uses_penalties.any())
        samp = bool(st.is_sampled.any())
        trunc = samp and bool(st.uses_truncation.any())
        return masks, samp, trunc

    # ------------------------------------------------------------------
    def tick(self, active: List[int]) -> int:
        """One propose/verify/commit round over the decode batch.

        Runs in place of the engine's per-tick decode+sample block,
        after the engine's room/COW pass.  Returns committed tokens.
        """
        eng, k = self.eng, self.k
        tr = self.tracer
        S = k + 1
        B = eng.n_rows
        kv_b = eng.kv

        # ---- per-row verify limits --------------------------------
        with tr.span("schedule"):
            limits = np.zeros((B,), np.int64)
            elig: List[int] = []
            for i in active:
                n = int(kv_b.lengths[i])
                req = eng.rows[i]
                want = min(S, eng.max_len - n,
                           req.sampling.max_tokens - len(req.tokens))
                v = max(want, 1)
                # best-effort room for the k+1-position write block: on
                # pool pressure shrink the block (never preempt for spec)
                while v > 1 and kv_b.ensure_room(i, n + v) != "ok":
                    v -= 1
                limits[i] = v
                # extras rows (image tokens) can't be replayed from token
                # ids alone, so the draft skips them: verify-only == one
                # baseline-equivalent token per tick
                if v >= 2 and n + k <= eng.max_len and not req.extras:
                    elig.append(i)
                else:
                    self.counts["baseline_rows"] += 1
        # a second COW should be impossible here (the engine's
        # ensure-room pass privatized every cursor page) but drain
        # defensively: a queued copy must land before the block write
        eng._drain_cow()
        # dispatch views AFTER ensure_room extended the page tables;
        # mid-prefill rows must neither write real pages nor attend
        table, lengths = kv_b.table, kv_b.lengths
        if eng._prefilling:
            table = table.copy()
            lengths = lengths.copy()
            for i in eng._prefilling:
                table[i, :] = TRASH_PAGE
                lengths[i] = 0

        masks, samp, trunc = self._sampler_flags()
        sst = eng._sampler_state
        proposals = np.zeros((B, k), np.int32)

        # ---- draft catch-up + propose (eligible rows only) --------
        if elig:
            with tr.span("spec_propose"):
                proposals = self._propose_rows(elig, k, masks, samp,
                                               trunc, sst)

        # ---- verify: one base-model block + one fused sampler -----
        with tr.span("spec_verify"):
            tokens_blk = np.zeros((B, S), np.int32)
            tokens_blk[:, 0] = eng._tokens[:, 0]
            tokens_blk[:, 1:] = proposals
            counts_v = np.zeros((B,), np.int32)
            for i in active:
                counts_v[i] = limits[i]
            logits, eng.pages = self._verify(
                eng.params, jnp.asarray(tokens_blk), eng.pages,
                jnp.asarray(table), jnp.asarray(lengths),
                jnp.asarray(counts_v))
        with tr.span("sample"):
            res = eng._sampler.run_block(logits, slice(None), proposals,
                                         kind="verify")
        self.counts["verify_dispatches"] += 1
        with tr.span("commit"):
            return self._commit(active, res, proposals, limits, elig)

    def _propose_rows(self, elig: List[int], k: int, masks: bool,
                      samp: bool, trunc: bool, sst) -> np.ndarray:
        """Catch the draft's cache up to each eligible row's committed
        history and sample its ``k`` proposals, (B, k) int32."""
        B = self.eng.n_rows
        kv_b = self.eng.kv
        rem = np.zeros((B,), np.int64)
        hist: Dict[int, np.ndarray] = {}
        for i in elig:
            n = int(kv_b.lengths[i])
            if i not in self.kv.row_pages:
                ok = self.kv.admit_row(i, 0)
                assert ok, "draft pool is fully provisioned"
            st = self.kv.ensure_room(i, n + k)
            assert st == "ok", f"draft room: {st}"
            hist[i] = self._history(i, n + 1)
            rem[i] = n + 1 - int(self.kv.lengths[i])
            assert rem[i] >= 1
        while any(rem[i] > _PROP_CATCH for i in elig):
            feed = np.zeros((B, _CATCH_CHUNK), np.int32)
            cnts = np.zeros((B,), np.int32)
            for i in elig:
                if rem[i] > _PROP_CATCH:
                    c = int(min(_CATCH_CHUNK, rem[i] - _PROP_CATCH))
                    dl = int(self.kv.lengths[i])
                    feed[i, :c] = hist[i][dl:dl + c]
                    cnts[i] = c
            self.pages = self._catchup(
                self.draft_params, jnp.asarray(feed), self.pages,
                jnp.asarray(self.kv.table),
                jnp.asarray(self.kv.lengths), jnp.asarray(cnts))
            self.counts["draft_dispatches"] += 1
            for i in elig:
                if cnts[i]:
                    self.kv.lengths[i] += cnts[i]
                    rem[i] -= cnts[i]
        feed = np.zeros((B, _PROP_CATCH), np.int32)
        cnts = np.zeros((B,), np.int32)
        step_mask = np.zeros((B,), np.int32)
        for i in elig:
            c = int(rem[i])
            dl = int(self.kv.lengths[i])
            feed[i, :c] = hist[i][dl:dl + c]
            cnts[i] = c
            step_mask[i] = 1
        knobs = sst.batch(slice(None), with_masks=False)
        pmasks = {"seen": sst.seen, "out_seen": sst.out_seen} \
            if masks else {}
        props, self.pages = self._propose(
            self.draft_params, jnp.asarray(feed),
            jnp.asarray(self.kv.lengths), jnp.asarray(cnts),
            jnp.asarray(step_mask), self.pages,
            jnp.asarray(self.kv.table), knobs, pmasks,
            masks=masks, samp=samp, trunc=trunc, k=k)
        with self.tracer.span(WAIT):
            proposals = np.asarray(props)
        self.counts["draft_dispatches"] += 1
        self.counts["proposed"] += k * len(elig)
        for i in elig:
            # catch-up wrote `cnts` positions, steps wrote k-1 more
            self.kv.lengths[i] += int(cnts[i]) + (k - 1)
        return proposals

    def _commit(self, active: List[int], res, proposals: np.ndarray,
                limits: np.ndarray, elig: List[int]) -> int:
        """Commit each row's verified targets up to its first draft
        mismatch, roll both caches back to the commit point, and step
        the adaptive controller.  Returns committed tokens."""
        eng, k = self.eng, self.k
        S = k + 1
        B = eng.n_rows
        kv_b = eng.kv
        # ---- accept / commit / rollback ---------------------------
        targets = res["token"].reshape(B, S)
        commits = sampling_lib.accept_counts(targets, proposals, limits)
        total = 0
        tick_accepted = 0
        for i in active:
            req = eng.rows[i]
            done = 0
            for s in range(int(commits[i])):
                kv_b.advance(i)
                eng._commit_token(i, req, res, i * S + s)
                done += 1
                if eng._stop_reason(req) is not None:
                    break
            n_new = int(kv_b.lengths[i])
            kv_b.truncate_row(i, n_new)      # free speculative pages
            rolled = int(limits[i]) - done
            if i in self.kv.row_pages:
                if self.tracer.enabled and rolled:
                    self.tracer.instant(REQUEST_PID, req.uid,
                                        "spec_rollback", tokens=rolled)
                # all accepted: the bonus target is committed but not
                # yet in any KV, so the draft re-feeds it next tick
                self.kv.truncate_row(i, min(n_new,
                                            int(self.kv.lengths[i])))
            self._h_accept.observe(done)
            if i in elig:
                self.counts["accepted_drafts"] += max(done - 1, 0)
                tick_accepted += max(done - 1, 0)
            self.counts["rollback_tokens"] += max(rolled, 0)
            total += done
        if self.adaptive and elig:
            # controller: EWMA the tick's draft accept rate, step k by
            # one within [1, k_max].  Output-safe by construction —
            # k only bounds how many equality-verified proposals each
            # tick attempts, never which tokens commit.
            rate = tick_accepted / (k * len(elig))
            if not self._ewma_primed:
                self._accept_ewma = rate
                self._ewma_primed = True
            else:
                a = self._EWMA_ALPHA
                self._accept_ewma = a * rate + (1 - a) * self._accept_ewma
            if self._accept_ewma > self._K_UP_AT and self.k < self.k_max:
                self.k += 1
            elif self._accept_ewma < self._K_DOWN_AT and self.k > 1:
                self.k -= 1
            self._g_k.set(self.k)
            self._g_ewma.set(round(self._accept_ewma, 6))
        self.counts["ticks"] += 1
        return total

    # ------------------------------------------------------------------
    def release_row(self, row: int) -> None:
        """Drop the row's draft pages (finish/preempt hook)."""
        if row in self.kv.row_pages:
            self.kv.release_row(row)

    def leak_check(self) -> None:
        """Refcount audit over the draft pool (Engine.shutdown)."""
        self.kv.leak_check()

    def stats(self) -> Dict[str, object]:
        proposed = int(self.counts["proposed"])
        accepted = int(self.counts["accepted_drafts"])
        return {
            "k": self.k,
            "k_max": self.k_max,
            "adaptive": self.adaptive,
            "accept_ewma": round(self._accept_ewma, 6),
            "ticks": int(self.counts["ticks"]),
            "proposed": proposed,
            "accepted_drafts": accepted,
            "accept_rate": accepted / proposed if proposed else 0.0,
            "mean_accept_len": self._h_accept.mean,
            "draft_dispatches": int(self.counts["draft_dispatches"]),
            "verify_dispatches": int(self.counts["verify_dispatches"]),
            "baseline_rows": int(self.counts["baseline_rows"]),
            "draft_pages_in_use": self.kv.alloc.num_used,
        }
