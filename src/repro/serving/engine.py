"""Continuous-batching serving engine over a paged KV cache.

Design (vLLM-style, adapted to XLA's static-shape world):

- A fixed decode batch of ``max_concurrency`` *rows*; each row holds one
  active sequence at its own position (per-row positions ride into the
  model, so rows of different lengths share one ``decode_step``).
- Decoder-kind models use the **paged backend**: K/V lives in fixed-size
  pages (`repro.serving.paged_cache`), rows hold page lists instead of a
  ``max_len`` reservation, and decode reads K/V through the page table
  (`kernels/paged_attention`, ref fallback in `kernels/ref`).  When the
  pool is oversubscribed and a row needs a page none are free, the
  youngest active row is preempted — its pages are released and it
  re-enters the queue head to be re-prefilled later (decode is
  reproducible across preemption — greedy trivially, sampled via the
  counter-based per-request PRNG streams below).
- **Prefix caching** (``prefix_cache=True``): a radix tree over token-id
  page chunks dedups shared prompt prefixes — a new request whose feed
  starts with an indexed prefix maps those pages by reference instead of
  recomputing them (refcounted, copy-on-write on the boundary page; see
  paged_cache).  Shared pages are read through the same page table, so
  the decode kernels need no new math, and greedy output is
  token-identical to the non-shared path by construction (prefix K/V is
  bitwise what a fresh prefill would have produced).
- **Chunked prefill** (``prefill_chunk=N``): long prompts prefill N
  tokens per tick, interleaved with decode ticks for the already-running
  rows — no head-of-line blocking on a long prompt.  Chunks write
  straight into the row's (possibly shared) pages; positions covered by
  a prefix hit are gathered from the tree's pages instead of recomputed.
  Chunked prefill is bitwise-identical to monolithic prefill (each query
  attends over the same full-width cache buffer either way; pinned in
  tests/test_kernels.py).
- **Batched ragged prefill** (``batched_prefill=True``, the default on
  paged decoder kinds): all rows mid-prefill advance in ONE ragged
  dispatch per tick (`kernels/flash_prefill`) — per-row start/length
  ride as scalars, shared-prefix pages are read through the page table,
  and fresh K/V lands straight in each row's private pages, no batch=1
  scratch-cache round trip.  Rows group by the same fixed compile-shape
  bucket menu as sequential chunking (the row count pads to a power of
  two); non-chunkable rows (extras, non-decoder kinds) fall back to the
  sequential path.  Emitted tokens are bitwise identical to sequential
  chunked prefill: every sublayer is row-wise for batch >= 2, the
  attention oracle mirrors the dense path op for op, and the LM head
  runs per completing row at the same M=1 dispatch shape
  (``model.logits_head``) — pinned in tests/test_serving_fuzz.py.
- Recurrent / encoder-decoder kinds (rwkv, zamba, encdec) keep the
  dense fixed-row cache (recurrent state is O(1) per row; paging buys
  nothing there).
- Admission/retirement happen *mid-flight*, every tick: a scheduler
  (`repro.serving.scheduler`) with a bounded queue (backpressure:
  ``submit`` returns False when full), FIFO-within-priority-class
  ordering, optional queue deadlines, and a prefill/decode interleaving
  knob decides who prefills next.  Finished rows free immediately — no
  head-of-line blocking on long generations.

Prefill is bucketed pad-and-mask (one compile per bucket) for pure
decoders.  **Sampling** is one fused jitted dispatch per decode tick
(`repro.serving.sampling`): every `SamplingParams` knob rides as a
per-row array — penalties, temperature, top-k (Pallas radix-select
kernel on TPU), top-p, min-p, and a counter-based PRNG keyed on
``(seed, generated-token index)`` — so mixed greedy/sampled batches
never branch per request in the hot loop, and seeded decoding is
bitwise reproducible across preemption-recompute, prefix-cache replay,
and chunked prefill.  ``Engine.submit`` returns a `RequestHandle`
(truthy iff accepted) that streams incremental `RequestOutput` deltas
(`repro.serving.api`).  All jitted functions are cache-functional
(cache in, cache out) so the same engine code runs under pjit on a
mesh.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.models.transformer import Model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import REQUEST_PID, WAIT, Step, Tracer
from repro.serving import sampling as sampling_lib
from repro.serving.api import (FINISH_CANCELLED, FINISH_DEADLINE,
                               FINISH_LENGTH, FINISH_STOP, FINISH_REASONS,
                               RequestHandle, SamplingParams)
from repro.serving.paged_cache import TRASH_PAGE, PagedKVCache
from repro.serving.scheduler import Scheduler, SchedulerConfig

# Clock discipline: every DURATION and DEADLINE (queue wait, TTFT,
# latency, tick/chunk timing, scheduler expiry) comes off the monotonic
# clock — wall time (`time.time`) steps under NTP/manual adjustment,
# which used to skew engine.ttft_s / engine.queue_wait_s (the old
# ``max(.., 0.0)`` clamps silently hid negative deltas) and could
# spuriously expire — or immortalize — deadlined requests.  Wall time
# survives only as the user-facing ``*_time`` timestamps on Request.
# Module-level indirections so tests can monkeypatch a stepping clock.
_now_wall = time.time
_now_mono = time.monotonic


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (P,) int32
    max_new_tokens: int = 32            # legacy mirror of sampling.max_tokens
    temperature: float = 0.0            # legacy mirror of sampling.temperature
    priority: int = 0                   # lower = more urgent
    model: Optional[str] = None         # tenant tag (multi-model engine)
    sampling: Optional[SamplingParams] = None
    # filled by the engine
    tokens: Optional[List[int]] = None
    done: bool = False
    extras: Optional[Dict[str, Any]] = None   # frames / image_embeds
    status: str = "new"       # queued/prefilling/running/preempted/done/...
    # wall-clock timestamps: user-facing only (logs, dashboards) —
    # NEVER subtracted from each other
    submit_time: Optional[float] = None
    first_admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # monotonic-clock marks: the source for every reported duration
    # (queue wait, TTFT, latency) and for scheduler deadline expiry
    submit_mono: Optional[float] = None
    first_admit_mono: Optional[float] = None
    first_token_mono: Optional[float] = None
    finish_mono: Optional[float] = None
    preemptions: int = 0
    truncated: bool = False             # force-retired at max_len
    finish_reason: Optional[str] = None       # stop / length / deadline
    token_logprobs: Optional[List[float]] = None   # chosen-token logprobs
    cumulative_logprob: float = 0.0
    topk_logprobs: Optional[List[List[Tuple[int, float]]]] = None
    seed_used: Optional[int] = None     # effective PRNG seed (engine-drawn
    #                                     when sampling.seed is None)
    # the engine steps that advanced it (a prefill chunk or a token)
    steps: List[Step] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        # Compat shim: the legacy flat knobs and the SamplingParams
        # surface stay coherent both ways.  A legacy
        # ``Request(temperature=t, max_new_tokens=n)`` lowers into an
        # equivalent SamplingParams; an explicit ``sampling=`` wins and
        # back-fills the mirrors so old readers keep working.
        if self.sampling is None:
            self.sampling = SamplingParams(temperature=self.temperature,
                                           max_tokens=self.max_new_tokens)
        else:
            self.temperature = self.sampling.temperature
            self.max_new_tokens = self.sampling.max_tokens
        if self.token_logprobs is None:
            self.token_logprobs = []
        if self.sampling.logprobs is not None and self.topk_logprobs is None:
            self.topk_logprobs = []


@dataclasses.dataclass
class _Prefill:
    """In-flight chunked prefill for one row."""
    req: Request
    feed: np.ndarray          # prompt + pre-preemption tokens
    target: int               # cached positions when complete (feed+extras)
    pos: int                  # cached positions so far (starts at prefix hit)
    cache: Any                # batch=1 scratch cache (None => zeros cache)
    chunkable: bool           # bucketed decoder without extras


def _slot_update(cache, slot_cache, slot_idx):
    """Scatter a batch=1 cache pytree into slot `slot_idx` of the batched
    cache.  Leaves whose leading dims are (layers, batch, ...) or
    (batch, ...) are handled by matching the batch-dim size."""
    def upd(full, one):
        one = jnp.asarray(one)
        if full.ndim != one.ndim or full.ndim == 0:
            return full            # index-like leaves: engine-managed
        # find the batch axis: first axis where full=N and one=1
        for ax in range(full.ndim):
            if one.shape[ax] == 1 and full.shape[ax] != 1:
                start = [0] * full.ndim
                start[ax] = slot_idx
                return jax.lax.dynamic_update_slice(
                    full, one.astype(full.dtype), tuple(start))
        return full
    return jax.tree.map(upd, cache, slot_cache)


def _copy_pages(pages, ck, cv, pids):
    """Scatter a prefill scratch cache's K/V into pages, one jitted call.

    ck/cv: (nl, 1, max_len, n_kv, hd) from the batch=1 prefill cache;
    pages: {"k","v"} (nl, P, ps, n_kv, hd); pids: (MAXP,) int32 — logical
    page j -> physical page pids[j].  Slots that must NOT be written
    (shared prefix pages, pages outside the chunk being landed, unused
    table slots) carry the trash page, whose contents are never read, so
    the loop writes all MAXP slots unconditionally — one compile covers
    every chunk shape.  The fori_loop carries the pools, so XLA
    bufferizes the updates in place — one pool rewrite per call instead
    of one per page.
    """
    nl, _, _, hkv, hd = ck.shape
    ps = pages["k"].shape[2]

    def body(j, pools):
        pk, pv = pools
        src = jnp.minimum(j * ps, ck.shape[2] - ps)
        chunk_k = jax.lax.dynamic_slice(ck, (0, 0, src, 0, 0),
                                        (nl, 1, ps, hkv, hd))
        chunk_v = jax.lax.dynamic_slice(cv, (0, 0, src, 0, 0),
                                        (nl, 1, ps, hkv, hd))
        pk = jax.lax.dynamic_update_slice(
            pk, chunk_k.astype(pk.dtype), (0, pids[j], 0, 0, 0))
        pv = jax.lax.dynamic_update_slice(
            pv, chunk_v.astype(pv.dtype), (0, pids[j], 0, 0, 0))
        return pk, pv

    pk, pv = jax.lax.fori_loop(0, pids.shape[0], body,
                               (pages["k"], pages["v"]))
    return {"k": pk, "v": pv}


def _gather_prefix(pages, pids, index):
    """Materialize a row's (possibly shared) prefix K/V from pages into
    a fresh batch=1 scratch cache so chunked prefill can resume at
    ``index`` — the read side of prefix sharing.  Positions beyond the
    hit hold stale pool bytes; they are either overwritten by the next
    chunk's cache write or causally invisible, exactly like the zeros
    scratch in the cold path."""
    nl, _, ps, hkv, hd = pages["k"].shape
    maxp = pids.shape[0]
    gk = jnp.take(pages["k"], pids, axis=1).reshape(nl, 1, maxp * ps,
                                                    hkv, hd)
    gv = jnp.take(pages["v"], pids, axis=1).reshape(nl, 1, maxp * ps,
                                                    hkv, hd)
    return {"k": gk, "v": gv, "index": index}


def _copy_page(pages, src, dst):
    """Device copy of one physical page (the COW drain): page ``dst``
    becomes a private replica of ``src`` across every layer."""
    nl, _, ps, hkv, hd = pages["k"].shape

    def one(pool):
        chunk = jax.lax.dynamic_slice(pool, (0, src, 0, 0, 0),
                                      (nl, 1, ps, hkv, hd))
        return jax.lax.dynamic_update_slice(pool, chunk,
                                            (0, dst, 0, 0, 0))

    return {"k": one(pages["k"]), "v": one(pages["v"])}


class Engine:
    BUCKET = 64
    # chunk buckets: small powers of two below BUCKET, then BUCKET
    # multiples (the monolithic ladder) — bounds prefill compiles while
    # chunk offsets roam
    _SUB_BUCKETS = (8, 16, 32)

    def __init__(self, model: Model, params, slots: int = 4,
                 max_len: int = 512, eos_id: int = 1, seed: int = 0, *,
                 max_concurrency: Optional[int] = None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 scheduler: Optional[SchedulerConfig] = None,
                 attn_impl: Optional[str] = None,
                 paged: Optional[bool] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 batched_prefill: bool = True,
                 max_logprobs: int = 8,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 debug_leak_check: bool = False,
                 draft: Optional[Tuple[Model, Any]] = None,
                 spec_k: int = 4,
                 spec_adaptive: bool = False,
                 mesh: Optional[Any] = None,
                 model_tag: Optional[str] = None,
                 page_allocator: Optional[Any] = None,
                 shared_pages: Optional[Any] = None,
                 page_quota: Optional[int] = None):
        """max_concurrency (alias: slots) fixes the decode batch width.

        Paged knobs (decoder kinds): ``page_size`` tokens per KV page;
        ``num_pages`` sizes the physical pool — default fully provisions
        every row to max_len (no preemption possible); pass less to
        oversubscribe memory and let preemption absorb the overflow.
        ``attn_impl``: None (the platform's choice: the decode step
        attends through the paged Pallas kernel on a TPU and through
        the gather oracle elsewhere; prefill and the speculative block
        keep the oracle), "ref" (the gather oracle everywhere) or
        "pallas" (the Pallas kernels everywhere; interpret mode
        off-TPU).  The choice is made once, here:
        ``decode_attn_impl`` and ``prefill_attn_impl``.
        ``prefix_cache`` dedups shared prompt prefixes across requests
        (radix tree + refcounts + COW); ``prefill_chunk`` prefills long
        prompts N tokens per tick interleaved with decode (None =
        monolithic).  Both require the paged backend.
        ``batched_prefill`` (default on) coalesces every chunkable row's
        prefill step into ONE ragged dispatch per tick over the paged
        pool (`kernels/flash_prefill` via ``model.prefill_paged``) —
        token-bitwise-identical to the sequential path; ignored when the
        model has no ``prefill_paged`` (MoE, non-decoder kinds) or the
        backend isn't paged.

        ``max_logprobs`` caps the per-token top-K logprob report any
        request may ask for (the fused sampler computes top-K once per
        tick at this fixed width); ``seed`` seeds the stream that
        assigns per-request sampling seeds to requests that did not
        pin one — for a fixed submit order the whole run is
        reproducible.

        Observability: ``metrics`` is the registry every component
        (engine, scheduler, paged cache, fused sampler) publishes into
        (default: a fresh private one — ``Engine.stats()`` stays a thin
        compat view over it); ``tracer`` (`repro.obs.trace`) puts each
        step and its phases on the profiler's clock, keeps each step's
        account (``Request.steps``, ``engine.step_s``,
        ``engine.step_host_s``) and, when enabled, records them and
        per-request tracks for Perfetto export (default: disabled).
        ``debug_leak_check`` (or env REPRO_DEBUG_LEAK_CHECK=1) makes
        ``shutdown()`` run the paged cache's refcount audit and export
        anomalies as the ``kv.leak_anomalies`` metric.

        ``draft``: an optional ``(model, params)`` pair (typically from
        `repro.serving.draft.build_draft` — a compressed policy variant
        of the served model) enabling self-speculative decoding: the
        draft proposes ``spec_k`` tokens per tick and the base model
        verifies them in one batched dispatch (`repro.serving.
        spec_decode`).  Emitted tokens are bitwise identical to the
        non-speculative engine for every SamplingParams mix, at an
        explicit ``attn_impl`` (under the platform's choice on a TPU
        the speculative block keeps the oracle while plain decode
        takes the kernel).  Requires
        the paged backend on a decoder kind (MoE excluded: its
        capacity routing is batch-shape dependent, so block-verify
        parity doesn't hold).

        ``mesh``: a ``(data, model)`` jax Mesh (`launch.mesh.
        make_serving_mesh`) enabling tensor-parallel decode/prefill:
        the page pool and hashed banks shard over the "model" axis,
        the paged attention dispatches run shard_mapped per head
        shard (`nn.attention`), and the scheduler/allocator/page
        table stay host-global.  Emitted tokens are BITWISE identical
        to the single-device engine (no cross-shard reduction ever
        runs: attention is per-head, the head shards are all-gathered
        — an exact concat — before the replicated projections).  When
        the head counts don't divide the mesh's model axis the pool
        replicates and each device redundantly computes the
        single-device math.  Requires the paged backend; speculative
        decoding on a mesh is not supported yet (the draft keeps a
        second, unsharded pool).

        ``spec_adaptive``: accept-rate EWMA controller varies the
        proposal depth within [1, spec_k] (spec_k becomes k_max);
        emitted tokens stay bitwise identical (acceptance is equality).

        Multi-tenant hosting (`repro.serving.multi_model`): ``model_tag``
        names this engine's tenant lane on a shared ``scheduler``
        (which may be a live `Scheduler` instance, not just a config);
        ``page_allocator`` / ``shared_pages`` bind it to a shared
        host-side allocator and device page pool; ``page_quota`` caps
        its distinct-page footprint on that pool.
        """
        self.model = model
        self.params = params
        rows = max_concurrency if max_concurrency is not None else slots
        self.n_rows = rows
        self.eos_id = eos_id
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.debug_leak_check = bool(
            debug_leak_check or os.environ.get("REPRO_DEBUG_LEAK_CHECK"))
        self.paged = (model.decode_paged is not None) if paged is None \
            else paged
        if self.paged and model.decode_paged is None:
            raise ValueError(
                f"arch kind {model.cfg.arch_kind!r} has no paged decode")
        self.mesh = mesh
        if mesh is not None:
            if not self.paged:
                raise ValueError("mesh= requires the paged backend "
                                 "(decoder kinds)")
            if draft is not None:
                raise ValueError("mesh= with speculative decoding is not "
                                 "supported (the draft keeps a second, "
                                 "unsharded page pool)")
        if not self.paged and (prefix_cache or prefill_chunk is not None):
            raise ValueError("prefix_cache/prefill_chunk require the "
                             "paged backend (decoder kinds)")
        if not self.paged and (page_allocator is not None
                               or shared_pages is not None
                               or page_quota is not None):
            raise ValueError("page_allocator/shared_pages/page_quota "
                             "require the paged backend (decoder kinds)")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1: {prefill_chunk}")
        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        self.model_tag = model_tag
        if isinstance(scheduler, Scheduler):
            self.sched = scheduler       # shared across hosted models
        else:
            self.sched = Scheduler(scheduler or SchedulerConfig(),
                                   metrics=self.metrics)
        self.rows: List[Optional[Request]] = [None] * rows
        self._row_seq = [0] * rows      # admission order, for preemption
        self._seq = 0
        self._done: List[Request] = []
        self._failed: List[Request] = []
        self._tokens = np.zeros((rows, 1), np.int32)
        self._prefill = jax.jit(model.prefill)
        self._prefilling: Dict[int, _Prefill] = {}
        # fused sampler: per-row SamplingParams state + ONE jitted
        # dispatch per decode tick (a second B=1 specialization serves
        # prefill completions); the specialization menu and its
        # observability live in sampling.FusedSampler
        vocab = model.cfg.vocab_size
        self._sampler = sampling_lib.FusedSampler(
            rows, vocab, max_logprobs, metrics=self.metrics,
            tracer=self.tracer)
        self._sampler_state = self._sampler.state
        self._logprob_k = self._sampler.logprob_k
        self._auto_seeds = np.random.default_rng(seed)
        # engine.* counters (registry-backed; stats() is the compat view)
        self._counts = self.metrics.group("engine", keys=(
            "ticks", "tokens", "done", "failed", "preemptions",
            "cancelled"))
        self._finish_counts = self.metrics.group("engine.finish",
                                                 keys=FINISH_REASONS)
        self._h_ttft = self.metrics.histogram("engine.ttft_s")
        self._h_qwait = self.metrics.histogram("engine.queue_wait_s")
        self._h_step = self.metrics.histogram("engine.step_s")
        self._h_step_host = self.metrics.histogram("engine.step_host_s")
        self._step_args = {"model": model_tag} if model_tag else {}
        # batched ragged prefill: dispatches = fused calls, rows/tokens =
        # work coalesced per call, fallback_chunks = rows that took the
        # sequential path (non-chunkable kinds, or batching off)
        self._pb_counts = self.metrics.group("engine.prefill_batch", keys=(
            "dispatches", "rows", "tokens", "fallback_chunks"))
        self._leak_anomalies = self.metrics.counter("kv.leak_anomalies")
        self.last_leak_error: Optional[str] = None
        # engine.shard.* exists only on mesh engines: non-mesh registry
        # snapshots (and the bench deltas diffed off them) stay unchanged
        self._shard_counts = None

        if self.paged:
            # page-aligned max_len keeps every prefill page copy in
            # bounds (dynamic_slice clamping would silently shift rows)
            self.max_len = -(-max_len // page_size) * page_size
            maxp = self.max_len // page_size
            if num_pages is None:
                num_pages = rows * maxp + 1          # +1: trash page
            self.kv = PagedKVCache(num_pages, page_size, rows, maxp,
                                   prefix_cache=prefix_cache,
                                   metrics=self.metrics,
                                   alloc=page_allocator,
                                   page_quota=page_quota)
            self._g_pages_used = self.metrics.gauge("kv.pages_in_use")
            self._g_pages_free = self.metrics.gauge("kv.pages_free")
            # live pages the decode attention read over the rows x MAXP
            # the table provisions: what the paged kernel reads of what
            # the gather oracle reads
            self._g_attn_share = self.metrics.gauge(
                "decode.attn_live_page_share")
            self._g_pages_held = self.metrics.gauge("kv.pages_held") \
                if (page_allocator is not None or page_quota is not None) \
                else None
            self.pages = shared_pages if shared_pages is not None \
                else model.init_paged_cache(num_pages, page_size)
            self._prefill_cache = model.init_cache(1, self.max_len)
            self.decode_attn_impl = attn_impl or (
                "pallas" if runtime.on_tpu() else "ref")
            self.prefill_attn_impl = attn_impl or "ref"
            decode_impl = self.decode_attn_impl
            prefill_impl = self.prefill_attn_impl
            # donate the page pools: without donation the functional
            # pages-in/pages-out contract would copy the whole pool per
            # decode tick / prefill (backends that can't donate just
            # warn and copy — no behavior change)
            def decode_paged(p, t, pg, tb, ln):
                return model.decode_paged(p, t, pg, tb, ln, decode_impl)

            self._decode_paged = jax.jit(decode_paged, donate_argnums=(2,))
            self._page_copy = jax.jit(_copy_pages, donate_argnums=(0,))
            self._gather = jax.jit(_gather_prefix)
            self._cow_copy = jax.jit(_copy_page, donate_argnums=(0,))
            # batched ragged prefill: one fused dispatch advances every
            # chunkable row's current chunk straight into its private
            # pages.  It returns the last-real-slot HIDDEN state; the
            # LM head runs separately per completing row at batch=1 —
            # the same M=1 GEMM dispatch the sequential path uses, so
            # sampled logits are bitwise identical (M=1 GEMV lowering
            # differs from M>=2 rows, which all agree with each other).
            self.batched_prefill = bool(batched_prefill) \
                and model.prefill_paged is not None
            if self.batched_prefill:
                def prefill_paged(p, t, pg, tb, st, cn, wf):
                    return model.prefill_paged(p, t, pg, tb, st, cn, wf,
                                               prefill_impl)

                self._prefill_batched = jax.jit(prefill_paged,
                                                donate_argnums=(2,))
                self._logits_head = jax.jit(model.logits_head)
            self.spec = None
            if draft is not None:
                if model.decode_paged_block is None \
                        or draft[0].decode_paged_block is None:
                    raise ValueError(
                        "speculative decoding needs decode_paged_block "
                        "(decoder kind, non-MoE)")
                from repro.serving.spec_decode import SpecDecoder
                self.spec = SpecDecoder(self, draft[0], draft[1],
                                        k=spec_k,
                                        attn_impl=attn_impl or "ref",
                                        adaptive=spec_adaptive)
            if mesh is not None:
                self._init_mesh(mesh)
        else:
            if draft is not None:
                raise ValueError("speculative decoding requires the "
                                 "paged backend (decoder kinds)")
            self.spec = None
            self.batched_prefill = False
            self.max_len = max_len
            self.cache = model.init_cache(rows, max_len)
            # per-row write positions: every row decodes at its own index
            # (continuous batching); supported by decoder/zamba/rwkv
            # kinds.  encdec keeps the scalar index (synchronous waves).
            self.per_row = model.cfg.arch_kind in ("decoder", "zamba",
                                                   "rwkv")
            if self.per_row:
                self.cache["index"] = jnp.zeros((rows,), jnp.int32)
            self._decode = jax.jit(model.decode_step)

    # ------------------------------------------------------------------
    @classmethod
    def from_artifact(cls, path_or_name: str, *,
                      registry_root: Optional[str] = None,
                      slots: int = 4, max_len: int = 512, eos_id: int = 1,
                      seed: int = 0, draft_policy=None,
                      **kwargs) -> "Engine":
        """Cold-start an engine from a compressed model artifact.

        path_or_name: a .hnart file path, or (with registry_root) a
        registered model name, optionally ``name@version``.  The artifact
        carries the config, hash seeds, and banks — no checkpoint or live
        training state is involved (repro.artifact).  Quantized banks are
        dequantized at load: the model layers need real arrays (a
        keep-quantized engine path waits on an int8 decompress kernel).
        Extra kwargs (page_size, prefix_cache, prefill_chunk, scheduler,
        max_logprobs, ...) pass through to Engine, so the full sampling
        & streaming surface (SamplingParams requests, RequestHandle
        deltas, seeded reproducibility) works identically on a
        cold-started artifact.

        ``draft_policy`` switches on self-speculative decoding: a
        `CompressionPolicy`, policy-JSON path, or ratio string ("1/16")
        naming the compressed draft variant, derived off the SAME
        loaded params (one mmap: equal-ratio banks alias by reference,
        deeper rungs project through the shared hash seeds — see
        `repro.serving.draft`).  ``spec_k`` (in kwargs) sets the
        proposal depth.
        """
        from repro.artifact import io as artifact_io
        if registry_root is not None:
            from repro.artifact import registry as artifact_registry
            entry = artifact_registry.resolve(registry_root, path_or_name)
            path_or_name = entry["path"]
        _, model, params = artifact_io.load_model(path_or_name)
        if draft_policy is not None:
            from repro.serving import draft as draft_lib
            _, dmodel, dparams = draft_lib.build_draft(
                model.cfg, params, draft_policy)
            kwargs["draft"] = (dmodel, dparams)
        return cls(model, params, slots=slots, max_len=max_len,
                   eos_id=eos_id, seed=seed, **kwargs)

    # ------------------------------------------------------------------
    def _init_mesh(self, mesh) -> None:
        """Tensor-parallel placement: shard the page pool on the kv-head
        axis and the hashed banks on their bucket axis, replicate every
        other param, and wrap each jitted serving dispatch so it traces
        and executes under the serving rule set (``tp_kv -> model``,
        all activation/dense-weight axes replicated —
        `distributed.sharding.serving_rules`).  Inside that context
        `nn.attention` shard_maps its scatter+kernel block per head
        shard and all-gathers the head outputs (an exact concat) before
        the replicated o-projection — no cross-shard reduction ever
        runs, so emitted tokens are bitwise the single-device ones."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd
        from repro.serving.paged_cache import pool_pspec

        cfg = self.model.cfg
        tp = mesh.shape.get("model", 1)
        self._rules = shd.serving_rules(cfg.num_heads, cfg.num_kv_heads,
                                        mesh)
        self.pages = jax.device_put(
            self.pages, NamedSharding(mesh, pool_pspec(
                cfg.num_kv_heads, cfg.num_heads, tp)))
        self.params = self._place_params(mesh, tp)

        def wrap(fn, replicate_out=False):
            def call(*a):
                with shd.use_mesh(mesh, self._rules):
                    out = fn(*a)
                if replicate_out:
                    # scratch caches feed the replicated sequential
                    # prefill path: re-replicate eagerly so no sharded
                    # operand leaks into an unconstrained dot (which
                    # GSPMD could partition into a psum — inexact)
                    out = jax.device_put(out, NamedSharding(mesh, P()))
                return out
            return call

        self._decode_paged = wrap(self._decode_paged)
        self._page_copy = wrap(self._page_copy)
        self._gather = wrap(self._gather, replicate_out=True)
        self._cow_copy = wrap(self._cow_copy)
        self._prefill = wrap(self._prefill)
        if self.batched_prefill:
            self._prefill_batched = wrap(self._prefill_batched)
            self._logits_head = wrap(self._logits_head)
        self.metrics.gauge("engine.shard.devices").set(mesh.size)
        self.metrics.gauge("engine.shard.tp").set(tp)
        self._shard_counts = self.metrics.group("engine.shard", keys=(
            "decode_dispatches", "prefill_dispatches"))

    def _place_params(self, mesh, tp: int):
        """Hashed banks shard over "model" (the bucket axis is a pure
        gather source — exact under sharding); everything else
        replicates.  Banks are the ONLY pspec leaves with a TUPLE axis
        containing "tp" (`nn.layers.bank_pspec`; layer-stacked banks
        carry it on axis 1 behind the stack axis); dense weights carry
        plain (fsdp, tp) axes and MUST stay replicated — sharding a
        projection's contraction dim would psum its output, breaking
        bitwise token-identity."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd

        specs = self.model.pspecs()
        rep = NamedSharding(mesh, P())

        def bank_axis(spec):
            for i, ax in enumerate(spec):
                if isinstance(ax, (tuple, list)) and "tp" in ax:
                    return i
            return None

        def place(spec, p):
            ax = bank_axis(spec)
            if ax is not None and p.shape[ax] % tp == 0:
                phys = shd.resolve_spec(spec, shd.SERVING_BANK_RULES)
                return jax.device_put(p, NamedSharding(mesh, phys))
            return jax.device_put(p, rep)

        return jax.tree.map(
            place, specs, self.params,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    # ------------------------------------------------------------------
    def _extra_tokens(self, req: Request) -> int:
        if req.extras and "image_embeds" in req.extras:
            return self.model.cfg.num_image_tokens
        return 0

    def submit(self, req: Request) -> RequestHandle:
        """Enqueue a request.  Returns a `RequestHandle` — truthy iff
        accepted (falsy: backpressure on the bounded queue, or the
        request could never fit the page pool), so ``if eng.submit(r)``
        keeps its legacy meaning.  Iterate the handle (or ``drain()``
        it) for streamed `RequestOutput` deltas."""
        if req.tokens is None:
            req.tokens = []
        if self.model_tag is not None:
            req.model = self.model_tag   # tenant lane on a shared sched
        sp = req.sampling
        if sp.logprobs is not None and sp.logprobs > self._logprob_k:
            raise ValueError(
                f"logprobs={sp.logprobs} exceeds engine "
                f"max_logprobs={self._logprob_k}")
        if self.paged:
            total = len(req.prompt) + self._extra_tokens(req) \
                + sp.max_tokens
            if not self.kv.fits_ever(total):
                req.status = "rejected"
                self._counts["failed"] += 1
                self._failed.append(req)
                return RequestHandle(self, req, accepted=False)
        if not self.sched.submit(req, _now_mono()):
            req.status = "rejected"
            self._counts["failed"] += 1
            self._failed.append(req)
            return RequestHandle(self, req, accepted=False)
        req.submit_time = _now_wall()
        if req.seed_used is None:
            # the effective PRNG stream seed: explicit, or drawn from
            # the engine's seeded stream (deterministic in submit order)
            req.seed_used = int(sp.seed) if sp.seed is not None \
                else int(self._auto_seeds.integers(0, 2 ** 31 - 1))
        req.status = "queued"
        if self.tracer.enabled:
            self.tracer.track(REQUEST_PID, req.uid, f"req {req.uid}")
            self.tracer.begin(REQUEST_PID, req.uid, "request",
                              prompt_len=len(req.prompt))
            self.tracer.begin(REQUEST_PID, req.uid, "queued")
        return RequestHandle(self, req, accepted=True)

    def _free_rows(self) -> List[int]:
        return [i for i, r in enumerate(self.rows) if r is None]

    def _can_bucket(self, req: Request) -> bool:
        """Pad-and-mask bucketing is sound only for pure KV-cache decoders:
        pads after the prompt are causally invisible and the true length
        rides through prefill, so garbage K/V stays masked.  Recurrent
        kinds (rwkv/zamba) would fold pads into their state, and extras
        (encoder frames / image tokens) shift positions — those stay
        exact-length."""
        return self.model.cfg.arch_kind == "decoder" and not req.extras

    def _feed(self, req: Request) -> np.ndarray:
        """Prefill token feed: the prompt plus anything generated before
        a preemption (re-prefilling them recomputes the evicted K/V)."""
        if req.tokens:
            return np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.tokens, np.int32)])
        return np.asarray(req.prompt, np.int32)

    def _prefix_ids(self, req: Request) -> Optional[np.ndarray]:
        """Token ids for prefix matching/indexing, or None when the row
        is ineligible: extras (image tokens shift every position, so the
        feed ids don't spell the cached content) and non-bucketable
        kinds stay out of the tree."""
        if self.paged and self.kv.prefix is not None \
                and self._can_bucket(req):
            return self._feed(req)
        return None

    def _can_admit(self, req: Request) -> bool:
        if not self.paged:
            return True
        feed = len(req.prompt) + len(req.tokens or ()) \
            + self._extra_tokens(req)
        return self.kv.can_admit(feed, token_ids=self._prefix_ids(req))

    def _admit(self, now: float) -> int:
        """Advance in-flight chunked prefills, then start new ones
        (continuous batching).  At most ``max_prefills_per_tick`` chunk
        steps run per tick — the prefill/decode interleave budget.
        With ``batched_prefill`` the advancing rows' chunks coalesce
        into one ragged dispatch per compile bucket instead of one
        dispatch each; budget accounting (chunk steps) is identical.
        Returns the number of chunk steps taken."""
        budget = self.sched.cfg.max_prefills_per_tick
        n_inflight = len(self._prefilling)
        advancing = sorted(self._prefilling,
                           key=lambda r: self._row_seq[r])[:budget]
        chunks = self._advance_rows(advancing)
        if n_inflight > budget:
            return chunks        # budget exhausted mid-flight
        admitted: List[int] = []
        with self.tracer.span("schedule"):
            while chunks + len(admitted) < budget:
                free = self._free_rows()
                if not free:
                    break
                req = self.sched.pop_admissible(self._can_admit,
                                                model=self.model_tag)
                if req is None:
                    break
                if not self._begin_prefill(free[0], req, now):
                    # can_admit is optimistic under prefix sharing
                    # (shared and reclaimable pages may overlap); put
                    # the head back
                    self.sched.unpop(req)
                    break
                admitted.append(free[0])
        # newly admitted rows take their first chunk TOGETHER — the
        # burst-arrival case (N submissions land in one tick) coalesces
        # into one ragged dispatch instead of N single-row ones.  Dense
        # (non-paged) rows finished inside _begin_prefill and are not
        # in _prefilling, so they drop out here but still count.
        self._advance_rows([r for r in admitted if r in self._prefilling])
        return chunks + len(admitted)

    # ------------------------------------------------------------------
    def _begin_prefill(self, row: int, req: Request, now: float) -> bool:
        """Bind a row: allocate/share pages and seed the scratch cache
        from any prefix hit.  The caller batches the first chunk step
        (``_admit`` advances all same-tick admissions as one ragged
        dispatch).  False if the pool came up short (caller
        re-queues)."""
        if not self.paged:
            self._prefill_into_dense(row, req, now)
            return True
        feed = self._feed(req)
        target = len(feed) + self._extra_tokens(req)
        ids = self._prefix_ids(req)
        if not self.kv.admit_row(row, target, token_ids=ids):
            return False
        hit = self.kv.row_meta[row].hit_tokens
        chunkable = self._can_bucket(req)
        cache = None
        if hit > 0 and self.batched_prefill and chunkable:
            # batched path: no scratch cache to seed — the ragged kernel
            # reads the shared prefix through the page table.  Only the
            # partial boundary page needs a private replica: its hit
            # bytes below ``hit`` are read but never recomputed (chunks
            # start at ``hit``; slide-back rewrites are bitwise equal).
            meta = self.kv.row_meta[row]
            if meta.tail_page is not None:
                dst = int(self.kv.table[row, meta.shared])
                with self.tracer.span("cow"):
                    self.pages = self._cow_copy(
                        self.pages, jnp.asarray(meta.tail_page, jnp.int32),
                        jnp.asarray(dst, jnp.int32))
                # copy dispatched; device ordering keeps it ahead of any
                # later pool write, so the pin can drop now
                self.kv.drop_tail_ref(row)
        elif hit > 0:
            pids = self.kv.gather_table(row)
            with self.tracer.span("prefill"):
                cache = self._gather(self.pages, jnp.asarray(pids),
                                     jnp.asarray(hit, jnp.int32))
            # the gather is dispatched; device ordering keeps it ahead
            # of any later pool write, so the pin can drop now
            self.kv.drop_tail_ref(row)
        self._prefilling[row] = _Prefill(
            req=req, feed=feed, target=target, pos=hit, cache=cache,
            chunkable=chunkable)
        self.rows[row] = req
        # (re)bind the row's sampling state: pure function of the
        # request's (params, prompt, tokens), so a preempted request
        # resumes its PRNG stream at exactly len(tokens)
        self._sampler_state.bind(row, req)
        self._seq += 1
        self._row_seq[row] = self._seq
        req.status = "prefilling"
        self._note_admitted(req, now, hit_tokens=hit)
        return True

    def _note_admitted(self, req: Request, now: float, *,
                       hit_tokens: int = 0) -> None:
        """Admission observability: close the request's ``queued`` span
        and, on FIRST admission, record the queue wait."""
        if self.tracer.enabled:
            self.tracer.end(REQUEST_PID, req.uid, "queued",
                            hit_tokens=hit_tokens)
        if req.first_admit_mono is None:
            req.first_admit_mono = now
            req.first_admit_time = _now_wall()
            self._h_qwait.observe(now - (req.submit_mono or now))

    def _chunk_shape(self, pos: int, c: int):
        """Compile shape for a chunk of c tokens at cached position pos:
        returns (start, bucket, length) with start + bucket <= max_len
        (dynamic_update clamping would silently shift the write) and
        length real tokens fed from ``start``.

        Buckets come from a FIXED menu — small powers of two, 64
        multiples, 8 multiples — so token-granular prefix-hit offsets
        can't mint unbounded compile shapes.  When no menu bucket fits
        between c and the remaining room, the window *slides back*
        (start < pos): up to 7 already-cached positions are recomputed —
        bitwise-identical values (the chunk-parity property), one extra
        sliver of compute instead of a fresh XLA compile per distinct
        hit length.  Monolithic prefill from position 0 keeps the legacy
        64-multiple ladder (one compile per 64-bucket — pinned by the
        artifact tests)."""
        room = self.max_len - pos
        if self.prefill_chunk is None and pos == 0:
            b = max(min(-(-c // self.BUCKET) * self.BUCKET, room), c)
            return 0, b, c
        for b in self._SUB_BUCKETS:
            if c <= b <= room:
                return pos, b, c
        mult = -(-c // self.BUCKET) * self.BUCKET
        if mult <= room:
            return pos, mult, c
        b = min(-(-c // 8) * 8, pos + c)     # slide-back: 8-grid bucket
        start = pos + c - b
        # the docstring's contract, re-checked on THIS branch too: holds
        # because pos + c <= target <= max_len, but an out-of-range
        # write would silently shift (dynamic_update clamping), so fail
        # loudly instead
        assert start + b <= self.max_len, (start, b, self.max_len)
        return start, b, b

    def _advance_prefill(self, row: int) -> None:
        """One chunk step: compute ``c`` more feed positions against the
        scratch cache, land their pages, and on completion sample the
        first token and hand the row to decode."""
        st = self._prefilling[row]
        req = st.req
        tr = self.tracer
        tr0 = tr.now()
        pos0 = st.pos
        with tr.span("schedule"):
            remaining = len(st.feed) - (st.pos if st.chunkable else 0)
            c = remaining if (self.prefill_chunk is None
                              or not st.chunkable) \
                else min(self.prefill_chunk, remaining)
            cache = st.cache if st.cache is not None \
                else self._prefill_cache
            if st.chunkable:
                start, bucket, real = self._chunk_shape(st.pos, c)
                if start != st.pos:
                    # slid-back window: rewind the write index;
                    # positions [start, pos) recompute to the same bytes
                    cache = dict(cache,
                                 index=jnp.asarray(start, jnp.int32))
                toks = st.feed[start:start + real]
                prompt = np.pad(toks, (0, bucket - real))
                batch = {"tokens": jnp.asarray(prompt[None, :]),
                         "cache": cache,
                         "length": jnp.asarray(real, jnp.int32)}
            else:
                real = len(st.feed)
                batch = {"tokens": jnp.asarray(st.feed[None, :]),
                         "cache": cache}
                if req.extras:
                    batch.update({k: jnp.asarray(v) for k, v in
                                  req.extras.items()})
        with tr.span("prefill"):
            logits, c1 = self._prefill(self.params, batch)
            if self._shard_counts is not None:
                self._shard_counts["prefill_dispatches"] += 1
            st.cache = c1
            with tr.span(WAIT):
                new_pos = int(np.asarray(c1["index"]))
            # land the freshly computed positions' pages; shared prefix
            # pages (slots below first_private_page) are never rewritten
            # — write targets outside the chunk resolve to the trash page
            lo = max(st.pos // self.kv.page_size,
                     self.kv.first_private_page(row))
            hi = self.kv.pages_for(new_pos)
            wpids = np.full((self.kv.maxp,), TRASH_PAGE, np.int32)
            wpids[lo:hi] = self.kv.table[row, lo:hi]
            self.pages = self._page_copy(self.pages, c1["k"], c1["v"],
                                         jnp.asarray(wpids))
        st.pos = new_pos
        self._note_chunk(req, real)
        if tr.enabled:
            tr.complete(REQUEST_PID, req.uid, "prefill_chunk", tr0,
                        start=pos0, end=st.pos)
        if st.pos < st.target:
            return
        self._complete_prefills([(row, logits[:, -1])])

    def _note_chunk(self, req: Request, tokens: int) -> None:
        """A prefill chunk of ``tokens`` advanced ``req`` in this step."""
        step = self.tracer.current
        if step is not None:
            step.prefill_rows += 1
            step.prefill_tokens += tokens
        self._note_step(req)

    def _note_step(self, req: Request) -> None:
        """The step in progress advanced ``req``."""
        step = self.tracer.current
        if step is not None and (not req.steps or req.steps[-1] is not step):
            req.steps.append(step)

    def _complete_prefills(self, done: List[Tuple[int, Any]]) -> None:
        """Prefill complete for each ``(row, last_logits)`` in order
        (``last_logits``: (1, V) last-real-position logits): sample each
        row's first token, then publish the feed's full pages for reuse
        (the partial boundary page is published at release, once decode
        stops writing it), commit the token and hand the row to
        decode.  Each row's sampler dispatch reads only its own row of
        the sampler state, so sampling them all before committing any
        draws the same tokens."""
        if not done:
            return
        with self.tracer.span("sample"):
            results = [self._run_sampler(lg, slice(row, row + 1),
                                         "prefill") for row, lg in done]
        with self.tracer.span("commit"):
            for (row, _), res in zip(done, results):
                st = self._prefilling.pop(row)
                req = st.req
                ids = self._prefix_ids(req)
                if ids is not None:
                    full = (st.target // self.kv.page_size) \
                        * self.kv.page_size
                    self.kv.index_row(row, ids, full)
                req.status = "running"
                self._commit_token(row, req, res, 0)
                self._note_first_token(req)

    def _advance_rows(self, rows_: List[int]) -> int:
        """Advance each row's prefill by one chunk step.  Chunkable rows
        coalesce into one ragged dispatch per compile bucket
        (``batched_prefill``); the rest take the sequential scratch-cache
        path.  Completions are processed in admission order either way,
        so sampler dispatch order — and thus every observable — matches
        the sequential engine.  Returns the number of chunk steps."""
        if not (self.batched_prefill and len(rows_) > 0):
            for row in rows_:
                self._advance_prefill(row)
            return len(rows_)
        groups: Dict[int, List[Tuple[int, int, int]]] = {}
        for row in rows_:
            st = self._prefilling[row]
            if not st.chunkable:
                self._pb_counts["fallback_chunks"] += 1
                self._advance_prefill(row)
                continue
            remaining = len(st.feed) - st.pos
            c = remaining if self.prefill_chunk is None \
                else min(self.prefill_chunk, remaining)
            start, bucket, real = self._chunk_shape(st.pos, c)
            groups.setdefault(bucket, []).append((row, start, real))
        done: Dict[int, Any] = {}
        for bucket in sorted(groups):
            self._dispatch_prefill_batch(bucket, groups[bucket], done)
        # admission order, like sequential
        self._complete_prefills([(row, done[row]) for row in rows_
                                 if row in done])
        return len(rows_)

    def _dispatch_prefill_batch(self, bucket: int,
                                entries: List[Tuple[int, int, int]],
                                done: Dict[int, Any]) -> None:
        """ONE ragged dispatch advancing every (row, start, real) entry
        by its current chunk: queries at positions [start, start+real)
        per row, fresh K/V scattered straight into the row's private
        pages, shared-prefix pages read through the page table.  The row
        count pads to a power of two (row-wise parity holds for any
        batch >= 2, so padding rows are free).  Rows that reach target
        stash their (1, V) last-position logits in ``done`` for ordered
        completion by the caller."""
        tr = self.tracer
        tr0 = tr.now()
        n = len(entries)
        with tr.span("schedule"):
            n_pad = max(2, 1 << (n - 1).bit_length())
            toks = np.zeros((n_pad, bucket), np.int32)
            starts = np.zeros((n_pad,), np.int32)
            counts = np.zeros((n_pad,), np.int32)
            wfrom = np.zeros((n_pad,), np.int32)
            tables = np.full((n_pad, self.kv.maxp), TRASH_PAGE, np.int32)
            for j, (row, start, real) in enumerate(entries):
                st = self._prefilling[row]
                toks[j, :real] = st.feed[start:start + real]
                starts[j] = start
                counts[j] = real
                # write protection: positions below the first private
                # page (shared prefix) — or below this chunk's landing
                # floor — must not be rewritten; slide-back recomputes
                # land bitwise-equal bytes so rewriting them above the
                # floor is safe
                lo = max(st.pos // self.kv.page_size,
                         self.kv.first_private_page(row))
                wfrom[j] = lo * self.kv.page_size
                tables[j] = self.kv.table[row]
        with tr.span("prefill"):
            x_last, self.pages = self._prefill_batched(
                self.params, jnp.asarray(toks), self.pages,
                jnp.asarray(tables), jnp.asarray(starts),
                jnp.asarray(counts), jnp.asarray(wfrom))
            for j, (row, start, real) in enumerate(entries):
                if start + real >= self._prefilling[row].target:
                    # per-row LM head at the sequential path's exact M=1
                    # dispatch shape (see __init__: bitwise parity)
                    done[row] = self._logits_head(
                        self.params, x_last[j:j + 1])[:, -1]
        self._pb_counts["dispatches"] += 1
        if self._shard_counts is not None:
            self._shard_counts["prefill_dispatches"] += 1
        self._pb_counts["rows"] += n
        self._pb_counts["tokens"] += int(counts.sum())
        for row, start, real in entries:
            st = self._prefilling[row]
            pos0 = st.pos
            st.pos = start + real
            self._note_chunk(st.req, real)
            if tr.enabled:
                tr.complete(REQUEST_PID, st.req.uid, "prefill_chunk", tr0,
                            start=pos0, end=st.pos)

    def _note_first_token(self, req: Request) -> None:
        if req.first_token_mono is None:
            req.first_token_mono = _now_mono()
            req.first_token_time = _now_wall()
            self._h_ttft.observe(
                req.first_token_mono
                - (req.submit_mono or req.first_token_mono))
            if self.tracer.enabled:
                self.tracer.instant(REQUEST_PID, req.uid, "first_token")

    def _prefill_into_dense(self, row: int, req: Request,
                            now: float) -> None:
        """Non-paged kinds (rwkv/zamba/encdec): monolithic prefill into
        the batched dense cache (the pre-chunking path)."""
        tr = self.tracer
        feed = self._feed(req)
        p = len(feed)
        if self._can_bucket(req):
            bucket = min(-(-p // self.BUCKET) * self.BUCKET, self.max_len)
            bucket = max(bucket, p)
            prompt = np.pad(feed, (0, bucket - p))
            batch = {"tokens": jnp.asarray(prompt[None, :]),
                     "cache": self.model.init_cache(1, self.max_len),
                     "length": jnp.asarray(p, jnp.int32)}
        else:
            batch = {"tokens": jnp.asarray(feed[None, :]),
                     "cache": self.model.init_cache(1, self.max_len)}
        if req.extras:
            batch.update({k: jnp.asarray(v) for k, v in
                          req.extras.items()})
        with tr.span("prefill"):
            logits, c1 = self._prefill(self.params, batch)
            with tr.span(WAIT):
                pos = int(np.asarray(c1["index"]))
            self.cache = _slot_update(self.cache, c1, row)
            if self.per_row:
                self.cache["index"] = self.cache["index"].at[row].set(pos)
            else:
                self.cache["index"] = c1["index"]
        self.rows[row] = req
        self._sampler_state.bind(row, req)
        self._seq += 1
        self._row_seq[row] = self._seq
        req.status = "running"
        self._note_admitted(req, now)
        self._note_chunk(req, p)
        with tr.span("sample"):
            res = self._run_sampler(logits[:, -1], slice(row, row + 1),
                                    "prefill")
        with tr.span("commit"):
            self._commit_token(row, req, res, 0)
            self._note_first_token(req)

    def _run_sampler(self, logits, sl: slice, kind: str
                     ) -> Dict[str, np.ndarray]:
        """One fused sampler dispatch over the row slice ``sl`` of the
        sampler state (full batch for decode ticks, the single admitted
        row for a prefill completion).  Thin delegate to
        `sampling.FusedSampler.run` — kept as a method so tests can
        subclass/spy on the engine's dispatch boundary."""
        return self._sampler.run(logits, sl, kind)

    def _commit_token(self, row: int, req: Request,
                      res: Dict[str, np.ndarray], j: int) -> None:
        """Record row ``row``'s sampled token (index ``j`` in the
        sampler result): request output + logprobs, the sampler's PRNG
        counter / penalty masks, and the next decode feed."""
        tok = int(res["token"][j])
        lp = float(res["logprob"][j])
        self._counts["tokens"] += 1
        req.tokens.append(tok)
        req.token_logprobs.append(lp)
        req.cumulative_logprob += lp
        kk = req.sampling.logprobs
        if kk is not None and "topk_ids" in res:
            req.topk_logprobs.append(list(zip(
                res["topk_ids"][j][:kk].tolist(),
                res["topk_logprobs"][j][:kk].tolist())))
        self._sampler_state.note(row, tok)
        self._tokens[row, 0] = tok
        self._note_step(req)

    # ------------------------------------------------------------------
    def _history_ids(self, row: int) -> np.ndarray:
        """Token ids spelling the row's cached positions (prompt plus
        generated tokens, clipped to what has actually been written)."""
        req = self.rows[row]
        ids = np.concatenate([np.asarray(req.prompt, np.int32),
                              np.asarray(req.tokens or [], np.int32)])
        return ids[:int(self.kv.lengths[row])]

    def _publish_row(self, row: int) -> None:
        """Index everything the row cached — full pages AND the partial
        boundary page — before its references drop.  Called when writes
        to the row's pages are provably over (finish/preempt)."""
        req = self.rows[row]
        if req is None or self._prefix_ids(req) is None:
            return
        if row in self._prefilling:
            st = self._prefilling[row]
            self.kv.index_row(row, st.feed[:st.pos], st.pos)
        else:
            ids = self._history_ids(row)
            self.kv.index_row(row, ids, len(ids))

    def _preempt(self, row: int) -> None:
        req = self.rows[row]
        self._publish_row(row)           # landed pages serve the resume
        self._prefilling.pop(row, None)
        self.rows[row] = None
        self.kv.release_row(row)
        if self.spec is not None:
            self.spec.release_row(row)
        self._sampler_state.clear(row)
        req.status = "preempted"
        req.preemptions += 1
        self._counts["preemptions"] += 1
        if self.tracer.enabled:
            self.tracer.instant(REQUEST_PID, req.uid, "preempt",
                                tokens=len(req.tokens or ()))
            self.tracer.begin(REQUEST_PID, req.uid, "queued")
        self.sched.requeue(req)

    def _finish(self, row: int, truncated: bool = False,
                reason: str = FINISH_STOP) -> None:
        req = self.rows[row]
        if self.paged:
            self._publish_row(row)
            self.rows[row] = None
            self.kv.release_row(row)
            if self.spec is not None:
                self.spec.release_row(row)
        else:
            self.rows[row] = None
        self._sampler_state.clear(row)
        req.done = True
        req.truncated = truncated
        req.status = "done"
        req.finish_reason = reason
        self._counts["done"] += 1
        self._finish_counts[reason] += 1
        req.finish_mono = _now_mono()
        req.finish_time = _now_wall()
        if self.tracer.enabled:
            self.tracer.end(REQUEST_PID, req.uid, "request",
                            finish=reason, tokens=len(req.tokens or ()))
        self._done.append(req)

    def _ensure_room(self, active: List[int]) -> List[int]:
        """Paged backend: make every active row's next write position
        addressable and privately writable (COW), preempting
        youngest-first on pool exhaustion."""
        for i in list(active):
            if self.rows[i] is None:        # preempted by an earlier row
                continue
            n0 = len(self.kv.pending_copies)
            while True:
                st = self.kv.ensure_decode_room(i)
                if st == "ok":
                    if self.tracer.enabled:
                        for src, dst in self.kv.pending_copies[n0:]:
                            self.tracer.instant(
                                REQUEST_PID, self.rows[i].uid, "cow_copy",
                                src=src, dst=dst)
                    break
                if st == "full":            # max_len hit: force-retire
                    self._finish(i, truncated=True, reason=FINISH_LENGTH)
                    break
                victims = [j for j in range(self.n_rows)
                           if self.rows[j] is not None]
                victim = max(victims, key=lambda j: self._row_seq[j])
                self._preempt(victim)
                if victim == i:
                    break
        return [i for i in active if self.rows[i] is not None]

    def _drain_cow(self) -> None:
        """Perform queued copy-on-write page copies before anything
        writes the pool (decode's token write must hit the private
        replica, never the shared original)."""
        if not self.kv.pending_copies:
            return
        with self.tracer.span("cow"):
            for src, dst in self.kv.pending_copies:
                self.pages = self._cow_copy(self.pages,
                                            jnp.asarray(src, jnp.int32),
                                            jnp.asarray(dst, jnp.int32))
            self.kv.pending_copies.clear()

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine tick: expire, admit/advance prefills, decode all
        running rows, retire.  Returns the number of rows decoded.  The
        tick is one ``engine.step`` of the tracer, its `Step` account
        observed into ``engine.step_s`` and ``engine.step_host_s``."""
        tr = self.tracer
        with tr.step(**self._step_args) as step:
            self._counts["ticks"] += 1
            decoded = self._step_inner(step)
            if self.paged:
                with tr.span("commit"):
                    self._g_pages_used.set(self.kv.alloc.num_used)
                    self._g_pages_free.set(self.kv.alloc.num_free)
                    if self._g_pages_held is not None:
                        self._g_pages_held.set(self.kv.pages_held())
        self._h_step.observe(step.t1 - step.t0)
        self._h_step_host.observe(step.host_s)
        return decoded

    def _step_inner(self, step: Step) -> int:
        tr = self.tracer
        with tr.span("schedule"):
            now = _now_mono()
            for r in self.sched.expire(now, model=self.model_tag):
                r.status = "expired"    # scheduler set finish_reason
                # stamp the finish clocks like _finish does: a streaming
                # client's terminal "deadline" delta and the latency
                # math must see real marks, not None
                r.finish_mono = now
                r.finish_time = _now_wall()
                self._counts["failed"] += 1
                self._finish_counts[FINISH_DEADLINE] += 1
                if tr.enabled:
                    tr.end(REQUEST_PID, r.uid, "queued")
                    tr.end(REQUEST_PID, r.uid, "request",
                           finish=FINISH_DEADLINE)
                self._failed.append(r)
        chunks = self._admit(now)
        with tr.span("commit"):
            # retire BEFORE decoding: a prefill that already satisfied
            # the request (max_new_tokens == 1, or EOS as the first
            # token) must not decode a surplus token
            self._retire()
            active = [i for i, r in enumerate(self.rows)
                      if r is not None and i not in self._prefilling]
            if not active:
                self.sched.account(chunks, 0)
                return 0
        if self.paged:
            with tr.span("schedule"):
                active = self._ensure_room(active)
            # drain queued COW copies in the SAME tick they were queued,
            # even when every row got preempted: a stale copy whose
            # target was released and re-allocated next tick would
            # clobber the new occupant's freshly prefilled K/V
            self._drain_cow()
            if not active:
                self.sched.account(chunks, 0)
                return 0
            if self.spec is not None:
                # speculative tick: draft-propose + block-verify commits
                # 1..spec_k+1 tokens per row, bitwise what the baseline
                # path below would have emitted (spec_decode)
                self.spec.tick(active)
            else:
                with tr.span("decode"):
                    table, lengths = self.kv.table, self.kv.lengths
                    if self._prefilling:
                        # rows mid-prefill must not write garbage K/V
                        # into their (real) pages, nor attend: point
                        # them at the trash page
                        table = table.copy()
                        lengths = lengths.copy()
                        for i in self._prefilling:
                            table[i, :] = TRASH_PAGE
                            lengths[i] = 0
                    ps = self.kv.page_size
                    step.attn_pages = int(
                        ((lengths[active] + ps) // ps).sum())
                    self._g_attn_share.set(step.attn_pages / table.size)
                    logits, self.pages = self._decode_paged(
                        self.params, jnp.asarray(self._tokens), self.pages,
                        jnp.asarray(table), jnp.asarray(lengths))
                    if self._shard_counts is not None:
                        self._shard_counts["decode_dispatches"] += 1
                # ONE fused dispatch for the whole decode batch;
                # inactive rows are sampled-and-discarded (the counter-
                # based PRNG makes discarded draws side-effect free)
                with tr.span("sample"):
                    res = self._run_sampler(logits[:, -1], slice(None),
                                            "decode")
                with tr.span("commit"):
                    for i in active:
                        self.kv.advance(i)
                        self._commit_token(i, self.rows[i], res, i)
        else:
            with tr.span("decode"):
                logits, self.cache = self._decode(
                    self.params, jnp.asarray(self._tokens), self.cache)
            with tr.span("sample"):
                res = self._run_sampler(logits[:, -1], slice(None),
                                        "decode")
            with tr.span("commit"):
                for i in active:
                    self._commit_token(i, self.rows[i], res, i)
        step.decode_rows = len(active)
        with tr.span("commit"):
            self._retire()
            self.sched.account(chunks, len(active))
        return len(active)

    def _stop_reason(self, r: Request) -> Optional[str]:
        """Terminal check for a decoding row: EOS or a stop sequence
        ("stop"), else the max_tokens budget ("length")."""
        if r.tokens and (r.tokens[-1] == self.eos_id
                         or sampling_lib.match_stop(r.tokens,
                                                    r.sampling.stop)):
            return FINISH_STOP
        if len(r.tokens) >= r.sampling.max_tokens:
            return FINISH_LENGTH
        return None

    def _retire(self) -> None:
        for i, r in enumerate(self.rows):
            if r is None or i in self._prefilling:
                continue
            reason = self._stop_reason(r)
            if reason is not None:
                self._finish(i, reason=reason)

    def pending(self) -> bool:
        """True while the engine has work: queued requests or occupied
        rows.  The public loop condition for callers driving their own
        ``step()`` loop (streamed serving).  On a shared scheduler only
        this engine's tenant lane counts."""
        if self.model_tag is not None:
            depth = self.sched.depth_by_model().get(self.model_tag, 0)
        else:
            depth = len(self.sched)
        return bool(depth or any(r is not None for r in self.rows))

    def cancel_queued(self) -> List[Request]:
        """Graceful-drain entry: remove every still-queued request (this
        engine's tenant lane only, on a shared scheduler) and mark it
        terminal with ``finish_reason="cancelled"`` — streaming clients
        see a terminal delta instead of a hung connection.  In-flight
        rows are untouched; keep ticking until ``pending()`` clears to
        let them finish."""
        now = _now_mono()
        out: List[Request] = []
        for r in self.sched.drain(model=self.model_tag):
            r.status = "cancelled"
            r.finish_reason = FINISH_CANCELLED
            r.finish_mono = now
            r.finish_time = _now_wall()
            self._counts["cancelled"] += 1
            self._counts["failed"] += 1
            if self.tracer.enabled:
                self.tracer.end(REQUEST_PID, r.uid, "queued")
                self.tracer.end(REQUEST_PID, r.uid, "request",
                                finish=FINISH_CANCELLED)
            self._failed.append(r)
            out.append(r)
        return out

    def run(self, max_ticks: int = 10000) -> List[Request]:
        ticks = 0
        while self.pending() and ticks < max_ticks:
            self.step()
            ticks += 1
        return self._done

    # ------------------------------------------------------------------
    @property
    def failed(self) -> List[Request]:
        """Requests refused (backpressure) or expired (deadline)."""
        return list(self._failed)

    @property
    def _n_preempt(self) -> int:
        """Legacy alias for the ``engine.preemptions`` counter."""
        return int(self._counts["preemptions"])

    @property
    def _n_ticks(self) -> int:
        """Legacy alias for the ``engine.ticks`` counter."""
        return int(self._counts["ticks"])

    def shutdown(self) -> None:
        """Final bookkeeping audit.  With ``debug_leak_check`` on a
        paged engine, runs the cache's refcount/leak audit over the
        now-idle pool; anomalies increment ``kv.leak_anomalies`` and
        the message lands in ``last_leak_error`` instead of raising
        (shutdown paths should report, not crash)."""
        if self.paged and self.debug_leak_check:
            try:
                self.kv.leak_check()
                if self.spec is not None:
                    self.spec.leak_check()   # draft pool audits too
            except AssertionError as e:
                self._leak_anomalies.inc()
                self.last_leak_error = str(e)

    def stats(self) -> Dict[str, Any]:
        # durations off the monotonic marks — NTP-step immune (the wall
        # *_time fields are display timestamps, never subtracted)
        lat = [r.finish_mono - r.submit_mono for r in self._done
               if r.finish_mono and r.submit_mono]
        ttft = [r.first_token_mono - r.submit_mono for r in self._done
                if r.first_token_mono and r.submit_mono]
        out = {
            "done": len(self._done),
            "failed": len(self._failed),
            # engine-level counter: per-request sums would miss requests
            # preempted (possibly mid-chunked-prefill) and still queued
            "preemptions": self._n_preempt,
            "tokens": sum(len(r.tokens) for r in self._done),
            "ticks": self._n_ticks,
            # why requests ended, and how often the fused sampler ran
            # (decode: exactly one dispatch per decoding tick, however
            # many distinct SamplingParams share the batch)
            "finish_reasons": dict(self._finish_counts),
            "sampler_dispatches": dict(self._sampler.dispatches),
            "sampler_time_s": round(self._sampler.time_s, 6),
        }
        if lat:
            out["latency_p50_s"] = float(np.percentile(lat, 50))
            out["latency_p99_s"] = float(np.percentile(lat, 99))
        if ttft:
            out["ttft_p50_s"] = float(np.percentile(ttft, 50))
            out["ttft_mean_s"] = float(np.mean(ttft))
        out.update(self.sched.snapshot())
        if self.paged:
            out["pages_in_use"] = self.kv.alloc.num_used
            out["pages_free"] = self.kv.alloc.num_free
            out.update(self.kv.prefix_stats())
            if self.spec is not None:
                out["spec"] = self.spec.stats()
        return out


def generate_batch(model: Model, params, prompts: List[np.ndarray],
                   max_new_tokens: int = 32, max_len: int = 512,
                   slots: int = 4, eos_id: int = 1,
                   extras: Optional[List[Dict]] = None,
                   sampling: Optional[List[SamplingParams]] = None,
                   **kwargs) -> List[List[int]]:
    """Convenience wrapper: submit all prompts, run to completion.

    ``sampling``: optional per-prompt SamplingParams (its max_tokens
    overrides ``max_new_tokens`` for that prompt).  All prompts are
    enqueued up front, so the queue bound is sized to the batch
    (backpressure is for live serving, not batch jobs)."""
    kwargs.setdefault("scheduler",
                      SchedulerConfig(max_queue=max(len(prompts), 1)))
    eng = Engine(model, params, slots=slots, max_len=max_len, eos_id=eos_id,
                 **kwargs)
    for i, p in enumerate(prompts):
        ok = eng.submit(Request(uid=i, prompt=np.asarray(p, np.int32),
                                max_new_tokens=max_new_tokens,
                                sampling=sampling[i] if sampling else None,
                                extras=extras[i] if extras else None))
        assert ok, f"request {i} rejected (queue/pool sizing)"
    done = eng.run()
    return [r.tokens for r in sorted(done, key=lambda r: r.uid)]
