"""Serving benchmark: continuous batching under increasing offered load.

For one dense and one hashed config (reduced qwen), drives the paged
continuous-batching engine at several concurrency levels and records

- tokens/s (decode throughput over the whole run),
- p50/p99 request latency (submit -> finish, includes queueing),
- p50 time-to-first-token, preemptions, pages in flight,

then writes ``BENCH_serving.json`` so the serving perf trajectory is
tracked in CI next to the policy and artifact benches.  Requests arrive
open-loop on a deterministic schedule (offered load ~ 2x what one row
sustains, so queueing pressure grows with the request count, and p99
spreads from p50 as concurrency saturates).

A second section benches the **shared-prefix workload** (N users x one
common system prompt — the millions-of-users common case): the same
request set runs with ``prefix_cache`` on vs off (both with chunked
prefill) and reports prefix-hit rate, pages saved, mean/p50 TTFT, and
tok/s, plus a token-identity cross-check between the two arms.

A third section benches the **mixed-sampling workload**: greedy,
seeded top-p, and stop-sequence rows share one decode batch (the fused
sampler's one-dispatch-per-tick contract), reporting tok/s, per-tick
sampler overhead, and the finish-reason split — plus a determinism
cross-check (a rerun with the same seeds must reproduce every token).

A fourth section benches **self-speculative decoding** on the hashed
config: the same workload runs with and without a compression-ladder
draft (`repro.serving.spec_decode`), reporting accept rate, tok/s for
both arms, and a bitwise token-identity cross-check (speculation must
never change what the engine emits).

A **sharded** section benches tensor-parallel serving: the same burst
workload runs on a single device and on a host-simulated mesh
(``Engine(mesh=...)``, page pool + attention heads sharded over
"model"), reporting tok/s for both arms, the sharded dispatch
counters, and a bitwise token-identity cross-check.

An **http_traffic** section drives the full asyncio HTTP front-end
(`repro.serving.http`) over a two-model engine sharing one page pool
(quota on the hashed tenant), replaying seeded Poisson and bursty
arrival processes as real streaming HTTP clients — reporting SLO
attainment, goodput, TTFT/e2e and queue-depth percentiles, plus
deterministic completed/429/504 counts and per-model token totals.

A fifth section measures **observability overhead**: the shared-prefix
workload with the span tracer off vs on, reporting the throughput
delta and a bitwise token-identity cross-check (tracing must never
change what the engine emits).  ``--trace-out`` exports the traced
arm's Perfetto file (the CI artifact).

A sixth section benches **batched ragged prefill** under a high
arrival rate: every request lands at t=0 (the burst that used to
serialize one chunk dispatch per request), and the same shared-prefix
workload runs with ``batched_prefill`` on vs off at a prefill budget
wide enough to coalesce — reporting mean/p50 TTFT for both arms, the
fused-dispatch counters, and a bitwise token-identity cross-check.

All counter numbers are workload-only deltas of the engine's metrics
registry (``repro.obs``) — snapshot after warmup, diff at the end —
instead of hand-rolled per-key subtraction.

    PYTHONPATH=src python -m benchmarks.serving_bench [--smoke]
        [--trace-out serving_trace.json]

``benchmarks/check_regression.py`` compares the emitted JSON against
the committed ``BENCH_serving.json`` baseline.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

import repro.configs as C
from repro.configs.reduced import reduced
from repro.models import build
from repro.obs import Tracer, diff_snapshots
from repro.serving.api import FINISH_REASONS, SamplingParams
from repro.serving.engine import Engine, Request
from repro.serving.scheduler import SchedulerConfig

# point-in-time gauges: meaningless as workload deltas, dropped from rows
_GAUGES = ("kv.pages_in_use", "kv.pages_free", "sched.queue_depth")


def _workload_delta(eng, base):
    """Registry delta since ``base``, gauges dropped."""
    d = diff_snapshots(eng.metrics.snapshot(), base)
    return {k: v for k, v in d.items() if k not in _GAUGES}


def _phase_histograms(reqs) -> dict:
    """The baseline's per-phase time summaries, read from the engine's
    account of the steps that advanced ``reqs`` (`repro.obs.trace.Step`,
    self seconds by phase): ``engine.decode_tick_s`` one per decoding
    step (its decode, sample and commit phases),
    ``engine.prefill_batch_s`` one per prefilling step and
    ``engine.prefill_chunk_s`` one per row chunk (its prefill phase)."""
    steps = list({id(s): s for r in reqs for s in r.steps}.values())

    def summary(vals):
        total = round(sum(vals), 9)
        return {"count": len(vals), "sum": total,
                "mean": round(total / len(vals), 9) if vals else 0.0}

    def phase(s, *names):
        return sum(s.phase_s.get(n, 0.0) for n in names)

    prefill = [s for s in steps if s.prefill_rows]
    return {
        "engine.decode_tick_s": summary(
            [phase(s, "decode", "sample", "commit")
             for s in steps if s.decode_rows]),
        "engine.prefill_batch_s": summary(
            [phase(s, "prefill") for s in prefill]),
        "engine.prefill_chunk_s": summary(
            [phase(s, "prefill") for s in prefill
             for _ in range(s.prefill_rows)])}


def _configs():
    base = reduced(C.get("qwen3-1.7b")).with_(dtype="float32")
    return [("qwen3-reduced-dense", base),
            ("qwen3-reduced-hashed", base.hashed_variant(0.125))]


def _requests(n: int, vocab: int, max_new: int, arrival_gap_s: float):
    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(n):
        plen = int(rng.integers(4, 24))
        reqs.append((uid * arrival_gap_s, Request(
            uid=uid,
            prompt=rng.integers(2, vocab, size=plen).astype(np.int32),
            max_new_tokens=max_new)))
    return reqs


def bench_level(model, params, cfg, *, concurrency: int, requests: int,
                max_new: int, max_len: int, page_size: int) -> dict:
    eng = Engine(model, params, max_concurrency=concurrency,
                 max_len=max_len, eos_id=-1, page_size=page_size,
                 scheduler=SchedulerConfig(max_queue=max(requests, 1)))
    # warmup: compile prefill buckets + decode before the clock starts
    eng.submit(Request(uid=-1, prompt=np.arange(5, dtype=np.int32) + 2,
                       max_new_tokens=2))
    eng.run()
    eng._done.clear()
    base = eng.metrics.snapshot()   # counter baseline: report deltas

    # offered load: one request per gap, ~2x one row's sustained rate
    gap = 0.0 if requests <= concurrency else 0.01
    schedule = _requests(requests, cfg.vocab_size, max_new, gap)
    t0 = time.perf_counter()
    pending = list(schedule)
    while pending or eng.pending():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            eng.submit(pending.pop(0)[1])
        eng.step()
    wall = time.perf_counter() - t0
    stats = eng.stats()
    d = _workload_delta(eng, base)
    d.update(_phase_histograms(eng._done))
    total_tokens = d["engine.tokens"]
    out = {"concurrency": concurrency, "requests": requests,
           "tokens": total_tokens,
           "wall_s": round(wall, 3),
           "tok_per_s": round(total_tokens / wall, 2),
           "done": stats["done"]}
    # latency/TTFT percentiles come from the workload's request set
    # (warmup requests were dropped from _done above)
    for k in ("latency_p50_s", "latency_p99_s",
              "ttft_p50_s", "ttft_mean_s"):
        if k in stats:
            out[k] = round(stats[k], 4)
    out["metrics"] = d
    return out


def bench_shared_prefix(model, params, cfg, *, concurrency: int,
                        users: int, sys_len: int, tail_len: int,
                        max_new: int, max_len: int, page_size: int,
                        prefill_chunk: int) -> dict:
    """N users x one system prompt, prefix cache on vs off."""
    rng = np.random.default_rng(1)
    sys_prompt = rng.integers(2, cfg.vocab_size,
                              size=sys_len).astype(np.int32)
    prompts = [np.concatenate([sys_prompt, rng.integers(
        2, cfg.vocab_size, size=tail_len).astype(np.int32)])
        for _ in range(users)]

    def run(prefix: bool):        # -> (stats dict, {uid: tokens})
        eng = Engine(model, params, max_concurrency=concurrency,
                     max_len=max_len, eos_id=-1, page_size=page_size,
                     prefix_cache=prefix, prefill_chunk=prefill_chunk,
                     scheduler=SchedulerConfig(max_queue=users + 2))
        # warmup compiles this arm's whole steady state: the cold
        # chunked prefill AND (prefix arm) the hit path — gather +
        # tail-chunk bucket — via a second request sharing the prefix
        warm_tail = np.asarray([2, 3] * (tail_len // 2 + 1),
                               np.int32)[:tail_len]
        for uid, tail in ((-1, warm_tail), (-2, warm_tail[::-1].copy())):
            eng.submit(Request(
                uid=uid, prompt=np.concatenate([sys_prompt, tail]),
                max_new_tokens=2))
        eng.run()
        eng._done.clear()
        # cumulative engine/tree counters include the warmup admissions;
        # report workload-only registry deltas so the headline hit-rate
        # and pages-saved numbers measure the measured requests alone
        base = eng.metrics.snapshot()

        reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run()
        wall = time.perf_counter() - t0
        eng.kv.leak_check()
        stats = eng.stats()
        d = _workload_delta(eng, base)
        tokens = d["engine.tokens"]

        hit = d.get("prefix.hit_tokens", 0)
        miss = d.get("prefix.miss_tokens", 0)
        shared = d.get("kv.pages_shared", 0)
        fresh = d.get("kv.pages_fresh", 0)
        out = {"tok_per_s": round(tokens / wall, 2),
               "wall_s": round(wall, 3),
               "ttft_mean_s": round(stats.get("ttft_mean_s", 0.0), 4),
               "ttft_p50_s": round(stats.get("ttft_p50_s", 0.0), 4),
               "prefix_hit_rate": round(hit / (hit + miss), 4)
               if hit + miss else 0.0,
               "pages_shared": shared,
               "pages_fresh": fresh,
               "pages_saved_frac": round(shared / (shared + fresh), 4)
               if shared + fresh else 0.0,
               "prefill_chunks": d["sched.prefill_chunks"],
               "preemptions": d["engine.preemptions"]}
        return out, {r.uid: list(r.tokens) for r in reqs}

    off, toks_off = run(False)
    on, toks_on = run(True)
    row = {"concurrency": concurrency, "users": users,
           "sys_prompt_len": sys_len, "tail_len": tail_len,
           "max_new": max_new, "prefill_chunk": prefill_chunk,
           "off": off, "on": on,
           "tokens_match": toks_on == toks_off,
           "pages_saved_frac": on["pages_saved_frac"],
           "ttft_speedup": round(off["ttft_mean_s"]
                                 / max(on["ttft_mean_s"], 1e-9), 3)}
    print(f"shared-prefix @ c={concurrency}: saved "
          f"{100 * row['pages_saved_frac']:.0f}% pages, hit rate "
          f"{on['prefix_hit_rate']:.2f}, ttft {off['ttft_mean_s']:.3f}s "
          f"-> {on['ttft_mean_s']:.3f}s "
          f"({row['ttft_speedup']}x), match={row['tokens_match']}")
    return row


def bench_mixed_sampling(model, params, cfg, *, concurrency: int,
                         requests: int, max_new: int, max_len: int,
                         page_size: int) -> dict:
    """Greedy + seeded top-p + stop-sequence rows in ONE decode batch.

    Measures the fused sampler's overhead (one dispatch per decode tick
    however the batch mixes SamplingParams) and cross-checks seeded
    determinism: a second run with identical seeds must reproduce every
    token.
    """
    rng = np.random.default_rng(2)
    reqs_spec = []
    for uid in range(requests):
        plen = int(rng.integers(4, 20))
        prompt = rng.integers(2, cfg.vocab_size,
                              size=plen).astype(np.int32)
        kind = ("greedy", "top_p", "stop")[uid % 3]
        if kind == "greedy":
            sp = SamplingParams(max_tokens=max_new)
        elif kind == "top_p":
            sp = SamplingParams(temperature=0.8, top_p=0.9, top_k=64,
                                max_tokens=max_new, seed=1000 + uid)
        else:   # sampled with a 1-token stop sequence (may trigger)
            sp = SamplingParams(temperature=1.0, top_p=0.95,
                                max_tokens=max_new, seed=1000 + uid,
                                stop=((int(rng.integers(
                                    2, cfg.vocab_size)),),))
        reqs_spec.append((prompt, sp, kind))

    def run():
        eng = Engine(model, params, max_concurrency=concurrency,
                     max_len=max_len, eos_id=-1, page_size=page_size,
                     scheduler=SchedulerConfig(max_queue=requests + 2))
        # warmup: compile prefill buckets + decode + the sampler
        # variants the workload will hit (all-greedy ticks dispatch the
        # with_sampling=False specialization, mixed ticks the full one)
        eng.submit(Request(uid=-1, prompt=np.arange(6, dtype=np.int32) + 2,
                           sampling=SamplingParams(temperature=0.7,
                                                   top_p=0.9, top_k=64,
                                                   max_tokens=2,
                                                   seed=0)))
        eng.submit(Request(uid=-2, prompt=np.arange(5, dtype=np.int32) + 2,
                           sampling=SamplingParams(max_tokens=2)))
        eng.run()
        eng.submit(Request(uid=-3, prompt=np.arange(7, dtype=np.int32) + 2,
                           sampling=SamplingParams(max_tokens=2)))
        eng.run()                  # an all-greedy batch, alone
        eng._done.clear()
        base = eng.metrics.snapshot()  # counter baseline: report deltas
        t0 = time.perf_counter()
        for uid, (prompt, sp, _) in enumerate(reqs_spec):
            eng.submit(Request(uid=uid, prompt=prompt.copy(),
                               sampling=sp))
        done = eng.run()
        wall = time.perf_counter() - t0
        d = _workload_delta(eng, base)
        ticks = d["engine.ticks"]
        sampler_s = d["sampler.dispatch_s"]["sum"]
        toks = {r.uid: list(r.tokens) for r in done}
        return {"tok_per_s": round(d["engine.tokens"] / wall, 2),
                "wall_s": round(wall, 3),
                "ticks": ticks,
                "sampler_time_s": round(sampler_s, 4),
                "sampler_ms_per_tick": round(1e3 * sampler_s
                                             / max(ticks, 1), 3),
                "sampler_frac": round(sampler_s / wall, 4),
                "sampler_dispatches": {
                    k: d[f"sampler.dispatches.{k}"]
                    for k in ("prefill", "decode")},
                "finish_reasons": {
                    k: d[f"engine.finish.{k}"]
                    for k in FINISH_REASONS}}, toks

    a, toks_a = run()
    _, toks_b = run()
    row = {"concurrency": concurrency, "requests": requests,
           "max_new": max_new,
           "mix": [k for _, _, k in reqs_spec],
           "deterministic_rerun": toks_a == toks_b}
    row.update(a)
    print(f"mixed-sampling @ c={concurrency}: {a['tok_per_s']} tok/s, "
          f"sampler {a['sampler_ms_per_tick']}ms/tick "
          f"({100 * a['sampler_frac']:.1f}% of wall), finish "
          f"{a['finish_reasons']}, rerun-identical="
          f"{row['deterministic_rerun']}")
    return row


def bench_spec_decode(model, params, cfg, *, concurrency: int,
                      requests: int, max_new: int, max_len: int,
                      page_size: int, spec_k: int,
                      draft_policy: str) -> dict:
    """Self-speculative decoding: spec on vs off, same workload.

    The draft is the compression-policy variant named by
    ``draft_policy``, derived off the served params (shared hash seeds;
    at the config's own ratio the banks alias by reference, so the
    draft is the base and every proposal verifies — the deterministic
    upper bound on accept rate).  Reports accept rate, tok/s both arms,
    and a bitwise token-identity cross-check — speculation must never
    change what the engine emits, only how fast it emits it.
    """
    from repro.serving.draft import build_draft
    _, dmodel, dparams = build_draft(cfg, params, draft_policy)

    rng = np.random.default_rng(4)
    reqs_spec = []
    for uid in range(requests):
        plen = int(rng.integers(4, 20))
        prompt = rng.integers(2, cfg.vocab_size,
                              size=plen).astype(np.int32)
        sp = SamplingParams(max_tokens=max_new) if uid % 3 else \
            SamplingParams(temperature=0.8, top_p=0.9, top_k=64,
                           max_tokens=max_new, seed=2000 + uid)
        reqs_spec.append((prompt, sp))

    def run(draft):
        eng = Engine(model, params, max_concurrency=concurrency,
                     max_len=max_len, eos_id=-1, page_size=page_size,
                     draft=draft, spec_k=spec_k,
                     scheduler=SchedulerConfig(max_queue=requests + 2))
        # Steady-state measurement: the first pass over the workload
        # pays every jit specialization its sampler mix and batch widths
        # dispatch — and the spec arm has strictly more shapes to
        # compile (propose/verify variants on top of the sampler
        # blocks).  Warm with the *full* workload, then time a clean
        # second pass, so neither arm is billed for compiles.
        for uid, (prompt, sp) in enumerate(reqs_spec):
            eng.submit(Request(uid=uid, prompt=prompt.copy(),
                               sampling=sp))
        eng.run()
        eng._done.clear()
        base = eng.metrics.snapshot()
        t0 = time.perf_counter()
        for uid, (prompt, sp) in enumerate(reqs_spec):
            eng.submit(Request(uid=uid, prompt=prompt.copy(),
                               sampling=sp))
        done = eng.run()
        wall = time.perf_counter() - t0
        d = _workload_delta(eng, base)
        toks = {r.uid: list(r.tokens) for r in done}
        spec_stats = None
        if eng.spec is not None:
            # per-pass accept stats from the registry delta, not the
            # decoder's lifetime counters (those include the warm pass)
            spec_stats = {
                "accept_rate": d["spec.accepted_drafts"]
                / max(d["spec.proposed"], 1),
                "mean_accept_len": d["spec.accept_len"]["mean"],
                "draft_dispatches": d["spec.draft_dispatches"],
                "verify_dispatches": d["spec.verify_dispatches"]}
        return (round(d["engine.tokens"] / wall, 2), toks, spec_stats, d)

    base_tps, toks_base, _, _ = run(None)
    spec_tps, toks_spec, spec_stats, d = run((dmodel, dparams))
    row = {"concurrency": concurrency, "requests": requests,
           "max_new": max_new, "spec_k": spec_k,
           "draft_policy": draft_policy,
           "tokens_match": toks_base == toks_spec,
           "baseline_tok_s": base_tps,
           "spec_tok_s": spec_tps,
           "speedup": round(spec_tps / base_tps, 3) if base_tps else 0.0,
           "accept_rate": round(spec_stats["accept_rate"], 4),
           "mean_accept_len": round(spec_stats["mean_accept_len"], 3),
           "draft_dispatches": spec_stats["draft_dispatches"],
           "verify_dispatches": spec_stats["verify_dispatches"]}
    print(f"spec-decode @ c={concurrency} k={spec_k} "
          f"draft={draft_policy}: {base_tps} -> {spec_tps} tok/s "
          f"({row['speedup']}x), accept {row['accept_rate']:.2f} "
          f"(mean len {row['mean_accept_len']:.2f}), "
          f"match={row['tokens_match']}")
    return row


def bench_obs_overhead(model, params, cfg, *, concurrency: int,
                       users: int, sys_len: int, tail_len: int,
                       max_new: int, max_len: int, page_size: int,
                       prefill_chunk: int,
                       trace_out: str = None) -> dict:
    """Tracer off vs on over the shared-prefix workload (the busiest
    instrumented path: chunked prefill + prefix hits + COW + decode).

    Acceptance target: < 3% tok/s regression with full tracing.  Also
    cross-checks that tracing is bitwise inert (same tokens) and, with
    ``trace_out``, exports the traced arm for Perfetto (CI artifact).
    """
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(2, cfg.vocab_size,
                              size=sys_len).astype(np.int32)
    prompts = [np.concatenate([sys_prompt, rng.integers(
        2, cfg.vocab_size, size=tail_len).astype(np.int32)])
        for _ in range(users)]

    def run(trace: bool):
        tracer = Tracer(enabled=trace)
        eng = Engine(model, params, max_concurrency=concurrency,
                     max_len=max_len, eos_id=-1, page_size=page_size,
                     prefix_cache=True, prefill_chunk=prefill_chunk,
                     tracer=tracer,
                     scheduler=SchedulerConfig(max_queue=users + 2))
        warm_tail = np.asarray([2, 3] * (tail_len // 2 + 1),
                               np.int32)[:tail_len]
        for uid, tail in ((-1, warm_tail), (-2, warm_tail[::-1].copy())):
            eng.submit(Request(
                uid=uid, prompt=np.concatenate([sys_prompt, tail]),
                max_new_tokens=2))
        eng.run()
        eng._done.clear()
        reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run()
        wall = time.perf_counter() - t0
        tokens = sum(len(r.tokens) for r in reqs)
        return (round(tokens / wall, 2),
                {r.uid: list(r.tokens) for r in reqs}, tracer)

    tps_off, toks_off, _ = run(False)
    tps_on, toks_on, tracer = run(True)
    if trace_out:
        tracer.export(trace_out)
        print(f"wrote trace -> {os.path.abspath(trace_out)}")
    overhead = round((tps_off - tps_on) / tps_off, 4) if tps_off else 0.0
    row = {"concurrency": concurrency, "users": users,
           "sys_prompt_len": sys_len, "tail_len": tail_len,
           "max_new": max_new, "prefill_chunk": prefill_chunk,
           "tok_per_s_trace_off": tps_off,
           "tok_per_s_trace_on": tps_on,
           "overhead_frac": overhead,
           "trace_events": len(tracer.events),
           "tokens_match": toks_on == toks_off}
    print(f"obs overhead @ c={concurrency}: {tps_off} tok/s untraced -> "
          f"{tps_on} tok/s traced ({100 * overhead:+.1f}%), "
          f"{row['trace_events']} events, match={row['tokens_match']}")
    return row


def bench_prefill_batch(model, params, cfg, *, concurrency: int,
                        users: int, sys_len: int, tail_len: int,
                        max_new: int, max_len: int, page_size: int,
                        prefill_chunk: int) -> dict:
    """High-arrival-rate TTFT: batched ragged prefill on vs off.

    All requests arrive at t=0 (a burst) and the prefill budget
    (``max_prefills_per_tick``) covers the whole batch, so the batched
    arm coalesces every row's chunk into one ragged dispatch per tick
    while the sequential arm pays one dispatch per row per tick — the
    serialization this section exists to measure.  Shared-prefix
    prompts keep the prefix cache in the loop (COW tail resolution on
    the batched path).  Cross-checks bitwise token identity between
    arms and reports the fused-dispatch counters.
    """
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(2, cfg.vocab_size,
                              size=sys_len).astype(np.int32)
    prompts = [np.concatenate([sys_prompt, rng.integers(
        2, cfg.vocab_size, size=tail_len).astype(np.int32)])
        for _ in range(users)]

    def run(batched: bool):
        eng = Engine(model, params, max_concurrency=concurrency,
                     max_len=max_len, eos_id=-1, page_size=page_size,
                     prefix_cache=True, prefill_chunk=prefill_chunk,
                     batched_prefill=batched,
                     scheduler=SchedulerConfig(
                         max_queue=users + 2,
                         max_prefills_per_tick=concurrency))
        # warmup compiles the arm's steady state: cold chunked prefill,
        # the prefix-hit path, and (batched arm) the ragged dispatch
        # widths the burst will hit
        warm_tail = np.asarray([2, 3] * (tail_len // 2 + 1),
                               np.int32)[:tail_len]
        for uid in range(-concurrency, 0):
            tail = warm_tail if uid % 2 else warm_tail[::-1].copy()
            eng.submit(Request(
                uid=uid, prompt=np.concatenate([sys_prompt, tail]),
                max_new_tokens=2))
        eng.run()
        eng._done.clear()
        base = eng.metrics.snapshot()

        reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for r in reqs:               # the burst: all requests at t=0
            eng.submit(r)
        eng.run()
        wall = time.perf_counter() - t0
        eng.kv.leak_check()
        stats = eng.stats()
        d = _workload_delta(eng, base)
        out = {"tok_per_s": round(d["engine.tokens"] / wall, 2),
               "wall_s": round(wall, 3),
               "ttft_mean_s": round(stats.get("ttft_mean_s", 0.0), 4),
               "ttft_p50_s": round(stats.get("ttft_p50_s", 0.0), 4),
               "prefill_chunks": d["sched.prefill_chunks"],
               "prefill_batch_dispatches":
                   d.get("engine.prefill_batch.dispatches", 0),
               "prefill_batch_rows":
                   d.get("engine.prefill_batch.rows", 0),
               "prefill_batch_tokens":
                   d.get("engine.prefill_batch.tokens", 0),
               "fallback_chunks":
                   d.get("engine.prefill_batch.fallback_chunks", 0)}
        return out, {r.uid: list(r.tokens) for r in reqs}

    off, toks_off = run(False)
    on, toks_on = run(True)
    row = {"concurrency": concurrency, "users": users,
           "sys_prompt_len": sys_len, "tail_len": tail_len,
           "max_new": max_new, "prefill_chunk": prefill_chunk,
           "off": off, "on": on,
           "tokens_match": toks_on == toks_off,
           "prefill_batch_dispatches": on["prefill_batch_dispatches"],
           "prefill_batch_rows": on["prefill_batch_rows"],
           "fallback_chunks": on["fallback_chunks"],
           "ttft_speedup": round(off["ttft_mean_s"]
                                 / max(on["ttft_mean_s"], 1e-9), 3)}
    print(f"prefill-batch @ c={concurrency}: ttft "
          f"{off['ttft_mean_s']:.3f}s -> {on['ttft_mean_s']:.3f}s "
          f"({row['ttft_speedup']}x), "
          f"{on['prefill_batch_dispatches']} fused dispatches / "
          f"{on['prefill_batch_rows']} row-chunks, "
          f"match={row['tokens_match']}")
    return row


def bench_sharded(model, params, cfg, *, concurrency: int, requests: int,
                  max_new: int, max_len: int, page_size: int) -> dict:
    """Tensor-parallel serving: mesh engine vs single device.

    The same burst workload (greedy + seeded top-p rows) runs once
    without a mesh and once with ``Engine(mesh=...)`` sharding the page
    pool and attention heads over the "model" axis; reports tok/s for
    both arms, the sharded dispatch counters, and the section's reason
    to exist: a bitwise token-identity cross-check (head-sharding with
    an exact all-gather must never change what the engine emits).

    CI provides the devices via a host-simulated mesh
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); with one
    device the section records the skip instead of failing.
    """
    ndev = jax.device_count()
    tp = next((t for t in (8, 4, 2)
               if t <= ndev and cfg.num_heads % t == 0
               and cfg.num_kv_heads % t == 0), 1)
    if ndev < 2 or tp < 2:
        print(f"sharded: skipped (devices={ndev}, usable tp={tp})")
        return {"skipped": True, "devices": ndev, "tp": tp}
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(tp)

    rng = np.random.default_rng(6)
    reqs_spec = []
    for uid in range(requests):
        plen = int(rng.integers(4, 20))
        prompt = rng.integers(2, cfg.vocab_size,
                              size=plen).astype(np.int32)
        sp = SamplingParams(max_tokens=max_new) if uid % 2 else \
            SamplingParams(temperature=0.8, top_p=0.9, top_k=64,
                           max_tokens=max_new, seed=3000 + uid)
        reqs_spec.append((prompt, sp))

    def run(m):
        eng = Engine(model, params, max_concurrency=concurrency,
                     max_len=max_len, eos_id=-1, page_size=page_size,
                     mesh=m,
                     scheduler=SchedulerConfig(max_queue=requests + 2))
        # warmup compiles the prefill buckets + decode + both sampler
        # specializations (all-greedy and mixed ticks)
        eng.submit(Request(uid=-1, prompt=np.arange(6, dtype=np.int32) + 2,
                           sampling=SamplingParams(
                               temperature=0.7, top_p=0.9, top_k=64,
                               max_tokens=2, seed=0)))
        eng.submit(Request(uid=-2, prompt=np.arange(5, dtype=np.int32) + 2,
                           max_new_tokens=2))
        eng.run()
        eng._done.clear()
        base = eng.metrics.snapshot()
        t0 = time.perf_counter()
        for uid, (prompt, sp) in enumerate(reqs_spec):
            eng.submit(Request(uid=uid, prompt=prompt.copy(),
                               sampling=sp))
        done = eng.run()
        wall = time.perf_counter() - t0
        d = _workload_delta(eng, base)
        return (round(d["engine.tokens"] / wall, 2), round(wall, 3),
                {r.uid: list(r.tokens) for r in done}, d)

    single_tps, single_wall, toks_single, _ = run(None)
    shard_tps, shard_wall, toks_shard, d = run(mesh)
    row = {"devices": mesh.size, "tp": tp,
           "concurrency": concurrency, "requests": requests,
           "max_new": max_new,
           "tokens_match": toks_single == toks_shard,
           "single_tok_s": single_tps, "sharded_tok_s": shard_tps,
           "single_wall_s": single_wall, "sharded_wall_s": shard_wall,
           "shard_decode_dispatches":
               d.get("engine.shard.decode_dispatches", 0),
           "shard_prefill_dispatches":
               d.get("engine.shard.prefill_dispatches", 0)}
    print(f"sharded tp={tp} over {mesh.size} devices: {single_tps} tok/s "
          f"single -> {shard_tps} tok/s sharded, "
          f"{row['shard_decode_dispatches']} decode + "
          f"{row['shard_prefill_dispatches']} prefill dispatches, "
          f"match={row['tokens_match']}")
    return row


def bench_http_traffic(dense_pack, hashed_pack, *, requests: int,
                       max_new: int, max_len: int, page_size: int,
                       quota_pages: int, burst_size: int) -> dict:
    """Arrival-process traffic through the full HTTP stack.

    Hosts the dense + hashed configs on one ``MultiModelEngine``
    (shared page pool, quota on the hashed tenant) behind the asyncio
    front-end, then replays two seeded arrival processes as real
    streaming HTTP clients:

    - **poisson** — exponential inter-arrivals at a fixed offered rate
      (the steady-state mixed-tenant load), and
    - **bursty** — all-at-once bursts of ``burst_size`` (the worst-case
      admission/queueing pattern).

    Per run: SLO attainment (client-side TTFT + e2e against fixed
    SLOs), goodput (tokens from SLO-met requests only), TTFT/e2e
    percentiles, queue-depth percentiles sampled during the run, and
    the deterministic accounting — completed / 429-rejected /
    504-expired counts and per-model token totals (greedy + fixed
    ``max_tokens``: exact counters the regression gate holds TIGHT).
    """
    import asyncio

    from repro.serving.http import HTTPFrontend
    from repro.serving.http import client as http_client
    from repro.serving.multi_model import MultiModelEngine

    model, params, cfg = dense_pack
    hmodel, hparams, hcfg = hashed_pack
    names = ("qwen3-reduced-dense", "qwen3-reduced-hashed")
    slo_ttft_s, slo_e2e_s = 2.0, 20.0

    def _arrivals(kind):
        """Seeded (t_arrive, model, prompt, seq) schedule."""
        rng = np.random.default_rng(7 if kind == "poisson" else 8)
        rate_rps = 40.0
        out, t = [], 0.0
        for i in range(requests):
            if kind == "poisson":
                t += float(rng.exponential(1.0 / rate_rps))
            else:
                t = (i // burst_size) * 0.25
            plen = int(rng.integers(4, 16))
            prompt = [int(x) for x in
                      rng.integers(2, cfg.vocab_size, size=plen)]
            out.append((t, names[int(rng.integers(0, 2))], prompt, i))
        return rate_rps, out

    async def _one_run(kind):
        mm = MultiModelEngine(page_size=page_size,
                              scheduler=SchedulerConfig(
                                  max_queue=requests + 4))
        mm.add_model(names[0], model, params, slots=4, max_len=max_len,
                     eos_id=-1, seed=0)
        mm.add_model(names[1], hmodel, hparams, slots=4,
                     max_len=max_len, eos_id=-1, seed=0,
                     page_quota=quota_pages)
        fe = HTTPFrontend(mm, port=0, default_model=names[0])
        await fe.start()
        # warmup: compile both models' prefill + decode off the clock
        for nm in names:
            await http_client.request(
                fe.host, fe.port, "POST", "/v1/completions",
                dict(model=nm, prompt=[2, 3, 4, 5], max_tokens=2,
                     temperature=0.0))

        rate_rps, sched = _arrivals(kind)
        loop = asyncio.get_running_loop()
        depths, stop = [], asyncio.Event()

        async def _sample_depth():
            while not stop.is_set():
                depths.append(len(mm.sched))
                await asyncio.sleep(0.004)

        async def _client(t_arrive, mdl, prompt, seq, t0):
            await asyncio.sleep(max(0.0, t_arrive - (loop.time() - t0)))
            payload = dict(model=mdl, prompt=prompt,
                           max_tokens=max_new, temperature=0.0)
            try:
                r = await http_client.collect_stream(
                    fe.host, fe.port, payload)
            except http_client.HTTPStreamError as e:
                return {"model": mdl, "status": e.status}
            return {"model": mdl, "status": 200,
                    "tokens": len(r["tokens"]),
                    "ttft_s": r["ttft_s"], "e2e_s": r["e2e_s"]}

        sampler = asyncio.create_task(_sample_depth())
        t0 = loop.time()
        results = await asyncio.gather(
            *(_client(*spec, t0) for spec in sched))
        wall = loop.time() - t0
        stop.set()
        await sampler
        await fe.aclose()

        ok = [r for r in results if r["status"] == 200]
        met = [r for r in ok
               if r["ttft_s"] is not None and r["ttft_s"] <= slo_ttft_s
               and r["e2e_s"] <= slo_e2e_s]
        per_model = {nm: sum(r["tokens"] for r in ok
                             if r["model"] == nm) for nm in names}
        total = sum(per_model.values())
        pct = lambda xs, q: round(  # noqa: E731
            float(np.percentile(xs, q)), 4) if xs else 0.0
        row = {"arrival": kind, "requests": requests,
               "rate_rps": rate_rps if kind == "poisson" else None,
               "bursts": None if kind == "poisson"
               else -(-requests // burst_size),
               "burst_size": None if kind == "poisson" else burst_size,
               "max_new": max_new, "models": list(names),
               "quota_pages": quota_pages,
               "slo_ttft_s": slo_ttft_s, "slo_e2e_s": slo_e2e_s,
               "completed": len(ok),
               "rejected_429": sum(1 for r in results
                                   if r["status"] == 429),
               "expired_504": sum(1 for r in results
                                  if r["status"] == 504),
               "per_model_tokens": per_model,
               "slo_attainment": round(len(met) / max(len(ok), 1), 4),
               "goodput_tok_s": round(
                   sum(r["tokens"] for r in met) / wall, 2),
               "tok_per_s": round(total / wall, 2),
               "wall_s": round(wall, 3),
               "ttft_p50_s": pct([r["ttft_s"] for r in ok], 50),
               "ttft_p99_s": pct([r["ttft_s"] for r in ok], 99),
               "e2e_p50_s": pct([r["e2e_s"] for r in ok], 50),
               "e2e_p99_s": pct([r["e2e_s"] for r in ok], 99),
               "queue_depth_p50": pct(depths, 50),
               "queue_depth_p95": pct(depths, 95)}
        print(f"http_traffic/{kind}: {row['completed']}/{requests} ok, "
              f"{row['tok_per_s']} tok/s, slo {row['slo_attainment']}, "
              f"goodput {row['goodput_tok_s']} tok/s, "
              f"ttft p99 {row['ttft_p99_s']}s, "
              f"qdepth p95 {row['queue_depth_p95']}")
        return row

    return {"runs": [asyncio.run(_one_run("poisson")),
                     asyncio.run(_one_run("bursty"))]}


def main(smoke: bool = False, out_json: str = "BENCH_serving.json",
         trace_out: str = None) -> dict:
    levels = (1, 2, 4) if smoke else (1, 4, 8)
    requests = 6 if smoke else 24
    max_new = 8 if smoke else 24
    results = {"smoke": smoke, "levels": list(levels), "configs": []}
    dense = None                 # (model, params) reused for shared-prefix
    hashed = None                # (model, params) reused for spec-decode
    for tag, cfg in _configs():
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        if dense is None:
            dense = (model, params, cfg)
        if cfg.hashed:
            hashed = (model, params, cfg)
        rows = []
        for c in levels:
            r = bench_level(model, params, cfg, concurrency=c,
                            requests=requests, max_new=max_new,
                            max_len=128, page_size=16)
            print(f"{tag} @ concurrency {c}: {r['tok_per_s']} tok/s, "
                  f"p50 {r.get('latency_p50_s', '-')}s "
                  f"p99 {r.get('latency_p99_s', '-')}s")
            rows.append(r)
        results["configs"].append({"name": tag,
                                   "hashed": bool(cfg.hashed),
                                   "levels": rows})
    # shared-prefix workload on the dense config (the prefix cache is
    # model-agnostic; one arm suffices to track the trajectory)
    model, params, cfg = dense
    results["shared_prefix"] = bench_shared_prefix(
        model, params, cfg, concurrency=8,
        users=8 if smoke else 16,
        sys_len=48 if smoke else 64, tail_len=8,
        max_new=4 if smoke else 16, max_len=128, page_size=16,
        prefill_chunk=32)
    # mixed-sampling workload (fused sampler: greedy + top-p + stop
    # rows share one batch, one dispatch per tick)
    results["mixed_sampling"] = bench_mixed_sampling(
        model, params, cfg, concurrency=4,
        requests=6 if smoke else 18,
        max_new=6 if smoke else 20, max_len=128, page_size=16)
    # self-speculative decoding on the hashed config: the draft is the
    # policy ladder's own rung (equal ratio -> banks alias, proposals
    # verify deterministically — the free-draft upper bound).  Low
    # concurrency + decode-heavy requests is the regime speculation is
    # for: each verified block replaces k+1 per-token dispatches, and
    # at small batch the baseline has no batching to amortize against.
    hmodel, hparams, hcfg = hashed
    results["spec_decode"] = bench_spec_decode(
        hmodel, hparams, hcfg, concurrency=2,
        requests=6 if smoke else 18,
        max_new=24, max_len=128, page_size=16,
        spec_k=4, draft_policy="1/8")
    # batched ragged prefill: burst arrival, batched on vs off
    results["prefill_batch"] = bench_prefill_batch(
        model, params, cfg, concurrency=8,
        users=8 if smoke else 16,
        sys_len=48 if smoke else 64, tail_len=8,
        max_new=4 if smoke else 16, max_len=128, page_size=16,
        prefill_chunk=16)
    # tensor-parallel serving: mesh vs single device, same workload
    # (needs a multi-device host mesh; records the skip otherwise)
    results["sharded"] = bench_sharded(
        model, params, cfg, concurrency=4,
        requests=6 if smoke else 12,
        max_new=6 if smoke else 16, max_len=128, page_size=16)
    # HTTP traffic harness: both configs behind the asyncio front-end
    # on one multi-model engine, seeded Poisson + bursty arrivals
    results["http_traffic"] = bench_http_traffic(
        dense, hashed,
        requests=8 if smoke else 20,
        max_new=4 if smoke else 8, max_len=128, page_size=16,
        quota_pages=40, burst_size=4)
    # observability overhead: tracer off vs on, same workload
    results["obs_overhead"] = bench_obs_overhead(
        model, params, cfg, concurrency=8,
        users=8 if smoke else 16,
        sys_len=48 if smoke else 64, tail_len=8,
        max_new=4 if smoke else 16, max_len=128, page_size=16,
        prefill_chunk=32, trace_out=trace_out)
    with open(out_json, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {os.path.abspath(out_json)}")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="export the traced obs-overhead arm as Chrome "
                         "trace-event JSON (open in Perfetto)")
    a = ap.parse_args()
    main(smoke=a.smoke, out_json=a.out, trace_out=a.trace_out)
