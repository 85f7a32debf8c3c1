"""Serving runner: batched prefill/decode with the continuous-batching
engine (repro.serving.engine).

    python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --requests 8 --max-new 16 \
        --temperature 0.8 --top-p 0.9 --seed 0 --stream

Sampling flags (--temperature/--top-k/--top-p/--min-p/--seed/--stop/
--logprobs) build one SamplingParams per request; --stream prints
RequestOutput deltas as tokens land.  With --seed, a rerun reproduces
every token (counter-based per-request PRNG streams).

Three cold-start sources, in priority order:

- --artifact <file.hnart>: compressed model artifact (config + hash
  seeds + banks in one mmap-able file; repro.artifact) — the production
  path: no checkpoint, no live config flags needed.
- --model-name <name[@version]> --registry <root>: resolve the artifact
  through the versioned registry (sha256-verified).
- --arch [--ckpt-dir]: build from config; load the generic training
  checkpoint if present, else random init.

Prints per-request generations + aggregate throughput.

Observability: --metrics-out dumps the full metrics-registry snapshot
as JSON (and prints a human-readable table on exit); --trace-out
records the engine's steps and their phases and per-request spans
(queued / prefill chunks / first token / preemptions / COW copies) as
Chrome trace-event JSON on the profiler's clock — open the file at
https://ui.perfetto.dev, beside a device trace of the same run.
--debug-leak-check audits the paged KV cache's refcounts at shutdown.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

import repro.configs as C
from repro import policy
from repro.configs.reduced import reduced as reduce_cfg
from repro.models import build
from repro.runtime import enable_compile_cache
from repro.serving.api import SamplingParams
from repro.serving.engine import Engine, Request
from repro.train import checkpoint as ckpt_lib


def main() -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, choices=C.names())
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--hashed", action="store_true")
    p.add_argument("--compression", type=float, default=None,
                   help="hashed compression ratio (default 0.125)")
    p.add_argument("--policy", default=None,
                   help="compression policy JSON (per-slot rules; "
                        "implies hashing)")
    p.add_argument("--budget", default=None,
                   help="equal-memory real-param target ratio "
                        "('0.125' or '1/8'; implies hashing)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=None,
                   help="deprecated alias for --max-concurrency")
    p.add_argument("--max-concurrency", type=int, default=None,
                   help="decode batch width (rows admitted mid-flight)")
    p.add_argument("--page-size", type=int, default=16,
                   help="KV cache page size in tokens (paged decoders)")
    p.add_argument("--num-pages", type=int, default=None,
                   help="physical KV page pool size; default fully "
                        "provisions every row, less oversubscribes "
                        "(preemption absorbs overflow)")
    p.add_argument("--scheduler", default="fifo",
                   choices=("fifo", "priority"),
                   help="admission policy (FIFO within priority class)")
    p.add_argument("--queue-limit", type=int, default=256,
                   help="bounded queue depth; submits beyond are refused")
    p.add_argument("--deadline", type=float, default=None,
                   help="max queue wait in seconds before a request "
                        "expires unserved")
    p.add_argument("--attn-impl", default=None,
                   choices=("ref", "pallas"),
                   help="paged attention: gather oracle or the Pallas "
                        "kernels (default: the platform's choice, the "
                        "decode kernel on a TPU and the oracle "
                        "elsewhere)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="dedup shared prompt prefixes across requests "
                        "(radix tree over KV pages, refcounts + "
                        "copy-on-write; paged decoders only)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill long prompts N tokens per tick, "
                        "interleaved with decode (default: monolithic "
                        "prefill; paged decoders only)")
    p.add_argument("--spec-draft", default=None, metavar="POLICY",
                   help="enable self-speculative decoding: a policy "
                        "JSON path or compression ratio ('1/8') naming "
                        "the draft variant derived off the served "
                        "weights (same hash seeds; equal-ratio aliases "
                        "by reference).  Output stays bitwise identical "
                        "to non-speculative decode")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft proposal depth per tick (with "
                        "--spec-draft); with --spec-adaptive this is "
                        "k_max")
    p.add_argument("--spec-adaptive", action="store_true",
                   help="adapt the proposal depth within [1, --spec-k] "
                        "from an accept-rate EWMA; emitted tokens stay "
                        "bitwise identical (acceptance is equality)")
    p.add_argument("--mesh", default=None, metavar="TP|DxM",
                   help="tensor-parallel serving mesh: a model-axis "
                        "size ('2'), or 'DxM' for (data, model).  The "
                        "page pool and hashed banks shard over the "
                        "model axis; tokens stay bitwise identical to "
                        "single-device.  On CPU, host-simulate devices "
                        "with XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=8")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; > 0 samples through the fused "
                        "top-k/top-p/min-p pipeline")
    p.add_argument("--top-k", type=int, default=0,
                   help="keep only the k highest logits (0 disables)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass (1.0 disables)")
    p.add_argument("--min-p", type=float, default=0.0,
                   help="drop tokens below min-p * max-prob (0 disables)")
    p.add_argument("--seed", type=int, default=None,
                   help="base sampling seed; request uid offsets it, so "
                        "a rerun reproduces every token (counter-based "
                        "PRNG: also bitwise across preemption and "
                        "prefix caching)")
    p.add_argument("--stop", action="append", default=None,
                   metavar="IDS",
                   help="comma-separated token ids forming a stop "
                        "sequence (repeatable); generation finishes "
                        "with reason 'stop' when the output ends with "
                        "any of them")
    p.add_argument("--logprobs", type=int, default=None,
                   help="report top-K (id, logprob) pairs per generated "
                        "token (0 = chosen token's logprob only)")
    p.add_argument("--stream", action="store_true",
                   help="print RequestOutput deltas as tokens land "
                        "instead of whole generations at the end")
    p.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write the metrics-registry snapshot (counters, "
                        "gauges, latency histograms) as JSON on exit")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="record engine step and per-request spans and "
                        "write Chrome trace-event JSON on exit (open in "
                        "Perfetto)")
    p.add_argument("--debug-leak-check", action="store_true",
                   help="audit paged KV refcounts at shutdown; anomalies "
                        "export as the kv.leak_anomalies metric")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--artifact", default=None,
                   help="serve from a compressed model artifact file")
    p.add_argument("--model-name", default=None,
                   help="registered model name[@version] (with --registry)")
    p.add_argument("--registry", default=None,
                   help="model registry root for --model-name")
    args = p.parse_args()

    if args.slots is not None and args.max_concurrency is not None:
        p.error("--slots is a deprecated alias for --max-concurrency; "
                "pass one, not both")
    concurrency = args.max_concurrency if args.max_concurrency is not None \
        else (args.slots if args.slots is not None else 4)
    from repro.obs import Tracer
    from repro.serving.scheduler import SchedulerConfig
    tracer = Tracer(enabled=bool(args.trace_out))
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh
        if "x" in args.mesh:
            d, m = (int(v) for v in args.mesh.lower().split("x"))
        else:
            d, m = 1, int(args.mesh)
        mesh = make_serving_mesh(m, data=d)
        print(f"serving mesh: (data={d}, model={m}) over "
              f"{mesh.size} devices")
    engine_kwargs = dict(
        mesh=mesh, spec_adaptive=args.spec_adaptive,
        slots=concurrency, max_len=args.max_len, eos_id=-1,
        tracer=tracer, debug_leak_check=args.debug_leak_check,
        page_size=args.page_size, num_pages=args.num_pages,
        attn_impl=args.attn_impl, prefix_cache=args.prefix_cache,
        prefill_chunk=args.prefill_chunk,
        # raise the engine's logprob cap when the CLI asks for more
        # than the default report width
        max_logprobs=max(8, args.logprobs or 0),
        scheduler=SchedulerConfig(policy=args.scheduler,
                                  max_queue=args.queue_limit,
                                  deadline_s=args.deadline))

    if args.artifact and args.model_name:
        p.error("--artifact and --model-name are mutually exclusive")
    if args.artifact and args.registry:
        p.error("--registry goes with --model-name; a direct --artifact "
                "path bypasses registry integrity checks")
    if args.model_name and not args.registry:
        p.error("--model-name requires --registry")
    if args.artifact or args.model_name:
        # the artifact IS the model: config flags / checkpoints would be
        # silently ignored, so reject the incoherent combination
        ignored = [flag for flag, on in [
            ("--arch", args.arch), ("--ckpt-dir", args.ckpt_dir),
            ("--hashed", args.hashed), ("--reduced", args.reduced),
            ("--compression", args.compression is not None),
            ("--policy", args.policy), ("--budget", args.budget)] if on]
        if ignored:
            p.error(f"{'/'.join(ignored)} cannot be combined with an "
                    f"artifact source (the artifact carries its own "
                    f"config and weights)")
        t_load = time.perf_counter()   # duration: never the wall clock
        eng = Engine.from_artifact(
            args.artifact or args.model_name,
            registry_root=args.registry if args.model_name else None,
            draft_policy=args.spec_draft, spec_k=args.spec_k,
            **engine_kwargs)
        cfg = eng.model.cfg
        print(f"cold start from artifact: {cfg.name} "
              f"({time.perf_counter() - t_load:.2f}s to "
              f"params-on-device)")
    else:
        if not args.arch:
            p.error("--arch is required without --artifact/--model-name")
        cfg = C.get(args.arch)
        if args.reduced:
            cfg = reduce_cfg(cfg)
        if args.policy or args.budget:
            if args.hashed or args.compression is not None:
                p.error("--policy/--budget replace --hashed/--compression "
                        "(pin ratios with a policy rule instead)")
            pol = (policy.load(args.policy) if args.policy
                   else policy.CompressionPolicy())
            if args.budget:
                pol = dataclasses.replace(
                    pol, budget=policy.parse_ratio(args.budget))
            cfg = cfg.policy_variant(pol)
        elif args.hashed:
            cfg = cfg.hashed_variant(args.compression
                                     if args.compression is not None
                                     else 0.125)
        model = build(cfg)

        params = model.init(jax.random.PRNGKey(0))
        if args.ckpt_dir and ckpt_lib.latest_step(args.ckpt_dir) is not None:
            state = ckpt_lib.restore(
                args.ckpt_dir, {"params": params, "opt": None, "step": 0})
            params = state["params"]
            print(f"loaded params from {args.ckpt_dir}")
        draft = None
        if args.spec_draft:
            from repro.serving.draft import build_draft
            _, dmodel, dparams = build_draft(cfg, params, args.spec_draft)
            draft = (dmodel, dparams)
        eng = Engine(model, params, draft=draft, spec_k=args.spec_k,
                     **engine_kwargs)

    stop = tuple(tuple(int(t) for t in s.split(","))
                 for s in (args.stop or ()))

    def params_for(uid: int) -> SamplingParams:
        return SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, min_p=args.min_p, stop=stop,
            max_tokens=args.max_new,
            seed=None if args.seed is None else args.seed + uid,
            logprobs=args.logprobs)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()   # duration: never the wall clock
    handles = []
    for uid in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompt = rng.integers(2, cfg.vocab_size, size=plen).astype(np.int32)
        extras = None
        if cfg.arch_kind == "encdec":
            extras = {"frames": rng.standard_normal(
                (1, cfg.encoder_seq, cfg.d_model)).astype(np.float32)}
        if cfg.num_image_tokens:
            extras = {"image_embeds": rng.standard_normal(
                (1, cfg.num_image_tokens, cfg.d_model)).astype(np.float32)}
        h = eng.submit(Request(uid=uid, prompt=prompt, extras=extras,
                               sampling=params_for(uid)))
        if not h:
            print(f"req {uid}: REFUSED (queue full or request can never "
                  f"fit the page pool — see --queue-limit/--num-pages)")
        else:
            handles.append(h)
    # graceful drain: first SIGINT/SIGTERM stops admitting (queued
    # requests get terminal "cancelled" deltas, in-flight rows finish,
    # the final metrics table still prints); a second one force-quits
    import signal
    drain = {"requested": False}

    def _on_signal(signum, frame):
        if drain["requested"]:
            raise SystemExit(130)
        drain["requested"] = True
        print(f"\n[signal {signum}] draining: finishing in-flight rows, "
              "cancelling queued (signal again to force-quit)")

    old_handlers = {s: signal.signal(s, _on_signal)
                    for s in (signal.SIGINT, signal.SIGTERM)}
    drained_queue = False
    try:
        # poll-style multiplexing: one engine loop, drain every handle's
        # available deltas per tick (terminal deltas too — including
        # "cancelled" ones emitted by a drain)
        while eng.pending():
            if drain["requested"] and not drained_queue:
                drained_queue = True
                n = len(eng.cancel_queued())
                if n:
                    print(f"cancelled {n} queued request(s)")
            eng.step()
            if args.stream:
                for h in handles:
                    for d in h.drain():
                        lp = "" if not d.new_logprobs else \
                            f"  lp={['%.3f' % v for v in d.new_logprobs]}"
                        fin = f"  [{d.finish_reason}]" if d.done else ""
                        print(f"req {d.uid} += {d.new_token_ids}{lp}{fin}")
        if args.stream and drain["requested"]:
            # emit any terminal deltas landed after the last tick
            for h in handles:
                for d in h.drain():
                    fin = f"  [{d.finish_reason}]" if d.done else ""
                    print(f"req {d.uid} += {d.new_token_ids}{fin}")
        done = [h.req for h in handles if h.req.done]
    finally:
        for s, old in old_handlers.items():
            signal.signal(s, old)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.tokens) for r in done)
    for r in sorted(done, key=lambda r: r.uid):
        print(f"req {r.uid}: {r.tokens}  "
              f"(finish={r.finish_reason}, seed={r.seed_used}, "
              f"logprob={r.cumulative_logprob:.3f})")
    stats = eng.stats()
    print(f"finish reasons: {stats['finish_reasons']}  "
          f"sampler dispatches: {stats['sampler_dispatches']} "
          f"({stats['sampler_time_s']:.3f}s in sampler over "
          f"{stats['ticks']} ticks)")
    if "spec" in stats:
        sp = stats["spec"]
        print(f"spec decode: accept_rate={sp['accept_rate']:.3f} "
              f"mean_accept_len={sp['mean_accept_len']:.2f} "
              f"(k={sp['k']}, {sp['draft_dispatches']} draft / "
              f"{sp['verify_dispatches']} verify dispatches)")
    summary = {"requests": len(done), "tokens": total_tokens,
               "wall_s": round(dt, 2),
               "tok_per_s": round(total_tokens / dt, 1)}
    summary.update(stats)
    print(json.dumps(summary))
    eng.shutdown()
    if eng.last_leak_error:
        print(f"LEAK CHECK FAILED:\n{eng.last_leak_error}")
    if args.metrics_out or drain["requested"]:
        # a drained run always prints the final table — the operator
        # asked the server to stop, not to discard its telemetry
        print("--- metrics ---")
        print(eng.metrics.render())
    if args.metrics_out:
        eng.metrics.export(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        tracer.export(args.trace_out)
        print(f"trace -> {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
