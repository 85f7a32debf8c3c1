#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip at qwen3-1.7b's
published widths (28 layers, d 2048, 16/8 heads, head_dim 128, d_ff
6144, vocab 151936), with random weights from ``PRNGKey(0)``.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # tensor-parallel engine, 4 chips

One chip runs, in order:

1. the device check: the first JAX device must be a TPU, else exit 2;
2. every Pallas kernel of the serving path (paged decode attention,
   ragged flash prefill, the top-k mask, block-mode hashed matmul),
   compiled for the chip, against its oracle in ``repro.kernels.ref``;
3. a dense ``Engine`` that answers 8 seeded requests of 16 tokens
   (greedy, and sampled with temperature + top-k + top-p, which runs the
   top-k kernel) with ``attn_impl="pallas"``, then the same requests
   with the ``"ref"`` oracles, compared token by token;
4. the same for the hashed variant at compression 1/8, 4 tokens each.

``--four-chips`` runs only a tensor-parallel (model axis 4) engine
against a single-device engine on the same greedy requests.

Any failed check raises, so the run exits non-zero and the last line is
missing; a run still going after ``WATCHDOG_S`` dumps its stacks and
exits non-zero.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Progress,
times, token counts and peak bytes go to stderr; they are smoke
diagnostics, not benchmark results.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
_T0 = time.perf_counter()

# bf16 tolerance for a kernel against its float32 oracle: the kernels
# read bf16 and round their output to bf16 (relative step 2**-8), and
# the MXU's default f32 precision adds a bf16 pass on top.  Outputs are
# O(1), so 2e-2 is a handful of bf16 steps.
KERNEL_TOL = 2e-2
# nats: how far two serving paths may disagree on a token's logprob.
# Two bf16 paths through 28 layers drift by rounding only; a wrong
# attention or weight moves logprobs by the logit spread, O(1).
LOGPROB_TOL = 0.25

SEED = 0
ROWS = 8
PROMPT_LENS = (5, 11, 14, 7, 16, 9, 3, 12)   # two prefill buckets: 8, 16
MAX_TOKENS = 16
# The hashed engine's element mode rebuilds every virtual matrix with a
# scalar gather on each forward pass (about 1.4 G bank entries over 28
# layers), whatever the batch: its cost is per tick, not per token, so
# the hashed phase runs fewer ticks.
HASHED_TOKENS = 4
MAX_LEN = 256
PAGE = 16
LOGPROB_K = 4
# seconds: a run still going after this dumps every thread's stack to
# stderr and exits non-zero, so a hang names the line it hangs on
WATCHDOG_S = 1100


def log(msg: str) -> None:
    """Progress goes to stderr, stamped with seconds since start: the
    tail of stderr is what a stopped run leaves to read, and stdout
    keeps only the result line."""
    print(f"[{time.perf_counter() - _T0:7.1f} s] {msg}", file=sys.stderr,
          flush=True)


def _close(name, got, want, tol):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite output"
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    log(f"  {name}: shape {got.shape}, max |err|/(1+|ref|) {err:.3e} "
        f"(tol {tol:g})")
    assert err <= tol, f"{name}: error {err} above {tol}"


def kernel_phase(cfg, *, interpret: bool, seed: int = SEED) -> None:
    """Each serving kernel once at ``cfg``'s widths against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.hashed import HashedSpec
    from repro.kernels import flash_prefill as FP
    from repro.kernels import ops as kernel_ops
    from repro.kernels import paged_attention as PA
    from repro.kernels import ref as KREF
    from repro.kernels import topk as TK

    rng = np.random.default_rng(seed)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    maxp = MAX_LEN // PAGE
    npages = ROWS * maxp + 1                       # +1: trash page 0

    def normal(*shape, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    pk = normal(npages, PAGE, hkv, d)
    pv = normal(npages, PAGE, hkv, d)
    table = jnp.asarray(rng.permutation(np.arange(1, npages))
                        .reshape(ROWS, maxp), jnp.int32)

    # paged decode, full attention and a sliding window
    q = normal(ROWS, hq, d)
    lengths = jnp.asarray(rng.integers(1, MAX_LEN + 1, ROWS), jnp.int32)
    decode = jax.jit(lambda *a: PA.paged_decode_attention(
        *a, interpret=interpret))
    for window in (0, 64):
        w = jnp.int32(window)
        _close(f"paged decode (B={ROWS}, Hq={hq}, Hkv={hkv}, D={d}, "
               f"window {window})",
               decode(q, pk, pv, table, lengths, w),
               KREF.paged_attention_ref(q.astype(jnp.float32), pk, pv,
                                        table, lengths, window),
               KERNEL_TOL)

    # ragged flash prefill
    s = 16
    qs = normal(ROWS, s, hq, d)
    starts = jnp.asarray(rng.integers(0, MAX_LEN - s + 1, ROWS), jnp.int32)
    counts = jnp.asarray(rng.integers(1, s + 1, ROWS), jnp.int32)
    prefill = jax.jit(lambda *a: FP.paged_prefill_attention(
        *a, interpret=interpret))
    _close(f"flash prefill (B={ROWS}, S={s})",
           prefill(qs, pk, pv, table, starts, counts, jnp.int32(0)),
           KREF.paged_prefill_ref(qs.astype(jnp.float32), pk, pv, table,
                                  starts, counts, 0),
           KERNEL_TOL)

    # top-k mask: exact (the radix select returns one of the inputs)
    v = cfg.vocab_size
    logits = normal(ROWS, v, dtype=jnp.float32)
    k = jnp.asarray([0, 1, 2, 20, 50, 1000, v, 7][:ROWS], jnp.int32)
    got = np.asarray(jax.jit(lambda x, kk: TK.topk_mask(
        x, kk, use_pallas=True, interpret=interpret))(logits, k))
    want = np.asarray(KREF.topk_mask_ref(logits, k))
    kept = (got > TK.NEG).sum(axis=1)
    log(f"  top-k mask (B={ROWS}, V={v}): kept per row {kept.tolist()}")
    np.testing.assert_array_equal(got, want, err_msg="top-k mask")

    # block-mode hashed matmul on the FFN up-projection shape
    blk = (math.gcd(128, cfg.d_model), math.gcd(128, cfg.d_ff))
    spec = HashedSpec((cfg.d_model, cfg.d_ff), 0.125, mode="block",
                      seed=seed + 3, block_shape=blk)
    bank = normal(*spec.real_param_shape(),
                  scale=1.0 / math.sqrt(cfg.d_model))
    mm = jax.jit(lambda x, w: kernel_ops.hashed_matmul(
        x, w, spec, interpret=interpret))
    for m in (8, 256):
        x = normal(m, cfg.d_model)
        _close(f"hashed matmul block {blk} ({m}x{cfg.d_model} @ "
               f"{cfg.d_model}x{cfg.d_ff} at 1/8)",
               mm(x, bank), KREF.hashed_matmul_ref(x, bank, spec,
                                                   dtype=jnp.float32),
               KERNEL_TOL)


def requests(vocab: int, *, sampled: bool, max_tokens: int = MAX_TOKENS):
    """ROWS seeded requests of ``max_tokens`` each; with ``sampled`` the
    odd ones sample with temperature + top-k + top-p (the top-k kernel's
    path)."""
    import numpy as np

    from repro.serving.api import SamplingParams
    from repro.serving.engine import Request

    rng = np.random.default_rng(SEED)
    out = []
    for i, plen in enumerate(PROMPT_LENS[:ROWS]):
        prompt = rng.integers(2, vocab, size=plen).astype(np.int32)
        if sampled and i % 2:
            sp = SamplingParams(temperature=0.8, top_k=40, top_p=0.9,
                                max_tokens=max_tokens, seed=SEED + i,
                                logprobs=LOGPROB_K)
        else:
            sp = SamplingParams(max_tokens=max_tokens, logprobs=LOGPROB_K)
        out.append(Request(uid=i, prompt=prompt, sampling=sp))
    return out


def serve(engine, reqs):
    """Submit ``reqs``, step ``engine`` until it drains; check every
    request finished at its full length with in-vocab tokens and finite
    logprobs.  Returns ({index: request}, [seconds of each tick])."""
    import numpy as np

    handles = [engine.submit(r) for r in reqs]
    assert all(handles), "engine refused a request"
    longest = max(r.sampling.max_tokens for r in reqs)
    budget = 4 * len(reqs) * (longest + 2)
    ticks = []
    while engine.pending():
        t0 = time.perf_counter()
        engine.step()
        ticks.append(time.perf_counter() - t0)
        assert len(ticks) < budget, "engine did not drain"
    vocab = engine.model.cfg.vocab_size
    done = {}
    for i, h in enumerate(handles):
        r = h.req
        assert r.done and r.finish_reason == "length", (r.uid,
                                                        r.finish_reason)
        assert len(r.tokens) == r.sampling.max_tokens, (r.uid,
                                                        len(r.tokens))
        assert all(0 <= t < vocab for t in r.tokens), r.uid
        assert np.isfinite(r.token_logprobs).all(), r.uid
        done[i] = r
    return done, ticks


def _ticks(ticks) -> str:
    rest = sorted(ticks[1:]) or [0.0]
    return (f"{len(ticks)} ticks in {sum(ticks):.1f} s, the first "
            f"{ticks[0]:.1f} s (compiles included), median of the rest "
            f"{rest[len(rest) // 2]:.3f} s")


def compare(name, got, want, indices, *, greedy: bool) -> bool:
    """Token agreement of two engines on the requests at ``indices``.

    Up to the first differing token both engines saw the same context, so
    their chosen-token logprobs must agree within LOGPROB_TOL.  For greedy
    requests the first differing token must also be a near tie: ``want``'s
    top-k ranks ``got``'s choice within LOGPROB_TOL of its own.  Past it
    the contexts differ and nothing is compared.  Returns whether every
    token matched."""
    same = total = 0
    worst_lp = worst_tie = 0.0
    for i in indices:
        a, b = got[i], want[i]
        n = 0
        while n < len(a.tokens) and a.tokens[n] == b.tokens[n]:
            n += 1
        same += n
        total += len(a.tokens)
        for t in range(n):
            worst_lp = max(worst_lp, abs(a.token_logprobs[t]
                                         - b.token_logprobs[t]))
        if greedy and n < len(a.tokens):
            ranked = dict(b.topk_logprobs[n])
            assert a.tokens[n] in ranked, (
                f"{name}: request {i} token {n}: {a.tokens[n]} is not in "
                f"the reference's top-{LOGPROB_K} {b.topk_logprobs[n]}")
            worst_tie = max(worst_tie, b.token_logprobs[n]
                            - ranked[a.tokens[n]])
    log(f"  {name}: {same}/{total} tokens agree before the first "
        f"difference; max |logprob diff| on shared context "
        f"{worst_lp:.4f}" + (f", widest near tie {worst_tie:.4f}"
                              if greedy else "")
        + f" (tol {LOGPROB_TOL})")
    assert worst_lp <= LOGPROB_TOL, f"{name}: logprobs differ by {worst_lp}"
    assert worst_tie <= LOGPROB_TOL, f"{name}: divergence gap {worst_tie}"
    return same == total


def _peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def build_model(cfg):
    import jax

    from repro.models import build
    model = build(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.PRNGKey(SEED)))
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {n / 1e6:.1f} M real params "
        f"({time.perf_counter() - t0:.1f} s to init)")
    return model, params


def engine_phase(cfg, *, max_len: int = MAX_LEN,
                 max_tokens: int = MAX_TOKENS) -> None:
    """Pallas and ref engines on the same requests, compared."""
    from repro.serving.engine import Engine

    model, params = build_model(cfg)
    outs = {}
    for impl in ("pallas", "ref"):
        eng = Engine(model, params, max_concurrency=ROWS, max_len=max_len,
                     page_size=PAGE, attn_impl=impl, eos_id=-1, seed=SEED)
        done, ticks = serve(eng, requests(cfg.vocab_size, sampled=True,
                                          max_tokens=max_tokens))
        eng.shutdown()
        del eng
        outs[impl] = done
        log(f"  {cfg.name} attn_impl={impl}: "
            f"{sum(len(r.tokens) for r in done.values())} tokens, "
            f"{_ticks(ticks)}; peak bytes in use {_peak_bytes()}")
    greedy = [i for i in outs["ref"] if not i % 2]
    sampled = [i for i in outs["ref"] if i % 2]
    compare(f"{cfg.name} greedy pallas vs ref", outs["pallas"],
            outs["ref"], greedy, greedy=True)
    compare(f"{cfg.name} sampled pallas vs ref", outs["pallas"],
            outs["ref"], sampled, greedy=False)


def four_chip_phase(cfg, tp: int = 4) -> None:
    """A tensor-parallel engine against a single-device engine."""
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.serving.engine import Engine

    assert len(jax.devices()) >= tp, f"needs {tp} devices"
    model, params = build_model(cfg)
    outs = {}
    for name, mesh in (("single device", None),
                       (f"tp={tp}", make_serving_mesh(tp))):
        eng = Engine(model, params, max_concurrency=ROWS, max_len=MAX_LEN,
                     page_size=PAGE, attn_impl="pallas", eos_id=-1,
                     seed=SEED, mesh=mesh)
        outs[name], ticks = serve(eng, requests(cfg.vocab_size,
                                                sampled=False))
        eng.shutdown()
        del eng
        log(f"  {cfg.name} {name}: {_ticks(ticks)}; peak bytes in use on "
            f"device 0 {_peak_bytes()}")
    identical = compare(f"{cfg.name} tp={tp} vs single device",
                        outs[f"tp={tp}"], outs["single device"],
                        range(ROWS), greedy=True)
    log(f"  tp={tp} greedy tokens identical to single device: {identical}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the tensor-parallel phase on 4 chips")
    args = p.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU, JAX sees {device}", file=sys.stderr)
        return 2

    import repro.configs as C
    from repro.runtime import enable_compile_cache
    log(f"device: {device}; compile cache: {enable_compile_cache()}")
    cfg = C.get("qwen3-1.7b")

    t0 = time.perf_counter()
    if args.four_chips:
        log("phase: tensor-parallel engine")
        four_chip_phase(cfg)
    else:
        log("phase: kernels")
        kernel_phase(cfg, interpret=False)
        log("phase: dense engine")
        engine_phase(cfg)
        log("phase: hashed engine (1/8)")
        engine_phase(cfg.hashed_variant(0.125), max_tokens=HASHED_TOKENS)
    faulthandler.cancel_dump_traceback_later()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
