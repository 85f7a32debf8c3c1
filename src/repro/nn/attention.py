"""Grouped-query attention with RoPE, qk-norm, sliding window, KV cache,
and optional cross-attention — every projection optionally hashed.

Shapes: x (B, S, d_model); KV cache (B, T_max, n_kv, head_dim) per k/v.
GQA is computed with grouped einsums (no materialized KV repeat).
Softmax and score accumulation are float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import hashed as H
from repro.nn import layers as L


def _serving_tp():
    """(mesh, tp) when the active sharding rules put the KV-head axis on
    a >1 "model" axis (tensor-parallel serving), else (None, 1).

    Attention is per-head independent, so splitting the paged pool and
    the q/k/v head dims across a mesh axis and running the scatter +
    kernel per shard is BITWISE identical to the single-device dispatch
    — no reduction crosses shards.  The engine activates
    ``distributed.sharding.serving_rules`` around its jitted paths;
    without an active mesh (unit tests, single device) this returns
    (None, 1) and the paged paths below compile exactly as before.
    """
    from repro.distributed import sharding as shd
    mesh = shd.active_mesh()
    if mesh is None:
        return None, 1
    axis = shd.resolve_spec(P(L.TP_KV))[0]
    if isinstance(axis, (tuple, list)):
        axis = axis[0] if len(axis) == 1 else None
    if axis != "model":
        return None, 1
    tp = mesh.shape.get("model", 1)
    return (mesh, tp) if tp > 1 else (None, 1)


def _shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with the settings every serving dispatch needs:
    check_vma off (pallas_call inside a shard_map cannot carry the
    varying-manual-axes checking rule set)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    qk_norm: bool = False
    sliding_window: int = 0          # 0 = full attention
    causal: bool = True
    cross: bool = False              # kv from encoder output
    dtype: Any = jnp.bfloat16
    # hashed specs per projection (None = dense)
    hash_q: Optional[H.HashedSpec] = None
    hash_k: Optional[H.HashedSpec] = None
    hash_v: Optional[H.HashedSpec] = None
    hash_o: Optional[H.HashedSpec] = None
    hash_path: str = "auto"

    # memory-bounded attention: queries processed in chunks of q_chunk
    # (scores never materialize beyond (B, chunk, T)); 0 = auto
    # (chunk 512 once S > 2048), -1 = never chunk.
    q_chunk: int = 0

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


def _lin(plan: AttentionPlan, in_dim, out_dim, hspec, pspec):
    return L.LinearPlan(in_dim, out_dim, hashed=hspec, pspec=pspec,
                        dtype=plan.dtype, hash_path=plan.hash_path)


def init(plan: AttentionPlan, key):
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    for name, k, lin in [
        ("q", ks[0], _lin(plan, plan.d_model, plan.q_dim, plan.hash_q,
                          (L.FSDP, L.TP))),
        ("k", ks[1], _lin(plan, plan.d_model, plan.kv_dim, plan.hash_k,
                          (L.FSDP, L.TP))),
        ("v", ks[2], _lin(plan, plan.d_model, plan.kv_dim, plan.hash_v,
                          (L.FSDP, L.TP))),
        ("o", ks[3], _lin(plan, plan.q_dim, plan.d_model, plan.hash_o,
                          (L.TP, L.FSDP))),
    ]:
        p, s = L.linear_init(lin, k)
        params[name], specs[name] = p, s
    if plan.qk_norm:
        params["q_norm"], specs["q_norm"] = L.rmsnorm_init(plan.head_dim)
        params["k_norm"], specs["k_norm"] = L.rmsnorm_init(plan.head_dim)
    return params, specs


def _project(plan, params, name, x, out_heads):
    lin = {
        "q": _lin(plan, plan.d_model, plan.q_dim, plan.hash_q, (L.FSDP, L.TP)),
        "k": _lin(plan, plan.d_model, plan.kv_dim, plan.hash_k, (L.FSDP, L.TP)),
        "v": _lin(plan, plan.d_model, plan.kv_dim, plan.hash_v, (L.FSDP, L.TP)),
    }[name]
    y = L.linear_apply(lin, params[name], x)
    b, s = x.shape[0], x.shape[1]
    return y.reshape(b, s, out_heads, plan.head_dim)


def attend(plan: AttentionPlan, q, k, v, q_pos, kv_pos, kv_valid,
           is_global=None):
    """Core grouped attention, memory-bounded.

    q: (B, S, Hq, D); k/v: (B, T, Hkv, D)
    q_pos: (S,) absolute positions of queries
    kv_pos: (T,) absolute positions of keys
    kv_valid: (T,) bool — whether the cache slot holds a real key
    is_global: optional traced bool — when the plan has a sliding window,
      a True value disables it for this layer (gemma3 5:1 local:global
      pattern under scan-over-layers).

    Long sequences: queries are processed in chunks via lax.scan so the
    live score tensor is (B, Hkv, G, chunk, T), never (.., S, T) — the
    flash-attention memory bound in TPU/XLA idiom (each query row's
    softmax is still computed over the full T at once, so results are
    bit-identical to the unchunked path).
    """
    b, s, hq, d = q.shape
    chunk = plan.q_chunk if plan.q_chunk != 0 else (512 if s > 2048 else -1)
    if 0 < chunk < s:
        pad = (-s) % chunk
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
            q_pos = jnp.pad(q_pos, (0, pad), constant_values=q_pos[-1])
        nc = (s + pad) // chunk
        qc = jnp.moveaxis(q.reshape(b, nc, chunk, hq, d), 1, 0)
        qp = q_pos.reshape(nc, chunk)

        def body(carry, xs):
            qi, qpi = xs
            out = _attend_unchunked(plan, qi, k, v, qpi, kv_pos, kv_valid,
                                    is_global)
            return carry, out

        _, outs = jax.lax.scan(body, None, (qc, qp))   # (nc, B, chunk, HD)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s + pad, hq * d)
        return out[:, :s]
    return _attend_unchunked(plan, q, k, v, q_pos, kv_pos, kv_valid,
                             is_global)


def _attend_unchunked(plan: AttentionPlan, q, k, v, q_pos, kv_pos, kv_valid,
                      is_global=None):
    b, s, hq, d = q.shape
    t = k.shape[1]
    n_kv = plan.num_kv_heads
    g = hq // n_kv
    qg = q.reshape(b, s, n_kv, g, d)

    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32) * scale,
        k.astype(jnp.float32), preferred_element_type=jnp.float32)

    # q_pos may be (S,) or (B, S) (per-row decode positions, continuous
    # batching); kv_valid may be (T,) or (B, T).  Everything broadcasts
    # to (B|1, S, T).
    qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]
    kv2 = kv_valid if kv_valid.ndim == 2 else kv_valid[None, :]
    mask = kv2[:, None, :]                          # (B|1, 1, T)
    if plan.causal:
        mask = mask & (kv_pos[None, None, :] <= qp[:, :, None])
        if plan.sliding_window > 0:
            in_window = (qp[:, :, None] - kv_pos[None, None, :]
                         < plan.sliding_window)
            if is_global is not None:
                in_window = in_window | is_global
            mask = mask & in_window
    else:
        mask = jnp.broadcast_to(mask, (mask.shape[0], s, t))
    neg = jnp.asarray(-1e30, jnp.float32)
    scores = jnp.where(mask[:, None, None, :, :], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    # bf16 probs for the value contraction (flash-attention practice):
    # keeps the (B,T,H,D)-sized backward cotangents in bf16.
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, s, hq * d).astype(plan.dtype)


@jax.named_scope("attn")
def apply(plan: AttentionPlan, params, x, *, positions, cache=None,
          cache_index=None, kv_source=None, is_global=None):
    """Returns (out, new_cache).

    - training / encoder: cache=None, attends within x (or kv_source).
    - prefill: cache=(k,v) zero-filled, cache_index=0; writes S entries.
    - decode:  cache=(k,v), cache_index=current length; S is typically 1.
    kv_source: (B, T_enc, d_model) encoder output for cross-attention.
    """
    b, s, _ = x.shape
    q = _project(plan, params, "q", x, plan.num_heads)

    kv_in = kv_source if plan.cross else x
    k = _project(plan, params, "k", kv_in, plan.num_kv_heads)
    v = _project(plan, params, "v", kv_in, plan.num_kv_heads)

    if plan.qk_norm:
        q = L.rmsnorm_apply(params["q_norm"], q)
        k = L.rmsnorm_apply(params["k_norm"], k)

    if plan.use_rope and not plan.cross:
        q = L.rope(q, positions, plan.rope_theta)
        kv_positions = positions
        k = L.rope(k, kv_positions, plan.rope_theta)

    new_cache = None
    if plan.cross:
        # cross-attention: no cache mutation, all encoder positions valid
        t = k.shape[1]
        kv_pos = jnp.arange(t)
        kv_valid = jnp.ones((t,), bool)
        q_pos = positions
        out = attend(plan, q, k, v, q_pos, kv_pos, kv_valid)
    elif cache is None:
        kv_pos = positions
        kv_valid = jnp.ones((s,), bool)
        out = attend(plan, q, k, v, positions, kv_pos, kv_valid,
                     is_global=is_global)
    else:
        ck, cv = cache
        t_max = ck.shape[1]
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim == 1:
            # per-row write offsets (continuous batching: every slot is at
            # its own position)
            upd = jax.vmap(
                lambda c, x, i: jax.lax.dynamic_update_slice(
                    c, x.astype(c.dtype), (i, 0, 0)))
            ck = upd(ck, k, idx)
            cv = upd(cv, v, idx)
            kv_pos = jnp.arange(t_max)
            kv_valid = kv_pos[None, :] < (idx[:, None] + s)    # (B, T)
        else:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, idx, 0, 0))
            kv_pos = jnp.arange(t_max)
            kv_valid = kv_pos < (idx + s)
        out = attend(plan, q, ck, cv, positions, kv_pos, kv_valid,
                     is_global=is_global)
        new_cache = (ck, cv)

    o_lin = _lin(plan, plan.q_dim, plan.d_model, plan.hash_o,
                 (L.TP, L.FSDP))
    return L.linear_apply(o_lin, params["o"], out), new_cache


@jax.named_scope("attn")
def apply_paged(plan: AttentionPlan, params, x, *, pages, page_table,
                lengths, is_global=None, impl: str = "ref"):
    """One decode step (S=1) through a paged KV cache.

    x: (B, 1, d_model); pages: (pk, pv) each (P, ps, Hkv, D);
    page_table: (B, MAXP) int32; lengths: (B,) int32 — tokens already
    cached per row EXCLUDING the current one (so the current token's
    position is ``lengths`` and its k/v lands at page
    ``table[b, lengths // ps]`` offset ``lengths % ps``).

    Returns (out (B, 1, d_model), (new_pk, new_pv)).  impl: "ref"
    (gather-then-attend oracle) or "pallas" (flash-decode kernel that
    copies only each row's live pages; interpret mode off-TPU).
    """
    from repro.kernels import paged_attention as PA
    from repro.kernels import ref as KREF

    b = x.shape[0]
    q = _project(plan, params, "q", x, plan.num_heads)
    k = _project(plan, params, "k", x, plan.num_kv_heads)
    v = _project(plan, params, "v", x, plan.num_kv_heads)
    if plan.qk_norm:
        q = L.rmsnorm_apply(params["q_norm"], q)
        k = L.rmsnorm_apply(params["k_norm"], k)
    positions = lengths[:, None]                      # (B, 1)
    if plan.use_rope:
        q = L.rope(q, positions, plan.rope_theta)
        k = L.rope(k, positions, plan.rope_theta)

    pk, pv = pages

    if plan.sliding_window > 0:
        window = jnp.asarray(plan.sliding_window, jnp.int32)
        if is_global is not None:
            window = jnp.where(is_global, 0, window)
    else:
        window = jnp.asarray(0, jnp.int32)

    fn = PA.paged_decode_attention if impl == "pallas" \
        else KREF.paged_attention_ref

    def scatter_attend(q1, k1, v1, pk, pv, page_table, lengths, window):
        ps = pk.shape[1]
        pidx = jnp.take_along_axis(page_table, (lengths // ps)[:, None],
                                   axis=1)[:, 0]
        poff = lengths % ps
        # distinct live rows own distinct pages (allocator invariant);
        # idle rows all write the trash page, collisions harmless there
        with jax.named_scope("kv_write"):
            npk = pk.at[pidx, poff].set(k1.astype(pk.dtype))
            npv = pv.at[pidx, poff].set(v1.astype(pv.dtype))
        return fn(q1, npk, npv, page_table, lengths + 1, window), npk, npv

    mesh, _tp = _serving_tp()
    if mesh is not None:
        # per-head-shard scatter + attend: each shard owns Hkv/tp kv
        # heads of the pool and the matching Hq/tp q heads (GQA groups
        # ride along), table/lengths replicated — no cross-shard math,
        # so the sharded dispatch is bitwise the single-device one
        head = P(None, "model", None)
        pool = P(None, None, "model", None)
        out, pk, pv = _shard_map(
            scatter_attend, mesh,
            in_specs=(head, head, head, pool, pool,
                      P(None, None), P(None), P()),
            out_specs=(head, pool, pool),
        )(q[:, 0], k[:, 0], v[:, 0], pk, pv, page_table, lengths, window)
        # exact all-gather of the head shards (a concat, not a psum)
        # BEFORE the o-projection, which then runs replicated with the
        # single-device reduction order — the bitwise-identity contract
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, P(None, None, None)))
    else:
        out, pk, pv = scatter_attend(q[:, 0], k[:, 0], v[:, 0], pk, pv,
                                     page_table, lengths, window)
    out = out.reshape(b, 1, plan.q_dim).astype(plan.dtype)

    o_lin = _lin(plan, plan.q_dim, plan.d_model, plan.hash_o,
                 (L.TP, L.FSDP))
    return L.linear_apply(o_lin, params["o"], out), (pk, pv)


@jax.named_scope("attn")
def apply_paged_block(plan: AttentionPlan, params, x, *, pages, page_table,
                      lengths, counts, is_global=None, impl: str = "ref"):
    """Multi-token decode block (speculative propose/verify) through a
    paged KV cache.

    x: (B, S, d_model); slot ``s`` of row ``b`` holds the token at
    absolute position ``lengths[b] + s`` and is real iff
    ``s < counts[b]``.  Real slots write K/V into the row's pages (slot
    s attends to slots < s written in the same call); padding slots
    write the trash page (0), whose contents are never read back — the
    page-table mask ``kv_pos < lengths`` already excludes every
    table slot that maps to it.  With S == 1 (counts all 1) the
    projections, RoPE positions, KV scatter targets, and attention
    masks are identical to :func:`apply_paged`, so the block path is
    bitwise-equal to the per-token path — the parity the speculative
    engine's token-identity guarantee rests on.

    Returns (out (B, S, d_model), (new_pk, new_pv)).
    """
    from repro.kernels import paged_attention as PA
    from repro.kernels import ref as KREF

    b, s_blk, _ = x.shape
    q = _project(plan, params, "q", x, plan.num_heads)
    k = _project(plan, params, "k", x, plan.num_kv_heads)
    v = _project(plan, params, "v", x, plan.num_kv_heads)
    if plan.qk_norm:
        q = L.rmsnorm_apply(params["q_norm"], q)
        k = L.rmsnorm_apply(params["k_norm"], k)
    offs = jnp.arange(s_blk, dtype=jnp.int32)[None, :]
    positions = lengths[:, None] + offs               # (B, S)
    if plan.use_rope:
        q = L.rope(q, positions, plan.rope_theta)
        k = L.rope(k, positions, plan.rope_theta)

    pk, pv = pages
    ps = pk.shape[1]
    maxp = page_table.shape[1]
    valid = offs < counts[:, None]                    # (B, S)
    # clamp the page slot for padding positions that run past the
    # table; their writes are redirected to the trash page anyway
    pno = jnp.minimum(positions // ps, maxp - 1)
    pidx = jnp.where(valid,
                     jnp.take_along_axis(page_table, pno, axis=1), 0)
    poff = positions % ps
    with jax.named_scope("kv_write"):
        pk = pk.at[pidx.reshape(-1), poff.reshape(-1)].set(
            k.reshape(b * s_blk, *k.shape[2:]).astype(pk.dtype))
        pv = pv.at[pidx.reshape(-1), poff.reshape(-1)].set(
            v.reshape(b * s_blk, *v.shape[2:]).astype(pv.dtype))

    if plan.sliding_window > 0:
        window = jnp.asarray(plan.sliding_window, jnp.int32)
        if is_global is not None:
            window = jnp.where(is_global, 0, window)
    else:
        window = jnp.asarray(0, jnp.int32)

    fn = PA.paged_decode_attention if impl == "pallas" \
        else KREF.paged_attention_ref
    outs = [fn(q[:, s], pk, pv, page_table,
               jnp.minimum(lengths + s + 1, maxp * ps), window)
            for s in range(s_blk)]
    out = jnp.stack(outs, axis=1).reshape(b, s_blk, plan.q_dim)
    out = out.astype(plan.dtype)

    o_lin = _lin(plan, plan.q_dim, plan.d_model, plan.hash_o,
                 (L.TP, L.FSDP))
    return L.linear_apply(o_lin, params["o"], out), (pk, pv)


@jax.named_scope("attn")
def apply_paged_prefill(plan: AttentionPlan, params, x, *, pages,
                        page_table, starts, counts, write_from,
                        is_global=None, impl: str = "ref"):
    """Batched ragged prefill chunk through a paged KV cache.

    x: (B, S, d_model); slot ``s`` of row ``b`` holds the prompt token
    at absolute position ``starts[b] + s`` and is real iff
    ``s < counts[b]`` (rows with ``counts == 0`` are inert padding
    rows).  Real slots at positions >= ``write_from[b]`` write K/V
    straight into the row's pages through the table — ``write_from`` is
    the first position of the row's private whole-page landing zone, so
    shared (refcount > 1) prefix pages are never written even when a
    slid-back chunk recomputes positions that live on them.  All other
    slots write the trash page (0), whose contents are never read back.
    Every real slot then attends its own causal band over the row's
    paged prefix (shared pages read through the table, like decode).

    For a real query this produces bitwise the scores/probs/output of
    the sequential dense-scratch-cache path (``apply`` with a scalar
    cache index) — the parity the engine's batched-vs-sequential
    token-identity guarantee rests on; see kernels.ref.paged_prefill_ref.

    Returns (out (B, S, d_model), (new_pk, new_pv)).  impl: "ref"
    (gather-then-attend oracle, bitwise vs the dense path) or "pallas"
    (ragged flash-prefill kernel; interpret mode off-TPU).
    """
    from repro.kernels import flash_prefill as FP
    from repro.kernels import ref as KREF

    b, s_blk, _ = x.shape
    q = _project(plan, params, "q", x, plan.num_heads)
    k = _project(plan, params, "k", x, plan.num_kv_heads)
    v = _project(plan, params, "v", x, plan.num_kv_heads)
    if plan.qk_norm:
        q = L.rmsnorm_apply(params["q_norm"], q)
        k = L.rmsnorm_apply(params["k_norm"], k)
    offs = jnp.arange(s_blk, dtype=jnp.int32)[None, :]
    positions = starts[:, None] + offs                # (B, S)
    if plan.use_rope:
        q = L.rope(q, positions, plan.rope_theta)
        k = L.rope(k, positions, plan.rope_theta)

    pk, pv = pages

    if plan.sliding_window > 0:
        window = jnp.asarray(plan.sliding_window, jnp.int32)
        if is_global is not None:
            window = jnp.where(is_global, 0, window)
    else:
        window = jnp.asarray(0, jnp.int32)

    fn = FP.paged_prefill_attention if impl == "pallas" \
        else KREF.paged_prefill_ref

    def scatter_attend(q_, k_, v_, pk, pv, page_table, starts, counts,
                       write_from, window):
        ps = pk.shape[1]
        maxp = page_table.shape[1]
        offs_ = jnp.arange(q_.shape[1], dtype=jnp.int32)[None, :]
        positions_ = starts[:, None] + offs_
        wvalid = (offs_ < counts[:, None]) \
            & (positions_ >= write_from[:, None])     # (B, S)
        # clamp the page slot for padding positions that run past the
        # table; their writes are redirected to the trash page anyway
        pno = jnp.minimum(positions_ // ps, maxp - 1)
        pidx = jnp.where(wvalid,
                         jnp.take_along_axis(page_table, pno, axis=1), 0)
        poff = positions_ % ps
        nb, ns = q_.shape[0], q_.shape[1]
        with jax.named_scope("kv_write"):
            npk = pk.at[pidx.reshape(-1), poff.reshape(-1)].set(
                k_.reshape(nb * ns, *k_.shape[2:]).astype(pk.dtype))
            npv = pv.at[pidx.reshape(-1), poff.reshape(-1)].set(
                v_.reshape(nb * ns, *v_.shape[2:]).astype(pv.dtype))
        return fn(q_, npk, npv, page_table, starts, counts, window), \
            npk, npv

    mesh, _tp = _serving_tp()
    if mesh is not None:
        # see apply_paged: per-head-shard scatter + kernel, replicated
        # ragged metadata, exact head concat before the o-projection
        head = P(None, None, "model", None)
        pool = P(None, None, "model", None)
        rep1 = P(None)
        out, pk, pv = _shard_map(
            scatter_attend, mesh,
            in_specs=(head, head, head, pool, pool,
                      P(None, None), rep1, rep1, rep1, P()),
            out_specs=(head, pool, pool),
        )(q, k, v, pk, pv, page_table, starts, counts, write_from, window)
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, P(None, None, None, None)))
    else:
        out, pk, pv = scatter_attend(q, k, v, pk, pv, page_table, starts,
                                     counts, write_from, window)
    out = out.reshape(b, s_blk, plan.q_dim).astype(plan.dtype)

    o_lin = _lin(plan, plan.q_dim, plan.d_model, plan.hash_o,
                 (L.TP, L.FSDP))
    return L.linear_apply(o_lin, params["o"], out), (pk, pv)


def init_cache(plan: AttentionPlan, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    shape = (batch, max_len, plan.num_kv_heads, plan.head_dim)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def cache_pspec() -> Tuple[P, P]:
    """KV cache logical sharding: batch over (pod,data); exactly one of
    tp_kv (heads) / tp_hd (head_dim) resolves to model, by divisibility
    (launch/specs.rules_for).  For batch=1 long-context cells the rules
    re-map seq over data."""
    return (P(L.BATCH, None, L.TP_KV, L.TP_HD),
            P(L.BATCH, None, L.TP_KV, L.TP_HD))
