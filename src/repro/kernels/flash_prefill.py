"""Batched ragged flash-prefill: multi-request chunked prefill over a
paged KV cache in ONE dispatch.

Chunked prefill used to run one request per dispatch through a batch=1
scratch cache — the TTFT bottleneck at high arrival rates.  This kernel
processes every prefilling row's current chunk together: each row b
brings S query slots (its chunk, padded to the compile bucket) sitting
at positions ``starts[b] + s``, and attends over its OWN paged prefix —
shared-prefix pages read through the page table exactly like decode —
under the causal band.  ``counts[b]`` marks the real (un-padded) slots;
pad slots and ``counts == 0`` rows (the padding rows that round the
batch out to a compile shape) produce zeros.

Layout mirrors ``paged_attention.py``: grid ``(B, MAXP)``, page table +
ragged ``starts``/``counts`` in scalar prefetch so the BlockSpec
``index_map`` resolves physical pages before the body runs, online
softmax (running max / sum / accumulator in VMEM scratch) across the
page walk.  The query block is pre-shaped to ``(n_kv, g*S, d)`` on the
host so the in-kernel score product is one batched ``dot_general`` over
kv heads (GQA without repeat), same as the decode kernel.

The chunk's fresh K/V must already be scattered into each row's private
pages before the call (``nn.attention.apply_paged_prefill`` does the
scatter) — the kernel then reads old prefix and fresh chunk uniformly
through the table, so no per-request scratch cache round-trip exists.

Compiles for TPU v5e at qwen3-1.7b widths (tests/test_tpu_compile.py);
off-TPU it runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import on_tpu

_NEG = -1e30


def _prefill_kernel(table_ref, start_ref, count_ref, win_ref, q_ref,
                    k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                    *, ps, n_kv, g, s_blk, d, maxp):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                      # (n_kv, g*S, d)
    k = k_ref[0].transpose(1, 0, 2).astype(jnp.float32)   # (n_kv, ps, d)
    v = v_ref[0].transpose(1, 0, 2).astype(jnp.float32)

    # (n_kv, g*S, ps) scores, batched over kv heads (GQA without repeat)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)

    start = start_ref[b]
    count = count_ref[b]
    # flat query index j = gi*S + si -> slot si = j % S at position
    # start + si; pad slots (si >= count) are fully masked
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, g * s_blk, 1), 1) % s_blk
    q_pos = start + slot
    kv_pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    valid = (kv_pos <= q_pos) & (kv_pos < start + count) & (slot < count)
    win = win_ref[0]
    # a boolean or, not a select: Mosaic cannot legalize arith.select
    # over i1 vectors
    valid = valid & ((win <= 0) | (q_pos - kv_pos < win))
    s = jnp.where(valid, s, _NEG)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    # exp() of a fully-masked row is exp(_NEG - _NEG) = 1; re-mask so
    # trash/garbage pages and pad slots contribute exactly zero weight
    w = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + w.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        w, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(p == maxp - 1)
    def _flush():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)[..., None]
        o_ref[...] = out[None].astype(o_ref.dtype)


def paged_prefill_attention(q, pages_k, pages_v, page_table, starts,
                            counts, window=0, *, interpret=None):
    """Ragged batched prefill attention through a paged KV cache.

    q:          (B, S, Hq, D) chunk queries, rotated to positions
                ``starts[b] + s``; scaled by 1/sqrt(D) in-kernel (fp32).
    pages_k/v:  (P, page_size, Hkv, D) physical page pool with the
                chunk's K/V already scattered into each row's private
                pages (page 0 is the reserved trash page).
    page_table: (B, MAXP) int32 — logical page i of row b lives in
                physical page ``page_table[b, i]``; unused slots are 0.
    starts:     (B,) int32 — position of each row's first query slot
                (tokens already cached before this chunk).
    counts:     (B,) int32 — real query slots per row; slots >= counts
                are pad, rows with 0 are inert padding rows.
    window:     scalar int32 — sliding-window size; 0 disables (a traced
                value: the per-layer gemma-style local/global pattern
                feeds it from inside the layer scan).

    Returns (B, S, Hq, D) in q.dtype; pad slots are zero.
    """
    b, s_blk, hq, d = q.shape
    npages, ps, n_kv, dk = pages_k.shape
    assert dk == d and hq % n_kv == 0, (q.shape, pages_k.shape)
    g = hq // n_kv
    maxp = page_table.shape[1]
    if interpret is None:
        interpret = not on_tpu()

    scale = 1.0 / (d ** 0.5)
    # (B, S, n_kv, g, d) -> (B, n_kv, g*S, d): flat j = gi*S + si, so the
    # kernel recovers the slot as j % S
    qk = (q.astype(jnp.float32) * scale).reshape(b, s_blk, n_kv, g, d)
    qk = qk.transpose(0, 2, 3, 1, 4).reshape(b, n_kv, g * s_blk, d)

    kernel = functools.partial(_prefill_kernel, ps=ps, n_kv=n_kv, g=g,
                               s_blk=s_blk, d=d, maxp=maxp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, n_kv, g * s_blk, d),
                         lambda bi, p, tbl, st, cn, wn: (bi, 0, 0, 0)),
            pl.BlockSpec((1, ps, n_kv, d),
                         lambda bi, p, tbl, st, cn, wn:
                         (tbl[bi, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, n_kv, d),
                         lambda bi, p, tbl, st, cn, wn:
                         (tbl[bi, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_kv, g * s_blk, d),
                               lambda bi, p, tbl, st, cn, wn:
                               (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, g * s_blk), jnp.float32),
            pltpu.VMEM((n_kv, g * s_blk), jnp.float32),
            pltpu.VMEM((n_kv, g * s_blk, d), jnp.float32),
        ],
    )
    win = jnp.full((1,), window, jnp.int32) if jnp.ndim(window) == 0 \
        else jnp.asarray(window, jnp.int32).reshape(1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g * s_blk, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(starts, jnp.int32),
      jnp.asarray(counts, jnp.int32), win, qk, pages_k, pages_v)
    # (B, n_kv, g*S, d) -> (B, S, Hq, D)
    out = out.reshape(b, n_kv, g, s_blk, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s_blk, hq, d)
