"""Paged-gather decode attention: flash-decoding over a paged KV cache.

Serving keeps K/V in fixed-size *pages* (``(num_pages, page_size, n_kv,
head_dim)`` per k/v) so requests of different lengths share one decode
batch without reserving ``max_len`` per row — the allocator hands pages
to rows on demand and the per-row *page table* maps logical token
position ``t`` to physical page ``table[b, t // page_size]``.

The kernel is the decode hot path (S=1 per row): grid ``(B, MAXP)``,
one program per (row, logical page).  The page table rides in scalar
prefetch, so the BlockSpec ``index_map`` resolves the *physical* page to
DMA before the body runs — the gather through the table costs nothing
beyond the DMA it would issue anyway (the TPU answer to "non-coalesced
access", same trick as the block-mode hashed GEMM kernels).  Softmax is
online (running max / sum / accumulator in VMEM scratch across the page
walk), so no (B, T) score tensor ever materializes.

Unused table slots point at page 0 — a reserved *trash page* no live row
owns — and are masked out through ``lengths``; rows with ``length == 0``
(idle decode rows) produce zeros.

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


def _decode_kernel(table_ref, len_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, ps, n_kv, g, d, maxp, scale):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].reshape(n_kv, g, d).astype(jnp.float32) * scale
    k = k_ref[0].transpose(1, 0, 2).astype(jnp.float32)   # (n_kv, ps, d)
    v = v_ref[0].transpose(1, 0, 2).astype(jnp.float32)

    # (n_kv, g, ps) scores, batched over kv heads (GQA without repeat)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)

    length = len_ref[b]
    kv_pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    valid = kv_pos < length
    win = win_ref[0]
    q_pos = length - 1
    # a boolean or, not a select: Mosaic cannot legalize arith.select
    # over i1 vectors
    valid = valid & ((win <= 0) | (q_pos - kv_pos < win))
    s = jnp.where(valid, s, _NEG)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    # exp() of a fully-masked row is exp(_NEG - _NEG) = 1; re-mask so
    # trash/garbage pages contribute exactly zero weight
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
        o_ref[...] = out.reshape(1, n_kv * g, d).astype(o_ref.dtype)


def paged_decode_attention(q, pages_k, pages_v, page_table, lengths,
                           window=0, *, interpret=None):
    """One decode step of attention through a paged KV cache.

    q:          (B, Hq, D) current-token queries, rotated to position
                ``lengths - 1``; scaled by 1/sqrt(D) in-kernel (fp32).
    pages_k/v:  (P, page_size, Hkv, D) physical page pool (page 0 is the
                reserved trash page).
    page_table: (B, MAXP) int32 — logical page i of row b lives in
                physical page ``page_table[b, i]``; unused slots are 0.
    lengths:    (B,) int32 — valid cached tokens per row INCLUDING the
                current token's k/v (already written to its page).
    window:     scalar int32 — sliding-window size; 0 disables (a traced
                value: the per-layer gemma-style local/global pattern
                feeds it from inside the layer scan).

    Returns (B, Hq, D) in q.dtype.
    """
    b, hq, d = q.shape
    npages, ps, n_kv, dk = pages_k.shape
    assert dk == d and hq % n_kv == 0, (q.shape, pages_k.shape)
    g = hq // n_kv
    maxp = page_table.shape[1]
    if interpret is None:
        interpret = not on_tpu()

    kernel = functools.partial(_decode_kernel, ps=ps, n_kv=n_kv, g=g, d=d,
                               maxp=maxp, scale=1.0 / (d ** 0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, hq, d),
                         lambda bi, p, tbl, ln, wn: (bi, 0, 0)),
            pl.BlockSpec((1, ps, n_kv, d),
                         lambda bi, p, tbl, ln, wn: (tbl[bi, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, n_kv, d),
                         lambda bi, p, tbl, ln, wn: (tbl[bi, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, d),
                               lambda bi, p, tbl, ln, wn: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, g), jnp.float32),
            pltpu.VMEM((n_kv, g), jnp.float32),
            pltpu.VMEM((n_kv, g, d), jnp.float32),
        ],
    )
    win = jnp.full((1,), window, jnp.int32) if jnp.ndim(window) == 0 \
        else jnp.asarray(window, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      win, q, pages_k, pages_v)
