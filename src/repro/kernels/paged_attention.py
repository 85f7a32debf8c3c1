"""Paged decode attention: flash-decoding over a paged KV cache that
reads only each row's live pages.

Serving keeps K/V in fixed-size *pages* (``(num_pages, page_size, n_kv,
head_dim)`` per k/v) so requests of different lengths share one decode
batch without reserving ``max_len`` per row — the allocator hands pages
to rows on demand and the per-row *page table* maps logical token
position ``t`` to physical page ``table[b, t // page_size]``.

The kernel is the decode hot path (S=1 per row).  Its grid is ``(B,
MAXP / pages_per_block)``: each program attends one *block* of
``pages_per_block`` consecutive table slots of one row, about 256
tokens, a size taken from the shapes (`pages_per_block`).  The pool
stays in HBM; the page table and the lengths ride in scalar prefetch,
and each program copies exactly the block's live pages — one DMA per
page, a page with all its KV heads being one contiguous slab — into a
VMEM buffer.  While a block computes, the next live block's copies (the
row's next block, or the first block of the next row with a nonzero
length) run into the other of two buffers.  A block that starts at or
past the row's length issues no copy and no compute, so a row costs
DMA and arithmetic in proportion to its length, not to the table.

Softmax is online (running max / sum / accumulator in VMEM scratch
across the row's blocks, float32), each KV head's products batched on
their own (GQA without repeat).

Unused table slots point at page 0 — a reserved *trash page* no live row
owns — and are never read: only slots below the length are copied, and
every position at or past the length (or outside the window) is masked
out of both the scores and the values, so whatever the buffer held
there cannot reach the output.  Rows with ``length == 0`` (idle decode
rows) produce zeros.

Compiles for TPU v5e at qwen3-1.7b widths (tests/test_tpu_compile.py);
off-TPU it runs in Pallas's TPU interpret mode, which simulates the
DMAs and semaphores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import on_tpu

_NEG = -1e30
# a block holds at most this many tokens: large enough that the grid's
# fixed cost per program is small beside a block's copies, small enough
# that a short row's last block wastes little compute
_BLOCK_TOKENS = 256
# double-buffered K and V blocks plus their float32 working copies must
# fit well inside the 16 MiB of VMEM a kernel may use by default on v5e
_VMEM_BUDGET = 8 * 1024 * 1024


def block_vmem_bytes(ppb: int, ps: int, n_kv: int, d: int,
                     itemsize: int) -> int:
    """VMEM a block of ``ppb`` pages takes: two buffers each of K and V,
    and the float32 values and keys of the one being attended."""
    rows = ppb * ps * n_kv
    return 2 * 2 * rows * d * itemsize + 2 * rows * d * 4


def pages_per_block(maxp: int, ps: int, n_kv: int, d: int,
                     itemsize: int) -> int:
    """The largest divisor of ``maxp`` whose block holds at most
    `_BLOCK_TOKENS` tokens and whose buffers fit `_VMEM_BUDGET`; at
    least 1."""
    best = 1
    for n in range(1, maxp + 1):
        if maxp % n == 0 and n * ps <= _BLOCK_TOKENS and \
                block_vmem_bytes(n, ps, n_kv, d, itemsize) <= _VMEM_BUDGET:
            best = n
    return best


def _decode_kernel(table_ref, len_ref, win_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, state, m_ref, l_ref, acc_ref, *,
                   ps, ppb, n_kv, g, d, nblk, scale):
    b = pl.program_id(0)
    i = pl.program_id(1)
    nrows = pl.num_programs(0)
    bk = ppb * ps
    win = win_ref[0]

    def page_live(row, blk, j):
        # the page holds a position below the row's length and, under a
        # window, one the query at length - 1 attends
        start = (blk * ppb + j) * ps
        ln = len_ref[row]
        return (start < ln) & ((win <= 0) | (start + ps > ln - win))

    def page_copies(row, blk, slot, op):
        # ``op`` ("start" or "wait") on the copy of each live page of the
        # block, K and V each on the slot's own semaphore
        def one(j, carry):
            @pl.when(page_live(row, blk, j))
            def _():
                page = table_ref[row, blk * ppb + j]
                for n, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(hbm.at[page], buf.at[slot, j],
                                               sems.at[n, slot])
                    getattr(cp, op)()
            return carry
        jax.lax.fori_loop(0, ppb, one, 0)

    @pl.when((b == 0) & (i == 0))
    def _first_program():
        state[0] = 0        # the buffer the next live block lands in
        state[1] = 1        # 1 until the first live block has started

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(i * bk < length)
    def _attend():
        slot = state[0]

        @pl.when(state[1] == 1)
        def _():
            page_copies(b, i, slot, "start")
            state[1] = 0

        # the next live block in grid order: this row's next block, else
        # block 0 of the next row with a nonzero length
        same = (i + 1 < nblk) & ((i + 1) * bk < length)

        def next_row():
            return jax.lax.fori_loop(
                b + 1, nrows,
                lambda r, c: jnp.where((c == nrows) & (len_ref[r] > 0),
                                       r, c),
                nrows)

        to_row = jax.lax.cond(same, lambda: b, next_row)
        to_blk = jnp.where(same, i + 1, 0)

        @pl.when(to_row < nrows)
        def _():
            page_copies(to_row, to_blk, 1 - slot, "start")
            state[0] = 1 - slot

        page_copies(b, i, slot, "wait")

        # (n_kv, bk, d): each KV head's keys and values in token order
        k = k_buf[slot].astype(jnp.float32).reshape(bk, n_kv, d) \
            .transpose(1, 0, 2)
        v = v_buf[slot].astype(jnp.float32).reshape(bk, n_kv, d) \
            .transpose(1, 0, 2)
        q = q_ref[0].reshape(n_kv, g, d).astype(jnp.float32) * scale
        # (n_kv, g, bk) scores, batched over kv heads (GQA without
        # repeat): each head's sums are its own, whatever the other
        # heads, so a head shard computes bitwise what the whole does
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)

        def in_span(shape, dim):
            # the block's positions the query attends; a boolean or, not
            # a select: Mosaic cannot legalize arith.select over i1
            pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, shape, dim)
            return (pos < length) & ((win <= 0) | (length - 1 - pos < win))

        valid = in_span((1, 1, bk), 2)
        s = jnp.where(valid, s, _NEG)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        # exp() of a fully-masked row is exp(_NEG - _NEG) = 1: re-mask
        w = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        # positions outside the row's span hold whatever the buffer had
        # (another row's block, or nothing copied): zero them, so that a
        # NaN there cannot leak through a zero weight
        v = jnp.where(in_span((1, bk, 1), 1), v, 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + w.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
            w, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(i == nblk - 1)
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
    interpret:  None compiles on a TPU and interprets elsewhere.

    Returns (B, Hq, D) in q.dtype.
    """
    b, hq, d = q.shape
    _, ps, n_kv, dk = pages_k.shape
    assert dk == d and hq % n_kv == 0, (q.shape, pages_k.shape)
    g = hq // n_kv
    maxp = page_table.shape[1]
    ppb = pages_per_block(maxp, ps, n_kv, d, pages_k.dtype.itemsize)
    nblk = maxp // ppb
    if interpret is None:
        interpret = not on_tpu()
    if interpret is True:
        # the TPU interpreter: it runs the DMAs and semaphores
        interpret = pltpu.InterpretParams()

    kernel = functools.partial(_decode_kernel, ps=ps, ppb=ppb, n_kv=n_kv,
                               g=g, d=d, nblk=nblk, scale=1.0 / (d ** 0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda bi, i, tbl, ln, wn: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hq, d),
                               lambda bi, i, tbl, ln, wn: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, n_kv, d), pages_k.dtype),
            pltpu.VMEM((2, ppb, ps, n_kv, d), pages_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
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
        # both axes in order: a program starts the copies of the next
        # live block, which may belong to the next row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      win, q, pages_k, pages_v)
