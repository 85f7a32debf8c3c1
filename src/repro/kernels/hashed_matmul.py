"""Fused decompress-GEMM Pallas TPU kernels for HashedNets.

The performance-critical op of the paper at deployment time is
``y = x @ V`` where ``V`` never exists in memory — only the hashed bank
``w`` does.  These kernels keep ``w`` compressed in HBM and expand one
MXU-aligned tile of ``V`` at a time into VMEM:

- element mode: the virtual tile's bucket indices + signs are *recomputed
  in-kernel* from the murmur-mix hash over a 2-D iota (zero index storage,
  exactly the paper's point), then gathered from the panel's bucket slice
  (which the BlockSpec pipeline has staged into VMEM).
- block mode: the bank tile for virtual tile (ti, tj) is selected with a
  scalar-prefetch indexed BlockSpec — a *dense contiguous* HBM->VMEM DMA.
  This is the TPU answer to the paper's §7 "non-coalesced access" problem.

Grids iterate (m, n, k) with k innermost; partial products accumulate in a
float32 VMEM scratch and are flushed to the output on the last k step.

The backward kernels realize paper Eq. 12:
- dx = g @ V^T reuses the forward structure with virtual coordinates
  swapped (``transpose=True``).
- dw scatter-reduces sign-weighted outer-product tiles into the bank.  The
  block-mode dw kernel orders the virtual-tile walk by bank index (a static
  permutation — the hash is static given the spec) so that all writes to a
  bank tile are consecutive grid steps, which makes output-block revisiting
  with accumulate-in-place legal under TPU's sequential grid semantics.

TPU lowering: the block-mode kernels use only dense dots and
scalar-prefetch DMAs, and compile for v5e (tests/test_tpu_compile.py).
The element-mode in-VMEM gather (``jnp.take`` of a 1-D bucket slice by a
2-D index tile) is refused by Mosaic, which lowers only 2-D gathers, so
element mode runs in interpret mode only; ``ops.hashed_matmul`` raises
rather than compile it for TPU.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashed, hashing

# ---------------------------------------------------------------------------
# element-mode forward / transpose-forward
# ---------------------------------------------------------------------------


def _element_tile(spec: hashed.HashedSpec, wvec, r0, c0, bk, bn, transpose,
                  dtype):
    """Decompress one (bk, bn) tile of V (or V^T if transpose) into VMEM.

    r0/c0 are the tile's top-left coordinates in the *operand being
    multiplied* (i.e. in V^T coordinates when transpose=True).  wvec is the
    bucket slice staged for this tile's panel (local indices).
    """
    di = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
    dj = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1)
    if transpose:
        i = c0 + dj  # virtual row of V
        j = r0 + di  # virtual col of V
    else:
        i = r0 + di
        j = c0 + dj
    kp = spec.buckets_per_panel
    h = hashing.bucket_hash(i, j, kp, spec.seed)
    tile = jnp.take(wvec, h, axis=0)
    if spec.use_sign:
        tile = tile * hashing.sign_hash(i, j, spec.seed).astype(wvec.dtype)
    return tile.astype(dtype)


def _element_fwd_kernel(x_ref, w_ref, o_ref, acc_ref, *, spec, bm, bk, bn,
                        nk, transpose):
    ci = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vtile = _element_tile(
        spec, w_ref[...], ki * bk, ci * bn, bk, bn, transpose, x_ref.dtype
    )
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], vtile, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def element_matmul(x, w, spec: hashed.HashedSpec, *, block=(128, 128, 128),
                   transpose: bool = False, interpret: bool = True,
                   out_dtype=None):
    """x @ V (transpose=False) or x @ V^T (transpose=True), element mode.

    x: (M, R) where R = spec.rows (or spec.cols when transpose).
    """
    assert spec.mode == "element"
    out_dtype = out_dtype or x.dtype
    bm, bk, bn = block
    m, r = x.shape
    c = spec.cols if not transpose else spec.rows
    assert r == (spec.rows if not transpose else spec.cols), (x.shape, spec)
    assert m % bm == 0 and r % bk == 0 and c % bn == 0, (x.shape, c, block)

    kp = spec.buckets_per_panel
    panel_cols = spec.panel_cols if spec.panel_cols > 0 else spec.cols
    # a kernel tile must sit inside a single bucket panel
    pdim = bk if transpose else bn  # tile extent along virtual columns
    assert panel_cols % pdim == 0, (panel_cols, pdim)

    nk = r // bk

    if transpose:
        # panel determined by the contraction index (virtual column)
        def w_index(mi, ci, ki):
            return ((ki * bk) // panel_cols,)
    else:
        def w_index(mi, ci, ki):
            return ((ci * bn) // panel_cols,)

    kernel = functools.partial(
        _element_fwd_kernel, spec=spec, bm=bm, bk=bk, bn=bn, nk=nk,
        transpose=transpose,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // bm, c // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ci, ki: (mi, ki)),
            pl.BlockSpec((kp,), w_index),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ci, ki: (mi, ci)),
        out_shape=jax.ShapeDtypeStruct((m, c), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# element-mode dw (paper Eq. 12)
# ---------------------------------------------------------------------------


def _element_dw_kernel(x_ref, g_ref, o_ref, acc_ref, *, spec, bk, bn, nm,
                       panel_cols):
    ci = pl.program_id(0)
    ki = pl.program_id(1)
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # accumulate the (bk, bn) slab of x^T g over the batch dimension
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(mi == nm - 1)
    def _scatter():
        i = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
        j = ci * bn + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1)
        kp = spec.buckets_per_panel
        h = hashing.bucket_hash(i, j, kp, spec.seed)
        val = acc_ref[...]
        if spec.use_sign:
            val = val * hashing.sign_hash(i, j, spec.seed).astype(val.dtype)
        seg = jax.ops.segment_sum(val.ravel(), h.ravel(), num_segments=kp)
        first_of_panel = (ci * bn) % panel_cols == 0

        @pl.when(jnp.logical_and(first_of_panel, ki == 0))
        def _store():
            o_ref[...] = seg

        @pl.when(jnp.logical_not(jnp.logical_and(first_of_panel, ki == 0)))
        def _accum():
            o_ref[...] += seg


def element_dw(x, g, spec: hashed.HashedSpec, *, block=(128, 128, 128),
               interpret: bool = True):
    """dw (num_buckets,) from upstream grad g of y = x @ V."""
    assert spec.mode == "element"
    bm, bk, bn = block
    m, r = x.shape
    mg, c = g.shape
    assert m == mg and r == spec.rows and c == spec.cols
    assert m % bm == 0 and r % bk == 0 and c % bn == 0
    kp = spec.buckets_per_panel
    panel_cols = spec.panel_cols if spec.panel_cols > 0 else spec.cols
    assert panel_cols % bn == 0
    nm = m // bm

    kernel = functools.partial(
        _element_dw_kernel, spec=spec, bk=bk, bn=bn, nm=nm,
        panel_cols=panel_cols,
    )
    return pl.pallas_call(
        kernel,
        grid=(c // bn, r // bk, nm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda ci, ki, mi: (mi, ki)),
            pl.BlockSpec((bm, bn), lambda ci, ki, mi: (mi, ci)),
        ],
        out_specs=pl.BlockSpec((kp,), lambda ci, ki, mi: ((ci * bn) // panel_cols,)),
        out_shape=jax.ShapeDtypeStruct((spec.num_buckets,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, g)


# ---------------------------------------------------------------------------
# block-mode forward / transpose-forward (scalar-prefetch tile gather)
# ---------------------------------------------------------------------------


def _block_fwd_kernel(idx_ref, sgn_ref, x_ref, bank_ref, o_ref, acc_ref, *,
                      nk, transpose):
    del idx_ref  # consumed by the index_map
    ci = pl.program_id(1)
    ki = pl.program_id(2)
    ncols = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = bank_ref[0]
    if transpose:
        tile = tile.T
        sgn = sgn_ref[ci * nk + ki]  # sgn indexed by (virtual ti=ci?, tj)
    else:
        sgn = sgn_ref[ki * ncols + ci]
    tile = tile * sgn.astype(tile.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], tile.astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_matmul(x, w, spec: hashed.HashedSpec, *, bm: int = 128,
                 transpose: bool = False, interpret: bool = True,
                 out_dtype=None):
    """x @ V (or x @ V^T), block mode.  Tile shape = spec.block_shape."""
    assert spec.mode == "block"
    out_dtype = out_dtype or x.dtype
    brow, bcol = spec.block_shape
    gi, gj = spec.tile_grid
    m, r = x.shape
    if transpose:
        nk, nc, bk, bn = gj, gi, bcol, brow
    else:
        nk, nc, bk, bn = gi, gj, brow, bcol
    assert r == nk * bk, (x.shape, spec.virtual_shape)
    assert m % bm == 0

    # (gi, gj) arrays, kept row-major: the index_map linearizes (ti, tj) as
    # ti * gj + tj in both orientations (transpose only swaps which of
    # ci/ki plays ti vs tj).
    idx, sgn = hashed.block_indices(spec)
    idx_flat = idx.reshape(-1)
    sgn_flat = sgn.reshape(-1)

    def bank_index(mi, ci, ki, idx_ref, sgn_ref):
        del mi, sgn_ref
        # virtual tile walk order matches idx_flat layout: (k-major, c-minor)
        if transpose:
            return (idx_ref[ci * nk + ki], 0, 0)
        return (idx_ref[ki * nc + ci], 0, 0)

    kernel = functools.partial(_block_fwd_kernel, nk=nk, transpose=transpose)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // bm, nc, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ci, ki, idx_ref, sgn_ref: (mi, ki)),
            pl.BlockSpec((1, brow, bcol), bank_index),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda mi, ci, ki, idx_ref, sgn_ref: (mi, ci)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nc * bn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(idx_flat, sgn_flat, x, w)


# ---------------------------------------------------------------------------
# block-mode dw: bank-ordered virtual-tile walk with output revisiting
# ---------------------------------------------------------------------------


def _block_dw_kernel(bank_ref, ti_ref, tj_ref, sgn_ref, first_ref, x_ref,
                     g_ref, o_ref, acc_ref, *, nm):
    del bank_ref  # consumed by the output index_map
    t = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(mi == nm - 1)
    def _flush():
        contrib = acc_ref[...] * sgn_ref[t].astype(jnp.float32)

        @pl.when(first_ref[t] == 1)
        def _store():
            o_ref[0] = contrib

        @pl.when(first_ref[t] == 0)
        def _accum():
            o_ref[0] += contrib


def block_dw(x, g, spec: hashed.HashedSpec, *, bm: int = 128,
             interpret: bool = True):
    """dbank (bank_tiles, brow, bcol) from upstream grad of y = x @ V."""
    assert spec.mode == "block"
    brow, bcol = spec.block_shape
    gi, gj = spec.tile_grid
    m, r = x.shape
    mg, c = g.shape
    assert m == mg and r == spec.rows and c == spec.cols and m % bm == 0
    nm = m // bm

    idx, sgn = hashed.block_indices(spec)
    idx_np = np.asarray(idx).reshape(-1)
    # static permutation: walk virtual tiles grouped by bank index so writes
    # to a bank tile are consecutive grid steps
    order = np.argsort(idx_np, kind="stable").astype(np.int32)
    sorted_bank = idx_np[order]
    first = np.ones_like(sorted_bank)
    first[1:] = (sorted_bank[1:] != sorted_bank[:-1]).astype(np.int32)
    ti = (order // gj).astype(np.int32)
    tj = (order % gj).astype(np.int32)
    sgn_sorted = np.asarray(sgn).reshape(-1)[order].astype(np.int32)

    def out_index(t, mi, bank_ref, ti_ref, tj_ref, sgn_ref, first_ref):
        del ti_ref, tj_ref, sgn_ref, first_ref, mi
        return (bank_ref[t], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(gi * gj, nm),
        in_specs=[
            pl.BlockSpec(
                (bm, brow),
                lambda t, mi, b_ref, ti_ref, tj_ref, s_ref, f_ref:
                    (mi, ti_ref[t])),
            pl.BlockSpec(
                (bm, bcol),
                lambda t, mi, b_ref, ti_ref, tj_ref, s_ref, f_ref:
                    (mi, tj_ref[t])),
        ],
        out_specs=pl.BlockSpec((1, brow, bcol), out_index),
        scratch_shapes=[pltpu.VMEM((brow, bcol), jnp.float32)],
    )
    kernel = functools.partial(_block_dw_kernel, nm=nm)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((spec.bank_tiles, brow, bcol),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(sorted_bank, jnp.int32), jnp.asarray(ti), jnp.asarray(tj),
      jnp.asarray(sgn_sorted), jnp.asarray(first), x, g)
