"""Top-k logit filtering: radix-select threshold kernel for the sampler.

The serving sampler's hot loop filters every decode row's logits to its
top-k before sampling (`repro.serving.sampling`).  Per-row k varies
freely across the batch (mixed `SamplingParams`), so the static-k
`jax.lax.top_k` can't be dispatched once for the whole batch — the
sorted fallback pays a full (B, V) sort per tick.

The Pallas kernel selects the k-th largest value WITHOUT sorting: an
MSB-first radix walk over the fp32 bit patterns.  IEEE-754 floats map
monotonically onto int32 by flipping the low 31 bits of negatives
(``s = i ^ ((i >> 31) & 0x7fffffff)``); XOR-ing the top bit then turns
unsigned radix order into native signed compares.  32 fixed iterations
build the threshold's bit pattern top-down — each step keeps the
candidate bit iff at least k lane values still sit at-or-above it — so
the selected threshold is EXACTLY the k-th largest element (bitwise: it
is one of the inputs), and the emitted mask ``x >= threshold`` matches
the `jax.lax.top_k`-derived oracle tie-for-tie (ties at the boundary
all survive, same as the oracle's value-threshold semantics).

One program per row (grid ``(B,)``), the k vector rides in scalar
prefetch, and the whole row stays in VMEM as one ``(R, 128)`` tile (the
row padded to a multiple of 8 * 128, so the block meets the TPU's
8x128 tiling whatever B is): 32 compare+reduce passes over it replace
sort's O(V log V) shuffles — no data movement at all beyond the initial
row DMA.  Off-TPU callers get the `jax.lax.top_k` (full-sort) fallback
instead; tests run the kernel in interpret mode.

k <= 0 or k >= V disables filtering for that row (the "no top-k" case
in SamplingParams), matching the oracle.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import on_tpu

NEG = -1e30
# numpy scalars (not jnp arrays): they inline as literals inside the
# Pallas kernel instead of being captured as device constants
_INT_MIN = np.int32(-(2 ** 31))
_LOW31 = np.int32(0x7FFFFFFF)
_LANES = 128


def _sortable(x_f32):
    """Monotone fp32 -> int32 map: a >= b (float) iff s(a) >= s(b) (int).

    -0.0 is canonicalized to +0.0 first: float compares treat them as
    equal, but their bit patterns sort apart — without this a +-0.0
    threshold would mask differently from the float-comparing oracle
    and lax fallback."""
    x_f32 = jnp.where(x_f32 == 0.0, 0.0, x_f32)
    xi = jax.lax.bitcast_convert_type(x_f32, jnp.int32)
    return xi ^ (jnp.right_shift(xi, 31) & _LOW31)


def _topk_kernel(k_ref, x_ref, o_ref, *, v_real, fill):
    b = pl.program_id(0)
    x = x_ref[0].astype(jnp.float32)                    # (R, 128)
    s = _sortable(x)
    col = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    valid = col < v_real
    kk = k_ref[b]

    def body(i, p):
        # u-space (unsigned radix order) candidate; compare in s-space
        cand = p | jnp.left_shift(np.int32(1), 31 - i)
        cand_s = cand ^ _INT_MIN
        cnt = jnp.sum(jnp.where(valid & (s >= cand_s), 1, 0))
        return jnp.where(cnt >= kk, cand, p)

    p = jax.lax.fori_loop(0, 32, body, np.int32(0))
    thr = p ^ _INT_MIN                                  # k-th largest, s-space
    disabled = (kk <= 0) | (kk >= v_real)
    keep = disabled | (s >= thr)
    o_ref[0] = jnp.where(keep & valid, x_ref[0],
                         jnp.asarray(fill, o_ref.dtype))


def _topk_mask_lax(logits, k, fill):
    """Off-TPU fallback: jax.lax.top_k with k=V (a full descending sort)
    so one dispatch still covers every per-row k in the batch."""
    b, v = logits.shape
    x = logits.astype(jnp.float32)
    vals = jax.lax.top_k(x, v)[0]                       # (B, V) descending
    idx = jnp.clip(k - 1, 0, v - 1)
    thr = jnp.take_along_axis(vals, idx[:, None], axis=1)
    disabled = (k <= 0) | (k >= v)
    keep = disabled[:, None] | (x >= thr)
    return jnp.where(keep, logits, jnp.asarray(fill, logits.dtype))


def topk_mask(logits, k, fill=NEG, *, use_pallas=None, interpret=None):
    """Mask each row of ``logits`` to its top-``k[row]`` values.

    logits: (B, V) float; k: (B,) int32 per-row k — ``k <= 0`` or
    ``k >= V`` disables filtering for that row.  Values strictly below
    the row's k-th largest become ``fill``; boundary ties all survive
    (value-threshold semantics, identical to `ref.topk_mask_ref`).
    Comparisons happen on the fp32 view of the input; the surviving
    values pass through in the input dtype, bit-untouched.
    """
    b, v = logits.shape
    k = jnp.asarray(k, jnp.int32)
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return _topk_mask_lax(logits, k, fill)
    if interpret is None:
        interpret = not on_tpu()
    tile = 8 * _LANES
    vp = -(-v // tile) * tile
    x = logits if vp == v else jnp.pad(
        logits, ((0, 0), (0, vp - v)),
        constant_values=jnp.asarray(fill, logits.dtype))
    rows = vp // _LANES
    x = x.reshape(b, rows, _LANES)
    kernel = functools.partial(_topk_kernel, v_real=v, fill=fill)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, rows, _LANES),
                               lambda bi, kr: (bi, 0, 0))],
        out_specs=pl.BlockSpec((1, rows, _LANES),
                               lambda bi, kr: (bi, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, _LANES), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(k, x)
    return out.reshape(b, vp)[:, :v]
