"""jit'd public entry points for the hashed decompress-GEMM kernels.

``hashed_matmul(x, w, spec)`` accepts arbitrary leading batch dims, pads the
flattened row count to the kernel's block multiple, dispatches element/block
kernels, and wires a custom VJP whose backward pass is *also* kernelized
(dx = transpose-forward kernel, dw = scatter-reduce kernel; paper Eq. 12).

On non-TPU backends the kernels run in interpret mode (pure-Python grid
walk) — numerically identical, used for CI on CPU.  Only the block-mode
kernels compile for TPU: element mode raises there at trace time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashed
from repro.kernels import hashed_matmul as hk
from repro.runtime import on_tpu


def _pad_rows(x2, bm):
    m = x2.shape[0]
    pad = (-m) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, m


def _pick_bm(m: int, target: int = 128) -> int:
    """Largest power-of-two block <= target that keeps padding waste small."""
    bm = target
    while bm > 8 and m % bm and m < bm:
        bm //= 2
    return bm


def _fwd_impl(x, w, spec: hashed.HashedSpec, dtype, interpret, block):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    bm = _pick_bm(x2.shape[0], block[0])
    x2, m = _pad_rows(x2, bm)
    if spec.mode == "element":
        y = hk.element_matmul(x2, w, spec, block=(bm, block[1], block[2]),
                              interpret=interpret, out_dtype=dtype)
    else:
        y = hk.block_matmul(x2, w, spec, bm=bm, interpret=interpret,
                            out_dtype=dtype)
    return y[:m].reshape(lead + (spec.cols,))


def _bwd_dx_impl(g, w, spec: hashed.HashedSpec, dtype, interpret, block):
    lead = g.shape[:-1]
    g2 = g.reshape(-1, g.shape[-1])
    bm = _pick_bm(g2.shape[0], block[0])
    g2, m = _pad_rows(g2, bm)
    if spec.mode == "element":
        dx = hk.element_matmul(g2, w, spec, block=(bm, block[1], block[2]),
                               transpose=True, interpret=interpret,
                               out_dtype=dtype)
    else:
        dx = hk.block_matmul(g2, w, spec, bm=bm, transpose=True,
                             interpret=interpret, out_dtype=dtype)
    return dx[:m].reshape(lead + (spec.rows,))


def _bwd_dw_impl(x, g, spec: hashed.HashedSpec, interpret, block):
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    bm = _pick_bm(x2.shape[0], block[0])
    x2, _ = _pad_rows(x2, bm)
    g2, _ = _pad_rows(g2, bm)
    if spec.mode == "element":
        return hk.element_dw(x2, g2, spec, block=(bm, block[1], block[2]),
                             interpret=interpret)
    return hk.block_dw(x2, g2, spec, bm=bm, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _hashed_matmul(x, w, spec, dtype, interpret, block):
    return _fwd_impl(x, w, spec, dtype, interpret, block)


def _vjp_fwd(x, w, spec, dtype, interpret, block):
    return _fwd_impl(x, w, spec, dtype, interpret, block), (x, w)


def _vjp_bwd(spec, dtype, interpret, block, res, g):
    x, w = res
    dx = _bwd_dx_impl(g, w, spec, x.dtype, interpret, block)
    dw = _bwd_dw_impl(x, g, spec, interpret, block).astype(w.dtype)
    return dx, dw


_hashed_matmul.defvjp(_vjp_fwd, _vjp_bwd)


def hashed_matmul(x, w, spec: hashed.HashedSpec, dtype=None,
                  interpret=None, block=(128, 128, 128)):
    """y = x @ decompress(w, spec), fused Pallas kernel, differentiable."""
    spec.validate()
    if spec.mode == "block":
        bm_, bn_ = spec.block_shape
        if spec.rows % bm_ or spec.cols % bn_:
            raise ValueError(
                f"pallas block path needs block_shape {spec.block_shape} to "
                f"divide virtual_shape {spec.virtual_shape}; use the scan or "
                f"materialize path for ragged grids")
    dtype = dtype or x.dtype
    if interpret is None:
        interpret = not on_tpu()
    if spec.mode == "element" and not interpret:
        raise NotImplementedError(
            "the element-mode hashed_matmul kernel does not compile for "
            "TPU (Mosaic lowers only 2-D gathers; the in-VMEM bucket "
            "gather is a 1-D take with 2-D indices).  Use hash_mode="
            "'block' with the pallas path, or hash_path='scan' / "
            "'materialize' for element mode")
    return _hashed_matmul(x, w, spec, dtype, bool(interpret), tuple(block))
