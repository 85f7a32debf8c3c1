"""HashedNets parameterization (Chen et al., ICML 2015) + TPU-native block mode.

A *virtual* 2-D weight matrix ``V`` of shape ``(rows, cols)`` is represented
by a small *real* parameter bank:

- ``element`` mode (paper-faithful, Eq. 3/7):
      V[i, j] = xi(i, j) * w[h(i, j)]
  with ``w`` of size ``K ~= compression * rows * cols``.  For TPU locality the
  bucket space is optionally stratified into column *panels*: each panel of
  ``panel_cols`` columns owns ``K / n_panels`` buckets and the hash randomizes
  freely within the panel.  ``panel_cols=0`` gives the paper's single global
  bucket space.

- ``block`` mode (TPU-native adaptation, see DESIGN.md §2):
      tile(ti, tj) = sigma(ti, tj) * bank[h(ti, tj)]
  where tiles are MXU-aligned ``(block_rows, block_cols)`` slabs and ``bank``
  holds ``K_t ~= compression * n_tiles`` real tiles.  Decompression is a dense
  tile gather.

Three numerically-identical execution paths (all differentiable; gradients
realize paper Eq. 12 as the autodiff transpose of the gather):

- :func:`materialize`           — build V explicitly (small layers, oracle)
- :func:`matmul` path="scan"    — lax.scan over column panels; peak live
                                  intermediate is a single panel (used by the
                                  multi-pod dry-run so compiled memory reflects
                                  the compressed footprint)
- path="pallas"                 — fused decompress-GEMM kernel
                                  (repro.kernels.hashed_matmul)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing


@dataclasses.dataclass(frozen=True)
class HashedSpec:
    """Static description of one hashed virtual matrix."""

    virtual_shape: Tuple[int, int]  # (rows, cols); used as x @ V
    compression: float              # c = real params / virtual params
    mode: str = "element"           # "element" | "block"
    seed: int = 0
    panel_cols: int = 0             # element mode: 0 => global bucket space
    block_shape: Tuple[int, int] = (128, 128)
    use_sign: bool = True
    # Execution hint, NOT part of the matrix's identity: which matmul path
    # the policy picked for this slot ("" = caller's default).  Excluded
    # from equality/serialization so policy-resolved specs stay
    # byte-identical to pre-policy ones (see repro.policy).
    exec_path: str = dataclasses.field(default="", compare=False)

    # ---- derived sizes -------------------------------------------------
    @property
    def rows(self) -> int:
        return self.virtual_shape[0]

    @property
    def cols(self) -> int:
        return self.virtual_shape[1]

    @property
    def virtual_size(self) -> int:
        return self.rows * self.cols

    @property
    def n_panels(self) -> int:
        if self.mode != "element":
            raise ValueError("n_panels is element-mode only")
        if self.panel_cols <= 0:
            return 1
        return max(1, math.ceil(self.cols / self.panel_cols))

    @property
    def buckets_per_panel(self) -> int:
        k_total = max(self.n_panels, int(round(self.compression * self.virtual_size)))
        return max(1, k_total // self.n_panels)

    @property
    def num_buckets(self) -> int:
        """Real parameter count, element mode."""
        return self.buckets_per_panel * self.n_panels

    @property
    def tile_grid(self) -> Tuple[int, int]:
        bm, bn = self.block_shape
        return (math.ceil(self.rows / bm), math.ceil(self.cols / bn))

    @property
    def num_tiles(self) -> int:
        gi, gj = self.tile_grid
        return gi * gj

    @property
    def bank_tiles(self) -> int:
        return max(1, int(round(self.compression * self.num_tiles)))

    def real_param_shape(self) -> Tuple[int, ...]:
        if self.mode == "element":
            return (self.num_buckets,)
        bm, bn = self.block_shape
        return (self.bank_tiles, bm, bn)

    def real_param_count(self) -> int:
        return int(np.prod(self.real_param_shape()))

    def validate(self) -> None:
        if self.mode not in ("element", "block"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if not (0.0 < self.compression <= 1.0):
            raise ValueError("compression must be in (0, 1]")
        if self.mode == "block":
            bm, bn = self.block_shape
            if bm <= 0 or bn <= 0:
                raise ValueError(f"bad block_shape {self.block_shape}")
            # Non-divisible virtual shapes are allowed: the tile grid is
            # ceil-sized and every consumer slices back to (rows, cols).
            # Only the fused Pallas kernel requires exact divisibility
            # (checked in repro.kernels.ops at dispatch).

    # ---- serialization (artifact header / registry metadata) -----------
    def to_dict(self) -> dict:
        """JSON-safe description; exact inverse of :func:`spec_from_dict`.

        Everything needed to regenerate the virtual matrix from the bank
        alone — this is what the paper's storage claim rests on: the hash
        is stateless, so an artifact stores only these few scalars + the
        real parameters."""
        return {
            "virtual_shape": [int(x) for x in self.virtual_shape],
            "compression": float(self.compression),
            "mode": self.mode,
            "seed": int(self.seed),
            "panel_cols": int(self.panel_cols),
            "block_shape": [int(x) for x in self.block_shape],
            "use_sign": bool(self.use_sign),
        }


def spec_to_dict(spec: HashedSpec) -> dict:
    return spec.to_dict()


def spec_from_dict(d: dict) -> HashedSpec:
    spec = HashedSpec(
        virtual_shape=tuple(int(x) for x in d["virtual_shape"]),
        compression=float(d["compression"]),
        mode=str(d["mode"]),
        seed=int(d["seed"]),
        panel_cols=int(d.get("panel_cols", 0)),
        block_shape=tuple(int(x) for x in d.get("block_shape", (128, 128))),
        use_sign=bool(d.get("use_sign", True)),
    )
    spec.validate()
    return spec


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------

def init(key, spec: HashedSpec, scale: Optional[float] = None, dtype=jnp.float32):
    """Initialize the real bank so that the *virtual* matrix has fan-in
    scaled variance.  Because xi decorrelates colliding entries, initializing
    ``w ~ N(0, scale^2)`` gives ``Var(V_ij) = scale^2`` — identical to a dense
    init of V (paper trains with standard init on w)."""
    spec.validate()
    if scale is None:
        scale = 1.0 / math.sqrt(spec.rows)
    return (jax.random.normal(key, spec.real_param_shape(), jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# index computation (shared by all paths + the Pallas kernel)
# ---------------------------------------------------------------------------

def element_indices(spec: HashedSpec, i, j):
    """Bucket index + sign for absolute virtual coordinates (i, j).

    Panel-local stratification: bucket = panel * Kp + h(i,j) % Kp.
    """
    kp = spec.buckets_per_panel
    if spec.panel_cols > 0:
        panel = jnp.asarray(j, jnp.int32) // spec.panel_cols
    else:
        panel = jnp.zeros_like(jnp.asarray(j, jnp.int32))
    h = hashing.bucket_hash(i, j, kp, spec.seed)
    idx = panel * kp + h
    if spec.use_sign:
        sgn = hashing.sign_hash(i, j, spec.seed)
    else:
        sgn = jnp.ones_like(idx)
    return idx, sgn


def block_indices(spec: HashedSpec):
    """Tile->bank index map + per-tile sign for the whole grid (tiny arrays,
    recomputable from the hash at any time — no stored index structure)."""
    gi, gj = spec.tile_grid
    ti = jnp.arange(gi, dtype=jnp.int32)[:, None]
    tj = jnp.arange(gj, dtype=jnp.int32)[None, :]
    idx = hashing.bucket_hash(ti, tj, spec.bank_tiles, spec.seed)
    if spec.use_sign:
        sgn = hashing.sign_hash(ti, tj, spec.seed)
    else:
        sgn = jnp.ones_like(idx)
    return idx, sgn


# ---------------------------------------------------------------------------
# materialization (oracle / small layers)
# ---------------------------------------------------------------------------

@jax.named_scope("hashed_expand")
def materialize(w, spec: HashedSpec, dtype=None):
    """Build the full virtual matrix V (rows, cols)."""
    spec.validate()
    dtype = dtype or w.dtype
    if spec.mode == "element":
        i = jnp.arange(spec.rows, dtype=jnp.int32)[:, None]
        j = jnp.arange(spec.cols, dtype=jnp.int32)[None, :]
        idx, sgn = element_indices(spec, i, j)
        v = w[idx] * sgn.astype(w.dtype)
        return v.astype(dtype)
    idx, sgn = block_indices(spec)
    gi, gj = spec.tile_grid
    bm, bn = spec.block_shape
    tiles = w[idx] * sgn[..., None, None].astype(w.dtype)  # (gi, gj, bm, bn)
    v = tiles.transpose(0, 2, 1, 3).reshape(gi * bm, gj * bn)
    return v[: spec.rows, : spec.cols].astype(dtype)


@jax.named_scope("hashed_expand")
def materialize_rows(w, spec: HashedSpec, row_ids, dtype=None):
    """Gather virtual rows V[row_ids, :] without building all of V.

    Used by hashed embedding lookup: row_ids (...,) -> (..., cols).
    """
    spec.validate()
    dtype = dtype or w.dtype
    if spec.mode == "element":
        i = jnp.asarray(row_ids, jnp.int32)[..., None]
        j = jnp.arange(spec.cols, dtype=jnp.int32)
        j = j.reshape((1,) * (i.ndim - 1) + (spec.cols,))
        idx, sgn = element_indices(spec, i, j)
        return (w[idx] * sgn.astype(w.dtype)).astype(dtype)
    # block mode: gather the tile-row each id lives in, then slice.
    bm, bn = spec.block_shape
    gi, gj = spec.tile_grid
    idx, sgn = block_indices(spec)  # (gi, gj)
    rid = jnp.asarray(row_ids, jnp.int32)
    trow = rid // bm
    roff = rid % bm
    row_tiles = w[idx[trow]]                       # (..., gj, bm, bn)
    row_tiles = row_tiles * sgn[trow][..., None, None].astype(w.dtype)
    sliced = jnp.take_along_axis(
        row_tiles, roff[..., None, None, None].astype(jnp.int32), axis=-2
    )                                               # (..., gj, 1, bn)
    out = sliced.squeeze(-2).reshape(rid.shape + (gj * bn,))
    return out[..., : spec.cols].astype(dtype)


# ---------------------------------------------------------------------------
# matmul paths
# ---------------------------------------------------------------------------

def _panel_matmul_element(x, w, spec: HashedSpec, j0, panel_cols, dtype):
    """y_panel = x @ V[:, j0:j0+panel_cols] for element mode."""
    i = jnp.arange(spec.rows, dtype=jnp.int32)[:, None]
    j = j0 + jnp.arange(panel_cols, dtype=jnp.int32)[None, :]
    idx, sgn = element_indices(spec, i, j)
    v = (w[idx] * sgn.astype(w.dtype)).astype(dtype)
    return jax.lax.dot_general(
        x, v, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dtype)


def matmul_scan(x, w, spec: HashedSpec, panel_cols: int = 0, dtype=None,
                vspec=None):
    """x @ V with bounded peak memory: lax.scan over column panels.

    The per-panel body is rematerialized (jax.checkpoint) so the backward
    pass re-derives each panel from ``w`` instead of storing all panels —
    peak live memory stays ~one panel in fwd and bwd.
    """
    spec.validate()
    dtype = dtype or x.dtype

    def _constrain_panel(v):
        if vspec is None:
            return v
        from repro.distributed import sharding as shd
        return shd.constraint(v, vspec)
    cols = spec.cols
    if panel_cols <= 0:
        panel_cols = spec.panel_cols if spec.panel_cols > 0 else min(cols, 1024)
    if spec.mode == "element" and spec.panel_cols > 0:
        # align scan panels with bucket panels (any multiple works)
        if panel_cols % spec.panel_cols and spec.panel_cols % panel_cols:
            panel_cols = spec.panel_cols
    n_panels = math.ceil(cols / panel_cols)
    pad = n_panels * panel_cols - cols

    lead_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])

    if spec.mode == "element":
        def body(carry, j0):
            def panel(w_, x_):
                with jax.named_scope("hashed_expand"):
                    i = jnp.arange(spec.rows, dtype=jnp.int32)[:, None]
                    j = j0 + jnp.arange(panel_cols,
                                        dtype=jnp.int32)[None, :]
                    idx, sgn = element_indices(spec, i, j)
                    v = _constrain_panel(
                        (w_[idx] * sgn.astype(w_.dtype)).astype(dtype))
                return jax.lax.dot_general(
                    x_, v, (((x_.ndim - 1,), (0,)), ((), ())))

            y = jax.checkpoint(panel)(w, x2)
            return carry, y

        j0s = jnp.arange(n_panels, dtype=jnp.int32) * panel_cols
        _, ys = jax.lax.scan(body, None, j0s)          # (n_panels, B, panel)
        y = jnp.moveaxis(ys, 0, 1).reshape(x2.shape[0], n_panels * panel_cols)
    else:
        bm, bn = spec.block_shape
        gi, gj = spec.tile_grid
        idx, sgn = block_indices(spec)                  # (gi, gj)
        rpad = gi * bm - spec.rows
        if rpad:
            # ragged tile grid: zero-pad the contraction dim (zero rows of
            # x contribute nothing against the padded virtual rows)
            x2 = jnp.pad(x2, ((0, 0), (0, rpad)))
        xt = x2.reshape(x2.shape[0], gi, bm)

        def body(carry, args):
            idx_col, sgn_col = args                     # (gi,)

            def panel(w_, xt_):
                with jax.named_scope("hashed_expand"):
                    tiles = (w_[idx_col] * sgn_col[:, None, None]
                             .astype(w_.dtype))             # (gi,bm,bn)
                    vpanel = _constrain_panel(
                        tiles.reshape(gi * bm, bn).astype(dtype))
                return jax.lax.dot_general(
                    xt_.reshape(xt_.shape[0], gi * bm), vpanel,
                    (((1,), (0,)), ((), ())))

            return carry, jax.checkpoint(panel)(w, xt)

        _, ys = jax.lax.scan(body, None, (idx.T, sgn.T))  # (gj, B, bn)
        y = jnp.moveaxis(ys, 0, 1).reshape(x2.shape[0], gj * bn)
        pad = gj * bn - cols

    if pad:
        y = y[:, :cols]
    return y.reshape(lead_shape + (cols,))


def matmul(x, w, spec: HashedSpec, path: str = "auto", dtype=None,
           panel_cols: int = 0, vspec=None):
    """Dispatch x @ V over execution paths.

    path: "materialize" | "scan" | "pallas" | "auto".
    "auto": materialize for small virtual matrices, scan otherwise.
    (The pallas path is dispatched in repro.kernels.ops to avoid a
    circular import; model code calls repro.nn.linear which routes.)

    vspec: logical PartitionSpec for the DECOMPRESSED virtual matrix
    (same spec a dense weight of that shape would carry).  Without it the
    materialized V is unannotated and GSPMD replicates the whole matmul
    on every model shard — measured 16x the flops of the dense baseline
    at llama3-405b scale (EXPERIMENTS.md §Perf).
    """
    spec.validate()
    dtype = dtype or x.dtype
    if path == "auto":
        path = "materialize" if spec.virtual_size <= (4096 * 4096) else "scan"
    if path == "materialize":
        v = materialize(w, spec, dtype=dtype)
        if vspec is not None:
            from repro.distributed import sharding as shd
            v = shd.constraint(v, vspec)
        return jax.lax.dot_general(
            x, v, (((x.ndim - 1,), (0,)), ((), ())))
    if path == "scan":
        return matmul_scan(x, w, spec, panel_cols=panel_cols, dtype=dtype,
                           vspec=vspec)
    if path == "pallas":
        from repro.kernels import ops as kernel_ops
        return kernel_ops.hashed_matmul(x, w, spec, dtype=dtype)
    raise ValueError(f"unknown path {path!r}")
