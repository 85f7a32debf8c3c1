"""Production mesh construction.

Function (not module constant) on purpose: importing this module must not
touch jax device state — the dry-run sets XLA_FLAGS before first jax init,
smoke tests see one device.

Single pod  : (data=16, model=16)              = 256 chips (v5e pod)
Multi-pod   : (pod=2, data=16, model=16)       = 512 chips
The "pod" axis is the slow-ICI/DCN dimension: pure data parallelism,
gradient all-reduce only (optionally compressed, train/grad_compress.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh for elastic-rescale restarts (e.g. (8,16) after
    losing half a pod): checkpoints restore onto any mesh (train/checkpoint
    elastic-remesh path).

    Every axis is Auto: the sharding code relies on GSPMD propagation
    (``with_sharding_constraint``, the bank gather), which Explicit axes
    — ``jax.make_mesh``'s default in current JAX — reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh():
    """1-chip mesh with the standard axis names (CI / examples)."""
    return make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(tp: int, *, data: int = 1):
    """(data, model=tp) mesh over the FIRST data*tp local devices.

    Built with a raw ``Mesh`` over a device subset (``jax.make_mesh``
    wants to place over all devices) so a tp=2 engine works on a
    host-simulated 8-device CPU (XLA_FLAGS=--xla_force_host_platform_
    device_count=8) and on a partial slice.
    """
    import numpy as np
    from jax.sharding import Mesh

    n = data * tp
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"mesh (data={data}, model={tp}) needs {n} devices, "
            f"have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(data, tp), ("data", "model"))


# TPU v5e hardware model for the roofline (assignment constants)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per axis direction)
HBM_BYTES = 16 * 2 ** 30      # 16 GiB per chip
