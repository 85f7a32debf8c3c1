"""Compile rehearsals: the serving path's Pallas kernels at qwen3-1.7b
widths (d 2048, 16/8 heads, head_dim 128, d_ff 6144, vocab 151936),
compiled for a described TPU v5e that is not attached.

Nothing runs: each test asks the TPU compiler to accept the kernel and
checks that the compiled program holds it as a Mosaic custom call.  The
topology is described inside a fixture, never at import, so every
xdist worker collects the same tests and only the worker that runs them
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hashed import HashedSpec
from repro.kernels import flash_prefill as FP
from repro.kernels import ops as kernel_ops
from repro.kernels import paged_attention as PA
from repro.kernels import topk as TK

HQ, HKV, D, PAGE = 16, 8, 128, 16
D_MODEL, D_FF, VOCAB = 2048, 6144, 151936
POOL, MAXP = 512, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_paged_decode_compiles(one_chip):
    b = 8
    _compile(
        lambda q, pk, pv, tbl, ln: PA.paged_decode_attention(
            q, pk, pv, tbl, ln, 0, interpret=False),
        one_chip,
        ((b, HQ, D), jnp.bfloat16),
        ((POOL, PAGE, HKV, D), jnp.bfloat16),
        ((POOL, PAGE, HKV, D), jnp.bfloat16),
        ((b, MAXP), jnp.int32), ((b,), jnp.int32))


def test_flash_prefill_compiles(one_chip):
    b, s = 8, 16
    _compile(
        lambda q, pk, pv, tbl, st, cn: FP.paged_prefill_attention(
            q, pk, pv, tbl, st, cn, 0, interpret=False),
        one_chip,
        ((b, s, HQ, D), jnp.bfloat16),
        ((POOL, PAGE, HKV, D), jnp.bfloat16),
        ((POOL, PAGE, HKV, D), jnp.bfloat16),
        ((b, MAXP), jnp.int32), ((b,), jnp.int32), ((b,), jnp.int32))


def test_topk_compiles(one_chip):
    b = 8
    _compile(
        lambda x, k: TK.topk_mask(x, k, use_pallas=True, interpret=False),
        one_chip, ((b, VOCAB), jnp.float32), ((b,), jnp.int32))


def test_block_hashed_matmul_compiles(one_chip):
    spec = HashedSpec((D_MODEL, D_FF), 0.125, mode="block", seed=3)
    m = 8
    _compile(
        lambda x, w: kernel_ops.hashed_matmul(x, w, spec, interpret=False),
        one_chip,
        ((m, D_MODEL), jnp.bfloat16),
        (spec.real_param_shape(), jnp.bfloat16))
