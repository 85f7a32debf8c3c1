"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, as a checkpoint would hold them,
so that the plain reference takes nothing the program made.  Leaves come
in the program's tree and types (bfloat16 matrices and banks, float32
norm scales); the tree's shapes are read with ``jax.eval_shape`` and
nothing is computed by the program.

- Matrices and banks: normal, std ``1/sqrt(fan_in)`` of the virtual
  matrix, so hashed and dense layers see the same activation scale.
- Embedding: normal, std ``1/sqrt(d)``; the rows past the published
  vocabulary, which the program adds to pad its table, are zero, as a
  loader pads a checkpoint.
- Norm scales: normal, std 0.1.  The program's RMSNorm multiplies by
  ``1 + scale``; the reference reads the same convention.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def path_names(path) -> Tuple[str, ...]:
    return tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path)


def _std(names: Tuple[str, ...], shape, fan_in: Dict[Tuple[str, ...], int],
         d: int) -> float:
    if names[-1] == "scale":
        return NORM_STD
    if names == ("embed", "emb"):
        return 1.0 / float(np.sqrt(d))
    if names in fan_in:                       # a hashed bank
        return 1.0 / float(np.sqrt(fan_in[names]))
    return 1.0 / float(np.sqrt(shape[-2]))    # dense (layers, in, out)


def make(model, vocab: int, banks: Dict[Tuple[str, ...], Dict[str, Any]],
         seed: int):
    """Params for ``model`` from ``seed``.  ``banks`` maps a leaf path to
    its hashed spec dict (``virtual_shape`` first)."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    fan_in = {k: int(v["virtual_shape"][0]) for k, v in banks.items()}
    d = int(model.cfg.d_model)
    plan = [(path_names(p), s.shape, s.dtype) for p, s in leaves]

    def build(key):
        out = []
        for i, (names, shape, dtype) in enumerate(plan):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32) \
                * _std(names, shape, fan_in, d)
            if names == ("embed", "emb") and shape[0] > vocab:
                x = x.at[vocab:].set(0.0)
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(build)(key_for(seed)))
