"""Plain float32 Qwen3 decoder: the reference that decides ``correct``.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, one full causal forward
pass over a token sequence, no cache, no kernels, no batching.  It
imports nothing of the program under test.  It reads weights as the
benchmark made them (``bench/weights.py``), in the checkpoint layout the
program also loads: layer-stacked leaves under ``layers``, RMSNorm
scales applied as ``1 + scale``.

Qwen3 (hf:Qwen/Qwen3-1.7B): RMSNorm (eps 1e-6) before attention and
before the MLP; per-head RMSNorm of q and k before RoPE; RoPE on the two
halves of each head (theta from the config); grouped-query attention,
causal, softmax in float32; SwiGLU MLP ``down(silu(gate(x)) * up(x))``
(``in`` is the up projection); final RMSNorm; LM head tied to the
embedding, over the published vocabulary only.

A hashed projection is expanded here from its bank.  Block mode: the
virtual matrix is a grid of ``block`` tiles, tile ``(ti, tj)`` is
``sign(ti, tj) * bank[h(ti, tj)]``, cut back to the matrix's shape.  The
bucket ``h`` and the sign are the murmur3-finalizer hashes that define
the weights (stored in a checkpoint as each bank's seed), computed here
on the host in plain integer arithmetic.

``mode="fp8"`` is the control: every projection and the LM head take
their operands in float8 (e4m3, scaled per row of the activations and
per column of the weights) with float32 accumulation, the lower
precision a later change might be tempted to serve in.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
_MASK = 0xFFFFFFFF
_GOLDEN, _M1, _M2 = 0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35
_SIGN_SALT = 0x5BF03635
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# the hash that defines a block-mode bank's weights
# ---------------------------------------------------------------------------

def _mix32(x: np.ndarray) -> np.ndarray:
    x = x & _MASK
    x = x ^ (x >> 16)
    x = (x * _M1) & _MASK
    x = x ^ (x >> 13)
    x = (x * _M2) & _MASK
    return x ^ (x >> 16)


def _hash(i: np.ndarray, j: np.ndarray, seed: int) -> np.ndarray:
    h = _mix32((i * _GOLDEN + (seed & _MASK)) & _MASK)
    return _mix32(h ^ ((j * _M1 + 0x165667B1) & _MASK))


def tile_map(spec: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """(bank index, sign) of every tile of a block-mode virtual matrix."""
    rows, cols = spec["virtual_shape"]
    bm, bn = spec["block_shape"]
    gi, gj = math.ceil(rows / bm), math.ceil(cols / bn)
    n_bank = max(1, int(round(spec["compression"] * gi * gj)))
    ti = np.arange(gi, dtype=np.uint64)[:, None]
    tj = np.arange(gj, dtype=np.uint64)[None, :]
    idx = (_hash(ti, tj, spec["seed"]) % n_bank).astype(np.int32)
    if spec.get("use_sign", True):
        top = _hash(ti, tj, spec["seed"] ^ _SIGN_SALT) >> 31
        sign = (1 - 2 * top.astype(np.int64)).astype(np.float32)
    else:
        sign = np.ones(idx.shape, np.float32)
    return idx, sign


def expand(bank, spec: Dict[str, Any]):
    """The virtual (rows, cols) matrix of one layer's bank, float32."""
    if spec["mode"] != "block":
        raise ValueError(f"reference expands block mode only, not "
                         f"{spec['mode']!r}")
    idx, sign = tile_map(spec)
    rows, cols = spec["virtual_shape"]
    bm, bn = spec["block_shape"]
    gi, gj = idx.shape
    tiles = bank.astype(jnp.float32)[idx] * sign[..., None, None]
    v = tiles.transpose(0, 2, 1, 3).reshape(gi * bm, gj * bn)
    return v[:rows, :cols]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode: str):
    """x (..., n) @ w (n, m)."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta: float):
    """x (T, H, D): rotate the two halves of each head by position."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _weight(layer, names, banks):
    leaf = layer
    for n in names:
        leaf = leaf[n]
    spec = banks.get(("layers",) + names)
    if spec is not None:
        return expand(leaf, spec)
    return leaf.astype(jnp.float32)


def _layer(x, layer, dims, banks, mode):
    t = x.shape[0]
    h_q, h_kv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    w = functools.partial(_weight, layer, banks=banks)
    a = layer["attn"]
    h = _rms(x, layer["ln1"]["scale"])
    q = _mm(h, w(("attn", "q", "w")), mode).reshape(t, h_q, hd)
    k = _mm(h, w(("attn", "k", "w")), mode).reshape(t, h_kv, hd)
    v = _mm(h, w(("attn", "v", "w")), mode).reshape(t, h_kv, hd)
    q = _rope(_rms(q, a["q_norm"]["scale"]), dims["rope_theta"])
    k = _rope(_rms(k, a["k_norm"]["scale"]), dims["rope_theta"])
    g = h_q // h_kv
    qg = q.reshape(t, h_kv, g, hd)
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HI) / math.sqrt(hd)
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI).reshape(t, h_q * hd)
    x = x + _mm(o, w(("attn", "o", "w")), mode)
    h = _rms(x, layer["ln2"]["scale"])
    up = _mm(h, w(("ffn", "in", "w")), mode)
    gate = _mm(h, w(("ffn", "gate", "w")), mode)
    return x + _mm(jax.nn.silu(gate) * up, w(("ffn", "out", "w")), mode)


def dims_of(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "vocab": config["vocab_size"],
            "rope_theta": float(config["rope_theta"])}


@functools.partial(jax.jit,
                   static_argnames=("banks", "dims", "mode", "chunk"))
def _logit_stats(params, tokens, targets, *, banks, dims, mode, chunk):
    dims = dict(dims)
    banks = dict(banks)
    emb = params["embed"]["emb"]
    x = emb[tokens].astype(jnp.float32)

    def body(x, layer):
        return _layer(x, layer, dims, banks, mode), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"])
    head = emb[:dims["vocab"]].astype(jnp.float32).T   # (d, V)
    t = x.shape[0]

    def block(args):
        xs, tg = args
        logits = _mm(xs, head, mode)                    # (chunk, V)
        best = jnp.max(logits, axis=-1)
        top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        at = jnp.take_along_axis(
            logits, jnp.clip(tg, 0, dims["vocab"] - 1)[:, None], axis=-1)[:, 0]
        return best, at, top, jax.nn.logsumexp(logits, axis=-1)

    outs = jax.lax.map(block, (x.reshape(t // chunk, chunk, -1),
                               targets.reshape(t // chunk, chunk)))
    return tuple(o.reshape(t) for o in outs)


def logit_stats(params, banks: Dict[Tuple[str, ...], Dict[str, Any]],
                config: Dict[str, Any], tokens: np.ndarray,
                targets: np.ndarray, mode: str = "f32",
                chunk: int = 256):
    """For each position of ``tokens``: the largest logit over the
    vocabulary, the logit of ``targets`` there, the argmax, and the
    log-sum-exp (so ``at - lse`` is the target's log-probability).

    ``tokens`` and ``targets`` are padded by the caller to one length (a
    multiple of ``chunk``); positions past the real ones are never read
    by earlier ones (causal) and their stats are ignored by the caller.
    """
    dims = tuple(sorted(dims_of(config).items()))
    banks_t = tuple(sorted(banks.items()))
    out = _logit_stats(params, jnp.asarray(tokens), jnp.asarray(targets),
                       banks=_Static(banks_t), dims=dims, mode=mode,
                       chunk=min(chunk, len(tokens)))
    return tuple(np.asarray(a) for a in out)


class _Static:
    """Hashable wrapper so bank specs ride into jit as a static value."""

    def __init__(self, items):
        self.items = items

    def __iter__(self):
        return iter(self.items)

    def __hash__(self):
        return hash(repr(self.items))

    def __eq__(self, other):
        return isinstance(other, _Static) and repr(self.items) == repr(
            other.items)
