"""Model builder: ArchConfig -> Model (init / train_loss / prefill / decode).

All stacks scan over layers with layer-stacked parameters (compile-size
friendly at 126 layers).  Four architecture kinds:

- decoder : [attn + ffn|moe] x L                     (llama/gemma/qwen/moe/vlm)
- encdec  : encoder [bidir attn + ffn] x Le, decoder [self + cross + ffn] x Ld
- rwkv    : [time_mix + channel_mix] x L             (attention-free)
- zamba   : 9 groups x [hybrid_group mamba layers + ONE shared attn/ffn block]

Hashing: when cfg.hashed, every projection's weight is a HashedNets bank.
With scan-over-layers the *bucket pattern* is shared across layers while the
bank values differ per layer (paper deviation documented in DESIGN.md §2 —
each layer still has its own w^l; per-layer h^l is kept for the non-scanned
paper MLP experiments).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import policy as POL
from repro.configs.base import ArchConfig
from repro.core import hashed as H
from repro.core.hashing import derive_seed
from repro.distributed import sharding as shd
from repro.nn import attention as ATT
from repro.nn import ffn as FFN
from repro.nn import layers as L
from repro.nn import mamba2 as MB
from repro.nn import moe as MOE
from repro.nn import rwkv6 as RW


class Model(NamedTuple):
    cfg: ArchConfig
    init: Callable          # key -> params
    pspecs: Callable        # () -> logical PartitionSpec tree (matches params)
    train_loss: Callable    # (params, batch) -> (loss, metrics)
    prefill: Callable       # (params, batch) -> (logits_last, cache)
    decode_step: Callable   # (params, tokens(B,1), cache) -> (logits, cache)
    init_cache: Callable    # (batch, max_len) -> cache
    cache_pspecs: Callable  # (batch, max_len) -> spec tree for cache
    # paged-KV serving path (decoder kinds only; None elsewhere):
    # init_paged_cache: (num_pages, page_size) -> {"k","v"} page pools
    # decode_paged: (params, tokens(B,1), pages, page_table(B,MAXP),
    #                lengths(B,), impl) -> (logits, pages)
    init_paged_cache: Optional[Callable] = None
    decode_paged: Optional[Callable] = None
    # decode_paged_block: (params, tokens(B,S), pages, page_table,
    #                      lengths(B,), counts(B,), impl) -> (logits, pages)
    # multi-token decode for speculative propose/verify; None for model
    # kinds where a wider batch is not bitwise row-equivalent (MoE
    # capacity routing mixes rows).
    decode_paged_block: Optional[Callable] = None
    # prefill_paged: (params, tokens(B,S), pages, page_table(B,MAXP),
    #                 starts(B,), counts(B,), write_from(B,), impl)
    #   -> (last_hidden(B,1,d_model), pages)
    # batched ragged prefill straight into paged KV — returns the last
    # REAL slot's hidden state, not logits: the engine runs logits_head
    # on the (1, 1, d) per-row slice so the LM-head GEMM keeps the exact
    # M=1 dispatch shape of the sequential path (M=1 GEMV results are
    # not bitwise row-equal to wider GEMMs).  None for MoE.
    prefill_paged: Optional[Callable] = None
    # logits_head: (params, x(B,S,d_model)) -> logits — final norm + LM
    # head, exactly the tail of prefill/decode.
    logits_head: Optional[Callable] = None


# ---------------------------------------------------------------------------
# spec-capture helper (PartitionSpec can't cross trace boundaries)
# ---------------------------------------------------------------------------

def _is_spec(x):
    return isinstance(x, P)


def stack_init(init_fn, keys):
    """vmap an (params, specs) initializer over layer keys.

    Returns (stacked_params, specs_with_leading_None_axis)."""
    cell = []

    def only_params(k):
        p, s = init_fn(k)
        if not cell:
            cell.append(s)
        return p

    params = jax.vmap(only_params)(keys)
    specs = jax.tree.map(lambda s: P(None, *s), cell[0], is_leaf=_is_spec)
    return params, specs


# ---------------------------------------------------------------------------
# hashed-slot inventory + policy resolution
# ---------------------------------------------------------------------------

def _slot_seed(seed_key: str) -> int:
    # zlib.crc32, NOT builtin hash(): the latter is salted per process
    # (PYTHONHASHSEED) and would give every host a different weight-sharing
    # pattern — fatal for multi-host SPMD and checkpoint restore.
    return derive_seed(0xC0FFEE, zlib.crc32(seed_key.encode()) & 0x7FFFFFFF)


def hash_slots(cfg: ArchConfig) -> Tuple[POL.Slot, ...]:
    """Every hashable projection slot of a model, declaratively.

    One entry per param-leaf path (layer stacking adds a leading array
    axis, never a path component) with its dense virtual shape and hash
    seed.  Seeds keep the pre-policy derivation (``attn.q``, ``ffn.out``,
    ``embed``, ...) so legacy flat-knob configs resolve to byte-identical
    weight-sharing patterns; encoder/decoder FFNs in encdec share seed
    keys (they historically shared one plan).  ``default_on`` encodes the
    legacy embedding gate (``hash_embeddings``), overridable per rule.
    """
    if not cfg.hashed:
        return ()
    d = cfg.d_model
    hq = cfg.num_heads * cfg.head_dim
    hkv = cfg.num_kv_heads * cfg.head_dim
    gated = cfg.activation in ("swiglu", "geglu")
    slots = []

    def add(path, seed_key, vshape, on=True):
        slots.append(POL.Slot(path=tuple(path), virtual_shape=tuple(vshape),
                              seed=_slot_seed(seed_key), default_on=on))

    def add_attn(base, prefix):
        add(base + ("q", "w"), f"{prefix}.q", (d, hq))
        add(base + ("k", "w"), f"{prefix}.k", (d, hkv))
        add(base + ("v", "w"), f"{prefix}.v", (d, hkv))
        add(base + ("o", "w"), f"{prefix}.o", (hq, d))

    def add_ffn(base, prefix):
        add(base + ("in", "w"), f"{prefix}.in", (d, cfg.d_ff))
        if gated:
            add(base + ("gate", "w"), f"{prefix}.gate", (d, cfg.d_ff))
        add(base + ("out", "w"), f"{prefix}.out", (cfg.d_ff, d))

    # every arch kind embeds through _emb_plan; the bank exists whenever
    # the policy turns the slot on (default: the hash_embeddings knob)
    add(("embed", "emb"), "embed", (cfg.padded_vocab, d),
        on=cfg.hash_embeddings)

    if cfg.arch_kind == "decoder":
        add_attn(("layers", "attn"), "attn")
        if cfg.moe:
            # MoE expert banks sit directly under their name (no "w" leaf)
            e, f = cfg.num_experts, cfg.moe_d_ff
            add(("layers", "moe", "in"), "moe.in", (e * d, f))
            if gated:
                add(("layers", "moe", "gate"), "moe.gate", (e * d, f))
            add(("layers", "moe", "out"), "moe.out", (e * f, d))
        else:
            add_ffn(("layers", "ffn"), "ffn")
        if not cfg.tie_embeddings:
            # only the decoder builder hashes its untied lm_head
            add(("lm_head", "w"), "lm_head", (d, cfg.padded_vocab),
                on=cfg.hash_embeddings)
    elif cfg.arch_kind == "rwkv":
        for name in ("r", "k", "v", "g", "o"):
            add(("layers", "tm", name, "w"), f"rwkv.{name}", (d, d))
        add(("layers", "cm", "k", "w"), "cmix.k", (d, cfg.d_ff))
        add(("layers", "cm", "v", "w"), "cmix.v", (cfg.d_ff, d))
        add(("layers", "cm", "r", "w"), "cmix.r", (d, d))
    elif cfg.arch_kind == "zamba":
        mb = _mamba_geometry(cfg)
        add(("mamba_groups", "mamba", "in_proj", "w"), "mamba.in",
            (d, mb.in_dim))
        add(("mamba_groups", "mamba", "out_proj", "w"), "mamba.out",
            (mb.d_inner, d))
        add_attn(("shared", "attn"), "attn")
        add_ffn(("shared", "ffn"), "ffn")
    elif cfg.arch_kind == "encdec":
        add_attn(("encoder", "attn"), "enc")
        add_attn(("decoder", "self"), "dec")
        add_attn(("decoder", "cross"), "xattn")
        add_ffn(("encoder", "ffn"), "ffn")
        add_ffn(("decoder", "ffn"), "ffn")
    return tuple(slots)


@functools.lru_cache(maxsize=128)
def slot_assignments(cfg: ArchConfig) -> Dict[tuple, POL.SlotAssignment]:
    """Policy resolution for a config: param-leaf path -> SlotAssignment.

    This is THE source of truth for which slots are hashed and how —
    plan factories, the artifact subsystem, the compression report, and
    the budget solver all read it.  Cached: resolution walks every rule
    for every slot and may run the budget solver.
    """
    return POL.resolve(POL.effective(cfg), hash_slots(cfg))


def bank_spec_map(cfg: ArchConfig) -> Dict[tuple, H.HashedSpec]:
    """Map param-leaf paths -> HashedSpec for every hashed bank in a model.

    Keys are the nested-dict key tuples of ``model.init`` params.  This is
    the ground truth the artifact subsystem serializes: bank leaves carry
    their spec in the header so the virtual matrix is reconstructible from
    the file alone.
    """
    return {path: a.spec for path, a in slot_assignments(cfg).items()
            if a.spec is not None}


def _slot_spec(cfg: ArchConfig, path: tuple) -> Optional[H.HashedSpec]:
    a = slot_assignments(cfg).get(tuple(path))
    return a.spec if a is not None else None


# ---------------------------------------------------------------------------
# plans from config
# ---------------------------------------------------------------------------

def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _attn_plan(cfg: ArchConfig, base=("layers", "attn"), cross=False,
               causal=True, use_rope=True) -> ATT.AttentionPlan:
    sp = functools.partial(_slot_spec, cfg)
    return ATT.AttentionPlan(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        use_rope=use_rope, qk_norm=cfg.qk_norm,
        sliding_window=cfg.sliding_window, causal=causal, cross=cross,
        dtype=_dtype(cfg),
        hash_q=sp(base + ("q", "w")),
        hash_k=sp(base + ("k", "w")),
        hash_v=sp(base + ("v", "w")),
        hash_o=sp(base + ("o", "w")),
        hash_path=cfg.hash_path,
    )


def _ffn_plan(cfg: ArchConfig, base=("layers", "ffn")) -> FFN.FFNPlan:
    sp = functools.partial(_slot_spec, cfg)
    return FFN.FFNPlan(
        d_model=cfg.d_model, d_ff=cfg.d_ff, activation=cfg.activation,
        dtype=_dtype(cfg),
        hash_in=sp(base + ("in", "w")),
        hash_gate=sp(base + ("gate", "w")),
        hash_out=sp(base + ("out", "w")),
        hash_path=cfg.hash_path,
    )


def _moe_plan(cfg: ArchConfig, base=("layers", "moe")) -> MOE.MoEPlan:
    sp = functools.partial(_slot_spec, cfg)
    return MOE.MoEPlan(
        d_model=cfg.d_model, d_ff=cfg.moe_d_ff,
        num_experts=cfg.num_experts, top_k=cfg.top_k,
        activation=cfg.activation, capacity_factor=cfg.capacity_factor,
        dtype=_dtype(cfg),
        hash_in=sp(base + ("in",)),
        hash_gate=sp(base + ("gate",)),
        hash_out=sp(base + ("out",)),
    )


def _mamba_geometry(cfg: ArchConfig) -> MB.Mamba2Plan:
    """Bare mamba plan (no hash fields): the single source of the
    projection geometry (in_dim/d_inner) for both the slot inventory and
    the full plan."""
    return MB.Mamba2Plan(d_model=cfg.d_model, d_state=cfg.ssm_state,
                         head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk,
                         dtype=_dtype(cfg))


def _mamba_plan(cfg: ArchConfig,
                base=("mamba_groups", "mamba")) -> MB.Mamba2Plan:
    sp = functools.partial(_slot_spec, cfg)
    return dataclasses.replace(
        _mamba_geometry(cfg),
        hash_in=sp(base + ("in_proj", "w")),
        hash_out=sp(base + ("out_proj", "w")),
        hash_path=cfg.hash_path,
    )


def _rwkv_plan(cfg: ArchConfig, base=("layers", "tm")) -> RW.RWKV6Plan:
    d = cfg.d_model
    sp = functools.partial(_slot_spec, cfg)
    return RW.RWKV6Plan(
        d_model=d, head_dim=cfg.head_dim, dtype=_dtype(cfg),
        lora_dim=min(32, max(4, d // 128)),
        decay_lora_dim=min(64, max(4, d // 64)),
        hash_r=sp(base + ("r", "w")),
        hash_k=sp(base + ("k", "w")),
        hash_v=sp(base + ("v", "w")),
        hash_g=sp(base + ("g", "w")),
        hash_o=sp(base + ("o", "w")),
        hash_path=cfg.hash_path,
    )


def _cmix_plan(cfg: ArchConfig, base=("layers", "cm")) -> RW.ChannelMixPlan:
    sp = functools.partial(_slot_spec, cfg)
    return RW.ChannelMixPlan(
        d_model=cfg.d_model, d_ff=cfg.d_ff, dtype=_dtype(cfg),
        hash_k=sp(base + ("k", "w")),
        hash_v=sp(base + ("v", "w")),
        hash_r=sp(base + ("r", "w")),
        hash_path=cfg.hash_path,
    )


def _emb_plan(cfg: ArchConfig) -> L.EmbeddingPlan:
    return L.EmbeddingPlan(cfg.padded_vocab, cfg.d_model,
                           hashed=_slot_spec(cfg, ("embed", "emb")),
                           dtype=_dtype(cfg),
                           scale_by_sqrt_dim=cfg.scale_embeddings)


def _norm_fns(cfg: ArchConfig):
    if cfg.norm == "rmsnorm":
        return lambda: L.rmsnorm_init(cfg.d_model), L.rmsnorm_apply
    return lambda: L.layernorm_init(cfg.d_model), L.layernorm_apply


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def softmax_xent(logits, targets, vocab_size):
    """logits (B,S,Vp) fp32; targets (B,S) int32, -1 = masked."""
    logits = logits.astype(jnp.float32)
    vp = logits.shape[-1]
    if vp > vocab_size:
        # mask padded vocab slots
        pad_mask = jnp.arange(vp) < vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    mask = (targets >= 0)
    tgt = jnp.where(mask, targets, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1)
    loss = nll.sum() / denom
    acc = ((jnp.argmax(logits, -1) == tgt) * mask).sum() / denom
    return loss, {"nll": loss, "accuracy": acc, "tokens": denom}


# ===========================================================================
# decoder-kind model (llama / gemma / qwen / moe / vlm)
# ===========================================================================

def _build_decoder(cfg: ArchConfig) -> Model:
    dt = _dtype(cfg)
    attn_plan = _attn_plan(cfg)
    use_moe = cfg.moe
    ffn_plan = None if use_moe else _ffn_plan(cfg)
    moe_plan = _moe_plan(cfg) if use_moe else None
    emb_plan = _emb_plan(cfg)
    norm_init, norm_apply = _norm_fns(cfg)
    nl = cfg.num_layers

    # per-layer global-attention flags (gemma3 5:1 pattern)
    if cfg.global_every > 0:
        is_global = jnp.array(
            [(i % cfg.global_every) == cfg.global_every - 1
             for i in range(nl)])
    else:
        is_global = jnp.ones((nl,), bool)  # irrelevant when no window

    def layer_init(key):
        ks = jax.random.split(key, 4)
        params, specs = {}, {}
        params["attn"], specs["attn"] = ATT.init(attn_plan, ks[0])
        params["ln1"], specs["ln1"] = norm_init()
        params["ln2"], specs["ln2"] = norm_init()
        if use_moe:
            params["moe"], specs["moe"] = MOE.init(moe_plan, ks[1])
        else:
            params["ffn"], specs["ffn"] = FFN.init(ffn_plan, ks[1])
        return params, specs

    def build_params(key, spec_cell=None):
        kemb, klayers, kout, khead = jax.random.split(key, 4)
        params, specs = {}, {}
        params["embed"], specs["embed"] = L.embedding_init(emb_plan, kemb)
        params["layers"], specs["layers"] = stack_init(
            layer_init, jax.random.split(klayers, nl))
        params["final_norm"], specs["final_norm"] = norm_init()
        if not cfg.tie_embeddings:
            p, s = L.linear_init(
                L.LinearPlan(cfg.d_model, cfg.padded_vocab,
                             hashed=_slot_spec(cfg, ("lm_head", "w")),
                             pspec=(L.FSDP, L.TP), dtype=dt), khead)
            params["lm_head"], specs["lm_head"] = p, s
        if spec_cell is not None:
            spec_cell.append(specs)
        return params

    def layer_body(x, lp, glob, positions, cache_kv=None, cache_index=None):
        h = norm_apply(lp["ln1"], x)
        a, new_kv = ATT.apply(attn_plan, lp["attn"], h, positions=positions,
                              cache=cache_kv, cache_index=cache_index,
                              is_global=glob)
        x = x + a
        h = norm_apply(lp["ln2"], x)
        if use_moe:
            f, aux = MOE.apply(moe_plan, lp["moe"], h)
        else:
            f, aux = FFN.apply(ffn_plan, lp["ffn"], h), 0.0
        x = shd.constraint(x + f, P(L.BATCH, None, None))
        return x, aux, new_kv

    def embed_input(params, batch):
        x = L.embedding_lookup(emb_plan, params["embed"], batch["tokens"])
        if cfg.num_image_tokens > 0 and "image_embeds" in batch:
            x = jnp.concatenate(
                [batch["image_embeds"].astype(x.dtype), x], axis=1)
        return shd.constraint(x, P(L.BATCH, None, None))

    @jax.named_scope("lm_head")
    def logits_fn(params, x):
        x = norm_apply(params["final_norm"], x)
        if cfg.tie_embeddings:
            return L.embedding_logits(emb_plan, params["embed"], x)
        return L.linear_apply(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab, dtype=dt,
                         hashed=_slot_spec(cfg, ("lm_head", "w")),
                         hash_path=cfg.hash_path),
            params["lm_head"], x)

    def train_loss(params, batch):
        x = embed_input(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)

        def body(carry, xs):
            x, aux = carry
            lp, glob = xs

            def inner(x, lp):
                y, a, _ = layer_body(x, lp, glob, positions)
                return y, a

            if cfg.remat:
                inner = jax.checkpoint(inner)
            x, a = inner(x, lp)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, 0.0),
                                   (params["layers"], is_global))
        logits = logits_fn(params, x)
        if cfg.num_image_tokens > 0 and "image_embeds" in batch:
            logits = logits[:, cfg.num_image_tokens:, :]
        loss, metrics = softmax_xent(logits, batch["targets"],
                                     cfg.vocab_size)
        total = loss + aux
        metrics["aux_loss"] = aux
        return total, metrics

    def init_cache(batch, max_len):
        shape = (nl, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "index": jnp.zeros((), jnp.int32)}

    def cache_pspecs(batch, max_len):
        # seq axis resolution is per-cell (launch/specs.rules_for):
        # decode cells shard cache seq over the model axis
        # (flash-decoding: partial softmax + tiny all-reduces) -- train
        # cells resolve seq to None.
        kv = P(None, L.CACHE_BATCH, L.SEQ, L.TP_KV, L.TP_HD)
        return {"k": kv, "v": kv, "index": P()}

    def fwd_with_cache(params, x, cache, start, length=None):
        s = x.shape[1]
        start = jnp.asarray(start)
        if start.ndim == 1:     # per-slot decode positions (continuous batching)
            positions = start[:, None] + jnp.arange(s)[None, :]
        else:
            positions = start + jnp.arange(s)

        # Layer caches ride the scan as xs/ys.  (A carried-buffer +
        # dynamic-update-slice variant was tried and REFUTED: XLA CPU
        # inserts two extra full-stack copies for read+write carries —
        # §Perf it.4.  On TPU, ys-stacking dus bufferizes in place.)
        def body(carry, xs):
            x = carry
            lp, glob, ck, cv = xs
            y, _, new_kv = layer_body(x, lp, glob, positions,
                                      cache_kv=(ck, cv), cache_index=start)
            return y, new_kv

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], is_global, cache["k"], cache["v"]))
        if length is not None:
            # Bucketed prefill (pad-and-mask): tokens were right-padded to
            # a static bucket; pads sit AFTER the real prompt so causal
            # attention never lets a real query see one.  Slice the single
            # last real position before the LM head (also skips computing
            # vocab logits for every pad), and advance the write index by
            # the true length — the garbage K/V rows beyond it stay
            # invisible (kv_valid masks >= index) and are overwritten as
            # decode proceeds.
            x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            new_cache = {"k": nk, "v": nv, "index": start + length}
        else:
            new_cache = {"k": nk, "v": nv, "index": start + s}
        return logits_fn(params, x), new_cache

    def prefill(params, batch):
        x = embed_input(params, batch)
        cache = batch["cache"]
        logits, cache = fwd_with_cache(params, x, cache, cache["index"],
                                       length=batch.get("length"))
        return logits[:, -1:, :], cache

    def decode_step(params, tokens, cache):
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        x = shd.constraint(x, P(L.BATCH, None, None))
        logits, cache = fwd_with_cache(params, x, cache, cache["index"])
        return logits, cache

    def init_paged_cache(num_pages, page_size):
        shape = (nl, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def decode_paged(params, tokens, pages, page_table, lengths,
                     impl="ref"):
        """One continuous-batching decode step through the paged KV cache.

        tokens (B, 1); pages {"k","v"} (nl, P, ps, n_kv, hd); page_table
        (B, MAXP) int32 (unused slots -> trash page 0); lengths (B,)
        int32 cached-token counts EXCLUDING the current token.  Layer
        page pools ride the scan as xs/ys exactly like the dense cache.
        """
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        x = shd.constraint(x, P(L.BATCH, None, None))

        def body(x, xs):
            lp, glob, pk, pv = xs
            h = norm_apply(lp["ln1"], x)
            a, (nk, nv) = ATT.apply_paged(
                attn_plan, lp["attn"], h, pages=(pk, pv),
                page_table=page_table, lengths=lengths, is_global=glob,
                impl=impl)
            # pin the pool's head-shard layout across layers (serving
            # rules resolve tp_kv -> model on a serving mesh, else no-op)
            nk = shd.constraint(nk, P(None, None, L.TP_KV, L.TP_HD))
            nv = shd.constraint(nv, P(None, None, L.TP_KV, L.TP_HD))
            x = x + a
            h = norm_apply(lp["ln2"], x)
            if use_moe:
                f, _ = MOE.apply(moe_plan, lp["moe"], h)
            else:
                f = FFN.apply(ffn_plan, lp["ffn"], h)
            x = shd.constraint(x + f, P(L.BATCH, None, None))
            return x, (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], is_global, pages["k"], pages["v"]))
        return logits_fn(params, x), {"k": nk, "v": nv}

    def decode_paged_block(params, tokens, pages, page_table, lengths,
                           counts, impl="ref"):
        """Multi-token paged decode for speculative propose/verify.

        tokens (B, S); slot s of row b is the token at position
        ``lengths[b] + s``, real iff ``s < counts[b]`` (padding slots
        write trash-page K/V and produce garbage logits the caller
        ignores).  Per-row compute is bitwise-identical to S successive
        ``decode_paged`` steps — every sublayer is row-wise (GEMMs,
        norms, elementwise) and the attention masks match, the same
        invariance the chunked-prefill parity pin rests on.
        """
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        x = shd.constraint(x, P(L.BATCH, None, None))

        def body(x, xs):
            lp, glob, pk, pv = xs
            h = norm_apply(lp["ln1"], x)
            a, (nk, nv) = ATT.apply_paged_block(
                attn_plan, lp["attn"], h, pages=(pk, pv),
                page_table=page_table, lengths=lengths, counts=counts,
                is_global=glob, impl=impl)
            x = x + a
            h = norm_apply(lp["ln2"], x)
            f = FFN.apply(ffn_plan, lp["ffn"], h)
            x = shd.constraint(x + f, P(L.BATCH, None, None))
            return x, (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], is_global, pages["k"], pages["v"]))
        return logits_fn(params, x), {"k": nk, "v": nv}

    def prefill_paged(params, tokens, pages, page_table, starts, counts,
                      write_from, impl="ref"):
        """Batched ragged prefill chunks straight into the paged cache.

        tokens (B, S); slot s of row b is the prompt token at position
        ``starts[b] + s``, real iff ``s < counts[b]`` (``counts == 0``
        rows are inert padding).  Fresh K/V lands in each row's private
        pages through the table (positions below ``write_from[b]`` —
        shared prefix pages — are write-protected), no per-request
        scratch cache.  Returns the LAST REAL slot's hidden state
        (B, 1, d_model) — run ``logits_head`` on a per-row (1, 1, d)
        slice to finish, preserving the sequential path's M=1 LM-head
        dispatch.  Per-row compute is bitwise-identical to the
        sequential chunked path: every sublayer is row-wise and the
        attention arithmetic mirrors the dense-scratch path op for op
        (kernels.ref.paged_prefill_ref).
        """
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        x = shd.constraint(x, P(L.BATCH, None, None))

        def body(x, xs):
            lp, glob, pk, pv = xs
            h = norm_apply(lp["ln1"], x)
            a, (nk, nv) = ATT.apply_paged_prefill(
                attn_plan, lp["attn"], h, pages=(pk, pv),
                page_table=page_table, starts=starts, counts=counts,
                write_from=write_from, is_global=glob, impl=impl)
            # pin the pool's head-shard layout across layers (see
            # decode_paged)
            nk = shd.constraint(nk, P(None, None, L.TP_KV, L.TP_HD))
            nv = shd.constraint(nv, P(None, None, L.TP_KV, L.TP_HD))
            x = x + a
            h = norm_apply(lp["ln2"], x)
            f = FFN.apply(ffn_plan, lp["ffn"], h)
            x = shd.constraint(x + f, P(L.BATCH, None, None))
            return x, (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], is_global, pages["k"], pages["v"]))
        last = jnp.clip(counts - 1, 0, tokens.shape[1] - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        return x, {"k": nk, "v": nv}

    def pspecs():
        cell = []
        jax.eval_shape(lambda k: build_params(k, cell),
                       jax.random.PRNGKey(0))
        return cell[0]

    return Model(cfg, lambda key: build_params(key), pspecs, train_loss,
                 prefill, decode_step, init_cache, cache_pspecs,
                 init_paged_cache=init_paged_cache,
                 decode_paged=decode_paged,
                 # MoE capacity routing is batch-shape dependent, so a
                 # wider block is not bitwise row-equal there
                 decode_paged_block=None if use_moe else decode_paged_block,
                 prefill_paged=None if use_moe else prefill_paged,
                 logits_head=logits_fn)


# ===========================================================================
# rwkv-kind model (attention-free)
# ===========================================================================

def _build_rwkv(cfg: ArchConfig) -> Model:
    dt = _dtype(cfg)
    tm_plan = _rwkv_plan(cfg)
    cm_plan = _cmix_plan(cfg)
    emb_plan = _emb_plan(cfg)
    norm_init, norm_apply = _norm_fns(cfg)
    nl = cfg.num_layers

    def layer_init(key):
        ks = jax.random.split(key, 2)
        params, specs = {}, {}
        params["tm"], specs["tm"] = RW.init(tm_plan, ks[0])
        params["cm"], specs["cm"] = RW.channel_mix_init(cm_plan, ks[1])
        params["ln1"], specs["ln1"] = norm_init()
        params["ln2"], specs["ln2"] = norm_init()
        return params, specs

    def build_params(key, spec_cell=None):
        kemb, klayers, khead = jax.random.split(key, 3)
        params, specs = {}, {}
        params["embed"], specs["embed"] = L.embedding_init(emb_plan, kemb)
        params["layers"], specs["layers"] = stack_init(
            layer_init, jax.random.split(klayers, nl))
        params["final_norm"], specs["final_norm"] = norm_init()
        p, s = L.linear_init(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab,
                         pspec=(L.FSDP, L.TP), dtype=dt), khead)
        params["lm_head"], specs["lm_head"] = p, s
        if spec_cell is not None:
            spec_cell.append(specs)
        return params

    def layer_body(x, lp, state):
        h = norm_apply(lp["ln1"], x)
        a, tm_state = RW.apply_time_mix(tm_plan, lp["tm"], h, state["tm"])
        x = x + a
        h = norm_apply(lp["ln2"], x)
        f, cm_state = RW.channel_mix_apply(cm_plan, lp["cm"], h, state["cm"])
        x = shd.constraint(x + f, P(L.BATCH, None, None))
        return x, {"tm": tm_state, "cm": cm_state}

    def zero_state(batch):
        return {"tm": RW.time_mix_state(tm_plan, batch),
                "cm": RW.channel_mix_state(cm_plan, batch)}

    def stacked_zero_state(batch):
        one = zero_state(batch)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (nl,) + a.shape), one)

    def run(params, x, state):
        def body(carry, xs):
            x = carry
            lp, st = xs

            def inner(x, lp, st):
                return layer_body(x, lp, st)

            if cfg.remat:
                inner = jax.checkpoint(inner)
            x, new_st = inner(x, lp, st)
            return x, new_st

        x, new_state = jax.lax.scan(body, x, (params["layers"], state))
        x = norm_apply(params["final_norm"], x)
        logits = L.linear_apply(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab, dtype=dt),
            params["lm_head"], x)
        return logits, new_state

    def train_loss(params, batch):
        x = L.embedding_lookup(emb_plan, params["embed"], batch["tokens"])
        x = shd.constraint(x, P(L.BATCH, None, None))
        logits, _ = run(params, x, stacked_zero_state(x.shape[0]))
        loss, metrics = softmax_xent(logits, batch["targets"],
                                     cfg.vocab_size)
        metrics["aux_loss"] = 0.0
        return loss, metrics

    def init_cache(batch, max_len):
        del max_len  # recurrent: O(1) state
        st = stacked_zero_state(batch)
        return {"state": st, "index": jnp.zeros((), jnp.int32)}

    def cache_pspecs(batch, max_len):
        del max_len
        return {"state": {
            "tm": {"shift": P(None, L.CACHE_BATCH, None),
                   "wkv": P(None, L.CACHE_BATCH, L.TP_KV, None, None)},
            "cm": {"shift": P(None, L.CACHE_BATCH, None)},
        }, "index": P()}

    def prefill(params, batch):
        x = L.embedding_lookup(emb_plan, params["embed"], batch["tokens"])
        x = shd.constraint(x, P(L.BATCH, None, None))
        cache = batch["cache"]
        logits, st = run(params, x, cache["state"])
        return logits[:, -1:, :], {"state": st,
                                   "index": cache["index"] + x.shape[1]}

    def decode_step(params, tokens, cache):
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        logits, st = run(params, x, cache["state"])
        return logits, {"state": st, "index": cache["index"] + 1}

    def pspecs():
        cell = []
        jax.eval_shape(lambda k: build_params(k, cell),
                       jax.random.PRNGKey(0))
        return cell[0]

    return Model(cfg, lambda key: build_params(key), pspecs, train_loss,
                 prefill, decode_step, init_cache, cache_pspecs)


# ===========================================================================
# zamba-kind model (mamba2 backbone + shared attention block)
# ===========================================================================

def _build_zamba(cfg: ArchConfig) -> Model:
    dt = _dtype(cfg)
    mb_plan = _mamba_plan(cfg)
    attn_plan = _attn_plan(cfg, base=("shared", "attn"))
    ffn_plan = _ffn_plan(cfg, base=("shared", "ffn"))
    emb_plan = _emb_plan(cfg)
    norm_init, norm_apply = _norm_fns(cfg)
    group = cfg.hybrid_group
    n_groups = cfg.num_layers // group
    assert n_groups * group == cfg.num_layers

    def mamba_layer_init(key):
        params, specs = {}, {}
        params["mamba"], specs["mamba"] = MB.init(mb_plan, key)
        params["ln"], specs["ln"] = norm_init()
        return params, specs

    def build_params(key, spec_cell=None):
        kemb, km, ka, kf, kh = jax.random.split(key, 5)
        params, specs = {}, {}
        params["embed"], specs["embed"] = L.embedding_init(emb_plan, kemb)
        # (n_groups, group, ...) stacked mamba layers
        mkeys = jax.random.split(km, cfg.num_layers).reshape(
            n_groups, group, 2)
        cell = []

        def group_init_capture(keys):
            p, s = stack_init(mamba_layer_init, keys)
            if not cell:
                cell.append(s)
            return p

        params["mamba_groups"] = jax.vmap(group_init_capture)(mkeys)
        specs["mamba_groups"] = jax.tree.map(
            lambda s: P(None, *s), cell[0], is_leaf=_is_spec)
        # ONE shared attention + ffn block (zamba's contribution)
        shared_p, shared_s = {}, {}
        shared_p["attn"], shared_s["attn"] = ATT.init(attn_plan, ka)
        shared_p["ffn"], shared_s["ffn"] = FFN.init(ffn_plan, kf)
        shared_p["ln1"], shared_s["ln1"] = norm_init()
        shared_p["ln2"], shared_s["ln2"] = norm_init()
        params["shared"], specs["shared"] = shared_p, shared_s
        params["final_norm"], specs["final_norm"] = norm_init()
        p, s = L.linear_init(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab,
                         pspec=(L.FSDP, L.TP), dtype=dt), kh)
        params["lm_head"], specs["lm_head"] = p, s
        if spec_cell is not None:
            spec_cell.append(specs)
        return params

    def shared_block(params, x, positions, cache_kv=None, cache_index=None):
        sp = params["shared"]
        h = norm_apply(sp["ln1"], x)
        a, new_kv = ATT.apply(attn_plan, sp["attn"], h, positions=positions,
                              cache=cache_kv, cache_index=cache_index)
        x = x + a
        h = norm_apply(sp["ln2"], x)
        x = x + FFN.apply(ffn_plan, sp["ffn"], h)
        return x, new_kv

    def mamba_zero_state(batch):
        one = MB.init_state(mb_plan, batch)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups, group) + a.shape), one)

    def run_train(params, x):
        positions = jnp.arange(x.shape[1])

        def group_body(carry, gp):
            x, aux = carry

            def inner_layer(x, lp):
                h = norm_apply(lp["ln"], x)
                y, _ = MB.apply_train(mb_plan, lp["mamba"], h)
                return x + y, None

            def inner_group(x, gp):
                x, _ = jax.lax.scan(
                    lambda c, lp: inner_layer(c, lp), x, gp)
                x, _ = shared_block(params, x, positions)
                return shd.constraint(x, P(L.BATCH, None, None))

            if cfg.remat:
                inner_group = jax.checkpoint(inner_group)
            x = inner_group(x, gp)
            return (x, aux), None

        (x, _), _ = jax.lax.scan(group_body, (x, 0.0),
                                 params["mamba_groups"])
        return x

    def train_loss(params, batch):
        x = L.embedding_lookup(emb_plan, params["embed"], batch["tokens"])
        x = shd.constraint(x, P(L.BATCH, None, None))
        x = run_train(params, x)
        x = norm_apply(params["final_norm"], x)
        logits = L.linear_apply(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab, dtype=dt),
            params["lm_head"], x)
        loss, metrics = softmax_xent(logits, batch["targets"],
                                     cfg.vocab_size)
        metrics["aux_loss"] = 0.0
        return loss, metrics

    def init_cache(batch, max_len):
        kv_shape = (n_groups, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv_shape, dt), "v": jnp.zeros(kv_shape, dt),
                "mamba": mamba_zero_state(batch),
                "index": jnp.zeros((), jnp.int32)}

    def cache_pspecs(batch, max_len):
        # seq axis resolution is per-cell (launch/specs.rules_for):
        # decode cells shard cache seq over the model axis
        # (flash-decoding: partial softmax + tiny all-reduces) -- train
        # cells resolve seq to None.
        kv = P(None, L.CACHE_BATCH, L.SEQ, L.TP_KV, L.TP_HD)
        ms = MB.state_pspec()
        return {"k": kv, "v": kv,
                "mamba": jax.tree.map(
                    lambda s: P(None, None, L.CACHE_BATCH, *s[1:])
                    if len(s) and s[0] == L.BATCH else P(None, None, *s),
                    ms, is_leaf=_is_spec),
                "index": P()}

    def run_cached(params, x, cache, start, decode: bool):
        start = jnp.asarray(start)
        if start.ndim == 1:
            positions = start[:, None] + jnp.arange(x.shape[1])[None, :]
        else:
            positions = start + jnp.arange(x.shape[1])

        def group_body(x, xs):
            gp, ck, cv, mstate = xs

            def inner_layer(x, args):
                lp, st = args
                h = norm_apply(lp["ln"], x)
                if decode:
                    y, new_st = MB.apply_decode(mb_plan, lp["mamba"], h, st)
                else:
                    y, new_st = MB.apply_train(mb_plan, lp["mamba"], h)
                return x + y, new_st

            x, new_mstate = jax.lax.scan(inner_layer, x, (gp, mstate))
            x, new_kv = shared_block(params, x, positions,
                                     cache_kv=(ck, cv), cache_index=start)
            return x, (new_kv[0], new_kv[1], new_mstate)

        x, (nk, nv, nms) = jax.lax.scan(
            group_body, x,
            (params["mamba_groups"], cache["k"], cache["v"],
             cache["mamba"]))
        new_cache = {"k": nk, "v": nv, "mamba": nms,
                     "index": start + x.shape[1]}
        x = norm_apply(params["final_norm"], x)
        logits = L.linear_apply(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab, dtype=dt),
            params["lm_head"], x)
        return logits, new_cache

    def prefill(params, batch):
        x = L.embedding_lookup(emb_plan, params["embed"], batch["tokens"])
        x = shd.constraint(x, P(L.BATCH, None, None))
        cache = batch["cache"]
        logits, cache = run_cached(params, x, cache, cache["index"],
                                   decode=False)
        return logits[:, -1:, :], cache

    def decode_step(params, tokens, cache):
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        logits, cache = run_cached(params, x, cache, cache["index"],
                                   decode=True)
        return logits, cache

    def pspecs():
        cell = []
        jax.eval_shape(lambda k: build_params(k, cell),
                       jax.random.PRNGKey(0))
        return cell[0]

    return Model(cfg, lambda key: build_params(key), pspecs, train_loss,
                 prefill, decode_step, init_cache, cache_pspecs)


# ===========================================================================
# enc-dec kind (whisper): stub audio frontend provides frame embeddings
# ===========================================================================

def _build_encdec(cfg: ArchConfig) -> Model:
    dt = _dtype(cfg)
    enc_attn = _attn_plan(cfg, base=("encoder", "attn"), causal=False,
                          use_rope=False)
    self_attn = _attn_plan(cfg, base=("decoder", "self"), causal=True,
                           use_rope=False)
    cross_attn = _attn_plan(cfg, base=("decoder", "cross"), cross=True,
                            causal=False, use_rope=False)
    # encoder/decoder FFNs share seed keys (one historical plan) but are
    # separate slots: a policy may compress them differently
    enc_ffn = _ffn_plan(cfg, base=("encoder", "ffn"))
    dec_ffn = _ffn_plan(cfg, base=("decoder", "ffn"))
    emb_plan = _emb_plan(cfg)
    norm_init, norm_apply = _norm_fns(cfg)
    nl, ne = cfg.num_layers, cfg.encoder_layers

    def enc_layer_init(key):
        ks = jax.random.split(key, 2)
        params, specs = {}, {}
        params["attn"], specs["attn"] = ATT.init(enc_attn, ks[0])
        params["ffn"], specs["ffn"] = FFN.init(enc_ffn, ks[1])
        params["ln1"], specs["ln1"] = norm_init()
        params["ln2"], specs["ln2"] = norm_init()
        return params, specs

    def dec_layer_init(key):
        ks = jax.random.split(key, 3)
        params, specs = {}, {}
        params["self"], specs["self"] = ATT.init(self_attn, ks[0])
        params["cross"], specs["cross"] = ATT.init(cross_attn, ks[1])
        params["ffn"], specs["ffn"] = FFN.init(dec_ffn, ks[2])
        params["ln1"], specs["ln1"] = norm_init()
        params["ln2"], specs["ln2"] = norm_init()
        params["ln3"], specs["ln3"] = norm_init()
        return params, specs

    def build_params(key, spec_cell=None):
        kemb, kenc, kdec, kh = jax.random.split(key, 4)
        params, specs = {}, {}
        params["embed"], specs["embed"] = L.embedding_init(emb_plan, kemb)
        params["encoder"], specs["encoder"] = stack_init(
            enc_layer_init, jax.random.split(kenc, ne))
        params["decoder"], specs["decoder"] = stack_init(
            dec_layer_init, jax.random.split(kdec, nl))
        params["enc_norm"], specs["enc_norm"] = norm_init()
        params["final_norm"], specs["final_norm"] = norm_init()
        p, s = L.linear_init(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab,
                         pspec=(L.FSDP, L.TP), dtype=dt), kh)
        params["lm_head"], specs["lm_head"] = p, s
        if spec_cell is not None:
            spec_cell.append(specs)
        return params

    def encode(params, frames):
        """frames: (B, T_enc, d_model) precomputed stub embeddings."""
        t = frames.shape[1]
        x = frames.astype(dt) + L.sinusoidal_positions(
            t, cfg.d_model).astype(dt)
        x = shd.constraint(x, P(L.BATCH, None, None))
        positions = jnp.arange(t)

        def body(x, lp):
            def inner(x, lp):
                h = norm_apply(lp["ln1"], x)
                a, _ = ATT.apply(enc_attn, lp["attn"], h,
                                 positions=positions)
                x = x + a
                h = norm_apply(lp["ln2"], x)
                return x + FFN.apply(enc_ffn, lp["ffn"], h), None

            if cfg.remat:
                inner = jax.checkpoint(inner)
            return inner(x, lp)

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return norm_apply(params["enc_norm"], x)

    def dec_layer(x, lp, enc_out, positions, cache_kv=None,
                  cache_index=None):
        h = norm_apply(lp["ln1"], x)
        a, new_kv = ATT.apply(self_attn, lp["self"], h, positions=positions,
                              cache=cache_kv, cache_index=cache_index)
        x = x + a
        h = norm_apply(lp["ln2"], x)
        a, _ = ATT.apply(cross_attn, lp["cross"], h, positions=positions,
                         kv_source=enc_out)
        x = x + a
        h = norm_apply(lp["ln3"], x)
        x = shd.constraint(x + FFN.apply(dec_ffn, lp["ffn"], h),
                           P(L.BATCH, None, None))
        return x, new_kv

    def embed_tokens(params, tokens, start, max_pos):
        x = L.embedding_lookup(emb_plan, params["embed"], tokens)
        s = tokens.shape[1]
        table = L.sinusoidal_positions(max_pos, cfg.d_model)
        pe = jax.lax.dynamic_slice_in_dim(table, start, s, axis=0)
        return x + pe.astype(x.dtype)

    def train_loss(params, batch):
        enc_out = encode(params, batch["frames"])
        s = batch["tokens"].shape[1]
        x = embed_tokens(params, batch["tokens"], 0, s)
        x = shd.constraint(x, P(L.BATCH, None, None))
        positions = jnp.arange(x.shape[1])

        def body(x, lp):
            def inner(x, lp):
                y, _ = dec_layer(x, lp, enc_out, positions)
                return y, None

            if cfg.remat:
                inner = jax.checkpoint(inner)
            return inner(x, lp)

        x, _ = jax.lax.scan(body, x, params["decoder"])
        x = norm_apply(params["final_norm"], x)
        logits = L.linear_apply(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab, dtype=dt),
            params["lm_head"], x)
        loss, metrics = softmax_xent(logits, batch["targets"],
                                     cfg.vocab_size)
        metrics["aux_loss"] = 0.0
        return loss, metrics

    def init_cache(batch, max_len):
        kv = (nl, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
                "enc": jnp.zeros((batch, cfg.encoder_seq, cfg.d_model), dt),
                "index": jnp.zeros((), jnp.int32)}

    def cache_pspecs(batch, max_len):
        # seq axis resolution is per-cell (launch/specs.rules_for):
        # decode cells shard cache seq over the model axis
        # (flash-decoding: partial softmax + tiny all-reduces) -- train
        # cells resolve seq to None.
        kv = P(None, L.CACHE_BATCH, L.SEQ, L.TP_KV, L.TP_HD)
        return {"k": kv, "v": kv, "enc": P(L.BATCH, None, None),
                "index": P()}

    def run_dec(params, x, enc_out, cache, start):
        positions = start + jnp.arange(x.shape[1])

        def body(x, xs):
            lp, ck, cv = xs
            y, new_kv = dec_layer(x, lp, enc_out, positions,
                                  cache_kv=(ck, cv), cache_index=start)
            return y, new_kv

        x, (nk, nv) = jax.lax.scan(body, x,
                                   (params["decoder"], cache["k"],
                                    cache["v"]))
        x = norm_apply(params["final_norm"], x)
        logits = L.linear_apply(
            L.LinearPlan(cfg.d_model, cfg.padded_vocab, dtype=dt),
            params["lm_head"], x)
        new_cache = {"k": nk, "v": nv, "enc": enc_out,
                     "index": start + x.shape[1]}
        return logits, new_cache

    def prefill(params, batch):
        enc_out = encode(params, batch["frames"])
        cache = batch["cache"]
        max_len = cache["k"].shape[2]
        x = embed_tokens(params, batch["tokens"], cache["index"], max_len)
        x = shd.constraint(x, P(L.BATCH, None, None))
        logits, cache = run_dec(params, x, enc_out, cache, cache["index"])
        return logits[:, -1:, :], cache

    def decode_step(params, tokens, cache):
        max_len = cache["k"].shape[2]
        x = embed_tokens(params, tokens, cache["index"], max_len)
        logits, cache = run_dec(params, x, cache["enc"], cache,
                                cache["index"])
        return logits, cache

    def pspecs():
        cell = []
        jax.eval_shape(lambda k: build_params(k, cell),
                       jax.random.PRNGKey(0))
        return cell[0]

    return Model(cfg, lambda key: build_params(key), pspecs, train_loss,
                 prefill, decode_step, init_cache, cache_pspecs)


# ===========================================================================

def build(cfg: ArchConfig) -> Model:
    if cfg.arch_kind == "decoder":
        return _build_decoder(cfg)
    if cfg.arch_kind == "rwkv":
        return _build_rwkv(cfg)
    if cfg.arch_kind == "zamba":
        return _build_zamba(cfg)
    if cfg.arch_kind == "encdec":
        return _build_encdec(cfg)
    raise ValueError(cfg.arch_kind)
