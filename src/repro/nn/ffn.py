"""Feed-forward blocks: gated (SwiGLU/GeGLU) and plain MLP, hashed-capable."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import hashed as H
from repro.nn import layers as L


@dataclasses.dataclass(frozen=True)
class FFNPlan:
    d_model: int
    d_ff: int
    activation: str = "swiglu"   # swiglu | geglu | gelu | relu | relu_sq
    dtype: Any = jnp.bfloat16
    hash_in: Optional[H.HashedSpec] = None    # applies to w_in (and w_gate)
    hash_gate: Optional[H.HashedSpec] = None
    hash_out: Optional[H.HashedSpec] = None
    hash_path: str = "auto"

    @property
    def gated(self) -> bool:
        return self.activation in ("swiglu", "geglu")

    @property
    def inner_act(self):
        if self.activation == "swiglu":
            return jax.nn.silu
        if self.activation == "geglu":
            return lambda x: jax.nn.gelu(x, approximate=True)
        return L.activation(self.activation)


def _lin(plan, i, o, h, ps):
    return L.LinearPlan(i, o, hashed=h, pspec=ps, dtype=plan.dtype,
                        hash_path=plan.hash_path)


def init(plan: FFNPlan, key):
    ks = jax.random.split(key, 3)
    params, specs = {}, {}
    p, s = L.linear_init(
        _lin(plan, plan.d_model, plan.d_ff, plan.hash_in, (L.FSDP, L.TP)),
        ks[0])
    params["in"], specs["in"] = p, s
    if plan.gated:
        p, s = L.linear_init(
            _lin(plan, plan.d_model, plan.d_ff, plan.hash_gate,
                 (L.FSDP, L.TP)), ks[1])
        params["gate"], specs["gate"] = p, s
    p, s = L.linear_init(
        _lin(plan, plan.d_ff, plan.d_model, plan.hash_out, (L.TP, L.FSDP)),
        ks[2])
    params["out"], specs["out"] = p, s
    return params, specs


@jax.named_scope("mlp")
def apply(plan: FFNPlan, params, x):
    h = L.linear_apply(
        _lin(plan, plan.d_model, plan.d_ff, plan.hash_in, (L.FSDP, L.TP)),
        params["in"], x)
    if plan.gated:
        g = L.linear_apply(
            _lin(plan, plan.d_model, plan.d_ff, plan.hash_gate,
                 (L.FSDP, L.TP)), params["gate"], x)
        h = plan.inner_act(g) * h
    else:
        h = plan.inner_act(h)
    return L.linear_apply(
        _lin(plan, plan.d_ff, plan.d_model, plan.hash_out, (L.TP, L.FSDP)),
        params["out"], h)
