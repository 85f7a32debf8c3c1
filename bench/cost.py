"""Operations and bytes of the work the algorithm needs, from shapes alone.

Counts follow the model's published shapes (a configuration file's keys)
and the real lengths of the work, never the program's layout: hashed
layers read their banks, not the expanded matrices, and attention reads
each row's real context, not the provisioned page table.  So a later
kernel or a fused expansion cannot make them stale.

Conventions, per token:

- a matrix product with a ``(n, m)`` weight costs ``2 n m`` operations;
- attention over ``ctx`` keys costs ``4 * heads * head_dim * ctx`` per
  layer (scores and the weighted sum);
- a decode step reads every real parameter once (the tied LM head reads
  the whole embedding) plus the K and V of each row's context.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import Dict, Iterable, Tuple

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _tiles(rows: int, cols: int, block: Tuple[int, int]) -> int:
    return math.ceil(rows / block[0]) * math.ceil(cols / block[1])


@dataclasses.dataclass(frozen=True)
class Model:
    layers: int
    d: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    compression: float = 1.0          # 1.0: dense
    block: Tuple[int, int] = (128, 128)
    bytes_per_param: int = 2          # bfloat16

    @classmethod
    def from_config(cls, c: Dict) -> "Model":
        h = c.get("hashed") or {}
        if not c.get("tie_word_embeddings", False):
            raise ValueError("only tied embeddings are counted")
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   d_ff=c["intermediate_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], vocab=c["vocab_size"],
                   compression=float(h.get("compression", 1.0)),
                   block=tuple(h.get("hash_block", (128, 128))))

    # ---- parameters ---------------------------------------------------
    def layer_matrices(self) -> Iterable[Tuple[int, int]]:
        """(in, out) of each projection of one layer: q, k, v, o, gate,
        in, out."""
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return [(self.d, hq), (self.d, hkv), (self.d, hkv), (hq, self.d),
                (self.d, self.d_ff), (self.d, self.d_ff),
                (self.d_ff, self.d)]

    def bank_params(self, rows: int, cols: int) -> int:
        """Real parameters of one projection: its bank in block mode."""
        if self.compression >= 1.0:
            return rows * cols
        bank_tiles = max(1, int(round(self.compression
                                      * _tiles(rows, cols, self.block))))
        return bank_tiles * self.block[0] * self.block[1]

    @property
    def virtual_layer_params(self) -> int:
        """Matrix parameters of all layers as the model computes them."""
        return self.layers * sum(r * c for r, c in self.layer_matrices())

    @property
    def real_layer_params(self) -> int:
        return self.layers * sum(self.bank_params(r, c)
                                 for r, c in self.layer_matrices())

    @property
    def norm_params(self) -> int:
        # two RMSNorms and the q/k norms per layer, and the final norm
        return self.layers * (2 * self.d + 2 * self.head_dim) + self.d

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d

    @property
    def real_params(self) -> int:
        return self.real_layer_params + self.norm_params + self.embed_params

    @property
    def virtual_params(self) -> int:
        return self.virtual_layer_params + self.norm_params \
            + self.embed_params

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.bytes_per_param

    # ---- work ---------------------------------------------------------
    def attn_flops(self, ctx: int) -> int:
        """Attention of one query over ``ctx`` keys, all layers."""
        return 4 * self.layers * self.heads * self.head_dim * ctx

    @property
    def head_flops(self) -> int:
        """The tied LM head for one position."""
        return 2 * self.vocab * self.d

    def decode_flops(self, rows: int, ctx_sum: int) -> int:
        """One decode step of ``rows`` rows whose contexts sum to
        ``ctx_sum`` keys (each row's own token included)."""
        per_row = 2 * self.virtual_layer_params + self.head_flops
        return rows * per_row + self.attn_flops(ctx_sum)

    def decode_bytes(self, rows: int, ctx_sum: int) -> int:
        if rows == 0:
            return 0
        return self.real_params * self.bytes_per_param \
            + ctx_sum * self.kv_bytes_per_token

    def prefill_flops(self, prompt_len: int) -> int:
        """A whole prompt: every position through every layer, causal
        attention over its own prefix, the LM head at the last one."""
        attn_keys = prompt_len * (prompt_len + 1) // 2
        return prompt_len * 2 * self.virtual_layer_params \
            + self.attn_flops(attn_keys) + self.head_flops

    def least_time(self, flops: float, nbytes: float,
                   peak: Dict[str, float]) -> float:
        """Roofline: the larger of compute time and memory time."""
        return max(flops / peak["bf16_flops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"])
