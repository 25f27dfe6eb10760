"""Operations and bytes the work needs, from a configuration's shapes.

These count the work of a dense decoder whatever implements it: padding,
masked positions and copies the implementation makes are not counted.
A matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations.
The configuration is the benchmark's own file (Hugging Face key names).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: dict) -> "Shapes":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], d=d, heads=h,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            tied=bool(cfg.get("tie_word_embeddings", False)),
            qkv_bias=bool(cfg.get("attention_bias", False)),
            dtype_bytes=DTYPE_BYTES[cfg["torch_dtype"]])

    # ---- parameters
    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim
        return (self.layer_matmul_params + (bias if self.qkv_bias else 0)
                + 2 * self.d)

    @property
    def params(self) -> int:
        emb = self.vocab * self.d * (1 if self.tied else 2)
        return self.layers * self.layer_params + emb + self.d

    @property
    def kv_bytes_per_token(self) -> int:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    # ---- operations
    def attn_flops(self, ctx: int) -> int:
        """One query position attending to ``ctx`` positions, all layers:
        scores and the weighted sum of values."""
        return 4 * ctx * self.heads * self.head_dim * self.layers

    @property
    def logits_flops(self) -> int:
        return 2 * self.d * self.vocab

    def token_flops(self, ctx: int) -> int:
        """One token through every layer, attending to ``ctx`` positions
        (itself included); no logits."""
        return 2 * self.layers * self.layer_matmul_params + self.attn_flops(ctx)

    def prefill_flops(self, n: int, start: int = 0) -> int:
        """``n`` prompt tokens at positions ``start .. start + n - 1`` and
        the logits of the last one."""
        return (sum(self.token_flops(start + i + 1) for i in range(n))
                + self.logits_flops)

    def decode_flops(self, contexts: Iterable[int]) -> int:
        """One decode step: one token per live row, each attending to its
        context (the new token included), with its logits."""
        return sum(self.token_flops(c) + self.logits_flops for c in contexts)

    # ---- bytes
    @property
    def weight_bytes(self) -> int:
        """Every weight a decode step reads once.  With tied embeddings the
        table is read whole for the logits (and a few rows for the
        lookup, not counted); untied, the head is read whole and the
        table is not."""
        table = 0 if self.tied else self.vocab * self.d
        return (self.params - table) * self.dtype_bytes

    def decode_bytes(self, contexts: Iterable[int]) -> int:
        """Weights once, the KV of each live row's context, and the new
        KV written."""
        contexts = list(contexts)
        return (self.weight_bytes
                + (sum(contexts) + len(contexts)) * self.kv_bytes_per_token)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
