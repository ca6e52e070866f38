"""What the algorithm needs, in FLOPs and bytes, for one prefill or decode
call of a dense decoder, whatever implements it.

Only real work counts: prefill counts the real prompt tokens of each row
(no pad rows, no bucket padding) and causal attention over their real
lengths; decode counts the lanes that hold a request, one read of the
weights and the KV of each lane's live positions.  A kernel that reads less
than the program does today (paged, ragged) can then approach but never pass
100% of its roofline.  Matmul FLOPs are 2 per multiply-add.  Sizes come from
the configuration file's published keys.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    vocab: int
    layernorm: bool
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], d=d, heads=h,
            kv=cfg["num_key_value_heads"], hd=cfg.get("head_dim") or d // h,
            ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            layernorm="layer_norm_eps" in cfg,
        )

    @property
    def layer_matmul_params(self) -> int:
        attn = self.d * self.hd * (2 * self.heads + 2 * self.kv)
        return attn + 3 * self.d * self.ff           # gated (SwiGLU) MLP

    @property
    def norm_params(self) -> int:
        per = self.d * (2 if self.layernorm else 1)
        return self.layers * 2 * per + per

    @property
    def weight_bytes(self) -> int:
        """Every weight a forward pass reads once: the layers, the norms and
        the output head (the embedding is read row by row, counted apart)."""
        n = self.layers * self.layer_matmul_params + self.norm_params + self.d * self.vocab
        return n * self.dtype_bytes

    @property
    def kv_bytes_per_position(self) -> int:
        return self.layers * 2 * self.kv * self.hd * self.dtype_bytes

    @property
    def flops_per_token(self) -> int:
        """Matmul FLOPs of one token through the layers and the head."""
        return 2 * (self.layers * self.layer_matmul_params + self.d * self.vocab)

    def attention_flops(self, queries: int, keys: int) -> int:
        """QK^T and PV for ``queries`` query positions over ``keys`` keys
        each, summed over layers and heads."""
        return self.layers * 4 * self.heads * self.hd * queries * keys


def prefill_work(dims: Dims, lengths) -> tuple[int, int]:
    """(FLOPs, bytes) of one prefill call over rows of these real lengths,
    emitting logits for each row's last token."""
    lengths = [int(n) for n in lengths if n > 0]
    flops = bytes_ = 0
    for n in lengths:
        flops += 2 * dims.layers * dims.layer_matmul_params * n
        flops += dims.attention_flops(1, n * (n + 1) // 2)   # causal pairs
        flops += 2 * dims.d * dims.vocab
        bytes_ += n * dims.d * dims.dtype_bytes              # embedding rows
        bytes_ += n * dims.kv_bytes_per_position            # KV written
        bytes_ += dims.vocab * dims.dtype_bytes             # logits out
    if lengths:
        bytes_ += dims.weight_bytes
    return flops, bytes_


def decode_work(dims: Dims, cached) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step over the live lanes, where lane j
    holds ``cached[j]`` positions before this step's token."""
    cached = [int(p) for p in cached]
    flops = bytes_ = 0
    for p in cached:
        flops += 2 * dims.layers * dims.layer_matmul_params
        flops += dims.attention_flops(1, p + 1)
        flops += 2 * dims.d * dims.vocab
        bytes_ += p * dims.kv_bytes_per_position            # KV read
        bytes_ += dims.kv_bytes_per_position                # new KV written
        bytes_ += dims.d * dims.dtype_bytes                 # embedding row
        bytes_ += dims.vocab * dims.dtype_bytes             # logits out
    if cached:
        bytes_ += dims.weight_bytes
    return flops, bytes_


def least_seconds(flops: int, bytes_: int, peak) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak.bf16_flops, bytes_ / peak.hbm_bytes)
