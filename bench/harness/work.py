"""Operations and bytes the algorithm needs, from a configuration's sizes.

Counted for a dense GQA decoder (the keys of a Hugging Face ``config.json``
of the Qwen2 family), in the precision the configuration states
(``torch_dtype``; norms are held in float32 by the program and counted so).
The counts are the same whatever implements the work: a kernel that skips
empty cache or unused logits does less than this and reads higher against
it; one that does more reads lower.

* Matmul operations are 2 per weight per token.  Attention is 4 x heads x
  head size per layer for each position a token attends to (scores and
  values).  Embedding lookups, norms and softmax are not counted.
* A prefill of S tokens attends causally (S (S + 1) / 2 positions) and
  needs the head for its last token only.
* A decode step of a slot at position p reads every weight once for the
  whole batch, the slot's K/V at positions 0..p-1, and writes its own K/V
  at p; its token attends to p + 1 positions.
"""
from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    tied: bool
    dtype_bytes: int

    @classmethod
    def of(cls, config: dict) -> "Sizes":
        d, h = config["hidden_size"], config["num_attention_heads"]
        return cls(layers=config["num_hidden_layers"], d_model=d,
                   d_ff=config["intermediate_size"], heads=h,
                   kv_heads=config["num_key_value_heads"],
                   head_dim=config.get("head_dim") or d // h,
                   vocab=config["vocab_size"],
                   tied=bool(config["tie_word_embeddings"]),
                   dtype_bytes=DTYPE_BYTES[config["torch_dtype"]])

    # ---------------------------------------------------------- weights --
    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Bytes read once per step: every layer's matmuls, biases and
        norms, the final norm and the head (the embedding rows a step
        gathers are counted with the head when tied, and are negligible
        when not)."""
        bias = self.layers * (self.heads + 2 * self.kv_heads) * self.head_dim
        norms = (2 * self.layers + 1) * self.d_model
        return (self.dtype_bytes * (self.layers * self.layer_matmul_params
                                    + self.head_params + bias)
                + 4 * norms)

    @property
    def kv_bytes_per_pos(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * \
            self.dtype_bytes

    @property
    def params(self) -> int:
        """Every parameter, for the memory reckoning."""
        bias = self.layers * (self.heads + 2 * self.kv_heads) * self.head_dim
        norms = (2 * self.layers + 1) * self.d_model
        embed = self.vocab * self.d_model * (1 if self.tied else 2)
        return self.layers * self.layer_matmul_params + bias + norms + embed

    def _attn_flops(self, positions: int) -> int:
        return 4 * self.layers * self.heads * self.head_dim * positions

    # ------------------------------------------------------------- work --
    def prefill(self, seq: int) -> tuple:
        """(flops, bytes) of one prefill of ``seq`` tokens."""
        flops = (2 * self.layers * self.layer_matmul_params * seq
                 + 2 * self.head_params
                 + self._attn_flops(seq * (seq + 1) // 2))
        return flops, self.weight_bytes + self.kv_bytes_per_pos * seq

    def decode_block(self, rows) -> tuple:
        """(flops, bytes) of one fused decode block: ``rows`` holds, for
        each slot that needs steps, (its position when the block starts,
        the steps it needs).  Weights are read once per step needed by any
        slot."""
        rows = [(int(p), int(n)) for p, n in rows if n > 0]
        if not rows:
            return 0, 0
        tokens = sum(n for _, n in rows)
        attended = sum(n * p + n * (n + 1) // 2 for p, n in rows)
        flops = (2 * (self.layers * self.layer_matmul_params
                      + self.head_params) * tokens
                 + self._attn_flops(attended))
        byts = max(n for _, n in rows) * self.weight_bytes \
            + self.kv_bytes_per_pos * attended
        return flops, byts


def least_seconds(flops: float, byts: float, peak: dict) -> tuple:
    """(least time, bound) for work on a chip with ``peak`` rates."""
    t_c = flops / peak["bf16_flops"]
    t_m = byts / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
