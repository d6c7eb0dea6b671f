"""Operations and bytes the algorithm needs, from shapes alone.

Counts what a step must do, not what the program compiled: prefill counts
only the prompt positions actually computed (the tail after a pool hit) and
the output head only where a token is sampled; decode reads each weight
once and each live context's KV once. The sizes come from a configuration
file under their published keys.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Tuple


@dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    weights: str                # "bf16" | "int8"

    @classmethod
    def of(cls, cfg: dict) -> "Sizes":
        return cls(layers=cfg["num_layers"], d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["multi_query_group_num"],
                   head_dim=cfg["kv_channels"], d_ff=cfg["ffn_hidden_size"],
                   vocab=cfg["padded_vocab_size"], weights=cfg["weights"])

    # (d_in, d_out) of every linear layer of one transformer layer
    @property
    def linears(self) -> List[Tuple[int, int]]:
        d, q, kv, f = (self.d_model, self.heads * self.head_dim,
                       self.kv_heads * self.head_dim, self.d_ff)
        return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]

    @property
    def linear_params(self) -> int:
        return sum(i * o for i, o in self.linears)

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 keys and values plus one int32 position, every layer."""
        return self.layers * (2 * self.kv_heads * self.head_dim * 2 + 4)

    @property
    def weight_bytes_read(self) -> int:
        """Bytes of weights one forward step reads: every linear layer
        (int8: one byte a weight plus an f32 scale per output), its biases
        and norms, and the bf16 output head. The embedding table is only
        indexed, a row per token, and is left out."""
        lin = self.layers * sum(
            i * o * (1 if self.weights == "int8" else 2)
            + (4 * o if self.weights == "int8" else 0)
            for i, o in self.linears)
        bias = self.layers * 2 * (self.heads + 2 * self.kv_heads) \
            * self.head_dim
        norms = (2 * self.layers + 1) * self.d_model * 2
        head = self.d_model * self.vocab * 2
        return lin + bias + norms + head


def token_flops(s: Sizes, position: int, sampled: bool) -> float:
    """FLOPs of one token at ``position`` (0-based): the linear layers, its
    attention over ``position + 1`` keys, and the output head if a token is
    sampled there."""
    lin = 2.0 * s.layers * s.linear_params
    attn = 4.0 * s.layers * s.heads * s.head_dim * (position + 1)
    head = 2.0 * s.d_model * s.vocab if sampled else 0.0
    return lin + attn + head


def prefill_flops(s: Sizes, start: int, plen: int) -> float:
    """FLOPs of prefilling positions ``[start, plen)`` of one prompt, with
    one token sampled at the last."""
    n = plen - start
    lin = 2.0 * s.layers * s.linear_params * n
    # sum of (p + 1) over p in [start, plen)
    keys = (plen * (plen + 1) - start * (start + 1)) / 2.0
    attn = 4.0 * s.layers * s.heads * s.head_dim * keys
    return lin + attn + 2.0 * s.d_model * s.vocab


def decode_step_cost(s: Sizes, positions: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step whose sequences each write the
    given position: the weights once, each context's KV once."""
    positions = list(positions)
    flops = sum(token_flops(s, p, True) for p in positions)
    kv = sum((p + 1) for p in positions) * s.kv_bytes_per_token
    return flops, float(s.weight_bytes_read + kv)


def dequant_matmul_cost(m: int, k: int, n: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``x (m, k) bf16 @ dequant(q (k, n) int8, scale
    (n,) f32) -> (m, n) bf16``."""
    return 2.0 * m * k * n, float(k * n + 4 * n + 2 * m * k + 2 * m * n)


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> Tuple[float, str]:
    """The roofline's least time and which bound holds."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")


def forward_dequant_calls(s: Sizes, rows: int) -> List[Dict[str, int]]:
    """The ``dequant_matmul`` calls of one forward over ``rows`` tokens:
    one per linear layer per transformer layer."""
    return [{"m": rows, "k": i, "n": o}
            for _ in range(s.layers) for i, o in s.linears]
