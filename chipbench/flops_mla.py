"""Operations and bytes the algorithm needs for a latent-attention (MLA),
expert-parallel MoE model, from shapes alone (`chipbench.flops` is the
dense GQA stack's).

Counts what a step must do, not what the program compiled. Attention is
counted in the absorbed form: W_uk folded into the query, scores and
context in latent space over every cached position, W_uv applied to the
context. An MoE layer counts the router, the shared experts and the
expected (token, expert) pairs that land on the experts this chip holds:
``top_k * held / experts`` a token. The bytes of held experts are those of
the experts any correct implementation must read: of ``held`` experts, M
tokens hit ``held * (1 - (1 - top_k / experts) ** M)`` distinct ones on
average. Sizes come from a configuration file under their published keys.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

BF16 = 2


@dataclass(frozen=True)
class MLASizes:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_lora: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    experts: int              # routed experts of the model (router width)
    held: int                 # routed experts this chip holds
    top_k: int
    shared: int
    vocab: int

    @classmethod
    def of(cls, cfg: dict) -> "MLASizes":
        return cls(layers=cfg["num_hidden_layers"],
                   dense_layers=cfg["first_k_dense_replace"],
                   d_model=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_lora=cfg["kv_lora_rank"],
                   nope_dim=cfg["qk_nope_head_dim"],
                   rope_dim=cfg["qk_rope_head_dim"],
                   v_dim=cfg["v_head_dim"],
                   dense_ff=cfg["intermediate_size"],
                   expert_ff=cfg["moe_intermediate_size"],
                   experts=cfg["expert_parallel"][
                       "published_n_routed_experts"],
                   held=cfg["n_routed_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   shared=cfg["n_shared_experts"],
                   vocab=cfg["vocab_size"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def attn_params(self) -> int:
        """Linear weights of one attention layer."""
        D, H, r = self.d_model, self.heads, self.kv_lora
        return (D * H * (self.nope_dim + self.rope_dim)
                + D * (r + self.rope_dim)
                + r * H * (self.nope_dim + self.v_dim)
                + H * self.v_dim * D)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.expert_ff

    @property
    def latent_bytes_per_token(self) -> int:
        """bf16 latent and rope key plus one int32 position, every layer."""
        return self.layers * ((self.kv_lora + self.rope_dim) * BF16 + 4)

    @property
    def replicated_bytes(self) -> int:
        """Bytes of the weights every forward reads whatever the routing:
        attention, norms, the dense layers, the router and shared experts
        of every MoE layer, the final norm and the bf16 output head. The
        embedding is only indexed, a row per token, and is left out."""
        D = self.d_model
        per_layer = self.attn_params + 2 * D + self.kv_lora
        dense = 3 * D * self.dense_ff
        moe = D * self.experts + self.shared * self.expert_params
        return BF16 * (self.layers * per_layer + self.dense_layers * dense
                       + self.moe_layers * moe + D + D * self.vocab)

    def held_hit(self, tokens: int) -> float:
        """Expected distinct held experts that ``tokens`` tokens route to,
        in one MoE layer."""
        return self.held * (1.0 - (1.0 - self.top_k / self.experts)
                            ** tokens)

    def held_pairs(self, tokens: int) -> float:
        """Expected (token, held expert) pairs of ``tokens`` tokens in one
        MoE layer."""
        return tokens * self.top_k * self.held / self.experts


def attend_flops(s: MLASizes, keys: int) -> float:
    """One query's latent attention over ``keys`` positions, one layer:
    scores over latent and rope key, context over the latent."""
    return 2.0 * s.heads * keys * (2 * s.kv_lora + s.rope_dim)


def token_flops(s: MLASizes, position: int, sampled: bool) -> float:
    """FLOPs of one token at ``position`` (0-based): projections, the
    absorption of W_uk and W_uv, attention over ``position + 1`` keys, the
    dense MLP or router, shared experts and expected held pairs, and the
    output head if a token is sampled there."""
    D, H, r = s.d_model, s.heads, s.kv_lora
    absorb = 2.0 * H * r * (s.nope_dim + s.v_dim)
    attn = 2.0 * s.attn_params + absorb + attend_flops(s, position + 1)
    dense = 2.0 * 3 * D * s.dense_ff
    moe = (2.0 * D * s.experts + 2.0 * s.shared * s.expert_params
           + 2.0 * s.expert_params * s.top_k * s.held / s.experts)
    head = 2.0 * D * s.vocab if sampled else 0.0
    return (s.layers * attn + s.dense_layers * dense + s.moe_layers * moe
            + head)


def prefill_flops(s: MLASizes, start: int, plen: int) -> float:
    """FLOPs of prefilling positions ``[start, plen)`` of one prompt, with
    one token sampled at the last."""
    return sum(token_flops(s, p, p == plen - 1) for p in range(start, plen))


def held_expert_cost(s: MLASizes, tokens: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the held experts over a forward of ``tokens``
    tokens, every MoE layer: the expected pairs, and the expected distinct
    held experts' weights read once."""
    flops = 2.0 * s.expert_params * s.held_pairs(tokens)
    nbytes = BF16 * s.expert_params * s.held_hit(tokens)
    return s.moe_layers * flops, s.moe_layers * nbytes


def decode_step_cost(s: MLASizes, positions: Iterable[int]
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step whose sequences each write the
    given position: the replicated weights once, the held experts the
    step's tokens hit once, each sequence's latent context once."""
    positions = list(positions)
    flops = sum(token_flops(s, p, True) for p in positions)
    ctx = sum(p + 1 for p in positions) * s.latent_bytes_per_token
    _, experts = held_expert_cost(s, len(positions))
    return flops, float(s.replicated_bytes + experts + ctx)
