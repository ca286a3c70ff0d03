"""Model operations of one training step of a dense decoder.

Matrix products: 6 operations per parameter per token (2 forward, 4
backward) over every weight that multiplies activations -- the attention and
MLP projections and the head at the vocabulary actually used; the embedding
lookup and the norm scales multiply nothing.  Attention: the causal score
and value products, 4 * heads * head_dim * S(S+1)/2 per layer and sequence
forward, three times that with the backward pass.  Recomputation is not
counted.
"""

from __future__ import annotations

import math

from bench import weights


def matmul_params(cfg: dict) -> int:
    """Weights that multiply activations, from the parameter tree."""
    shapes = weights.shapes(cfg)
    layer = shapes["layers"]["l0_attn_mlp"]
    n = sum(math.prod(s) for s in layer["attn"].values())
    n += sum(math.prod(s) for s in layer["mlp"].values())
    return n + cfg["hidden_size"] * cfg["vocab_size"]


def count(cfg: dict, sequences: int, seq: int) -> float:
    """Operations of one step over ``sequences`` rows of ``seq`` tokens."""
    heads, d = cfg["num_attention_heads"], cfg.get(
        "head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    attn_fwd = 4.0 * heads * d * seq * (seq + 1) / 2
    layers = cfg["num_hidden_layers"]
    return (6.0 * matmul_params(cfg) * sequences * seq
            + 3.0 * attn_fwd * layers * sequences)
