"""Seeded weights in the trainer's parameter layout, made on the device.

The layout is the trainer's published tree for a dense decoder (one
``l0_attn_mlp`` group stacked over the layers, vocabulary rows padded to a
multiple of 128); the harness checks it against the trainer's own spec
before a run.  The reference reads the same tree, so both start from the
same numbers without the benchmark taking weights from the program.
"""

from __future__ import annotations

import numpy as np

VOCAB_PAD = 128


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def shapes(cfg: dict) -> dict:
    """Nested dict of parameter shapes (and whether each is a norm scale)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim", d // h)
    n = cfg["num_hidden_layers"]
    vp = padded_vocab(cfg["vocab_size"])
    embed = {"table": (vp, d)}
    if not cfg.get("tie_word_embeddings", False):
        embed["head"] = (d, vp)
    return {
        "embed": embed,
        "final_norm": {"scale": (d,)},
        "layers": {"l0_attn_mlp": {
            "norm1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, dh), "wk": (n, d, kv, dh),
                     "wv": (n, d, kv, dh), "wo": (n, h, dh, d)},
            "norm2": {"scale": (n, d)},
            "mlp": {"gate": (n, d, f), "up": (n, d, f), "down": (n, f, d)},
        }},
    }


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from any non-negative seed (larger than 2**31 too)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return np.asarray(words, np.uint32)


def base_key(seed: int):
    import jax

    return jax.random.wrap_key_data(seed_words(seed), impl="threefry2x32")


def init(cfg: dict, key):
    """Parameters from ``key``: N(0, init_std) matrices, unit norm scales,
    float32.  Traceable: call it under ``jax.jit``."""
    import jax
    import jax.numpy as jnp

    std = cfg["training"]["init_std"]
    tree = shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        if path[-1].key == "scale":
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            k = jax.random.fold_in(key, i)
            leaves.append(std * jax.random.normal(k, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)
