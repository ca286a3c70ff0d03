"""The one traffic generator: training batches from a traffic file and a seed.

A traffic file gives the sequence length, the rows each chip takes and the
data: a walk on a fixed random first-order Markov chain over the vocabulary
(``branching`` successors a token), so the loss has structure to fall on,
made on the device from the seed.  Batch ``step`` is a pure
function of (seed, step): every step's rows differ, and every seed gets the
same sizes.  Targets are the next token of the same walk.
"""

from __future__ import annotations


def batch_fn(traffic: dict, vocab: int, global_batch: int):
    """``fn(key, step) -> {"tokens", "targets"}``, int32 (global_batch, seq)."""
    import jax
    import jax.numpy as jnp

    seq = traffic["seq_len"]
    data = traffic["data"]
    if data["kind"] != "markov":
        raise ValueError(f"unknown data kind {data['kind']!r}")
    b = data["branching"]

    def fn(key, step):
        succ = jax.random.randint(jax.random.fold_in(key, 0x7AB1E), (vocab, b),
                                  0, vocab, jnp.int32)
        k = jax.random.fold_in(jax.random.fold_in(key, 0xDA7A), step)
        k_start, k_walk = jax.random.split(k)
        start = jax.random.randint(k_start, (global_batch,), 0, vocab, jnp.int32)
        choice = jax.random.randint(k_walk, (seq, global_batch), 0, b, jnp.int32)

        def advance(tok, c):
            nxt = succ[tok, c]
            return nxt, nxt

        _, rest = jax.lax.scan(advance, start, choice)
        walk = jnp.concatenate([start[None], rest], axis=0).T
        return {"tokens": walk[:, :seq], "targets": walk[:, 1:]}

    return fn
