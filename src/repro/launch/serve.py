"""Serving CLI: batched generation on a local or production mesh.

Standalone (random init):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2_2b --reduced

Replica mode (DESIGN.md §20) — tail a training job's delta ring, fold every
compressed weight delta into the replica state, and generate with the final
weights once the publisher closes the stream:

    PYTHONPATH=src python -m repro.launch.serve --follow /path/to/ring
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import jaxcompat as compat
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import registry
from repro.serve import Engine, ReplicaSubscriber, ServeConfig


def _follow_ring(args):
    """-> (arch config, model, params) from a delta ring's final state."""
    sub = ReplicaSubscriber(args.follow)
    meta = sub.meta
    arch = meta.get("arch", args.arch)
    reduced = bool(meta.get("reduced", args.reduced))
    cfg = registry.get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = registry.build(cfg)
    template = model.init(jax.random.PRNGKey(0))

    def on_sync(stats):
        print(f"[serve] v{stats.version}: +{stats.applied} deltas, "
              f"{stats.bytes_read} bytes, "
              f"{stats.decompress_count} decompress"
              + (", snapshot fallback" if stats.gap_detected else ""))

    final_version = sub.follow(timeout_s=args.follow_timeout,
                               on_sync=on_sync)
    print(f"[serve] ring closed at v{final_version}; weights loaded")
    return cfg, model, sub.params_like(template)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2_2b", choices=registry.ARCH_NAMES)
    # NOTE: this was `default=True` until PR 10, which made the flag inert —
    # the full-size config was unreachable from the CLI
    ap.add_argument("--reduced", action="store_true", help="smoke-size config")
    ap.add_argument("--follow", default=None, metavar="RING_DIR",
                    help="replica mode: tail this delta ring "
                         "(serve/ring.py) until the publisher closes it, "
                         "then serve the final weights; arch/reduced come "
                         "from the ring manifest")
    ap.add_argument("--follow-timeout", type=float, default=300.0,
                    help="give up if the ring is not closed after this many "
                         "seconds")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.follow is not None:
        cfg, model, params = _follow_ring(args)
    else:
        cfg = registry.get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        model = registry.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
    mesh = make_local_mesh()
    with compat.set_mesh(mesh):
        engine = Engine(model, params, ServeConfig(
            max_seq=args.prompt_len + args.new_tokens + 8,
            batch=args.batch, temperature=args.temperature))
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size, jnp.int32)
        out = engine.generate(prompts, args.new_tokens)
    print(out)
    return out


if __name__ == "__main__":
    main()
