"""Benchmark entry point: one run of one cell, one JSON line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration, a traffic file and the chips it needs.  The run
refuses anything but a TPU with that many chips (exit 2, no result line).
It keeps JAX's persistent compilation cache in ``.jax_cache/`` at the root
of the checkout, so only a checkout's first run of a cell compiles.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device`` and, last, ``checks``: each number that decided
``correct`` beside its limit.  The same numbers close standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the trainer (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    # the cache key holds the path: one fixed directory inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print("timings " + json.dumps(result["timings"]), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
