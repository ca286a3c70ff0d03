"""The readings that the limits of ``correct`` are set from, at the cell's
own size, in one process:

    python3 -m bench.readings --workload <name> --seeds 1 2 ... --controls 3

For every seed, the program's checked steps (``harness.checked_steps``: the
compiled step and feed that the window drives) against the plain reference:
the lower readings.  For the first ``--controls`` seeds also the control and
the faults, each computed by the reference put in the program's place,
against the plain reference: the upper readings.  The control rounds every
matrix product's operands to float8 (one step below the configuration's
bfloat16); the faults are ``half_batch`` (half of each worker's rows left
out, the mean taken over the rest) and, on a cell of several chips,
``no_exchange`` (worker 0's gradient applied alone).  A state left unchanged
reads 1 on the change and needs no run.  Prints one JSON line a reading,
then the largest lower and the least upper reading of each number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
VARIANTS = (("float8", "none"), ("float32", "half_batch"), ("float32", "no_exchange"))


def readings(workload: str, seeds, n_controls: int, platform: str = "tpu", out=print):
    from bench import compare, harness, reference

    p = harness.prepare(workload, platform)
    names = reference.leaf_names(p.cfg)
    variants = [v for v in VARIANTS if v[1] != "no_exchange" or p.chips > 1]
    rows = []

    def emit(kind, seed, nums, seconds, got, ref):
        row = {"workload": workload, "kind": kind, "seed": seed, "seconds": seconds,
               **{k: nums[k]["value"] for k in compare.NUMBERS},
               "worst_leaf": {k: nums[k].get("leaf") for k in compare.NUMBERS},
               "grad_norms": [[float(a), float(b)] for a, b in
                              zip(got["grad_norms"], ref["grad_norms"])]}
        rows.append(row)
        out(json.dumps(row))

    for i, seed in enumerate(seeds):
        state, _, ours = harness.checked_steps(p, seed)
        harness.free(state)
        t1 = time.perf_counter()
        ref = reference.run(p.cfg, p.traffic, seed, p.chips, devices=p.devs)
        emit("program", seed, compare.numbers(ours, ref, names), time.perf_counter() - t1,
             ours, ref)
        if i >= n_controls:
            continue
        for precision, fault in variants:
            t0 = time.perf_counter()
            got = reference.run(p.cfg, p.traffic, seed, p.chips, precision=precision,
                                fault=fault, devices=p.devs)
            kind = "control" if precision != "float32" else fault
            emit(kind, seed, compare.numbers(got, ref, names), time.perf_counter() - t0,
                 got, ref)
    summary = {"workload": workload, "lower": {}, "upper": {}}
    for k in compare.NUMBERS:
        summary["lower"][k] = max(r[k] for r in rows if r["kind"] == "program")
        summary["upper"][k] = {kind: min(r[k] for r in rows if r["kind"] == kind)
                               for kind in {r["kind"] for r in rows} - {"program"}}
    out(json.dumps(summary))
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of the seeds also read the control and the faults")
    args = ap.parse_args(argv)
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    try:
        readings(args.workload, args.seeds, args.controls,
                 out=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
