"""Compile a cell's training step for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.aot --workload <name>

Lowers the trainer's step (``bench.job.build``) over the first 1 or 4
devices of a described ``v5e:2x2`` host, with shapes in place of arrays, and
prints the compiled step's per-device memory (``memory_analysis()``), its
Pallas kernel count and its collectives.  What the chip's compiler refuses,
this refuses too; nothing runs, so it gives no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def compile_for_v5e(workload_name: str):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench import hlo, job as job_mod, spec
    from repro.kernels import engine, runtime

    # the described chip compiles Mosaic; the trainer's auto backend asks
    engine.mosaic_available = runtime.mosaic_available = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    w = spec.workload(workload_name)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[: w["chips"]]), ("data",))
    job = job_mod.build(spec.config(w["config"]), spec.traffic(w["traffic"]), mesh)
    state = job_mod.abstract_state(job, NamedSharding(mesh, P()))
    batch = {k: jax.ShapeDtypeStruct((job.global_batch, job.seq), jnp.int32,
                                     sharding=job.step.batch_sharding)
             for k in ("tokens", "targets")}
    compiled = job.step.lower(state, batch).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "workload": workload_name,
        "chips": w["chips"],
        "step_bytes": hlo.step_bytes(mem),
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "tpu_custom_calls": hlo.custom_call_count(text),
        "collectives": hlo.summarize(hlo.parse_collectives(text, w["chips"])),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    print(json.dumps(compile_for_v5e(args.workload)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
