"""Where the benchmark's pieces live, found by the names in BENCHMARK.json.

Every configuration, traffic mix, correctness limit, per-layer metric reader
and work-count function is a file of its own under ``bench/``, named after
the entry that uses it:

    bench/configs/<config>.json     model sizes, as run (HF config.json keys)
    bench/traffic/<traffic>.json    the training job: shapes, data, exchange
    bench/limits/<workload>.json    the limits that decide ``correct``
    bench/metrics/<metric>.py       ``read(run) -> float | None``
    bench/work/<kernel>.py          operations and bytes of one kernel call

so a later change adds a cell or a metric by adding files, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# HF ``config.json`` key -> the trainer's ArchConfig field (dense decoders)
_ARCH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
}
_ACTIVATIONS = {"silu": "swiglu", "gelu_pytorch_tanh": "geglu"}


class SpecError(Exception):
    """A name in BENCHMARK.json that has no file, or a file that is unsound."""


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return _json(BENCH_DIR / "limits" / f"{workload_name}.json")


def _module(kind: str, name: str):
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no file bench/{kind}/{name}.py")
    loaded = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """``bench/metrics/<name>.py``; its ``read(run)`` gives the value or None."""
    return _module("metrics", name)


def work_counter(name: str):
    """``bench/work/<name>.py``; its ``count(...)`` gives the work of a call."""
    return _module("work", name)


def per_layer_metrics(workload_name: str) -> list:
    """The per-layer metric entries that this workload reports."""
    return [m for m in benchmark()["per_layer"]
            if "workloads" not in m or workload_name in m["workloads"]]


def arch_fields(cfg: dict) -> dict:
    """ArchConfig keyword arguments for a configuration file."""
    fields = {"name": cfg["name"], "family": "dense"}
    for hf, ours in _ARCH_KEYS.items():
        if hf in cfg:
            fields[ours] = cfg[hf]
    if "head_dim" not in fields:
        fields["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    act = cfg.get("hidden_act", "silu")
    if act not in _ACTIVATIONS:
        raise SpecError(f"{cfg['name']}: activation {act!r} has no gated MLP here")
    fields["mlp_activation"] = _ACTIVATIONS[act]
    return fields
