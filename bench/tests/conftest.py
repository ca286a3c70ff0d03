"""A reduced copy of the benchmark for CPU tests: the same files, plus a
tiny configuration and traffic, in a temporary root that ``bench.spec`` and
``bench.harness`` are pointed at.  The tiny cells are judged by
``tiny_limits.json``, set from CPU readings at this size: a smaller model
is noisier against its reference than the chip's cells."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

TINY = dict(name="tiny", hidden_size=128, intermediate_size=384,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            vocab_size=512)
SEQ, CHUNK = 128, 256
CELLS = {"tiny_fft": "fft_allgather_2x4096", "tiny_dense": "dense_2x4096"}


def make_tiny_root(tmp: Path, chips: int = 1) -> Path:
    """Copy the benchmark's data files into ``tmp`` and add the tiny cells."""
    from bench import harness, spec

    src = spec.BENCH_DIR
    (tmp / "bench").mkdir(parents=True)
    for d in ("configs", "traffic", "limits", "metrics", "work"):
        shutil.copytree(src / d, tmp / "bench" / d)
    cfg = json.loads((src / "configs/phi3_medium_14b_l1.json").read_text())
    cfg.update(TINY)
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"] = []
    for cell, traffic in CELLS.items():
        t = json.loads((src / f"traffic/{traffic}.json").read_text())
        t["seq_len"] = SEQ
        t["exchange"]["chunk"] = CHUNK
        (tmp / f"bench/traffic/{cell}.json").write_text(json.dumps(t))
        bm["workloads"].append({"name": cell, "config": "tiny", "traffic": cell,
                                "chips": chips, "why": "CPU test"})
        shutil.copy(Path(__file__).parent / "tiny_limits.json",
                    tmp / f"bench/limits/{cell}.json")
    for m in bm["per_layer"]:
        m["workloads"] = list(CELLS)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    peaks = json.loads(harness.PEAKS.read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (tmp / "peaks.json").write_text(json.dumps(peaks))
    return tmp


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    from bench import harness, spec

    root = make_tiny_root(tmp_path)
    monkeypatch.setattr(spec, "BENCH_DIR", root / "bench")
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(harness, "PEAKS", root / "peaks.json")
    return root
