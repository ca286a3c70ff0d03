"""A configuration, a cell or a per-layer metric is added by adding files:
the harness finds each by its name, with no edit to any file it had."""

import json

import pytest

from bench import spec


def test_new_files_are_found_by_name(tiny_root):
    # a new configuration and traffic mix, as files
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    cfg.update(name="tiny2", hidden_size=32)
    (tiny_root / "bench/configs/tiny2.json").write_text(json.dumps(cfg))
    t = json.loads((tiny_root / "bench/traffic/tiny_fft.json").read_text())
    t["rows_per_chip"] = 4
    (tiny_root / "bench/traffic/tiny_fft_4rows.json").write_text(json.dumps(t))
    (tiny_root / "bench/limits/tiny2_cell.json").write_text(
        (tiny_root / "bench/limits/tiny_fft.json").read_text())
    # a new per-layer metric: its reader is a file of its own
    (tiny_root / "bench/metrics/answer_ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "tiny2_cell", "config": "tiny2",
                            "traffic": "tiny_fft_4rows", "chips": 1, "why": "t"})
    bm["per_layer"].append({"name": "answer_ms", "unit": "ms", "better": "lower",
                            "source": "program_span", "layer": "x",
                            "moves": "tokens_per_s", "workloads": ["tiny2_cell"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bm))

    w = spec.workload("tiny2_cell")
    assert spec.config(w["config"])["hidden_size"] == 32
    assert spec.traffic(w["traffic"])["rows_per_chip"] == 4
    assert spec.limits("tiny2_cell")["loss_gap"]["limit"] > 0
    names = [m["name"] for m in spec.per_layer_metrics("tiny2_cell")]
    assert "answer_ms" in names
    assert "answer_ms" not in [m["name"] for m in spec.per_layer_metrics("tiny_fft")]
    assert spec.metric_reader("answer_ms").read(None) == 42.0
    assert spec.arch_fields(spec.config("tiny2"))["d_model"] == 32


def test_every_declared_metric_and_file_exists():
    bm = spec.benchmark()
    for m in bm["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    for w in bm["workloads"]:
        spec.config(w["config"]), spec.traffic(w["traffic"]), spec.limits(w["name"])
    for c in bm["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert spec.config(c["name"])["name"] == c["name"]


def test_missing_names_are_errors(tiny_root):
    with pytest.raises(spec.SpecError):
        spec.workload("nope")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("nope")
    with pytest.raises(spec.SpecError):
        spec.config("nope")
