"""Operation and byte counts, roofline shares and the peak table."""

import json

import pytest

from bench import harness, roofline, spec, weights


def cfg(**kw):
    base = json.loads((spec.BENCH_DIR / "configs/phi3_medium_14b_l1.json").read_text())
    base.update(kw)
    return base


def test_model_flops_by_hand():
    c = cfg(hidden_size=8, intermediate_size=16, num_attention_heads=2,
            num_key_value_heads=1, head_dim=4, vocab_size=10, num_hidden_layers=2)
    attn = 8 * (2 * 4) + 2 * (8 * 4) + (2 * 4) * 8  # wq, wk + wv, wo
    mlp = 3 * 8 * 16
    per_layer = attn + mlp
    n = 2 * per_layer + 8 * 10  # head at the vocabulary used, not padded
    mod = spec.work_counter("model_flops")
    assert mod.matmul_params(c) == n
    seq, rows = 5, 3
    attention = 3 * 4.0 * 2 * 4 * seq * (seq + 1) / 2 * 2 * rows
    assert mod.count(c, rows, seq) == pytest.approx(6.0 * n * rows * seq + attention)


def test_phi3_cell_flops():
    c = cfg()
    n = spec.work_counter("model_flops").matmul_params(c)
    assert n == 5120 * (5120 + 2 * 1280) + 5120 * 5120 + 3 * 5120 * 17920 + 5120 * 4008
    flops = spec.work_counter("model_flops").count(c, 2, 4096)
    assert 18.7e12 < flops < 18.9e12


def test_param_tree_counts():
    c = cfg()
    shapes = weights.shapes(c)
    assert shapes["embed"]["table"] == (4096, 5120)  # 4008 padded to 128
    total = sum(int(__import__("math").prod(s)) for s in
                __import__("jax").tree_util.tree_leaves(
                    shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert total == 382_745_600


def test_fused_compress_and_fold_counts():
    fc = spec.work_counter("fused_compress")
    ops, nbytes = fc.count(4096 * 10, 4096, 0.7)
    assert fc.keep_count(2049, 0.7) == 615
    assert nbytes == 10 * (3 * 2049 * 4 + 4) + 10 * (640 * 6 + 4)
    assert ops == 10 * (2 * 2049 + 2 * 615 * 20)
    sf = spec.work_counter("spectrum_fold")
    ops, nbytes = sf.count(4096 * 10 - 7, 4096, 0.7, 4)
    assert nbytes == 4 * 10 * 615 * 4 + 2 * 10 * 2049 * 4
    assert ops == 4 * 10 * 615 * 2 * 11


def test_roofline_share_and_bound():
    peaks = harness.peaks("TPU v5 lite")
    t, bound = roofline.least_seconds(1e9, 819e9, peaks)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = roofline.least_seconds(197e12 * 2, 1.0, peaks)
    assert bound == "flops" and t == pytest.approx(2.0)
    # two calls at the roofline take 2 s: 100%; at half speed: 50%
    assert roofline.share_pct(2, 0.0, 819e9, 2.0, peaks) == pytest.approx(100.0)
    assert roofline.share_pct(2, 0.0, 819e9, 4.0, peaks) == pytest.approx(50.0)
    assert roofline.share_pct(0, 0.0, 819e9, 4.0, peaks) is None


def test_peaks_table_is_keyed_by_device_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        harness.peaks("TPU v4")
