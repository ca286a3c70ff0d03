"""Each benchmark configuration keeps its model's published sizes: every
width in ``bench/configs/<config>.json`` equals the trainer's registry entry
for that model (``repro/configs/<model>.py``), and the only keys changed from
the published model are those in ``reduced``, each stated under
``published``."""

import pytest

from bench import spec

CONFIGS = sorted(p.stem for p in (spec.BENCH_DIR / "configs").glob("*.json"))
# HF config.json key -> the registry's ArchConfig field, for the keys that
# a configuration keeps as published
PUBLISHED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
             "head_dim": "head_dim", "rope_theta": "rope_theta",
             "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
             "attention_bias": "qkv_bias"}
# keys a configuration may cut, and their registry fields
CUTS = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size"}


def registry_entry(name: str):
    """The registry's model whose name the configuration's name starts with
    (``internlm2_20b_l1_vocab8th`` -> ``internlm2_20b``)."""
    from repro.models import registry

    found = [a for a in registry.ARCH_NAMES if name.startswith(a + "_")]
    assert len(found) == 1, (name, found)
    return registry.get_config(found[0])


@pytest.mark.parametrize("name", CONFIGS)
def test_widths_are_the_registrys(name):
    cfg = spec.config(name)
    arch = registry_entry(name)
    for key, field in PUBLISHED.items():
        assert key in cfg, (name, key)
        assert cfg[key] == getattr(arch, field), (name, key, cfg[key], getattr(arch, field))


@pytest.mark.parametrize("name", CONFIGS)
def test_only_reduced_keys_differ_from_the_published_model(name):
    cfg = spec.config(name)
    arch = registry_entry(name)
    assert set(cfg["reduced"]) == set(cfg["published"]) <= set(CUTS), name
    for key, field in CUTS.items():
        published = cfg["published"].get(key, cfg[key])
        assert published == getattr(arch, field), (name, key, published)
        assert (cfg[key] != published) is (key in cfg["reduced"]), (name, key)
    declared = {c["name"]: c for c in spec.benchmark()["configs"]}
    if name in declared:
        assert declared[name]["reduced"] == cfg["reduced"]
        assert declared[name]["source"] == cfg["source"]


def test_vocabulary_cuts_keep_at_least_an_eighth():
    for declared in spec.benchmark()["configs"]:
        cfg = spec.config(declared["name"])
        assert 8 * cfg["vocab_size"] >= cfg["published"]["vocab_size"], declared["name"]
