"""The benchmark's files: BENCHMARK.json keeps to its schema, every name in
it is found as a file of its own, and the configurations hold their
published widths."""

import json
import re

import pytest

from bench import registry

from bench_toy import ROOT

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]

# Widths from the published config.json of each model (the source URL in
# BENCHMARK.json); only the depth is cut.
PUBLISHED = {
    "qwen2.5-32b-l4": dict(
        hidden_size=5120, intermediate_size=27648, num_attention_heads=40,
        num_key_value_heads=8, head_dim=128, vocab_size=152064,
        rope_theta=1000000.0, rms_norm_eps=1e-05, qkv_bias=True,
        tie_word_embeddings=False, torch_dtype="bfloat16",
        max_position_embeddings=131072, num_hidden_layers=4),
    "deepseek-67b-l4": dict(
        hidden_size=8192, intermediate_size=22016, num_attention_heads=64,
        num_key_value_heads=8, head_dim=128, vocab_size=102400,
        rope_theta=10000.0, rms_norm_eps=1e-06, qkv_bias=False,
        tie_word_embeddings=False, torch_dtype="bfloat16",
        max_position_embeddings=4096, num_hidden_layers=4),
}


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1] in {
        str(p.relative_to(ROOT)) for p in (ROOT / "bench").glob("*.py")}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in {"lower", "higher"}
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_part_of_a_cell_is_found_by_name(cell):
    c = registry.cell(cell)
    assert c["config"]["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert callable(registry.module("loops", c["traffic"]["loop"]).make)
    assert c["evaluator"] in {"dense", "paged"}
    assert c["end_to_end"] and c["per_layer"]
    assert set(c["limits"]) == {"logit_rel_err", "select_gap",
                                "decision_faults", "unanswered"}
    assert c["search"]["algo"] == "wu_uct" and c["search"]["beta"] > 0
    for m in c["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))
    if c["evaluator"].startswith("paged"):
        assert isinstance(c["num_blocks"], int) and c["num_blocks"] > 0


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        registry.cell("no-such.cell")
    with pytest.raises(KeyError):
        registry.metric_reader("no_such_metric")
    with pytest.raises(KeyError):
        registry.module("loops", "no_such_loop")


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_config_files_hold_published_widths(name):
    c = registry.config(name)
    assert c["reduced"] == ["num_hidden_layers"]
    for entry in (e for e in BENCH["configs"] if e["name"] == name):
        assert c["source"] == entry["source"]
        assert c["reduced"] == entry["reduced"]
    for key, want in PUBLISHED[name].items():
        assert c[key] == want, key
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]


def test_peaks_table_is_keyed_by_device_kind():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "cloud.google.com" in table["source"]
    row = table["devices"]["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
