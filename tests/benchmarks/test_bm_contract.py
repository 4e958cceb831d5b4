"""BENCHMARK.json against the rules its contract states, and every cell's
files found by name."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def data(*parts):
    return os.path.join(REPO, "benchmarks", *parts)


def load(path):
    with open(path) as f:
        return json.load(f)


def all_names():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [(group, e["name"]) for e in BENCH[group]]
    names += [("config", w["config"]) for w in BENCH["workloads"]]
    names += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    names += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    return names


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("group,name", all_names())
def test_every_name_keeps_to_the_character_rules(group, name):
    assert NAME.match(name), (group, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_is_well_formed(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metrics(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert os.path.exists(data("end_to_end", metric["name"] + ".json"))


def test_the_end_to_end_metrics_are_the_issues():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names and len(names) <= 5
    assert names - {"setup_s"} <= {"ttft_p50_ms", "ttft_p90_ms", "tpot_p90_ms",
                                   "tpot_p50_ms", "out_tok_per_s"}
    assert not {"tpot_p90_ms", "tpot_p50_ms"} <= names


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    cells = {w["name"] for w in BENCH["workloads"]}
    moved_in = set(e2e[metric["moves"]].get("workloads", cells))
    assert set(metric.get("workloads", cells)) <= moved_in
    spec = load(data("layer_metrics", metric["name"] + ".json"))
    assert os.path.exists(data("layer_metrics", spec["reader"] + ".py"))
    for key in ("layer", "source", "unit", "moves"):
        assert spec[key] == metric[key], key
    if metric["name"].endswith("_roofline") or "_roofline." in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["source"].startswith("https://huggingface.co/")
    assert PATH.match(cfg["file"]) and cfg["file"].startswith("benchmarks/")
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    body = load(os.path.join(REPO, cfg["file"]))
    assert body["source"] == cfg["source"] and body["reduced"] == cfg["reduced"]
    for key in ("assumed", "precision", "serve", "deployment", "correct"):
        assert key in body, key
    for key in ("hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "vocab_size"):
        assert isinstance(body[key], int)
    from benchmarks import correctness

    limits = body["correct"]["limits"]
    assert set(limits) == set(correctness.NUMBERS)
    # every limit stands between its two readings, with room on both sides
    sound, controls = body["correct"]["sound"], body["correct"]["controls"]
    assert sound["seeds"] >= 12
    for name, limit in limits.items():
        assert 1.2 * sound[name][1] <= limit, name
        assert limit <= controls["w8a8"][name][0] / 1.2, name
        if name in controls["int4_weights"]:
            assert limit <= controls["int4_weights"][name][0] / 5, name
    assert sound["cache_bytes_per_token"] == correctness.cache_bytes_stated(
        body)
    assert body["serve"]["kv_block_tokens"] == 16
    assert body["precision"] == {"weights": "int8", "activations": "bfloat16",
                                 "kv_cache": "bfloat16"}
    assert not any(k.endswith(("_dim", "_rank")) for k in cfg["reduced"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = load(data("traffic", cell["traffic"] + ".json"))
    assert mix["loop"] in ("open", "closed") and mix["who"] and mix["why"]
    assert ("rate_rps" in mix) == (mix["loop"] == "open")
    reports = [m["name"] for m in BENCH["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in reports and len(reports) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in BENCH["per_layer"])


def test_chips_and_run_length():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells fits the 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_command_and_paths():
    assert BENCH["command"][-1] == "benchmarks/run.py"
    assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    for root in BENCH["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(REPO, root)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(dirpath, name), REPO)
                assert PATH.match(rel), rel


def test_every_percentile_reported_names_its_percentile():
    for m in BENCH["end_to_end"]:
        spec = load(data("end_to_end", m["name"] + ".json"))
        found = re.search(r"_p(\d+)_", m["name"])
        if found:
            assert spec["percentile"] == int(found.group(1))
            assert spec["percentile"] <= 90  # what 100+ requests support
