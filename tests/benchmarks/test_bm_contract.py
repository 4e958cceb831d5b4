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


def is_a_width(key):
    """A key ``reduced`` may never name (the vocabulary may be sliced)."""
    return (key.endswith(("_dim", "_rank")) or key == "num_experts_per_tok"
            or (key.endswith("_size") and key != "vocab_size"))


def check_configuration(cfg, body, data_root):
    """What the benchmark asks of every configuration, whatever its model
    family: ``cfg`` is its entry in BENCHMARK.json, ``body`` its file,
    ``data_root`` the directory where its family's module is found."""
    from benchmarks import correctness

    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["source"].startswith("https://huggingface.co/")
    assert PATH.match(cfg["file"]) and cfg["file"].startswith("benchmarks/")
    assert body["source"] == cfg["source"] and body["reduced"] == cfg["reduced"]
    assert len(cfg["reduced"]) <= 16
    assert not any(is_a_width(k) for k in cfg["reduced"]), cfg["reduced"]
    for key in ("assumed", "precision", "serve", "deployment", "correct"):
        assert key in body, key
    # the published keys its own reference reads are whole numbers
    fam = correctness.family(body, data_root)
    correctness.check_published(body, fam)
    kv_block = body["serve"]["kv_block_tokens"]
    assert isinstance(kv_block, int) and kv_block > 0
    # every stated precision is of a type the harness knows the bytes of
    precision = body["precision"]
    assert set(precision) == {"weights", "activations", "kv_cache"}
    assert set(precision.values()) <= set(correctness.TYPE_BYTES)

    limits = body["correct"]["limits"]
    assert set(limits) == set(correctness.NUMBERS)
    sound, controls = body["correct"]["sound"], body["correct"]["controls"]
    assert sound["seeds"] >= 12
    stated = correctness.cache_bytes_stated(body, data_root)
    assert sound["cache_bytes_per_token"] == stated
    # every limit stands over the sound runs' largest, with room
    for name, limit in limits.items():
        assert 1.2 * sound[name][1] <= limit, name
    # ... and under the smallest reading of each control that fails on it
    lowered = set()
    for name, control in controls.items():
        to, was = control["to"], precision[control["lowers"]]
        assert correctness.TYPE_BYTES[to] < correctness.TYPE_BYTES[was], name
        assert control["fails"] and control["fails_on"], \
            f"the control {name} does not fail"
        # an integer grid of half the bits is 16 times coarser: it has to
        # fail by far
        room = 5 if (control["lowers"] == "weights"
                     and to.startswith("int") and was.startswith("int")
                     and 2 * correctness.TYPE_BYTES[to]
                     == correctness.TYPE_BYTES[was]) else 1.2
        for number in control["fails_on"]:
            if number == "cache_bytes_per_token":
                assert control[number] != stated, \
                    f"the control {name} does not fail on {number}"
            else:
                assert limits[number] <= control[number][0] / room, \
                    f"the control {name} does not fail on {number}"
        lowered.add(control["lowers"])
    assert lowered == set(precision), \
        f"no control lowers {sorted(set(precision) - lowered)}"


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations(cfg):
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    check_configuration(cfg, load(os.path.join(REPO, cfg["file"])),
                        data())


# ---- a configuration of another family, in a root of its own ----------------

#: The ``config`` of the catalog's ``sarvam-105b`` row, verbatim (source:
#: https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json), with
#: the KV-head count as the row's top level gives it, null: latent attention
#: has no KV heads to count.
MLA_PUBLISHED = load(os.path.join(REPO, "tests", "benchmarks", "data",
                                  "sarvam-105b.published.json"))

#: A family's module that states what a token caches and which keys it
#: reads, and no forward: enough for the contract, not for a run.
MLA_STUB = '''"""Latent attention and routed experts: the statement, no equations."""
from benchmarks.correctness import TYPE_BYTES

REQUIRED_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
                 "qk_nope_head_dim", "v_head_dim", "intermediate_size",
                 "moe_intermediate_size", "num_experts",
                 "num_experts_per_tok", "num_shared_experts",
                 "first_k_dense_replace")


def cache_bytes_per_token(config):
    """The normed latent and the one roped key all heads share."""
    return int(config["num_hidden_layers"]
               * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
               * TYPE_BYTES[config["precision"]["kv_cache"]])


def shapes_of(config):
    raise NotImplementedError


make_weights = forward_logprobs = shapes_of
'''


def mla_configuration(**over):
    numbers = dict.fromkeys(("echo_prompt", "echo_decode", "traffic_decode",
                             "traffic_prefill"), [0.004, 0.005])
    failing = dict.fromkeys(numbers, [0.010, 0.012])
    body = dict(
        MLA_PUBLISHED, name="mla", reference="mla_reference",
        source="https://huggingface.co/sarvamai/sarvam-105b/blob/main/"
               "config.json",
        reduced=["num_hidden_layers", "num_experts", "vocab_size"],
        assumed={}, deployment="a test",
        precision={"weights": "bfloat16", "activations": "bfloat16",
                   "kv_cache": "bfloat16"},
        serve={"model": "none", "max_seq": 4096, "kv_block_tokens": 16,
               "args": []},
        correct={
            "limits": dict.fromkeys(numbers, 0.0075),
            "sound": dict(numbers, seeds=12,
                          cache_bytes_per_token=32 * 576 * 2),
            "controls": {
                "int8_weights": dict(failing, lowers="weights", to="int8",
                                     fails=True, fails_on=list(numbers)),
                "int8_activations": dict(failing, lowers="activations",
                                         to="int8", fails=True,
                                         fails_on=list(numbers)),
                "int8_cache": dict(lowers="kv_cache", to="int8", fails=True,
                                   fails_on=["cache_bytes_per_token"],
                                   cache_bytes_per_token=32 * 592)}})
    body.update(over)
    cfg = {"name": "mla", "source": body["source"],
           "file": "benchmarks/configs/mla.json",
           "reduced": body["reduced"], "why": "a test"}
    return cfg, body


def quiet_control(body, **change):
    """The configuration with its activations control changed."""
    controls = dict(body["correct"]["controls"])
    controls["int8_activations"] = dict(controls["int8_activations"],
                                        **change)
    return dict(body["correct"], controls=controls)


@pytest.fixture()
def family_root(tmp_path):
    """A data root with the dense family's module, as the repository has
    it, and the stub's beside it."""
    import shutil

    shutil.copy(data("reference.py"), tmp_path)
    (tmp_path / "mla_reference.py").write_text(MLA_STUB)
    return str(tmp_path)


def dense_keys_left_null():
    """What the dense family's reference needs and the published row does
    not give."""
    from benchmarks import reference

    return [k for k in reference.REQUIRED_KEYS if MLA_PUBLISHED[k] is None]


def test_a_configuration_without_kv_heads_is_accepted_with_its_own_family(
        family_root):
    cfg, body = mla_configuration()
    assert len(dense_keys_left_null()) == 1
    check_configuration(cfg, body, family_root)


REFUSED = {
    "no-family-of-its-own": (
        lambda body: dict(body, reference="reference"),
        lambda: dense_keys_left_null()[0] + " is None"),
    "a-family-that-is-no-file": (
        lambda body: dict(body, reference="no_such_family"),
        "there is no "),
    "a-family-named-by-a-path": (
        lambda body: dict(body, reference="../reference"),
        "named by a module's name"),
    "a-required-key-is-no-whole-number": (
        lambda body: dict(body, kv_lora_rank=512.5), "kv_lora_rank is 512.5"),
    "a-control-that-does-not-fail": (
        lambda body: dict(body, correct=quiet_control(body, fails=False)),
        "int8_activations does not fail"),
    "a-control-that-reads-inside-a-limit": (
        lambda body: dict(body, correct=quiet_control(
            body, echo_decode=[0.0080, 0.012])),
        "does not fail on echo_decode"),
    "a-precision-no-control-lowers": (
        lambda body: dict(body, correct=dict(
            body["correct"], controls={
                k: v for k, v in body["correct"]["controls"].items()
                if k != "int8_cache"})),
        "no control lowers ['kv_cache']"),
    "a-cache-control-of-the-stated-width": (
        lambda body: dict(body, correct=dict(
            body["correct"], controls=dict(
                body["correct"]["controls"], int8_cache=dict(
                    body["correct"]["controls"]["int8_cache"],
                    cache_bytes_per_token=32 * 576 * 2)))),
        "does not fail on cache_bytes_per_token"),
    "a-type-of-unknown-width": (
        lambda body: dict(body, precision=dict(body["precision"],
                                               kv_cache="fp6")), "fp6"),
    "a-width-in-reduced": (
        lambda body: dict(body, reduced=["kv_lora_rank"]), "kv_lora_rank"),
    "an-expert-width-in-reduced": (
        lambda body: dict(body, reduced=["moe_intermediate_size"]),
        "moe_intermediate_size"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_configuration_is_refused(case, family_root):
    change, message = REFUSED[case]
    cfg, body = mla_configuration()
    body = change(body)
    cfg = dict(cfg, reduced=body["reduced"])
    from benchmarks.stack import BenchFailure

    with pytest.raises((AssertionError, BenchFailure, KeyError)) as e:
        check_configuration(cfg, body, family_root)
    assert (message() if callable(message) else message) in str(e.value)


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


# ---- window counters are data ------------------------------------------------

@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_an_accepted_cell_reads_the_four_counters_it_always_read(cell):
    from benchmarks import run

    per_layer = run.metrics_of(BENCH, "per_layer", cell["name"])
    assert per_layer
    assert run.counters_of(data(), per_layer) == run.WINDOW_COUNTERS
    assert len(run.WINDOW_COUNTERS) == 4


METRICS_TEXT = """# HELP engine_tokens_total tokens
engine_cold_compiles_total 0
engine_prefix_hit_tokens_total 12
engine_prefill_tokens_total 3400
engine_tokens_total 560
engine_decode_row_steps_total 1700
"""


@pytest.mark.parametrize("named,read", [
    ([], {}),
    (["engine_decode_row_steps_total"],
     {"engine_decode_row_steps_total": 1700.0}),
    # one the program does not publish is left out, not an error
    (["engine_decode_row_steps_total", "engine_no_such_total"],
     {"engine_decode_row_steps_total": 1700.0}),
    # one of the four named again is read once
    (["engine_tokens_total"], {}),
], ids=["none", "one", "unpublished", "one-of-the-four"])
def test_a_metrics_file_names_the_counters_it_reads(tmp_path, named, read):
    from benchmarks import run

    metrics = tmp_path / "layer_metrics"
    metrics.mkdir()
    (metrics / "plain.json").write_text(json.dumps({"reader": "x"}))
    (metrics / "counting.json").write_text(
        json.dumps({"reader": "x", "counters": named}))
    names = run.counters_of(str(tmp_path),
                            [{"name": "plain"}, {"name": "counting"}])
    assert names[:4] == run.WINDOW_COUNTERS
    assert len(names) == len(set(names))
    assert set(names) == set(run.WINDOW_COUNTERS) | set(named)
    got = run.read_counters(METRICS_TEXT, names)
    assert got == dict({"engine_cold_compiles_total": 0.0,
                        "engine_prefix_hit_tokens_total": 12.0,
                        "engine_prefill_tokens_total": 3400.0,
                        "engine_tokens_total": 560.0}, **read)


def test_one_of_the_four_counters_missing_fails_the_run():
    from benchmarks import run
    from benchmarks.stack import BenchFailure

    text = METRICS_TEXT.replace("engine_cold_compiles_total 0\n", "")
    with pytest.raises(BenchFailure, match="engine_cold_compiles_total"):
        run.read_counters(text, run.WINDOW_COUNTERS)
