"""Laguna-S-2.1's byte and FLOP counts against the share's sizes worked out
by hand (the configuration's arithmetic), its reader on recorded numbers,
and what the cell's entries in BENCHMARK.json are."""

import json
import os
import types

import pytest

from benchmarks import laguna_moe_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")
CELL = "laguna-s-2.1.codeturns-closed"
NEW = ["decode_roofline.codeturns", "moe_experts_roofline.codeturns",
       "window_attn_roofline.codeturns", "window_attn_dev_pct.codeturns",
       "full_attn_dev_pct.codeturns"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_q and W_o 3072 x H x 128 each, W_k and W_v 3072 x 8 x 128 each, W_g 3072
# x H: 48 heads in a full layer, 72 in a window layer
FULL = 2 * 3072 * 48 * 128 + 2 * 3072 * 1024 + 3072 * 48
WINDOW = 2 * 3072 * 72 * 128 + 2 * 3072 * 1024 + 3072 * 72
DENSE = 3 * 3072 * 12288
EXPERT = 3 * 3072 * 1024
ROUTER = 3072 * 256
HEAD = 3072 * 12544
# what a step reads whatever it routes: attention, the dense layer, seven
# routers and shared experts, the head
REST = 2 * FULL + 6 * WINDOW + DENSE + 7 * (ROUTER + EXPERT) + HEAD


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert (FULL, WINDOW) == (44_187_648, 63_135_744)  # 44.18 M, 63.13 M
    assert (s["attention_full"], s["attention_window"]) == (
        2 * FULL, 6 * WINDOW)
    assert s["dense_ffn"] == DENSE == 113_246_208
    assert s["expert"] == s["shared"] == EXPERT == 9_437_184
    assert s["router"] == ROUTER == 786_432  # the published width
    assert (s["full_layers"], s["window_layers"], s["dense_layers"],
            s["expert_layers"]) == (2, 6, 1, 7)
    assert s["held"] == 32 and s["head"] == HEAD
    # a routed layer: 32 experts, the shared one and the router: 312.2 M
    assert 33 * EXPERT + ROUTER == pytest.approx(312.2e6, rel=1e-3)
    # a cached position: 8 KV heads x (128 + 128) values, 4,096 B
    assert s["row"] == 2048
    assert (s["per_position_full"], s["per_position_window"]) == (
        48 * 256, 72 * 256)
    # the cut: 2,843 M parameters = 5.69 GB in bfloat16
    assert roofline.parameters(config) == REST + HEAD + 7 * 32 * EXPERT
    assert roofline.parameters(config) == pytest.approx(2.843e9, rel=1e-3)
    # a slot: full planes 2 x 6,144 x 4,096 B, rings 6 x 1,024 x 4,096 B;
    # 64 slots 4.83 GB; the pool's 2,048 blocks of 16 tokens x 32,768 B
    assert 2 * 6144 * 4096 == 50_331_648 and 6 * 1024 * 4096 == 25_165_824
    assert 64 * (50_331_648 + 25_165_824) == pytest.approx(4.83e9, rel=1e-3)
    assert 2048 * 16 * 8 * 4096 == pytest.approx(1.07e9, rel=5e-3)
    # weights, slots and pool: 11.6 GB of 16
    assert (2 * roofline.parameters(config) + 64 * 75_497_472
            + 2048 * 16 * 32768) == pytest.approx(11.6e9, rel=5e-3)


def test_a_step_reads_the_rows_its_attention_needs_and_the_experts_it_touched(
        config):
    none = roofline.decode_step_bytes(config, 0, 0, 0)
    assert none == 2 * REST
    every = roofline.decode_step_bytes(config, 0, 0, 7 * 32)
    assert every - none == 2 * EXPERT * 224
    # more than are held cannot be touched
    assert roofline.decode_step_bytes(config, 0, 0, 1000) == every
    # positions x layers: 4,096 B in either kind
    assert roofline.decode_step_bytes(config, 1000, 0, 0) - none == 4_096_000
    assert roofline.decode_step_bytes(config, 0, 1000, 0) - none == 4_096_000
    # the issue's step: 64 live rows at a mean context near 1.5k; 80
    # assignments a layer over 32 held touch 29-30: 3.9 GB of routed
    # experts, 0.93 GB of attention weights, 0.8 GB of full planes, 0.8 GB
    # of rings by need (512 positions of a full window), 0.44 GB of dense
    # layer, shared experts, routers and head
    touched = 7 * 29.5
    full, window = 2 * 64 * 1536, 6 * 64 * 512
    step = roofline.decode_step_bytes(config, full, window, touched)
    assert 2 * EXPERT * touched == pytest.approx(3.9e9, rel=1e-2)
    assert 2 * (2 * FULL + 6 * WINDOW) == pytest.approx(0.934e9, rel=1e-2)
    assert full * 4096 == pytest.approx(0.805e9, rel=1e-2)
    assert window * 4096 == pytest.approx(0.805e9, rel=1e-2)
    assert 2 * (DENSE + 7 * (ROUTER + EXPERT) + HEAD) == pytest.approx(
        0.447e9, rel=1e-2)
    assert step == pytest.approx(6.89e9, rel=1e-2)
    # (with the whole ring read, 1,024 positions, as the program does today:
    # 7.7 GB, 9.4 ms at 819 GB/s)
    assert (step + window * 4096) / 819e9 == pytest.approx(9.4e-3, rel=1e-2)


def test_the_least_step_is_bound_by_memory_at_64_rows(config):
    least = roofline.least_step_seconds(
        config, peaks(), 64, 2 * 64 * 1536, 6 * 64 * 512, 7 * 29.5,
        7 * 64 * 10 / 8)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(6.89e9 / 819e9, rel=1e-2)
    assert least["by_flops_s"] == pytest.approx(
        (2 * REST * 64 + 2 * (48 * 256 * 2 * 64 * 1536
                              + 72 * 256 * 6 * 64 * 512)
         + 2 * EXPERT * 560) / 197e12)
    assert least["by_flops_s"] < 0.1 * least["by_bytes_s"]


def test_the_expert_products_are_bound_by_the_weights_they_read(config):
    held = 1024 * 10 * 7 / 8  # two rows of 512 positions, an eighth held
    one = roofline.experts_least_seconds(config, peaks(), 224, held)
    assert one["seconds"] == one["by_bytes_s"] > 3 * one["by_flops_s"]
    assert one["by_bytes_s"] == pytest.approx(
        (2 * EXPERT * 224 + held * (2 * 3072 + 4 * 1024) * 2) / 819e9)
    assert one["by_flops_s"] == pytest.approx(2 * EXPERT * held / 197e12)


def test_the_window_layers_attention_is_the_positions_by_need_and_the_gates(
        config):
    # 8 steps of 64 rows, every row past a full window: 6 layers x 512
    rows = 8 * 6 * 64 * 512
    one = roofline.window_attention_least_seconds(config, peaks(), 8, rows)
    assert one["seconds"] == one["by_bytes_s"] > one["by_flops_s"]
    assert one["by_bytes_s"] == pytest.approx(
        (rows * 4096 + 8 * 6 * 3072 * 72 * 2) / 819e9)
    assert one["by_flops_s"] == pytest.approx(
        2 * 72 * 256 * rows / 197e12)
    # against a whole ring of 1,024 read at the memory's peak it is a half
    ring = 8 * 6 * 64 * 1024 * 4096 / 819e9
    assert one["seconds"] / ring == pytest.approx(0.5, abs=0.01)


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def test_the_reader_asks_its_records_for_rows_and_experts(config):
    reader = load_module(os.path.join(LM, "laguna_moe_roofline_share.py"))
    assert reader.KEYS == ("moe_held", "moe_experts_touched", "kv_rows_full",
                           "kv_rows_window")
    assert set(reader.QUANTITIES) == {"decode_step", "experts", "window_attn"}
    with pytest.raises(ValueError):
        reader.read(ledger_ctx(config, []), "no-such")
    # operations that start inside a decode run are its own; a loop counts
    # without what runs nested inside it
    ops = [("unscoped", 0.0, 10.0), ("attn_window", 1.0, 3.0),
           ("attn", 3.0, 4.0), ("attn_window", 4.0, 4.5),
           ("attn_window", 12.0, 13.0), ("moe_experts", 20.0, 22.0)]
    own = reader.self_time_within(ops, [(0.0, 11.0), (19.0, 23.0)])
    assert own == pytest.approx({"unscoped": 6.5, "attn_window": 2.5,
                                 "attn": 1.0, "moe_experts": 2.0})
    # the gate inside a kind's scope is that kind's
    kinds = load_module(os.path.join(LM, "kind_scope_share.py"))
    known = load_module(os.path.join(LM, "scope_share.py")).SCOPES \
        + kinds.KINDS
    assert kinds.scope_of(
        "jit(_decode_fn)/while/body/attn/attn_window/attn_gate/mul:",
        known) == "attn_window"
    assert kinds.scope_of("jit(f)/attn/attn_full/attn_gate/logistic:",
                          known) == "attn_full"
    assert kinds.scope_of("jit(f)/ffn/moe_shared/dot_general:",
                          known) == "moe_shared"


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_find_nothing_on_a_run_without_a_trace(config, name):
    """What a CPU rehearsal gives them (the parent cannot run the cell at
    all): no device planes: nothing to read, nothing raised."""
    with open(os.path.join(LM, name + ".json")) as f:
        spec = json.load(f)
    assert spec["moves"] == "out_tok_per_s" and spec["unit"] == "%"
    assert "counters" not in spec
    reader = load_module(os.path.join(LM, spec["reader"] + ".py"))
    ctx = ledger_ctx(config, [("engine.decode_burst", 11.0, {"steps": 8})])
    ctx.cell, ctx.peaks, ctx.trace_span = "no-such-cell.rehearsal", None, None
    assert reader.read(ctx, **spec.get("args", {})) is None
    ctx.peaks, ctx.trace_span = peaks(), (0.0, 1.0)
    assert reader.read(ctx, **spec.get("args", {})) is None


def test_the_cells_metrics_are_the_ones_the_issue_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # another layer's metrics (start-up's list every cell) are not this
    # issue's to name: told by the entry's layer, not by their names
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", []) and m["layer"] != "start-up"}
    assert mine == set(NEW) | {
        "decode_fill_pct.closed", "decode_step_ctr_dev_ms.closed",
        "kv_move_dev_pct.closed", "moe_dev_pct.context",
        "moe_held_share_pct.context", "moe_imbalance.context"}
    better = {"decode_roofline.codeturns": "higher",
              "moe_experts_roofline.codeturns": "higher",
              "window_attn_roofline.codeturns": "higher",
              "window_attn_dev_pct.codeturns": "lower",
              "full_attn_dev_pct.codeturns": "lower"}
    own = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"]: m["better"] for m in own} == better
    for m in own:
        assert m["workloads"] == [CELL] and m["layer"] == "model + attention"
        assert m["source"] == "device_trace" and m["unit"] == "%"
    for name in NEW:
        with open(os.path.join(LM, name + ".json")) as f:
            assert os.path.exists(
                os.path.join(LM, json.load(f)["reader"] + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "codeturns-closed")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/poolside/"
                               "Laguna-S-2.1/blob/main/config.json")
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "codeturns-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["requests_per_client"],
            mix["lead_s"], mix["tail_s"], mix["request_timeout_s"]) == (
        "closed", 64, 16, 10.0, 0.0, 90.0)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.9, "min": 128, "max": 5632}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 384}
    assert "shared_prefix" not in mix
    # the generic routed-layer reader finds the held experts under the
    # published key, which states what is held
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        assert json.load(f)["num_experts"] == 32
