"""The state-space family's byte and FLOP counts against the share's sizes
worked out by hand, its readers on recorded numbers, and never over 100 % of
the peak for a step that takes what the chip must."""

import json
import os
import types

import pytest

from benchmarks import ssm_moe_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")
CELL = "nemotron-3-nano-30b-a3b.agents-closed"
NEW = ["decode_roofline.agents", "ssm_step_roofline.agents",
       "ssm_scan_roofline.agents", "moe_experts_roofline.agents",
       "ssm_dev_pct.agents"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_in 2688 x (4096 + 6144 + 64), W_out 4096 x 2688, the convolution's 4
# weights and a bias a channel
MAMBA = 2688 * 10304 + 4096 * 2688 + 5 * 6144
# W_q 2688 x 4096, W_k + W_v 2688 x 2 x 256, W_o 4096 x 2688
ATTENTION = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
EXPERT = 2 * 2688 * 1856
SHARED = 2 * 2688 * 3712
DENSE = (6 * MAMBA + 2 * ATTENTION + 5 * (2688 * 128 + SHARED)
         + 2688 * 65536)
STATE_ROW = 64 * 64 * 128 * 4 + 3 * 6144 * 2


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert (s["mamba_layers"], s["expert_layers"], s["attention_layers"]) \
        == (6, 5, 2)
    assert s["mamba"] == MAMBA == 38_737_920
    assert s["attention"] == ATTENTION == 23_396_352
    assert s["expert"] == EXPERT == 9_977_856
    assert s["shared"] == SHARED and s["router"] == 2688 * 128
    assert s["held"] == 64 and s["head"] == 2688 * 65536
    assert s["row_full"] == 2 * 2 * 128  # values a position and layer
    assert s["state_row_bytes"] == STATE_ROW == 2_134_016
    # the cut: 3,926 M parameters with the embedding and every held expert
    assert DENSE + 2688 * 65536 + 5 * 64 * EXPERT == pytest.approx(
        3.926e9, rel=1e-3)


def test_a_step_reads_and_writes_its_live_rows_state(config):
    none = roofline.decode_step_bytes(config, 0, 0, 0)
    assert none == 2 * DENSE
    every = roofline.decode_step_bytes(config, 0, 0, 5 * 64)
    assert every - none == 2 * EXPERT * 320
    # more than are held cannot be touched
    assert roofline.decode_step_bytes(config, 0, 0, 1000) == every
    # a position x layer: 2 KV heads x (128 + 128) values in bfloat16
    assert roofline.decode_step_bytes(config, 0, 1000, 0) - none == 1_024_000
    # a live row: its state of 6 layers in and out
    assert roofline.decode_step_bytes(config, 1, 0, 0) - none \
        == 2 * 6 * STATE_ROW == roofline.state_bytes(config, 1)
    # the issue's step: 128 live rows at 1,000 positions, every held expert
    # touched: 10.8 GB by its count, 11.04 GB with the shared experts and
    # the cache rows counted in; 30 % the state's, 58 % the experts'
    step = roofline.decode_step_bytes(config, 128, 2 * 128 * 1000, 320)
    assert step == 2 * DENSE + 2 * EXPERT * 320 + 256_000 * 1024 \
        + 2 * 128 * 6 * STATE_ROW
    assert step == pytest.approx(11.04e9, rel=1e-3)
    assert roofline.state_bytes(config, 128) / step == pytest.approx(
        0.30, abs=0.01)
    assert 2 * EXPERT * 320 / step == pytest.approx(0.58, abs=0.01)


def test_the_least_step_is_bound_by_memory_at_128_rows(config):
    least = roofline.least_step_seconds(
        config, peaks(), 128, 2 * 128 * 1000, 320, 128 * 6 * 5 / 2)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(11.04e9 / 819e9, rel=1e-3)
    assert least["by_flops_s"] < 0.1 * least["by_bytes_s"]


def test_the_state_updates_and_the_scans_are_bound_by_bytes(config):
    one = roofline.state_step_least_seconds(config, peaks(), 128 * 8)
    assert one["seconds"] == one["by_bytes_s"] == pytest.approx(
        2 * 1024 * 6 * STATE_ROW / 819e9)
    assert one["by_flops_s"] == pytest.approx(
        5 * 64 * 64 * 128 * 6 * 1024 / 197e12)
    scan = roofline.scan_least_seconds(config, peaks(), 2, 1024)
    assert scan["seconds"] == scan["by_bytes_s"] > scan["by_flops_s"]
    # two rows' state in and out, and 1,024 positions' z, xBC, dt and y
    assert scan["by_bytes_s"] == pytest.approx(
        (2 * 2 * 6 * STATE_ROW + 1024 * 6 * (2 * 4096 + 6144 + 64) * 2)
        / 819e9)


def test_the_expert_products_are_two_and_bound_by_the_weights(config):
    held = 129 * 6 / 2
    one = roofline.experts_least_seconds(config, peaks(), 64, held)
    assert one["seconds"] == one["by_bytes_s"] > 3 * one["by_flops_s"]
    assert one["by_flops_s"] == pytest.approx(
        2 * EXPERT * held / peaks()["bf16_flops_per_s"])
    assert one["by_bytes_s"] == pytest.approx(
        (EXPERT * 64 * 2 + held * (2 * 2688 + 2 * 1856) * 2) / 819e9)


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def test_a_state_scope_is_the_innermost_named_one():
    reader = load_module(os.path.join(LM, "ssm_scope_share.py"))
    fixed = load_module(os.path.join(LM, "scope_share.py"))
    known = fixed.SCOPES + reader.SSM
    path = "jit(_decode_fn)/while/body/closed_call/ssm_step/mul:"
    assert reader.scope_of(path, known) == "ssm_step"
    assert reader.scope_of("jit(f)/ssm_proj/dot_general:", known) == "ssm_proj"
    assert reader.scope_of("jit(f)/ffn/moe_experts/dot:", known) \
        == "moe_experts"
    assert reader.scope_of("jit(restore)/state_write/scatter:", known) \
        == "state_write"
    assert reader.scope_of("jit(f)/transpose:", known) is None
    # the reader with the fixed list knows none of them
    assert fixed.scope_of(path) is None


def test_the_reader_counts_only_records_that_carry_the_state(config):
    reader = load_module(os.path.join(LM, "ssm_moe_roofline_share.py"))
    counted = {"moe_held": 300, "moe_experts_touched": 320,
               "kv_rows_full": 9000, "state_rows": 1024, "steps": 8}
    ctx = ledger_ctx(config, [
        ("engine.decode_burst", 11.0, counted),
        ("engine.decode_burst", 12.0, {"steps": 8, "moe_held": 1}),
        ("engine.pool_copy", 12.5, counted)])
    assert list(reader._records_by_seq(ctx)) == [0]
    with pytest.raises(ValueError):
        reader.read(ctx, "no-such")


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_find_nothing_on_a_run_without_a_trace(config, name):
    """What the parent's traced run and a CPU rehearsal give them: no device
    planes, no counts on the records: nothing to read, nothing raised."""
    with open(os.path.join(LM, name + ".json")) as f:
        spec = json.load(f)
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
    mine = sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", [])
                  and m["layer"] != "start-up")
    assert mine == sorted([
        "decode_fill_pct.closed", "decode_step_ctr_dev_ms.closed",
        "kv_move_dev_pct.closed", "moe_dev_pct.context",
        "moe_held_share_pct.context", "moe_imbalance.context"] + NEW)
    # each new one is an entry and reads in this cell alone; where it lies
    # in the list is no test's to say: the next cell's metrics go after the
    # last (test_bm_room_for_a_cell.py holds that against this file too)
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in NEW] == [[CELL]] * len(NEW)
    assert [w["chips"] for w in bench["workloads"] if w["name"] == CELL] \
        == [1]
    # the generic routed-layer reader finds the held experts under the key
    # sarvam's file uses
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        assert json.load(f)["num_experts"] == 64
