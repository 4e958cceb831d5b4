"""The window-and-full family's byte and FLOP counts against the share's
sizes worked out by hand, its readers on recorded numbers, and never over
100 % of the peak for a step that takes what the chip must."""

import json
import os
import types

import pytest

from benchmarks import swa_moe_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")
CELL = "mimo-v2-flash.longmix-closed"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo-v2-flash.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_q 4096 x 64 x 192, W_k + W_v 4096 x K x (192 + 128), W_o 8192 x 4096
FULL = 4096 * 12288 + 4096 * 4 * 320 + 8192 * 4096
WINDOW = 4096 * 12288 + 4096 * 8 * 320 + 8192 * 4096
EXPERT = 3 * 4096 * 2048
REST = (2 * FULL + 5 * WINDOW + 3 * 4096 * 16384 + 6 * 4096 * 256
        + 4096 * 19072)


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert (FULL, WINDOW) == (89_128_960, 94_371_840)
    assert s["attention"] == 2 * FULL + 5 * WINDOW
    assert s["expert"] == EXPERT == 25_165_824
    assert s["dense_ffn"] == 3 * 4096 * 16384
    assert s["router"] == 4096 * 256  # the published width, whatever is held
    assert (s["full_layers"], s["window_layers"], s["dense_layers"],
            s["expert_layers"]) == (2, 5, 1, 6)
    assert s["held"] == 16 and s["head"] == 4096 * 19072
    # a cached position: the KV heads of the layer's kind x (192 + 128)
    assert (s["row_full"], s["row_window"]) == (1280, 2560)
    # the cut: 3,430 M parameters with the embedding and every held expert
    assert REST + 4096 * 19072 + 96 * EXPERT == pytest.approx(3.430e9, rel=1e-3)


def test_a_step_reads_the_rows_its_attention_needs_and_the_experts_it_touched(
        config):
    none = roofline.decode_step_bytes(config, 0, 0, 0)
    assert none == 2 * REST
    every = roofline.decode_step_bytes(config, 0, 0, 6 * 16)
    assert every - none == 2 * EXPERT * 96
    # more than are held cannot be touched
    assert roofline.decode_step_bytes(config, 0, 0, 1000) == every
    # positions x layers, by kind: 2,560 B in a full layer, 5,120 in a window
    assert roofline.decode_step_bytes(config, 1000, 0, 0) - none == 2_560_000
    assert roofline.decode_step_bytes(config, 0, 1000, 0) - none == 5_120_000
    # 48 rows at 2,000 positions: full layers read 2 x 96,000 positions,
    # window layers 5 x 48 x 128; every held expert touched: 7.35 GB (1.87
    # GB of attention, dense layer, routers and head, 4.83 GB of experts,
    # 0.65 GB of cache)
    step = roofline.decode_step_bytes(config, 2 * 96000, 5 * 48 * 128, 96)
    assert step == pytest.approx(2 * REST + 2 * EXPERT * 96 + 491_520_000
                                 + 157_286_400)
    assert step == pytest.approx(7.352e9, rel=1e-3)


def test_the_least_step_is_bound_by_memory_at_48_rows(config):
    least = roofline.least_step_seconds(
        config, peaks(), 48, 2 * 96000, 5 * 48 * 128, 96, 48 * 8 * 6 / 16)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(7.352e9 / 819e9, rel=1e-3)
    assert least["by_flops_s"] < 0.1 * least["by_bytes_s"]


def test_the_expert_products_are_bound_by_the_weights_they_read(config):
    held = 1024 * 8 * 6 / 16  # two rows of 512 positions, a sixteenth held
    one = roofline.experts_least_seconds(config, peaks(), 96, held)
    assert one["seconds"] == one["by_bytes_s"] > 3 * one["by_flops_s"]
    assert one["by_flops_s"] == pytest.approx(
        2 * EXPERT * held / peaks()["bf16_flops_per_s"])


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def test_the_rows_reader_sums_the_windows_decode_records(config):
    reader = load_module(os.path.join(LM, "kv_rows_ledger.py"))
    ctx = ledger_ctx(config, [
        ("engine.decode_burst", 11.0,
         {"kv_rows_full": 9000, "kv_rows_window": 600}),
        ("engine.decode_burst", 12.0,
         {"kv_rows_full": 400, "kv_rows_window": 0}),
        # a prefill record, one before the window, one without counts
        ("engine.prefill_segment", 12.5,
         {"kv_rows_full": 10**6, "kv_rows_window": 10**6}),
        ("engine.decode_burst", 9.0,
         {"kv_rows_full": 10**6, "kv_rows_window": 1}),
        ("engine.decode_burst", 13.0, {"steps": 8}),
    ])
    assert reader.read(ctx, "window_share") == pytest.approx(6.0)
    assert reader.read(ledger_ctx(config, []), "window_share") is None
    with pytest.raises(ValueError):
        reader.read(ctx, "no-such")


def test_a_kinds_scope_is_the_innermost_named_one():
    reader = load_module(os.path.join(LM, "kind_scope_share.py"))
    known = load_module(os.path.join(LM, "scope_share.py")).SCOPES \
        + reader.KINDS
    path = "jit(_decode_fn)/while/body/closed_call/attn/attn_window/dot:"
    assert reader.scope_of(path, known) == "attn_window"
    assert reader.scope_of("jit(f)/attn/attn_full/exp:", known) == "attn_full"
    assert reader.scope_of("jit(f)/attn/dot_general:", known) == "attn"
    assert reader.scope_of("jit(f)/transpose:", known) is None
    # the reader with the fixed list takes the same operations for attn's
    assert load_module(os.path.join(LM, "scope_share.py")).scope_of(path) \
        == "attn"


@pytest.mark.parametrize("name", [
    "window_attn_dev_pct.longmix", "full_attn_dev_pct.longmix",
    "kv_rows_window_share_pct.longmix", "decode_roofline.longmix",
    "moe_experts_roofline.longmix"])
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
        "moe_held_share_pct.context", "moe_imbalance.context",
        "window_attn_dev_pct.longmix", "full_attn_dev_pct.longmix",
        "kv_rows_window_share_pct.longmix", "decode_roofline.longmix",
        "moe_experts_roofline.longmix"])
    # the generic routed-layer reader finds the held experts under the key
    # sarvam's file uses
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo-v2-flash.json")) as f:
        body = json.load(f)
    assert body["num_experts"] == body["n_routed_experts"] == 16
