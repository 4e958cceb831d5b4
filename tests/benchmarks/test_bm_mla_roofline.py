"""The latent-attention, routed-expert family's byte and FLOP counts against
the share's sizes worked out by hand, its readers on recorded numbers, and
never over 100 % of the peak for a step that takes what the chip must."""

import json
import os
import types

import pytest

from benchmarks import mla_moe_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sarvam-105b.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_q 4096 x 64 x 192, W_kva 4096 x 576, W_kvb 512 x 64 x 256, W_o 8192 x 4096
ATTENTION = 4096 * 12288 + 4096 * 576 + 512 * 16384 + 8192 * 4096
EXPERT = 3 * 4096 * 2048


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert s["attention"] == ATTENTION == 94_633_984
    assert s["expert"] == s["shared"] == EXPERT == 25_165_824
    assert s["dense_ffn"] == 3 * 4096 * 16384
    assert s["router"] == 4096 * 128  # the published width, whatever is held
    assert (s["layers"], s["dense_layers"], s["expert_layers"]) == (6, 1, 5)
    assert s["held"] == 32 and s["head"] == 4096 * 65536
    assert roofline.cache_bytes_per_token(config) == 6912


def test_a_step_reads_only_the_experts_it_touched(config):
    none = roofline.decode_step_bytes(config, 0, 0)
    # attention of 6 layers, the dense feed-forward, 5 routers and shared
    # experts, the head: everything but routed experts and cache, bfloat16
    assert none == 2 * (6 * ATTENTION + 3 * 4096 * 16384
                        + 5 * (4096 * 128 + EXPERT) + 4096 * 65536)
    every = roofline.decode_step_bytes(config, 0, 5 * 32)
    assert every - none == 2 * EXPERT * 160
    # more than are held cannot be touched
    assert roofline.decode_step_bytes(config, 0, 1000) == every
    assert (roofline.decode_step_bytes(config, 1000, 0) - none) == 6_912_000
    assert none == 2_332_033_024
    # 87 % of the held experts touched is 7.0 GB of experts; with 48,000
    # live tokens the step reads 9.67 GB (the issue's 8.6 GB left the head
    # and the dense layer out)
    step = roofline.decode_step_bytes(config, 32 * 1500, 0.87 * 160)
    assert step == pytest.approx(9.669e9, rel=1e-3)


def test_the_least_step_is_bound_by_memory_at_32_rows(config):
    least = roofline.least_step_seconds(config, peaks(), 32, 32 * 1500,
                                        0.87 * 160, 32 * 8 * 5 / 4)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(9.669e9 / 819e9, rel=1e-3)
    assert least["by_flops_s"] < 0.1 * least["by_bytes_s"]


def test_the_expert_products_turn_compute_bound_only_past_4k_positions(config):
    """A dispatch that touches every held expert reads 8 GB of them (10 ms);
    a quarter of 8 assignments a token held here, 1,024 positions are 0.5
    TFLOP (2.6 ms): bound by the weights, as a decode step is."""
    held = 1024 * 8 * 5 / 4
    one = roofline.experts_least_seconds(config, peaks(), 160, held)
    assert one["seconds"] == one["by_bytes_s"] > 3 * one["by_flops_s"]
    assert one["by_flops_s"] == pytest.approx(
        2 * EXPERT * held / peaks()["bf16_flops_per_s"])
    wide = roofline.experts_least_seconds(config, peaks(), 160, 8 * held)
    assert wide["seconds"] == wide["by_flops_s"] > wide["by_bytes_s"]


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def counts(made, held, fullest, touched):
    return {"moe_assignments": made, "moe_held": held,
            "moe_expert_tokens_max": fullest, "moe_experts_touched": touched}


def test_the_ledger_reader_sums_the_windows_records(config):
    reader = load_module(os.path.join(LM, "moe_ledger.py"))
    ctx = ledger_ctx(config, [
        ("engine.decode_burst", 11.0, counts(1280, 320, 40, 120)),
        ("engine.prefill_segment", 12.0, counts(40960, 10240, 800, 160)),
        # before the window, and one without counts (a dense model's)
        ("engine.decode_burst", 9.0, counts(10**6, 10**6, 10**6, 1)),
        ("engine.decode_burst", 13.0, {"steps": 8}),
    ])
    assert reader.read(ctx, "held_share") == pytest.approx(25.0)
    # fullest 840 over (10560 held / 32 experts)
    assert reader.read(ctx, "imbalance") == pytest.approx(840 * 32 / 10560)
    assert reader.read(ledger_ctx(config, []), "held_share") is None
    with pytest.raises(ValueError):
        reader.read(ctx, "no-such")


def test_scopes_are_the_innermost_named_one():
    reader = load_module(os.path.join(LM, "scope_share.py"))
    assert reader.scope_of("jit(_decode_fn)/while/body/ffn/moe_experts/"
                           "ragged_dot:") == "moe_experts"
    assert reader.scope_of("jit(f)/while/body/mla_up/dot_general:") == "mla_up"
    assert reader.scope_of("jit(f)/ffn/moe_shared/dot_general:") == "moe_shared"
    assert reader.scope_of("jit(f)/attn/dot_general:") == "attn"
    assert reader.scope_of("jit(f)/transpose:") is None
    assert reader.scope_of(None) is None
    assert reader.kernel_scope("%ragged-dot-none.2") == "moe_experts"
    assert reader.kernel_scope("fusion.12") is None


@pytest.mark.parametrize("name", [
    "moe_dev_pct.context", "mla_attn_dev_pct.context",
    "moe_held_share_pct.context", "moe_imbalance.context",
    "decode_roofline.context", "moe_experts_roofline.context"])
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
