"""The block-generation family's byte and FLOP counts against the
configuration's sizes worked out by hand, its readers on recorded numbers,
and never over 100 % of the peak for a pass that takes what the chip must."""

import json
import os
import types

import pytest

from benchmarks import block_diffusion_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")
CELL = "sdar-30b-a3b.blockgen-closed"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_q 2048 x 32 x 128, W_k + W_v 2 x 2048 x 4 x 128, W_o 4096 x 2048
ATTENTION = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
ROUTER = 2048 * 128
EXPERT = 3 * 2048 * 768
HEAD = 2048 * 151936
REST = 7 * (ATTENTION + ROUTER) + HEAD


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert ATTENTION == 18_874_368 and EXPERT == 4_718_592
    assert (s["attention"], s["router"], s["expert"], s["head"]) == (
        ATTENTION, ROUTER, EXPERT, HEAD)
    assert (s["layers"], s["experts"], s["block"], s["group"]) == (
        7, 128, 4, 2)
    # a cached position: 4 KV heads x (128 + 128) values = 2,048 B
    assert s["kv_row"] == 1024 and 7 * s["kv_row"] * 2 == 14336
    # the cut: 4,984 M parameters with the embedding and every expert
    assert 7 * (ATTENTION + ROUTER + 128 * EXPERT) + 2 * HEAD \
        == pytest.approx(4.984e9, rel=1e-3)


def test_a_pass_reads_the_prefix_once_a_block_and_the_experts_it_touched(
        config):
    none = roofline.pass_bytes(config, 0, 0, 0)
    assert none == 2 * REST
    every = roofline.pass_bytes(config, 0, 7 * 128, 0)
    assert every - none == 2 * EXPERT * 896
    assert roofline.pass_bytes(config, 0, 10**6, 0) == every  # no more than are
    # the program counts positions x layers SEEN by 4 queries a block: the
    # prefix is read once for the four, 2,048 B a position and layer
    assert roofline.pass_bytes(config, 4000, 0, 0) - none == 1000 * 2048
    # a row that commits a block writes 4 rows in each of 7 layers, in a
    # pass of its own (the schedule before PR 48) or on the way of a
    # denoise pass (``row_commits_fused``, since): the same 57,344 B
    assert roofline.pass_bytes(config, 0, 0, 3) - none == 3 * 4 * 7 * 2048
    assert roofline.pass_bytes(config, 0, 0, 1) - none == 57_344
    # 48 rows at a base of 600: each pass's 4 queries see 604 positions in 7
    # layers; every expert touched; a third of the rows commit: 9.76 GB
    # (0.89 GB of attention, routers and head, 8.46 GB of experts, 0.42 GB
    # of cache)
    seen = 48 * 4 * 604 * 7
    one = roofline.pass_bytes(config, seen, 896, 16)
    assert one == pytest.approx(2 * REST + 2 * EXPERT * 896
                                + 48 * 604 * 7 * 2048 + 16 * 28 * 2048)
    assert one == pytest.approx(9.76e9, rel=1e-2)


def test_the_least_pass_is_bound_by_memory_at_48_rows(config):
    seen = 48 * 4 * 604 * 7
    least = roofline.least_pass_seconds(
        config, peaks(), 48, seen, 896, 48 * 4 * 8 * 7, 16)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(
        roofline.pass_bytes(config, seen, 896, 16) / 819e9)
    assert 0.0115 < least["seconds"] < 0.0125
    flops = roofline.pass_flops(config, 48, seen, 48 * 4 * 8 * 7)
    # 192 positions through 7 layers' attention and router, 96 through the
    # head, 10,752 assignments, and the scores
    assert flops == pytest.approx(
        2 * 7 * (ATTENTION + ROUTER) * 192 + 2 * HEAD * 96
        + 2 * EXPERT * 10752 + 2 * 2 * 32 * 128 * seen)
    assert least["by_flops_s"] < 0.15 * least["by_bytes_s"]


def test_a_pass_counts_the_block_before_where_a_row_carries_one(config):
    """Since PR 48 a row's pass forwards the block that awaits its commit
    beside the current one and writes its K/V on the way.  48 rows, 24 of
    them with a block behind (the first pass on each block but a row's
    first: half the row-passes): 288 positions through the attention
    weights and routers where 192 went, 16,128 assignments where 10,752,
    the head's 96 and the current blocks' scores as before; and 24 blocks'
    writes, 1.4 MB, beside 9.75 GB read."""
    seen = 48 * 4 * 604 * 7
    without = roofline.pass_flops(config, 48, seen, 48 * 4 * 8 * 7)
    assert roofline.pass_flops(config, 48, seen, 48 * 4 * 8 * 7, 0) == without
    held = (48 + 24) * 4 * 8 * 7
    with_pending = roofline.pass_flops(config, 48, seen, held, 24)
    assert held == 16_128
    assert with_pending == pytest.approx(
        2 * 7 * (ATTENTION + ROUTER) * 288 + 2 * HEAD * 96
        + 2 * EXPERT * 16_128 + 2 * 2 * 32 * 128 * seen)
    assert with_pending - without == pytest.approx(
        2 * 7 * (ATTENTION + ROUTER) * 96 + 2 * EXPERT * 5_376)
    # no row with a block behind: the pass of before, to the operation
    alone = roofline.least_pass_seconds(
        config, peaks(), 48, seen, 896, 48 * 4 * 8 * 7, 0)
    fused = roofline.least_pass_seconds(
        config, peaks(), 48, seen, 896, held, 24, 24)
    assert alone["by_flops_s"] == pytest.approx(without / 197e12)
    assert fused["by_flops_s"] == pytest.approx(with_pending / 197e12)
    assert (fused["by_bytes_s"] - alone["by_bytes_s"]) * 819e9 \
        == pytest.approx(24 * 4 * 7 * 2048) == pytest.approx(1_376_256)
    # still bound by memory, the arithmetic under a fifth of it
    assert fused["bound"] == "memory"
    assert fused["by_flops_s"] < 0.2 * fused["by_bytes_s"]
    assert fused["seconds"] - alone["seconds"] < 2e-6


def test_the_share_is_per_delivered_block(config):
    """A block of 4 needs 2 passes of its row.  Where every row-pass decides
    its 2 tokens the share of a block is the share of a pass; the schedule
    before PR 48 ran 3 passes a block (the third a commit alone) and reads
    two thirds of its per-pass share; tokens that reached no request (a
    finished row's last passes) lower it and nothing raises it."""
    assert roofline.row_passes_needed(config, 4) == 2
    least, measured = 0.0116, 0.0221
    a_pass = 100.0 * least / measured
    # 10 passes of 48 rows: 480 row-passes
    assert roofline.block_share(config, least, measured, 960, 480) \
        == pytest.approx(a_pass)
    # three passes a block: 480 row-passes deliver 160 blocks = 640 tokens
    assert roofline.block_share(config, least, measured, 640, 480) \
        == pytest.approx(a_pass * 2 / 3)
    assert roofline.block_share(config, least, measured, 957, 480) < a_pass
    # the whole of it by hand: 480 tokens need 240 row-passes = 5 passes of
    # 48 rows = 58 ms at the least; the 10 that ran took 221 ms
    assert roofline.block_share(config, least, measured, 480, 480) \
        == pytest.approx(100.0 * 5 * least / (10 * measured))


def test_the_expert_products_are_bound_by_the_weights_they_read(config):
    one = roofline.experts_least_seconds(config, peaks(), 896, 10752)
    assert one["seconds"] == one["by_bytes_s"] > 5 * one["by_flops_s"]
    assert one["by_flops_s"] == pytest.approx(
        2 * EXPERT * 10752 / peaks()["bf16_flops_per_s"])


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def test_the_block_ledger_sums_the_windows_decode_records(config):
    reader = load_module(os.path.join(LM, "block_ledger.py"))
    ctx = ledger_ctx(config, [
        # the program since PR 48: no pass is a commit alone
        ("engine.decode_burst", 11.0, {"row_passes_denoise": 300,
                                       "row_passes_commit": 0,
                                       "row_commits_fused": 148,
                                       "tokens_decided": 590}),
        # ... and before it: a third of the row-passes decided nothing
        ("engine.decode_burst", 12.0, {"row_passes_denoise": 60,
                                       "row_passes_commit": 40,
                                       "tokens_decided": 110}),
        # one before the window, one of another span, one without counts
        ("engine.decode_burst", 9.0, {"row_passes_denoise": 10**6,
                                      "row_passes_commit": 0,
                                      "tokens_decided": 1}),
        ("engine.prefill_segment", 12.5, {"row_passes_denoise": 10**6,
                                          "row_passes_commit": 0,
                                          "tokens_decided": 1}),
        ("engine.decode_burst", 13.0, {"steps": 8}),
    ])
    assert reader.read(ctx, "tokens_per_row_pass") == pytest.approx(1.75)
    assert reader.read(ledger_ctx(config, []), "tokens_per_row_pass") is None
    # the share of commit passes left with its metric (ISSUE 50: 0 by
    # construction since PR 48)
    for what in ("commit_share", "no-such"):
        with pytest.raises(ValueError):
            reader.read(ctx, what)


def test_a_tokens_device_cost_is_the_paired_runs_time_over_their_tokens():
    reader = load_module(os.path.join(LM, "block_token_cost.py"))
    bursts = [({"start": 1.0, "end": 1.176}, {"tokens_decided": 700}),
              ({"start": 1.2, "end": 1.288}, {"tokens_decided": 300})]
    # 264 ms on the device for 1,000 tokens
    assert reader.cost_us(bursts) == pytest.approx(264.0)
    assert reader.cost_us([]) is None
    assert reader.cost_us([({"start": 1.0, "end": 2.0},
                            {"tokens_decided": 0})]) is None


def test_a_blocks_scope_is_the_innermost_named_one():
    reader = load_module(os.path.join(LM, "block_scope_share.py"))
    kinds = load_module(os.path.join(LM, "kind_scope_share.py"))
    known = load_module(os.path.join(LM, "scope_share.py")).SCOPES \
        + reader.OWN
    path = "jit(_block_decode_fn)/while/body/attn/attn_block/dot_general:"
    assert kinds.scope_of(path, known) == "attn_block"
    assert kinds.scope_of(
        "jit(f)/head_sample/denoise_select/select_n:", known) \
        == "denoise_select"
    assert kinds.scope_of("jit(f)/attn/dot_general:", known) == "attn"
    # the readers with the fixed lists take the same operations for attn's
    assert load_module(os.path.join(LM, "scope_share.py")).scope_of(path) \
        == "attn"


# the cell's own; ``commit_pass_share_pct.blockgen`` left with ISSUE 50 (0
# by construction since PR 48) and ``decode_dev_us_per_token.blockgen`` came
NEW = ["tokens_per_row_pass.blockgen", "block_attn_dev_pct.blockgen",
       "decode_roofline.blockgen", "moe_experts_roofline.blockgen",
       "decode_dev_us_per_token.blockgen"]


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_find_nothing_on_a_run_without_them(config, name):
    """What the parent's traced run gives them, and for the device metrics a
    CPU rehearsal: no device planes, no counts on the records: nothing to
    read, nothing raised."""
    with open(os.path.join(LM, name + ".json")) as f:
        spec = json.load(f)
    reader = load_module(os.path.join(LM, spec["reader"] + ".py"))
    ctx = ledger_ctx(config, [("engine.decode_burst", 11.0, {"steps": 8})])
    ctx.cell, ctx.peaks, ctx.trace_span = "no-such-cell.rehearsal", None, None
    assert reader.read(ctx, **spec.get("args", {})) is None
    ctx.peaks, ctx.trace_span = peaks(), (0.0, 1.0)
    assert reader.read(ctx, **spec.get("args", {})) is None


def test_a_share_cannot_exceed_100_where_the_pass_takes_the_least_time(
        config):
    """The reader's own arithmetic on one recorded burst whose measured pass
    is exactly the least time and whose every row-pass decided its two
    tokens: 100 %, by construction; under it for any slower pass, and for
    any row-pass that decided less."""
    rec = {"steps": 8, "live_rows": 48, "kv_rows_full": 8 * 48 * 4 * 604 * 7,
           "moe_experts_touched": 8 * 896, "moe_held": 8 * 16128,
           "row_passes_commit": 0, "row_commits_fused": 8 * 24,
           "row_passes_denoise": 8 * 48, "tokens_decided": 8 * 48 * 2}
    per = {k: v / 8 for k, v in rec.items()}
    fused = per["row_commits_fused"]
    least = roofline.least_pass_seconds(
        config, peaks(), 48, per["kv_rows_full"],
        per["moe_experts_touched"], per["moe_held"],
        per["row_passes_commit"] + fused, fused)["seconds"]
    ran = rec["live_rows"] * rec["steps"]
    for measured, decided, share in (
            (least, rec["tokens_decided"], 100.0),
            (2 * least, rec["tokens_decided"], 50.0),
            (least, rec["tokens_decided"] - 77, 100.0 * (768 - 77) / 768)):
        assert roofline.block_share(config, least, measured, decided, ran) \
            == pytest.approx(share)
        assert decided <= 2 * rec["row_passes_denoise"]


def traced_ctx(config, bursts):
    """A run's context with the trace already summarised: ``bursts`` are
    (device seconds, record) of decode runs inside a 10 s window, each
    paired with its dispatch record (``dispatch_trace.of`` hands back what
    a context already holds)."""
    ctx = ledger_ctx(config, [("engine.decode_burst", 11.0 + i, rec)
                              for i, (_secs, rec) in enumerate(bursts)])
    ctx.cell, ctx.peaks, ctx.trace_span = CELL, peaks(), (0.0, 10.0)
    ctx.dispatch_trace = {
        "window": (0.0, 10.0), "fit": {"offset_s": 0.0}, "scopes": None,
        "records": None, "pairs": {"engine.decode_burst": [
            {"start": 1.0 + i, "end": 1.0 + i + secs,
             "annotation": {"seq": i, "steps": rec["steps"]}}
            for i, (secs, rec) in enumerate(bursts)]}}
    return ctx


def test_the_readers_on_a_traced_windows_records(config):
    """Both device readers of the cell through ``read`` itself: two bursts
    of 8 passes at 22.1 ms a pass, 48 rows, half the row-passes with a block
    behind; 1,530 of the 1,536 tokens the row-passes could decide reached a
    request."""
    one = {"steps": 8, "live_rows": 48, "kv_rows_full": 8 * 48 * 4 * 604 * 7,
           "moe_experts_touched": 8 * 896, "moe_held": 8 * 16128,
           "row_passes_denoise": 8 * 48, "row_passes_commit": 0,
           "row_commits_fused": 8 * 24, "tokens_decided": 765}
    ctx = traced_ctx(config, [(8 * 0.0221, one), (8 * 0.0221, one)])
    share = load_module(os.path.join(LM, "block_diffusion_roofline_share.py"))
    least = roofline.least_pass_seconds(
        config, peaks(), 48, 48 * 4 * 604 * 7, 896, 16128, 24, 24)["seconds"]
    got = share.read(ctx, "decode_step")
    assert got == pytest.approx(100.0 * least / 0.0221 * 1530 / 1536)
    assert 50.0 < got < 55.0
    cost = load_module(os.path.join(LM, "block_token_cost.py"))
    assert cost.read(ctx) == pytest.approx(16 * 22100.0 / 1530)
    # a program from before PR 48: a third of the row-passes commit alone,
    # no record has ``row_commits_fused``; read, not refused, and the
    # share of a block is two thirds of the share of a pass
    old = {"steps": 9, "live_rows": 48, "kv_rows_full": 9 * 48 * 4 * 604 * 7,
           "moe_experts_touched": 9 * 896, "moe_held": 9 * 10752,
           "row_passes_denoise": 6 * 48, "row_passes_commit": 3 * 48,
           "tokens_decided": 6 * 48 * 2}
    before = traced_ctx(config, [(9 * 0.0215, old)])
    least = roofline.least_pass_seconds(
        config, peaks(), 48, 48 * 4 * 604 * 7, 896, 10752, 16)["seconds"]
    assert share.read(before, "decode_step") == pytest.approx(
        100.0 * least / 0.0215 * 2 / 3)
    assert cost.read(before) == pytest.approx(9 * 21500.0 / 576)
    # records of another family (no passes, no decided tokens): nothing
    dense = traced_ctx(config, [(0.1, {
        "steps": 8, "live_rows": 48, "kv_rows_full": 1, "moe_held": 1,
        "moe_experts_touched": 1})])
    assert share.read(dense, "decode_step") is None
    assert cost.read(dense) is None


def test_the_cells_metrics_are_the_ones_the_issue_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # another layer's metrics (start-up's list every cell) are not this
    # issue's to name: told by the entry's layer, not by their names
    mine = sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", [])
                  and m["layer"] != "start-up")
    assert mine == sorted(NEW + [
        "decode_fill_pct.closed", "decode_step_ctr_dev_ms.closed",
        "kv_move_dev_pct.closed", "moe_dev_pct.context",
        "moe_held_share_pct.context", "moe_imbalance.context"])
    assert [m["workloads"] for m in bench["per_layer"]
            if m["name"] in NEW] == [[CELL]] * len(NEW)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b", "blockgen-closed", 1)
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "blockgen-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["requests_per_client"],
            mix["lead_s"], mix["request_timeout_s"]) == (
        "closed", 48, 16, 5.0, 90.0)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 512}
