"""The byte and FLOP counts of the family of a mixer and then an MLP a layer
against granite-4.0-h-micro's sizes worked out by hand, its reader on
recorded numbers, and what the cell's entries in BENCHMARK.json are."""

import json
import os
import types

import pytest

from benchmarks import granite_hybrid_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")
CELL = "granite-4.0-h-micro.assistants-closed"
NEW = ["decode_roofline.assistants", "ssm_step_roofline.assistants",
       "ssm_scan_roofline.assistants", "ssm_dev_pct.assistants",
       "ffn_dev_pct.assistants"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_in 2048 x (4096 + 4352 + 64), W_out 4096 x 2048, the convolution's 4
# weights and a bias a channel
MAMBA = 2048 * 8512 + 4096 * 2048 + 5 * 4352
# W_q 2048 x 2048, W_k + W_v 2048 x 2 x 512, W_o 2048 x 2048
ATTENTION = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
# W_i 2048 x 2 x 8192, W_o 8192 x 2048
MLP = 3 * 2048 * 8192
HEAD = 2048 * 100352
WEIGHTS = 36 * MAMBA + 4 * ATTENTION + 40 * MLP + HEAD
STATE_ROW = 64 * 64 * 128 * 4 + 3 * 4352 * 2


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert (s["layers"], s["mamba_layers"], s["attention_layers"]) \
        == (40, 36, 4)
    assert s["mamba"] == MAMBA == 25_842_944
    assert s["attention"] == ATTENTION == 10_485_760
    assert s["mlp"] == MLP == 50_331_648 and s["head"] == HEAD
    assert s["row_full"] == 2 * 8 * 64  # values a position and layer
    assert s["state_row_bytes"] == STATE_ROW == 2_123_264
    # 3,191 M parameters = 6.38 GB in bfloat16, the embedding counted once
    assert roofline.parameters(config) == WEIGHTS
    assert WEIGHTS == pytest.approx(3.191e9, rel=1e-3)
    # a slot's state: 76.4 MB, six times the 12.8 MB of the other cell
    assert 36 * STATE_ROW == 76_437_504


def test_a_step_is_the_weights_once_and_the_live_rows_state_twice(config):
    none = roofline.decode_step_bytes(config, 0, 0)
    assert none == 2 * WEIGHTS
    # a position x layer: 8 KV heads x (64 + 64) values in bfloat16
    assert roofline.decode_step_bytes(config, 0, 1000) - none == 2_048_000
    # a live row: its state of 36 layers in and out
    assert roofline.decode_step_bytes(config, 1, 0) - none \
        == 2 * 36 * STATE_ROW == roofline.state_bytes(config, 1)
    # the issue's step: 64 live rows at about 600 positions: 16.5 GB,
    # 59 % of it the state, under 2 % the keys and values
    kv_rows = 4 * 64 * 600
    step = roofline.decode_step_bytes(config, 64, kv_rows)
    assert step == 2 * WEIGHTS + kv_rows * 2048 + 2 * 64 * 36 * STATE_ROW
    assert step == pytest.approx(16.5e9, rel=5e-3)
    assert roofline.state_bytes(config, 64) / step == pytest.approx(
        0.59, abs=0.01)
    assert kv_rows * 2048 / step < 0.02
    least = roofline.least_step_seconds(config, peaks(), 64, kv_rows)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(20.1e-3, rel=5e-3)
    assert least["by_flops_s"] == pytest.approx(
        (2 * WEIGHTS * 64 + 5 * 64 * 64 * 128 * 36 * 64
         + 2 * 2 * 32 * 64 * kv_rows) / 197e12)
    assert least["by_flops_s"] < 0.15 * least["by_bytes_s"]


def test_the_state_updates_and_the_scans_are_bound_by_bytes(config):
    one = roofline.state_step_least_seconds(config, peaks(), 64 * 8)
    assert one["seconds"] == one["by_bytes_s"] == pytest.approx(
        2 * 512 * 36 * STATE_ROW / 819e9)
    assert one["by_flops_s"] == pytest.approx(
        5 * 64 * 64 * 128 * 36 * 512 / 197e12)
    scan = roofline.scan_least_seconds(config, peaks(), 2, 1024)
    assert scan["seconds"] == scan["by_bytes_s"] > scan["by_flops_s"]
    # two rows' state in and out, and 1,024 positions' z, xBC, dt and y
    assert scan["by_bytes_s"] == pytest.approx(
        (2 * 2 * 36 * STATE_ROW + 1024 * 36 * (2 * 4096 + 4352 + 64) * 2)
        / 819e9)


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def test_the_reader_asks_its_records_for_state_and_rows_and_no_router(
        config):
    reader = load_module(os.path.join(LM, "granite_hybrid_roofline_share.py"))
    assert reader.KEYS == ("kv_rows_full", "state_rows")
    with pytest.raises(ValueError):
        reader.read(ledger_ctx(config, []), "no-such")
    assert set(reader.QUANTITIES) == {"decode_step", "ssm_step", "ssm_scan",
                                      "ssm_share"}


# names and paths as the chip's trace gave them (my chip run, PR 46), when
# the convolution's tail lay as [.., K - 1, channels]: since PR 47 no
# program has a leaf of that shape, and the name is no leaf's
BEFORE_PR47 = ("%bitcast_dynamic-update-slice_fusion.106.remat_compressed = "
               "bf16[36,65,3,4352]{3,2,1,0:T(8,128)(2,1)} fusion(...)")
# the same copy of the leaf as the program lays it now: the tail as lanes of
# a slot's row, bf16[36,65,13056] in the cell
COMPRESSED = ("%bitcast_dynamic-update-slice_fusion.106.remat_compressed = "
              "bf16[36,65,13056]{2,1,0:T(8,128)(2,1)} fusion(...)")
OPS = [
    # (name, tf_op, start, end)
    ("%while.52 = (s32[]{:T(128)}, s32[65]{0:T(128)}, f32[36,65,64,64,128], "
     "bf16[36,65,13056]) while(...)", "", 0.0, 10.0),
    ("%ssm_step_rows.7 = f32[36,65,64,64,128] custom-call(...)",
     "jit(_decode_fn)/closed_call/ssm_step/pallas_call", 1.0, 3.0),
    (COMPRESSED, "", 3.0, 4.0),
    (COMPRESSED.replace("remat_compressed", "remat_uncompressed"), "",
     4.0, 4.5),
    ("%fusion.9 = bf16[65,13056] fusion(...)",
     "jit(_decode_fn)/closed_call/ssm_conv/dynamic_slice", 4.5, 5.0),
    ("%fusion.11 = bf16[65,16384] fusion(...)",
     "jit(_decode_fn)/closed_call/ffn/dot_general", 5.0, 8.0),
    ("%copy.3 = f32[36,129,64,64,128] copy(...)", "", 8.0, 9.0),
    ("%fusion.12 = f32[65,2048] fusion(...)", "", 9.0, 9.5),
    (BEFORE_PR47, "", 9.5, 9.75),
]


def test_a_state_leafs_unscoped_copy_is_the_state_updates_time(config):
    """The compiler's copies of a whole leaf around a layer's write carry
    no scope: the reader owns them by their result's shape (any count of
    rows), and owns neither the burst's loop, whose result is a tuple that
    holds the leaves, nor what a scope already owns, nor a shape that was a
    leaf's before PR 47 and is none now."""
    reader = load_module(os.path.join(LM, "granite_hybrid_roofline_share.py"))
    owner = reader.owner_of(config)
    assert [owner(name, tf_op) for name, tf_op, _s, _e in OPS] == [
        "unscoped", "ssm_step", "state_leaf", "state_leaf", "ssm_conv",
        "ffn", "state_leaf", "unscoped", "unscoped"]
    ops = [(owner(name, tf_op), s, e) for name, tf_op, s, e in OPS]
    own = reader.self_time_by_owner(ops, (0.0, 10.0))
    assert own == pytest.approx({
        "unscoped": 2.0, "ssm_step": 2.0, "state_leaf": 2.5,
        "ssm_conv": 0.5, "ffn": 3.0})
    # of one paired run alone, as the state updates' roofline takes it
    inside = reader.self_time_by_owner(ops, (0.0, 10.0), within=[(2.5, 4.2)])
    assert inside == pytest.approx({"state_leaf": 1.5})
    assert sum(own[s] for s in reader.STATE_SCOPES if s in own) == 5.0


def test_the_leaf_pattern_names_the_leaves_the_program_makes(config):
    """``state_leaf`` owns an operation by its result's shape, so the shape
    has to be a leaf's as ``models/ssm_moe.py`` lays it: PR 47 moved the
    convolution's tail into a row's lanes and the rule of PR 46 matched
    nothing for three PRs.  Here the reader's shapes, from the published
    keys alone, are held to the leaves ``init_kv_cache`` makes of the preset
    that ``tinycell_granite.py`` serves; a change of layout fails this."""
    import tinycell_granite
    from p2p_llm_tunnel_tpu.models import ssm_moe
    from p2p_llm_tunnel_tpu.models.config import get_config

    reader = load_module(os.path.join(LM, "granite_hybrid_roofline_share.py"))
    tiny = tinycell_granite.CONFIG
    cache = ssm_moe.init_kv_cache(get_config(tiny["serve"]["model"]), 5, 32)
    assert set(ssm_moe.STATE_KEYS) == {"ssm", "conv"}
    shapes = reader.leaf_shapes(tiny, 5)
    assert {k: cache[k].shape for k in ssm_moe.STATE_KEYS} == shapes
    pattern = reader.leaf_pattern(tiny)
    for key in ssm_moe.STATE_KEYS:
        leaf = cache[key]
        dims = ",".join(str(d) for d in leaf.shape)
        kind = {"float32": "f32", "bfloat16": "bf16"}[str(leaf.dtype)]
        assert pattern.search(f"%copy.1 = {kind}[{dims}]{{2,1,0}} copy(...)")
        # a layer's rows of it, and the tuple a loop returns, are no leaf
        rows = ",".join(str(d) for d in leaf.shape[1:])
        assert not pattern.search(f"%fusion.1 = {kind}[{rows}] fusion(...)")
        assert not pattern.search(f"%while.1 = (s32[], {kind}[{dims}]) while")
    # the cell's own: 36 layers, 3 x 4,352 lanes and 64 x 64 x 128 a row
    assert reader.leaf_shapes(config, 65) == {
        "conv": (36, 65, 13056), "ssm": (36, 65, 64, 64, 128)}


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_find_nothing_on_a_run_without_a_trace(config, name):
    """What a CPU rehearsal gives them (the parent cannot run the cell at
    all): no device planes: nothing to read, nothing raised."""
    with open(os.path.join(LM, name + ".json")) as f:
        spec = json.load(f)
    assert spec["moves"] == "out_tok_per_s" and spec["unit"] == "%"
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
    # the accepted metrics whose readers find something in the cell, and
    # the family's own five: entries since ISSUE 50, wherever in the list
    assert mine == set(NEW) | {
        "decode_fill_pct.closed", "decode_step_ctr_dev_ms.closed",
        "kv_move_dev_pct.closed"}
    better = {"decode_roofline.assistants": "higher",
              "ssm_step_roofline.assistants": "higher",
              "ssm_scan_roofline.assistants": "higher",
              "ssm_dev_pct.assistants": "lower",
              "ffn_dev_pct.assistants": "lower"}
    own = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert {m["name"]: m["better"] for m in own} == better
    for m in own:
        assert m["workloads"] == [CELL] and m["layer"] == "model + attention"
    for name in NEW:
        with open(os.path.join(LM, name + ".json")) as f:
            assert os.path.exists(
                os.path.join(LM, json.load(f)["reader"] + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "assistants-closed")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    assert next(c for c in bench["configs"]
                if c["name"] == cell["config"])["reduced"] == []
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "assistants-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["requests_per_client"],
            mix["lead_s"], mix["tail_s"], mix["request_timeout_s"]) == (
        "closed", 64, 24, 10.0, 0.0, 90.0)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.9, "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 384}
    assert "shared_prefix" not in mix
