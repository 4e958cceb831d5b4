"""The byte and FLOP counts of the family of delta-rule layers beside full
attention against Olmo-Hybrid-7B's sizes worked out by hand, its reader on
recorded numbers, and what the cell's entries in BENCHMARK.json are."""

import json
import math
import os
import types

import pytest

from benchmarks import olmo_hybrid_roofline as roofline
from benchmarks.correctness import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LM = os.path.join(REPO, "benchmarks", "layer_metrics")
CELL = "olmo-hybrid-7b.chatturns-closed"
NEW = ["decode_roofline.chatturns", "delta_step_roofline.chatturns",
       "delta_scan_roofline.chatturns", "delta_dev_pct.chatturns",
       "full_attn_dev_pct.chatturns"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# W_q | W_k | W_v | W_z 3840 x (2880 + 2880 + 5760 + 5760), W_a | W_b 3840 x
# 60, W_o 5760 x 3840, the convolution's 4 weights a channel
DELTA = 3840 * 17280 + 3840 * 60 + 5760 * 3840 + 4 * 11520
# W_q, W_k, W_v, W_o 3840 x 3840 each: 30 KV heads, no grouping
ATTENTION = 4 * 3840 * 3840
# W_i 3840 x 2 x 11008, W_o 11008 x 3840
MLP = 3 * 3840 * 11008
HEAD = 3840 * 100352
WEIGHTS = 12 * DELTA + 4 * ATTENTION + 16 * MLP + HEAD
STATE_ROW = 30 * 96 * 192 * 4 + 3 * 11520 * 2


def test_the_sizes_are_the_issues_arithmetic(config):
    s = roofline.sizes(config)
    assert (s["layers"], s["delta_layers"], s["attention_layers"]) \
        == (16, 12, 4)
    assert s["delta"] == DELTA == 88_750_080
    assert s["attention"] == ATTENTION == 58_982_400
    assert s["mlp"] == MLP == 126_812_160 and s["head"] == HEAD
    assert s["row_full"] == 2 * 30 * 128  # values a position and layer
    assert s["state_row_bytes"] == STATE_ROW == 2_280_960
    # the layers' 3.33 G + the head's 0.39 G = 7.43 GB in bfloat16 (the
    # embedding's other 0.39 G are looked up by the row, not read)
    assert roofline.parameters(config) == WEIGHTS
    assert WEIGHTS == pytest.approx(3.716e9, rel=1e-3)
    # a slot's state: 27.37 MB; a token's rows: 61,440 B
    assert 12 * STATE_ROW == 27_371_520
    assert 4 * s["row_full"] * 2 == 61_440
    # as stored: two rows of 192 side by side, 384 lanes = 3 tiles, 48
    # sublanes = 6: no padding
    assert roofline.held_as(config) == (48, 384)
    assert math.prod(roofline.held_as(config)) == 96 * 192


def test_a_step_is_the_weights_once_and_the_live_rows_state_twice(config):
    none = roofline.decode_step_bytes(config, 0, 0)
    assert none == 2 * WEIGHTS
    # a position x layer: 30 KV heads x (128 + 128) values in bfloat16
    assert roofline.decode_step_bytes(config, 0, 1000) - none == 15_360_000
    # a live row: its state of 12 layers in and out
    assert roofline.decode_step_bytes(config, 1, 0) - none \
        == 2 * 12 * STATE_ROW == roofline.state_bytes(config, 1)
    # the issue's step: 64 live rows at about 400 positions: 12.5 GB (the
    # issue counts the head at 0.39 GB where it is 0.77: 12.1), 28 % of it
    # the state, 13 % the keys and values
    kv_rows = 4 * 64 * 400
    step = roofline.decode_step_bytes(config, 64, kv_rows)
    assert step == 2 * WEIGHTS + kv_rows * 15360 + 2 * 64 * 12 * STATE_ROW
    assert step == pytest.approx(12.51e9, rel=5e-3)
    assert roofline.state_bytes(config, 64) / step == pytest.approx(
        0.28, abs=0.01)
    assert kv_rows * 15360 / step == pytest.approx(0.126, abs=0.01)
    least = roofline.least_step_seconds(config, peaks(), 64, kv_rows)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(15.3e-3, rel=5e-3)
    assert least["by_flops_s"] == pytest.approx(
        (2 * WEIGHTS * 64 + 7 * 30 * 96 * 192 * 12 * 64
         + 2 * 2 * 30 * 128 * kv_rows) / 197e12)
    assert least["by_flops_s"] < 0.2 * least["by_bytes_s"]


def test_the_state_updates_and_the_scans_are_bound_by_bytes(config):
    one = roofline.state_step_least_seconds(config, peaks(), 64 * 8)
    assert one["seconds"] == one["by_bytes_s"] == pytest.approx(
        2 * 512 * 12 * STATE_ROW / 819e9)
    assert one["by_flops_s"] == pytest.approx(
        7 * 30 * 96 * 192 * 12 * 512 / 197e12)
    scan = roofline.scan_least_seconds(config, peaks(), 2, 1024)
    assert scan["seconds"] == scan["by_bytes_s"] > scan["by_flops_s"]
    # two rows' state in and out, and 1,024 positions' q, k, v, g, beta and o
    assert scan["by_bytes_s"] == pytest.approx(
        (2 * 2 * 12 * STATE_ROW + 1024 * 12 * (11520 + 5760 + 60) * 2)
        / 819e9)


def ledger_ctx(config, records):
    ctx = types.SimpleNamespace()
    ctx.config = config
    ctx.load = types.SimpleNamespace(t0=10.0, t1=20.0)
    ctx.spans = [{"name": name, "ph": "X", "ts": ts * 1e6, "dur": 1000,
                  "args": dict(args, seq=i)}
                 for i, (name, ts, args) in enumerate(records)]
    return ctx


def test_the_reader_asks_its_records_for_state_and_rows(config):
    reader = load_module(os.path.join(LM, "olmo_hybrid_roofline_share.py"))
    assert reader.KEYS == ("kv_rows_full", "state_rows")
    with pytest.raises(ValueError):
        reader.read(ledger_ctx(config, []), "no-such")
    assert set(reader.QUANTITIES) == {"decode_step", "delta_step",
                                      "delta_scan", "delta_share",
                                      "attn_share"}


# names and paths as a chip's trace gives them
LEAF_COPY = ("%copy.7 = f32[12,65,30,48,384]{4,3,2,1,0:T(8,128)} "
             "copy(...)")
OPS = [
    # (name, tf_op, start, end)
    ("%while.52 = (s32[]{:T(128)}, s32[65]{0:T(128)}, "
     "f32[12,65,30,48,384], bf16[12,65,34560]) while(...)", "", 0.0, 10.0),
    ("%fusion.1332 = f32[12,65,30,48,384] fusion(...)",
     "jit(_decode_fn)/while/body/closed_call/delta_step/scatter", 1.0, 3.0),
    (LEAF_COPY, "", 3.0, 4.0),
    ("%copy.9 = bf16[12,129,34560]{2,1,0:T(8,128)(2,1)} copy(...)", "",
     4.0, 4.5),
    ("%fusion.9 = bf16[65,34560] fusion(...)",
     "jit(_decode_fn)/closed_call/delta_conv/dynamic_slice", 4.5, 5.0),
    ("%fusion.11 = bf16[65,22016] fusion(...)",
     "jit(_decode_fn)/closed_call/ffn/dot_general", 5.0, 7.0),
    ("%decode_attn_rows.3 = bf16[65,30,128] custom-call(...)",
     "jit(_decode_fn)/closed_call/attn/pallas_call", 7.0, 8.0),
    ("%fusion.12 = f32[65,3840] fusion(...)", "", 8.0, 8.5),
    ("%fusion.13 = f32[65,30,192] fusion(...)",
     "jit(_decode_fn)/closed_call/delta_proj/mul", 8.5, 9.0),
    # a head's state as [96, 192] is no leaf of this program's
    ("%copy.1 = f32[12,65,30,96,192]{4,3,2,1,0} copy(...)", "", 9.0, 9.5),
]


def test_operations_are_owned_by_the_familys_scopes(config):
    """The innermost known scope on an operation's path owns it; a whole
    state leaf's unscoped copy (any count of rows) is ``state_leaf``; the
    burst's loop, whose result is a tuple that holds the leaves, and a shape
    the program does not lay are not."""
    reader = load_module(os.path.join(LM, "olmo_hybrid_roofline_share.py"))
    owner = reader.owner_of(config)
    assert [owner(name, tf_op) for name, tf_op, _s, _e in OPS] == [
        "unscoped", "delta_step", "state_leaf", "state_leaf", "delta_conv",
        "ffn", "attn", "unscoped", "delta_proj", "unscoped"]
    ops = [(owner(name, tf_op), s, e) for name, tf_op, s, e in OPS]
    own = reader.self_time_by_owner(ops, (0.0, 10.0))
    assert own == pytest.approx({
        "unscoped": 2.5, "delta_step": 2.0, "state_leaf": 1.5,
        "delta_conv": 0.5, "ffn": 2.0, "attn": 1.0, "delta_proj": 0.5})
    assert sum(own[s] for s in reader.STEP_SCOPES if s in own) == 4.0
    assert sum(own[s] for s in reader.DELTA + (reader.STATE_LEAF,)
               if s in own) == 4.5
    assert sum(own[s] for s in reader.ATTENTION if s in own) == 1.0


def test_the_rooflines_bytes_are_the_leaves_the_program_makes(config):
    """The count's bytes a slot and the reader's leaf shapes, from the
    published keys alone, are held to the leaves ``init_kv_cache`` makes: of
    the preset ``tinycell_olmo.py`` serves, and of the cell's own at its 64
    slots and the scratch row (shapes only: nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    import tinycell_olmo
    from p2p_llm_tunnel_tpu.models import ssm_moe
    from p2p_llm_tunnel_tpu.models.config import get_config

    reader = load_module(os.path.join(LM, "olmo_hybrid_roofline_share.py"))
    for body, rows in ((tinycell_olmo.CONFIG, 5), (config, 65)):
        cfg = get_config(body["serve"]["model"])
        cache = jax.eval_shape(
            lambda: ssm_moe.init_kv_cache(cfg, rows, 128, jnp.bfloat16))
        keys = ssm_moe.state_keys(cfg)
        assert set(keys) == {"delta", "dconv"}
        shapes = reader.leaf_shapes(body, rows)
        assert {k: cache[k].shape for k in keys} == shapes
        s = roofline.sizes(body)
        stored = sum(math.prod(cache[k].shape) * cache[k].dtype.itemsize
                     for k in keys)
        assert stored == rows * s["delta_layers"] * s["state_row_bytes"] \
            == rows * ssm_moe.state_bytes_per_slot(cfg)
        planes = sum(math.prod(a.shape) * a.dtype.itemsize
                     for k, a in cache.items() if k not in keys)
        assert planes == rows * 128 * s["attention_layers"] \
            * s["row_full"] * 2
        pattern = reader.leaf_pattern(body)
        for key in keys:
            leaf = cache[key]
            dims = ",".join(str(d) for d in leaf.shape)
            kind = {"float32": "f32", "bfloat16": "bf16"}[str(leaf.dtype)]
            assert pattern.search(
                f"%copy.1 = {kind}[{dims}]{{2,1,0}} copy(...)")
            part = ",".join(str(d) for d in leaf.shape[1:])
            assert not pattern.search(
                f"%fusion.1 = {kind}[{part}] fusion(...)")
            assert not pattern.search(
                f"%while.1 = (s32[], {kind}[{dims}]) while")
    assert reader.leaf_shapes(config, 65) == {
        "dconv": (12, 65, 34560), "delta": (12, 65, 30, 48, 384)}


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
    assert mine == set(NEW) | {
        "decode_fill_pct.closed", "decode_step_ctr_dev_ms.closed",
        "kv_move_dev_pct.closed"}
    better = {"decode_roofline.chatturns": "higher",
              "delta_step_roofline.chatturns": "higher",
              "delta_scan_roofline.chatturns": "higher",
              "delta_dev_pct.chatturns": "lower",
              "full_attn_dev_pct.chatturns": "lower"}
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
    assert (cell["chips"], cell["traffic"]) == (1, "chatturns-closed")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    assert next(c for c in bench["configs"]
                if c["name"] == cell["config"])["reduced"] == [
        "num_hidden_layers", "layer_types"]
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "chatturns-closed.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["requests_per_client"], mix["lead_s"],
            mix["tail_s"], mix["request_timeout_s"]) == (
        "closed", 24, 10.0, 0.0, 90.0)
    # 64 clients = slots, or the issue's stated fallback of 48
    assert mix["clients"] in (64, 48)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.9, "min": 32, "max": 640}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 384}
    assert "shared_prefix" not in mix
