"""``mimo-v2-flash``'s preset and its share, the ring's size, the benchmark's
own copy of the reference and its configuration file, and the tiny cell in
one process (the programs: tests/test_swa_moe.py; the engine:
tests/test_swa_moe_engine.py).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import init_params
from tests import swa_moe_plain as plain
from tests.swa_moe_tiny import ATOL, RING, _prompt


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_ring_is_sized_by_the_segment():
    share = get_config("mimo-v2-flash-ep16s")
    assert share.ring_default(8192, 512) == 640  # window + segment
    assert share.ring_default(8192, 128) == 256
    assert share.ring_default(8192) == 256
    assert share.ring_default(512, 512) == 512  # never more than the slot
    assert get_config("tiny-swa-moe").ring_default(128, 16) == RING  # as preset
    assert get_config("mistral-7b").attn_kinds == ("window",) * 32
    assert get_config("tiny-gemma").attn_kinds == ("window", "full")
    assert get_config("tiny").attn_kinds == ("full", "full")


def test_the_published_preset_and_its_share():
    whole, share = get_config("mimo-v2-flash"), get_config("mimo-v2-flash-ep16s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        48, 256, 152576)
    assert whole.attn_kinds.count("full") == 9
    assert whole.attn_kinds.count("window") == 39
    assert whole.attn_kinds[:6] == ("full",) + ("window",) * 4 + ("full",)
    assert whole.attn_kinds[-1] == "full"
    assert whole.experts_held == (0, 256)
    assert share.experts_held == (0, 16) and share.n_layers == 7
    assert share.vocab_size * 8 == whole.vocab_size  # eighths, two chips each
    assert share.layer_kinds == ("dense",) + ("moe",) * 6
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "n_kv_heads", "window_kv_heads", "head_dim",
        "v_head_dim", "ffn_dim", "moe_ffn_dim", "n_experts",
        "n_experts_per_tok", "n_shared_experts", "sliding_window",
        "rotary_dim", "rope_theta", "window_rope_theta", "value_scale",
        "window_sink", "router_bias", "routed_scale", "router_score")}
    assert same == {f: getattr(share, f) for f in same}
    assert (whole.head_dim, whole.v_head_dim, whole.rotary_dim) == (192, 128, 64)
    assert (whole.n_kv_heads, whole.window_kv_heads) == (4, 8)
    # the cut's parameters, by the shapes the program would draw
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 3.42e9 < count < 3.44e9


# ---- the benchmark's copy and its configuration ---------------------------------

def _tiny_file():
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_swa

    return tinycell_swa


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_benchmarks_reference_is_the_same_model(share):
    """benchmarks/swa_moe_reference.py draws the program's weights from the
    seed and computes the plain reference's forward, whole and as a share."""
    from benchmarks import swa_moe_reference as bench

    config = dict(_tiny_file().CONFIG)
    if not share:
        config.update(n_routed_experts=8, layer_chips=1,
                      published_counts={"n_routed_experts": 8})
    cfg = get_config("tiny-swa-moe-ep2s" if share else "tiny-swa-moe")
    shapes = bench.shapes_of(config)
    assert shapes["rotary"] == cfg.rotary_dim == 8
    weights = bench.make_weights(shapes, 5)
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16)
    pairs = [(weights["full"][k], params["attn_full"][k])
             for k in ("wq", "wk", "wv", "wo")]
    pairs += [(weights["window"][k], params["attn_window"][k])
              for k in ("wq", "wk", "wv", "wo", "sink")]
    pairs += [(weights["moe"][a], params["blocks"][b]) for a, b in (
        ("gate", "moe_gate"), ("up", "moe_up"), ("down", "moe_down"),
        ("router", "router"), ("bias", "router_bias"))]
    pairs += [(weights["dense"]["down"], params["dense_ffn"]["w_down"]),
              (weights["embed"], params["embed"]),
              (weights["lm_head"], params["lm_head"])]
    for mine, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                      np.asarray(theirs, np.float32))
    tokens = _prompt(3, 37)
    got = np.asarray(bench.forward_logprobs(shapes, weights, tokens))
    want = np.asarray(plain.forward_logprobs(cfg, params, tokens))
    np.testing.assert_allclose(got, want, atol=ATOL)
    rounded = np.asarray(bench.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=8))
    assert 1e-3 < np.abs(rounded - want).mean() < 0.5
    assert bench.cache_bytes_per_token(config) == _tiny_file().CACHE_BYTES


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo-v2-flash.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row["config"] for row in map(json.loads, f)
                         if row["name"] == "MiMo-V2-Flash")
    reduced = {"num_hidden_layers": 7, "n_routed_experts": 16,
               "vocab_size": 19072}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert body["published_counts"] == {k: published[k] for k in reduced}
    assert body["layer_chips"] * body["n_routed_experts"] == \
        published["n_routed_experts"]
    assert 8 * body["vocab_size"] == published["vocab_size"]
    from benchmarks import swa_moe_reference as bench

    # 2 full layers x 4 heads + 5 window layers x 8 heads, 192 + 128 values
    assert bench.cache_bytes_per_token(body) == 2 * 2560 + 5 * 5120 == 30720
    shapes = bench.shapes_of(body)
    assert (shapes["held"], shapes["experts"], len(shapes["attn"])) == (
        16, 256, 7)
    share = get_config(body["serve"]["model"])
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]
    assert share.attn_kinds == shapes["attn"]
    assert share.layer_kinds == shapes["ffn"]
    assert (share.rotary_dim, share.value_scale) == (
        shapes["rotary"], shapes["value_scale"])
    # what a slot holds at the file's serve shape: the second statement
    chunk = int(body["serve"]["args"][body["serve"]["args"].index(
        "--prefill-chunk") + 1])
    ring = share.ring_default(body["serve"]["max_seq"], chunk)
    assert ring == 640
    assert 2 * 2560 * body["serve"]["max_seq"] + 5 * 5120 * ring == 58327040


# ---- the tiny cell, in one process ----------------------------------------------

TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
    "activations": ({"quant": "a8"}, None),
    "kv_cache": ({"kv_quant": "int8"}, None),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_swa.py's cell (the ``tiny-swa-moe-ep2s``
    share in bfloat16 against benchmarks/swa_moe_reference.py given the same
    share) through the engine in this process: what ``correct`` compares,
    as stated and with each stated precision lowered.  The ladder's
    prefixes reach the chunk program through the pool and the rings.
    (Through signal + serve + proxy: tests/benchmarks/
    test_bm_swa_rehearsal.py, ``slow``.)"""
    from tests.tiny_cell import _ask_in_process

    tiny = _tiny_file()
    from benchmarks import correctness, swa_moe_reference as bench, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    engine_args, weight_bits = TINY_CELL_MODES[mode]
    config, seed = tiny.CONFIG, 11
    limits = config["correct"]["limits"]
    vocab = config["vocab_size"]
    plan = traffic.make_plan(
        {"name": "t", "loop": "closed", "clients": 3,
         "requests_per_client": 4, "lead_s": 0.5, "tail_s": 0.0,
         "request_timeout_s": 30.0,
         "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
         "output_tokens": {"dist": "uniform", "min": 8, "max": 16}},
        seed, 3, vocab)
    seqs = correctness.sequences(plan, seed, vocab, 256)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, seed)
    stated = bench.cache_bytes_per_token(config)
    if weight_bits is None:
        class Words(ByteTokenizer):
            vocab_size = vocab

        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16,
                **engine_args),
            tokenizer=Words())
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
    else:  # the reference in the program's place, its weights rounded
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "control", os.path.join(REPO, "benchmarks", "control.py"))
        control = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(control)
        counted = stated
        for seq in seqs:
            control.pretend(seq)
            lp = np.asarray(bench.forward_logprobs(
                shapes, weights, seq["tokens"], weight_bits=weight_bits))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = np.asarray(bench.forward_logprobs(shapes, weights, seq["tokens"]))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    assert held is (mode == "stated"), "\n".join(said)
    if mode == "kv_cache":  # by its width alone
        # int8 values and one float32 scale a KV head beside each plane
        assert counted == (2 * 1 + 5 * 2) * (40 + 2 * 4)
        assert stated == tiny.CACHE_BYTES
        assert all(numbers[n]["mean_abs"] <= limits[n]
                   for n in correctness.NUMBERS), said
    elif mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
