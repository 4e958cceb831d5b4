"""``sarvam-105b``'s preset and its share, the benchmark's own copy of the
reference and its configuration file, and the tiny cell in one process (the
programs: tests/test_mla_moe.py; the engine: tests/test_mla_moe_engine.py).
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
from tests import mla_moe_plain as plain
from tests.mla_moe_tiny import ATOL, _prompt
from tests.tiny_cell import _ask_in_process


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_published_preset_and_its_share():
    whole, share = get_config("sarvam-105b"), get_config("sarvam-105b-ep4s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        32, 128, 262144)
    assert whole.experts_held == (0, 128)
    assert share.experts_held == (0, 32) and share.n_layers == 6
    assert share.vocab_size * share.layer_chips == whole.vocab_size
    assert share.layer_kinds == ("dense",) + ("moe",) * 5
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "head_dim", "ffn_dim", "moe_ffn_dim",
        "n_experts", "n_experts_per_tok", "n_shared_experts",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "router_bias", "routed_scale", "yarn")}
    assert same == {f: getattr(share, f) for f in same}
    assert whole.head_dim == whole.kv_lora_rank + whole.qk_rope_head_dim


# ---- the benchmark's copy and its configuration ---------------------------------

TINY_FILE = {
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "first_k_dense_replace": 1, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "use_qk_norm": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "vocab_size": 512,
    "rope_scaling": {"type": "deepseek_yarn", "factor": 40,
                     "original_max_position_embeddings": 16, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "precision": {"kv_cache": "bfloat16"},
}


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_benchmarks_reference_is_the_same_model(share):
    """benchmarks/mla_moe_reference.py draws the program's weights from the
    seed and computes the plain reference's forward, whole and as a share."""
    from benchmarks import mla_moe_reference as bench

    config = dict(TINY_FILE)
    if share:
        config.update(num_experts=4, layer_chips=2, chip_index=0,
                      published_counts={"num_experts": 8})
    cfg = get_config("tiny-mla-moe-ep2s" if share else "tiny-mla-moe")
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, 5)
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16)
    for mine, theirs in (("wq", "wq"), ("gate", "moe_gate"),
                         ("down", "moe_down"), ("router", "router"),
                         ("bias", "router_bias"),
                         ("shared_up", "shared_up")):
        np.testing.assert_array_equal(
            np.asarray(weights["moe"][mine], np.float32),
            np.asarray(params["blocks"][theirs], np.float32))
    np.testing.assert_array_equal(
        np.asarray(weights["dense"]["gate"], np.float32),
        np.asarray(params["dense_blocks"]["w_gate"], np.float32))
    np.testing.assert_array_equal(np.asarray(weights["embed"], np.float32),
                                  np.asarray(params["embed"], np.float32))
    tokens = _prompt(3, 21)
    got = np.asarray(bench.forward_logprobs(shapes, weights, tokens))
    want = np.asarray(plain.forward_logprobs(cfg, params, tokens))
    np.testing.assert_allclose(got, want, atol=ATOL)
    rounded = np.asarray(bench.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=8))
    assert 1e-3 < np.abs(rounded - want).mean() < 0.5
    assert bench.cache_bytes_per_token(config) == 4 * 40 * 2


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sarvam-105b.json")) as f:
        body = json.load(f)
    with open(os.path.join(REPO, "tests", "benchmarks", "data",
                           "sarvam-105b.published.json")) as f:
        published = json.load(f)
    reduced = {"num_hidden_layers": 6, "num_experts": 32, "vocab_size": 65536}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert body["published_counts"] == {k: published[k] for k in reduced}
    assert body["layer_chips"] * body["num_experts"] == published["num_experts"]
    assert body["layer_chips"] * body["vocab_size"] == published["vocab_size"]
    from benchmarks import mla_moe_reference as bench

    assert bench.cache_bytes_per_token(body) == 6912
    shapes = bench.shapes_of(body)
    assert (shapes["held"], shapes["experts"], shapes["layers"]) == (32, 128, 6)
    share = get_config(body["serve"]["model"])
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]


# ---- the tiny cell, in one process ----------------------------------------------

TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
    "activations": ({"quant": "a8"}, None),
    "kv_cache": ({"kv_quant": "int8"}, None),
}
# echo_prompt has the most positions (about 770) and is the steady one: as
# stated it reads 0.038 here, with 8-bit weights in the reference's place
# 0.064, with int8 activations 0.074 (a model this narrow routes a token
# elsewhere on a rounding, which is most of every number); the other three
# have a few hundred positions, read 0.014-0.058 as stated or with int8
# planes, and only have to hold.
TINY_CELL_LIMITS = {"echo_prompt": 0.05, "echo_decode": 0.1,
                    "traffic_decode": 0.1, "traffic_prefill": 0.1}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_mla.py's cell (the ``tiny-mla-moe-ep2s``
    share in bfloat16 against benchmarks/mla_moe_reference.py given the same
    share) through the engine in this process: what ``correct`` compares,
    as stated and with each stated precision lowered.  (Through signal +
    serve + proxy: tests/benchmarks/test_bm_mla_rehearsal.py, ``slow``.)"""
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_mla
    from benchmarks import correctness, mla_moe_reference as bench, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    engine_args, weight_bits = TINY_CELL_MODES[mode]
    config, seed = tinycell_mla.CONFIG, 11
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
                seed=seed, mux=True, prefix_cache=True, **engine_args),
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
    held = correctness.judge(numbers, TINY_CELL_LIMITS, counted, stated,
                             said.append)
    assert held is (mode == "stated"), "\n".join(said)
    if mode == "kv_cache":  # by its width alone
        assert counted == 4 * (40 + 8) and stated == 4 * 40 * 2
        assert all(numbers[n]["mean_abs"] <= TINY_CELL_LIMITS[n]
                   for n in correctness.NUMBERS), said
    elif mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > \
            TINY_CELL_LIMITS["echo_prompt"], said
