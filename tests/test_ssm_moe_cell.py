"""``nemotron-3-nano-30b-a3b``'s preset and its share, the benchmark's own
copy of the reference and its configuration file, and the tiny cell in one
process (the programs: tests/test_ssm_moe.py; the engine:
tests/test_ssm_moe_engine.py).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import init_params
from tests import ssm_moe_plain as plain
from tests.ssm_moe_tiny import ATOL, _prompt


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_published_preset_and_its_share():
    whole = get_config("nemotron-3-nano-30b-a3b")
    share = get_config("nemotron-3-nano-30b-a3b-ep2s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        52, 128, 131072)
    assert [whole.mixer_kinds.count(k) for k in "ME*"] == [23, 23, 6]
    assert share.mixer_kinds == "MEMEM*EMEMEM*"
    assert share.experts_held == (0, 64) and share.n_layers == 13
    assert share.vocab_size * 2 == whole.vocab_size
    assert len(share.attn_kinds) == 2
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "n_kv_heads", "head_dim", "moe_ffn_dim",
        "shared_expert_dim", "n_experts", "n_experts_per_tok", "ssm_heads",
        "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_conv", "ssm_chunk",
        "router_bias", "routed_scale", "router_score", "expert_gated", "act")}
    assert same == {f: getattr(share, f) for f in same}
    assert (share.ssm_inner, share.ssm_conv_dim) == (4096, 6144)
    # what a slot's state takes: 6 x (2.097 MB + 37 KB)
    assert ssm_moe.state_bytes_per_slot(share) == 6 * (
        64 * 64 * 128 * 4 + 3 * 6144 * 2) == 12_804_096
    # the cut's parameters, by the published shapes (an expert held 1920
    # wide counts its 1856)
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    count -= 5 * 64 * 2 * 2688 * 64
    assert 3.92e9 < count < 3.93e9


# ---- the benchmark's copy and its configuration -----------------------------------

def _tiny_file():
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_ssm

    return tinycell_ssm


@pytest.mark.usefixtures("full_optimiser")  # weights held to the bit
def test_the_benchmarks_reference_is_the_same_model(share=True):
    """benchmarks/ssm_moe_reference.py draws the program's weights from the
    seed and computes the plain reference's forward, whole and as a share."""
    from benchmarks import ssm_moe_reference as bench

    config = dict(_tiny_file().CONFIG)
    if not share:
        config.update(n_routed_experts=8, layer_chips=1,
                      published_counts={"n_routed_experts": 8})
    cfg = get_config("tiny-ssm-moe-ep2s" if share else "tiny-ssm-moe")
    shapes = bench.shapes_of(config)
    assert shapes["kinds"] == cfg.mixer_kinds
    weights = bench.make_weights(shapes, 5)
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16)
    pairs = [(weights["attn"][k], params["attn"][k])
             for k in ("wq", "wk", "wv", "wo")]
    pairs += [(weights["mamba"][k], params["mamba"][k]) for k in (
        "w_in", "conv_w", "conv_b", "w_out", "dt_bias", "a_log")]
    pairs += [(weights["moe"][a], params["blocks"][b]) for a, b in (
        ("up", "moe_up"), ("down", "moe_down"), ("router", "router"),
        ("bias", "router_bias"), ("shared_up", "shared_up"),
        ("shared_down", "shared_down"))]
    pairs += [(weights["embed"], params["embed"]),
              (weights["lm_head"], params["lm_head"])]
    for mine, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                      np.asarray(theirs, np.float32))
    assert float(jnp.abs(params["mamba"]["d_skip"] - 1).max()) == 0
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
                           "nemotron-3-nano-30b-a3b.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(
            row["config"] for row in map(json.loads, f)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    reduced = {"num_hidden_layers": 13,
               "hybrid_override_pattern": "MEMEM*EMEMEM*",
               "n_routed_experts": 64, "vocab_size": 65536}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert {k: body["published_counts"][k] for k in reduced} == {
        k: published[k] for k in reduced}
    assert body["layer_chips"] * body["n_routed_experts"] == \
        published["n_routed_experts"]
    assert 2 * body["vocab_size"] == published["vocab_size"]
    from benchmarks import ssm_moe_reference as bench

    # 2 attention layers x 2 KV heads x (128 + 128) values in bfloat16
    assert bench.cache_bytes_per_token(body) == 2048
    shapes = bench.shapes_of(body)
    share = get_config(body["serve"]["model"])
    assert share.mixer_kinds == shapes["kinds"]
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]
    assert (share.ssm_heads, share.ssm_head_dim, share.ssm_groups,
            share.ssm_state, share.ssm_conv) == (
        shapes["ssm_heads"], shapes["ssm_p"], shapes["ssm_groups"],
        shapes["ssm_n"], shapes["conv"])
    assert (share.ssm_dt_min, share.ssm_dt_max, share.ssm_dt_floor) == (
        shapes["dt_min"], shapes["dt_max"], shapes["dt_floor"])
    assert share.ssm_chunk == body["chunk_size"]
    assert jnp.dtype(ssm_moe.STATE_DTYPE).name == body["state_type"] \
        == "float32"
    assert (share.expert_dim, share.shared_expert_dim) == (
        shapes["expert_ffn"], shapes["shared_ffn"])
    # the cell's clients are the file's slots
    slots = int(body["serve"]["args"][body["serve"]["args"].index(
        "--slots") + 1])
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "agents-closed.json")) as f:
        assert json.load(f)["clients"] == slots


# ---- the tiny cell, in one process ------------------------------------------------

#: (the activations and cache controls of the same cell: through the stack,
#: tests/benchmarks/test_bm_ssm_rehearsal.py, ``slow``)
TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_ssm.py's cell (the ``tiny-ssm-moe-ep2s``
    share in bfloat16 against benchmarks/ssm_moe_reference.py given the same
    share) through the engine in this process: what ``correct`` compares,
    as stated and with each stated precision lowered.  The ladder's
    prefixes reach the chunk program through the pool and the snapshots.
    (Through signal + serve + proxy: tests/benchmarks/
    test_bm_ssm_rehearsal.py, ``slow``.)"""
    from tests.tiny_cell import _ask_in_process

    tiny = _tiny_file()
    from benchmarks import correctness, ssm_moe_reference as bench, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

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

        restores = global_metrics.counter("engine_state_restores_total")
        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16,
                **engine_args),
            tokenizer=Words())
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
        # the ladder went through the snapshots
        assert global_metrics.counter("engine_state_restores_total") \
            > restores
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
    assert stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
