"""``sdar-30b-a3b``'s published shape, the benchmark's copy of the family
and its configuration file, and the tiny cell in one process (the programs:
tests/test_block_diffusion.py; the engine:
tests/test_block_diffusion_engine.py).
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
from tests import block_diffusion_plain as plain
from tests.block_diffusion_tiny import ATOL, _prompt, model


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_published_shape_is_the_preset():
    """The preset carries the published widths and the cut only drops
    layers; what a cached token takes follows."""
    whole, cut = get_config("sdar-30b-a3b"), get_config("sdar-30b-a3b-pp7s")
    assert (whole.dim, whole.n_heads, whole.n_kv_heads, whole.head_dim,
            whole.n_experts, whole.n_experts_per_tok, whole.expert_dim,
            whole.vocab_size, whole.n_layers) == (
        2048, 32, 4, 128, 128, 8, 768, 151936, 48)
    assert whole.qk_norm and whole.block_length == 4
    assert whole.denoise_steps == 2 and whole.mask_token_id == 151669
    assert cut.n_layers == 7 and cut.published_layers == 48
    assert cut.experts_held == (0, 128) and cut.vocab_size == 151936
    assert 7 * 2 * cut.n_kv_heads * cut.head_dim * 2 == 14336


# ---- the benchmark's copy of the family -----------------------------------------

def _tiny_file():
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_bd

    return tinycell_bd


def test_the_benchmarks_reference_is_the_same_model(model):
    """benchmarks/block_diffusion_reference.py draws the program's weights
    from the seed and computes the plain reference's distributions: row
    ``p`` is position ``p + 1``'s, the last row a position past the
    sequence, which its padding cannot move."""
    from benchmarks import block_diffusion_reference as bench

    tiny = _tiny_file()
    config = dict(tiny.CONFIG, vocab_size=259, mask_token_id=258)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, 11)
    cfg, _ = model
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.bfloat16)
    names = {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
             "q_norm": "q_norm", "k_norm": "k_norm", "router": "router",
             "gate": "moe_gate", "up": "moe_up", "down": "moe_down"}
    for theirs, ours in names.items():
        np.testing.assert_array_equal(
            np.asarray(weights["layers"][theirs], np.float32),
            np.asarray(params["blocks"][ours], np.float32), err_msg=theirs)
    for name in ("embed", "lm_head"):
        np.testing.assert_array_equal(np.asarray(weights[name], np.float32),
                                      np.asarray(params[name], np.float32))
    ids = _prompt(5, 24)
    want = np.asarray(plain.denoise_logprobs(cfg, params, ids))
    got = np.asarray(bench.forward_logprobs(shapes, weights, ids))
    np.testing.assert_allclose(got, want[1:], atol=ATOL)
    padded = np.asarray(bench.forward_logprobs(shapes, weights,
                                               ids + [0] * 8))
    np.testing.assert_allclose(padded[:24], got, atol=ATOL)
    assert bench.cache_bytes_per_token(config) == tiny.CACHE_BYTES
    with pytest.raises(ValueError):
        bench.forward_logprobs(shapes, weights, ids[:22])


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row["config"] for row in map(json.loads, f)
                         if row["name"] == "SDAR-30B-A3B-Chat")
    assert body["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        assert body[key] == (7 if key == "num_hidden_layers" else value), key
    assert body["published_counts"] == {"num_hidden_layers": 48}
    assert body["layer_chips"] == 1
    from benchmarks import block_diffusion_reference as bench

    assert bench.cache_bytes_per_token(body) == 14336
    shapes = bench.shapes_of(body)
    cut = get_config(body["serve"]["model"])
    assert (shapes["layers"], shapes["experts"], shapes["top_k"],
            shapes["expert_ffn"], shapes["vocab"], shapes["kv"]) == (
        cut.n_layers, cut.n_experts, cut.n_experts_per_tok, cut.expert_dim,
        cut.vocab_size, cut.n_kv_heads)
    assert (shapes["block"], shapes["group"], shapes["mask"]) == (
        cut.block_length, cut.block_length // cut.denoise_steps,
        cut.mask_token_id)
    assert (shapes["eps"], shapes["theta"]) == (cut.norm_eps, cut.rope_theta)
    for key in ("block_length", "denoising_steps", "remasking", "remainder",
                "echo", "mask_token_id", "qk_norm", "rotary", "slots",
                "max_seq", "prefix_pool_blocks", "tokenizer",
                "in_place_prediction", "commit_pass"):
        assert key in body["assumed"], key


TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
    "activations": ({"quant": "a8"}, None),
    "kv_cache": ({"kv_quant": "int8"}, None),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_bd.py's cell (``tiny-sdar-moe`` in bfloat16
    against benchmarks/block_diffusion_reference.py) through the engine in
    this process: what ``correct`` compares, as stated and with each stated
    precision lowered.  The echoed prompts run through the decode passes,
    the ladder's prefixes reach the first decode pass through chunk prefill
    and the pool.  (Through signal + serve + proxy:
    tests/benchmarks/test_bm_bd_rehearsal.py, ``slow``.)"""
    from tests.tiny_cell import _ask_in_process

    tiny = _tiny_file()
    from benchmarks import block_diffusion_reference as bench
    from benchmarks import correctness, traffic
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
        assert eng.mcfg.mask_token_id == config["mask_token_id"]
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
            pad = seq["tokens"] + [0] * (-len(seq["tokens"]) % 4)
            lp = np.asarray(bench.forward_logprobs(
                shapes, weights, pad, weight_bits=weight_bits))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        pad = seq["tokens"] + [0] * (-len(seq["tokens"]) % 4)
        lp = np.asarray(bench.forward_logprobs(shapes, weights, pad))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    print("\n".join(said))
    assert held is (mode == "stated"), "\n".join(said)
    if mode == "kv_cache":  # by its width alone
        # int8 values and one float32 scale a KV head beside each plane
        assert counted == 3 * 2 * (32 + 2 * 4)
        assert stated == tiny.CACHE_BYTES
    elif mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
