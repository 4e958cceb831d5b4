"""``laguna-s-2.1``'s presets, the benchmark's reference on the program's
model, its configuration file, and the tiny cell in one process (the
programs: tests/test_laguna_moe.py; the engine:
tests/test_laguna_moe_engine.py).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import laguna_moe_reference as bench
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import init_params
from tests.laguna_moe_tiny import REPO, _config, _prompt, as_reference, tiny


# ---- the presets, the benchmark's reference and its configuration -----------------

def test_the_published_preset_and_its_share():
    whole, share = get_config("laguna-s-2.1"), get_config("laguna-s-2.1-ep8s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        48, 256, 100352)
    assert whole.attn_kinds == ("full", "window", "window", "window") * 12
    assert whole.layer_kinds == ("dense",) + ("moe",) * 47
    assert share.attn_kinds == ("full", "window", "window", "window") * 2
    assert share.experts_held == (0, 32) and share.n_layers == 8
    assert share.vocab_size * 8 == whole.vocab_size
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "window_heads", "n_kv_heads", "head_dim",
        "v_head_dim", "ffn_dim", "moe_ffn_dim", "shared_expert_dim",
        "n_experts", "n_experts_per_tok", "n_shared_experts",
        "sliding_window", "rotary_dim", "window_rotary_dim", "rope_theta",
        "window_rope_theta", "yarn", "attn_gate", "qk_norm", "router_bias",
        "routed_scale", "router_score")}
    assert same == {f: getattr(share, f) for f in same}
    assert (whole.dim, whole.head_dim, whole.n_kv_heads) == (3072, 128, 8)
    assert (whole.heads_of("full"), whole.heads_of("window")) == (48, 72)
    assert (whole.ffn_dim, whole.moe_ffn_dim, whole.shared_expert_dim) == (
        12288, 1024, 1024)
    assert (whole.n_experts_per_tok, whole.routed_scale) == (10, 2.5)
    # window 512 + the cell's segments of 512
    assert share.ring_default(6144, 512) == 1024
    # the cut's parameters, by the shapes the program would draw
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    count = {k: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    assert count["attn_full"] == pytest.approx(2 * 44.18e6, rel=1e-3)
    assert count["attn_window"] == pytest.approx(6 * 63.13e6, rel=1e-3)
    assert count["dense_ffn"] == pytest.approx(113.2e6, rel=1e-3)
    assert count["blocks"] == pytest.approx(7 * 312.2e6, rel=1e-3)
    assert count["embed"] + count["lm_head"] == 2 * 12544 * 3072
    assert sum(count.values()) == pytest.approx(2.843e9, rel=1e-3)


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_benchmarks_reference_draws_the_programs_model(share):
    """benchmarks/laguna_moe_reference.py draws the program's weights from
    the seed, whole and as a share; with 8-bit weights it reads apart."""
    config = _config(share)
    cfg = get_config("tiny-laguna-ep2s" if share else "tiny-laguna")
    shapes = bench.shapes_of(config)
    assert (shapes["heads_full"], shapes["heads_window"]) == (6, 9)
    assert (len(shapes["freqs_full"]), len(shapes["freqs_window"])) == (4, 8)
    weights = bench.make_weights(shapes, 5)
    mine = as_reference(init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16))
    for group, leaves in mine.items():
        for name, theirs in (leaves.items() if isinstance(leaves, dict)
                             else [(None, leaves)]):
            got = weights[group] if name is None else weights[group][name]
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(theirs, np.float32))
    tokens = _prompt(3, 37)
    plain = np.asarray(bench.forward_logprobs(shapes, weights, tokens))
    rounded = np.asarray(bench.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=8))
    assert 1e-3 < np.abs(rounded - plain).mean() < 0.5
    assert bench.cache_bytes_per_token(config) == tiny.CACHE_BYTES


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(row for row in map(json.loads, f)
                   if row["name"] == "Laguna-S-2.1")
    published = row["config"]
    assert body["source"] == row["source_url"]
    reduced = {"num_hidden_layers": 8, "num_experts": 32, "vocab_size": 12544}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert body["published_counts"] == {k: published[k] for k in reduced}
    assert body["layer_chips"] * body["num_experts"] == published["num_experts"]
    assert body["layer_chips"] * body["vocab_size"] == published["vocab_size"]
    assert set(body["assumed"]) >= {"router_weights", "qk_norm",
                                    "shared_expert", "selection_bias",
                                    "gate_weights", "rotary"}
    # 8 layers x 8 KV heads x (128 + 128) values x 2 B
    assert bench.cache_bytes_per_token(body) == 8 * 4096 == 32768
    shapes = bench.shapes_of(body)
    assert (shapes["held"], shapes["experts"], len(shapes["attn"])) == (
        32, 256, 8)
    share = get_config(body["serve"]["model"])
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]
    assert share.attn_kinds == shapes["attn"]
    assert share.layer_kinds == shapes["ffn"]
    assert (share.heads_of("full"), share.heads_of("window")) == (
        shapes["heads_full"], shapes["heads_window"])
    assert share.yarn.attention_factor == shapes["factor_full"]
    # what a slot holds at the file's serve shape: the second statement
    args = body["serve"]["args"]
    chunk = int(args[args.index("--prefill-chunk") + 1])
    ring = share.ring_default(body["serve"]["max_seq"], chunk)
    assert ring == 1024
    assert 2 * 4096 * body["serve"]["max_seq"] + 6 * 4096 * ring == 75497472


# ---- the tiny cell, in one process ----------------------------------------------

#: (int8 activations and int8 planes, which each start an engine of their
#: own: tests/benchmarks/test_bm_laguna_rehearsal.py, ``slow``)
TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_laguna.py's cell (the ``tiny-laguna-ep2s``
    share in bfloat16 against benchmarks/laguna_moe_reference.py given the
    same share) through the engine in this process: what ``correct``
    compares, as stated and with the reference's own weights rounded to 8
    bits in the program's place.  The tolerances are the file's
    ``correct.limits``, each with its reason there.  (Through signal + serve + proxy: tests/benchmarks/
    test_bm_laguna_rehearsal.py, ``slow``.)"""
    from tests.tiny_cell import _ask_in_process

    from benchmarks import correctness, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    engine_args, weight_bits = TINY_CELL_MODES[mode]
    config, seed = tiny.CONFIG, 11
    limits = config["correct"]["limits"]
    vocab = config["vocab_size"]
    import tinycell

    plan = traffic.make_plan(dict(tinycell.TINY_CLOSED, name="t"), seed, 3,
                             vocab)
    seqs = correctness.sequences(plan, seed, vocab, 256)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, seed)
    stated = bench.cache_bytes_per_token(config)

    def scored(tokens, bits=None):
        """(one length, one program: the mask is causal, so what is added
        after a sequence moves nothing before it)"""
        padded = list(tokens) + [0] * (256 - len(tokens))
        return np.asarray(bench.forward_logprobs(
            shapes, weights, padded, weight_bits=bits))

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
            lp = scored(seq["tokens"], weight_bits)
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = scored(seq["tokens"])
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    assert held is (mode == "stated"), "\n".join(said)
    assert counted == stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
