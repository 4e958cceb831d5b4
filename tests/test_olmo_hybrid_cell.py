"""``olmo-hybrid-7b``'s published preset and its count of parameters, the
configuration file, the benchmark's reference on the program's weights, and
the tiny cell in one process (the programs: tests/test_olmo_hybrid.py; the
engine: tests/test_olmo_hybrid_engine.py).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import olmo_hybrid_reference as bench
from p2p_llm_tunnel_tpu.models import delta, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import init_kv_cache, init_params
from tests.olmo_hybrid_tiny import REPO, SHAPES, _as_reference, tiny


# ---- the published preset -------------------------------------------------------

def test_the_published_preset_counts_4101_m_parameters():
    cfg = get_config("olmo-hybrid-7b")
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # 12 x (88.75 M a delta mixer + 126.81 M an MLP) + 4 x (58.98 M + 126.81
    # M) + 770.7 M of embedding and head (the issue's 4,097.5 M counts a
    # delta mixer at 88.5 M)
    assert abs(count - 4100.8e6) < 1e6, count
    assert cfg.mixer_kinds == "LLL*" * 4 and cfg.published_layers == 32
    # a slot's state: 12 x (30 x 96 x 192 float32 + 3 x 11,520 bfloat16)
    assert ssm_moe.state_bytes_per_slot(cfg) == 12 * (2211840 + 69120) \
        == 27_371_520
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 65, 1024))
    # whole (8, 128) tiles a row: 48 sublanes x 384 lanes a head
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (4, 65, 1024, 3840), "v": (4, 65, 1024, 3840),
        "delta": (12, 65, 30, 48, 384), "dconv": (12, 65, 3 * 11520)}


def test_the_configuration_file_keeps_every_published_key():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row for row in map(json.loads, f)
                         if row["name"] == "Olmo-Hybrid-7B")
    assert body["source"] == published["source_url"]
    assert body["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in published["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    # four whole periods of the published list
    assert body["num_hidden_layers"] == 16
    assert body["layer_types"] == published["config"]["layer_types"][:16]
    shapes = bench.shapes_of(body)
    cfg = get_config(body["serve"]["model"])
    assert "".join("L" if k == "linear_attention" else "*"
                   for k in shapes["kinds"]) == cfg.mixer_kinds
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
            cfg.vocab_size, cfg.norm_eps) == (
        shapes["dim"], shapes["heads"], shapes["kv"], shapes["hd"],
        shapes["ffn"], shapes["vocab"], shapes["eps"])
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.delta_conv, 2.0 if cfg.delta_neg_eigval else 1.0) == (
        shapes["d_heads"], shapes["dk"], shapes["dv"], shapes["conv"],
        shapes["beta_x"])
    assert (cfg.ssm_dt_min, cfg.ssm_dt_max) == (bench.DT_MIN, bench.DT_MAX)
    assert delta.UNIT_EPS == bench.UNIT_EPS
    assert not cfg.tie_embeddings and cfg.mixer_mlp and cfg.norm_after
    assert jnp.dtype(ssm_moe.STATE_DTYPE).name == body["state_type"]
    # 4 attention layers x 2 x 30 KV heads of 128 in bfloat16
    assert bench.cache_bytes_per_token(body) == 61440
    # the cell's clients are the file's slots
    args = body["serve"]["args"]
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "chatturns-closed.json")) as f:
        assert json.load(f)["clients"] == int(
            args[args.index("--slots") + 1])


@pytest.mark.usefixtures("full_optimiser")  # weights held to the bit
def test_the_benchmarks_reference_draws_the_programs_weights():
    cfg = get_config("tiny-delta-mlp")
    weights = bench.make_weights(SHAPES, 5)
    mine = _as_reference(init_params(cfg, jax.random.PRNGKey(5),
                                     jnp.bfloat16))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), weights, mine)
    # the embedding's rows are drawn at a unit RMS (0.88: the truncation)
    assert float(jnp.std(weights["embed"].astype(jnp.float32))) \
        == pytest.approx(0.88, rel=0.05)
    assert bench.cache_bytes_per_token(tiny.CONFIG) == tiny.CACHE_BYTES


# ---- the tiny cell, in one process ------------------------------------------------

@pytest.mark.parametrize("mode", ["stated", "weights"])
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_olmo.py's cell (``tiny-delta-mlp`` in
    bfloat16 against benchmarks/olmo_hybrid_reference.py) through the
    engine in this process: what ``correct`` compares, as stated and with
    the weights' precision lowered.  The ladder's prefixes reach the chunk
    program through the pool and the snapshots.  (Through signal + serve +
    proxy: tests/benchmarks/test_bm_olmo_rehearsal.py, ``slow``.)"""
    from tests.tiny_cell import _ask_in_process

    from benchmarks import correctness, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

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
    weights = bench.make_weights(SHAPES, seed)
    stated = bench.cache_bytes_per_token(config)
    if mode == "stated":
        class Words(ByteTokenizer):
            vocab_size = vocab

        restores = global_metrics.counter("engine_state_restores_total")
        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16),
            tokenizer=Words())
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
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
                SHAPES, weights, seq["tokens"], weight_bits=8))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = np.asarray(bench.forward_logprobs(SHAPES, weights, seq["tokens"]))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    print("\n".join(said))
    assert held is (mode == "stated"), "\n".join(said)
    assert stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
