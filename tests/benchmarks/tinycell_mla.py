"""``tinycell.build``'s root with one more cell, of the latent-attention,
routed-expert family: the program's ``tiny-mla-moe-ep2s`` preset (one of 2
chips that share each layer) served in bfloat16, against
``benchmarks/mla_moe_reference.py`` given the same share; its per-layer
metrics read the routed layers' counts from the dispatch ledger."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-mla-moe.tiny-closed"

#: sarvam-105b's published keys at the size of the ``tiny-mla-moe`` preset,
#: cut to a share of 2 as the repository's configuration is to one of 4.
CONFIG = {
    "model_type": "sarvam_mla", "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": None, "head_dim": 40,
    "kv_lora_rank": 32, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "q_head_dim": 24, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "first_k_dense_replace": 1,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "use_qk_norm": True, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "vocab_size": 512,
    "rope_scaling": {"type": "deepseek_yarn", "factor": 40,
                     "original_max_position_embeddings": 16, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "name": "tiny-mla-moe",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-mla-moe-ep2s",
    "reduced": ["num_experts", "vocab_size"],
    "reference": "mla_moe_reference",
    "published_counts": {"num_experts": 8, "vocab_size": 1024},
    "layer_chips": 2, "chip_index": 0,
    "deployment": "a test: one of 2 chips that share each layer",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    "serve": {"model": "tiny-mla-moe-ep2s", "max_seq": 256,
              "kv_block_tokens": 16, "args": ["--slots", "4"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # echo_prompt has the most positions (about 770) and is the steady one:
    # as stated it reads about 0.038 on the CPU, with 8-bit weights in the
    # reference's place 0.064, with int8 activations 0.074 (tests/
    # test_mla_moe.py, the same cell in one process; a model this narrow
    # routes a token elsewhere on a rounding, which is most of every number
    # here); the other three have a few hundred positions, read 0.014-0.058
    # as stated and only have to hold
    "correct": {"limits": {"echo_prompt": 0.05, "echo_decode": 0.1,
                           "traffic_decode": 0.1, "traffic_prefill": 0.1}},
}
#: 4 layers x (32 latent + 8 rope key) values, in bfloat16
CACHE_BYTES = 4 * 40 * 2


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(REPO, "benchmarks", "mla_moe_reference.py"),
                data)
    with open(os.path.join(data, "configs", "tiny-mla-moe.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-mla-moe", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-mla-moe.json",
         "reduced": CONFIG["reduced"], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-mla-moe", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: latent attention and a share of the "
                            "experts, from files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".context"):
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
