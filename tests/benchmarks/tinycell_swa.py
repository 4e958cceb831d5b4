"""``tinycell.build``'s root with one more cell, of the family that mixes
window and full attention layers over routed experts: the program's
``tiny-swa-moe-ep2s`` preset (one of 2 chips that share each layer, rings of
16 positions) served in bfloat16, against ``benchmarks/swa_moe_reference.py``
given the same share; its per-layer metrics read the dispatch ledger's counts
of cache rows by layer kind and of the routed layers."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-swa-moe.tiny-closed"

#: MiMo-V2-Flash's published keys at the size of the ``tiny-swa-moe`` preset,
#: cut to a share of 2 as the repository's configuration is to one of 16.
CONFIG = {
    "model_type": "mimo_v2_flash", "hidden_size": 64, "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16, "swa_v_head_dim": 16,
    "sliding_window": 8, "sliding_window_size": 8,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "attention_bias": False,
    "attention_value_scale": 0.707, "partial_rotary_factor": 0.334,
    "rope_theta": 5000000, "swa_rope_theta": 10000,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts": 4, "n_shared_experts": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": None,
    "layernorm_epsilon": 1e-05, "vocab_size": 512,
    "name": "tiny-swa-moe",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-swa-moe-ep2s",
    "reduced": ["n_routed_experts", "vocab_size"],
    "reference": "swa_moe_reference",
    "published_counts": {"n_routed_experts": 8, "vocab_size": 1024},
    "layer_chips": 2, "chip_index": 0,
    "deployment": "a test: one of 2 chips that share each layer",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    # segments of 16 tokens: as wide as the preset's rings, so a prompt's
    # blocks are saved while the rings hold them and a hit restores a ring
    "serve": {"model": "tiny-swa-moe-ep2s", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--slots", "4", "--prefill-chunk", "16"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # echo_prompt has the most positions (about 770) and is the steady one:
    # as stated it reads 0.022 on the CPU (seed 11; 0.028 on seed 12), with
    # 8-bit weights in the reference's place 0.047 (0.040), with int8
    # activations 0.049 (0.057), with int8 planes 0.022 (tests/
    # test_swa_moe.py, the same cell in one process); the other three have
    # a few hundred positions, read 0.013-0.054 in every mode, and only
    # have to hold
    "correct": {"limits": {"echo_prompt": 0.035, "echo_decode": 0.1,
                           "traffic_decode": 0.1, "traffic_prefill": 0.1}},
}
#: 2 full layers x 1 KV head + 5 window layers x 2 KV heads, each head 24 +
#: 16 values, in bfloat16
CACHE_BYTES = (2 * 1 + 5 * 2) * (24 + 16) * 2


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(REPO, "benchmarks", "swa_moe_reference.py"),
                data)
    with open(os.path.join(data, "configs", "tiny-swa-moe.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-swa-moe", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-swa-moe.json",
         "reduced": CONFIG["reduced"], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-swa-moe", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: window rings beside full planes and a "
                            "share of the experts, from files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".longmix") or m["name"] in (
                "moe_held_share_pct.context", "moe_imbalance.context"):
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
