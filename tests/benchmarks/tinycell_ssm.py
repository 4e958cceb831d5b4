"""``tinycell.build``'s root with one more cell, of the family of one mixer
a layer (Mamba-2 state-space layers, routed experts of two products,
attention): the program's ``tiny-ssm-moe-ep2s`` preset (one of 2 chips that
share each layer) served in bfloat16, against
``benchmarks/ssm_moe_reference.py`` given the same share; its per-layer
metrics read the dispatch ledger's counts of the routed layers."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-ssm-moe.tiny-closed"

#: NVIDIA-Nemotron-3-Nano-30B-A3B's published keys at the size of the
#: ``tiny-ssm-moe`` preset, cut to a share of 2 as the repository's
#: configuration is.
CONFIG = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_hidden_layers": 7,
    "hybrid_override_pattern": "MEM*EM*",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "mlp_bias": False, "use_bias": False,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False,
    "use_conv_bias": True, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 0.0001,
    "intermediate_size": 24, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "mlp_hidden_act": "relu2",
    "n_routed_experts": 4, "num_experts": 4, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.5,
    "norm_eps": 1e-05, "layer_norm_epsilon": 1e-05,
    "tie_word_embeddings": False, "vocab_size": 512,
    "name": "tiny-ssm-moe",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-ssm-moe-ep2s",
    "reduced": ["n_routed_experts", "vocab_size"],
    "reference": "ssm_moe_reference",
    "published_counts": {"n_routed_experts": 8, "vocab_size": 1024},
    "layer_chips": 2, "chip_index": 0, "state_type": "float32",
    "deployment": "a test: one of 2 chips that share each layer",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    # segments of 16 tokens = one block: every segment's end is a boundary
    # that gets a snapshot of the state, so the ladder's hits restore one
    "serve": {"model": "tiny-ssm-moe-ep2s", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--slots", "4", "--prefill-chunk", "16"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # the limits only have to hold the cell as stated and to fail the
    # controls on echo_prompt, the number with the most positions
    # (tests/test_ssm_moe.py reads all four in every mode, the same cell in
    # one process)
    "correct": {"limits": {"echo_prompt": 0.03, "echo_decode": 0.1,
                           "traffic_decode": 0.1, "traffic_prefill": 0.1}},
}
#: 2 attention layers x 2 KV heads x (16 + 16) values, in bfloat16
CACHE_BYTES = 2 * 2 * 32 * 2


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(REPO, "benchmarks", "ssm_moe_reference.py"),
                data)
    with open(os.path.join(data, "configs", "tiny-ssm-moe.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-ssm-moe", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-ssm-moe.json",
         "reduced": CONFIG["reduced"], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-ssm-moe", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: a recurrent state a slot beside the KV "
                            "planes and a share of the experts, from files "
                            "alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".agents") or m["name"] in (
                "moe_held_share_pct.context", "moe_imbalance.context"):
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
