"""``tinycell.build``'s root with one more cell, of the family of a mixer and
then a dense gated MLP a layer (Mamba-2 state-space layers and attention by
a list of layer types, four multipliers, a tied head): the program's
``tiny-ssm-mlp`` preset served whole in bfloat16, against
``benchmarks/granite_hybrid_reference.py``."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-ssm-mlp.tiny-closed"

#: granite-4.0-h-micro's published keys at the size of the ``tiny-ssm-mlp``
#: preset; nothing cut.
CONFIG = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_bias": False, "attention_multiplier": 0.125,
    "embedding_multiplier": 6, "residual_multiplier": 0.4,
    "logits_scaling": 2, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
    "max_position_embeddings": 256, "tie_word_embeddings": True,
    "vocab_size": 512,
    "name": "tiny-ssm-mlp",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-ssm-mlp",
    "reduced": [],
    "reference": "granite_hybrid_reference",
    "state_type": "float32",
    "deployment": "a test: the whole model in one process",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    # segments of 16 tokens = one block: every segment's end is a boundary
    # that gets a snapshot of the state, so the ladder's hits restore one
    "serve": {"model": "tiny-ssm-mlp", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--slots", "4", "--prefill-chunk", "16"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # the limits only have to hold the cell as stated and to fail the
    # weights' control on echo_prompt, which reads 0.0038 as stated and
    # 0.0067 with 8-bit weights at the test's one seed
    # (tests/test_granite_hybrid.py reads both, the same cell in one process)
    "correct": {"limits": {"echo_prompt": 0.005, "echo_decode": 0.05,
                           "traffic_decode": 0.05, "traffic_prefill": 0.05}},
}
#: 1 attention layer x 2 KV heads x (16 + 16) values, in bfloat16
CACHE_BYTES = 1 * 2 * 32 * 2


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    for name in ("granite_hybrid_reference.py", "granite_hybrid_roofline.py"):
        shutil.copy(os.path.join(REPO, "benchmarks", name), data)
    with open(os.path.join(data, "configs", "tiny-ssm-mlp.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-ssm-mlp", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-ssm-mlp.json",
         "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-ssm-mlp", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: a mixer and an MLP a layer, a state a "
                            "slot beside the KV planes, a tied head, from "
                            "files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    # the family's five per-layer metrics, entries since ISSUE 50
    for m in bench["per_layer"]:
        if m["name"].endswith(".assistants"):
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
