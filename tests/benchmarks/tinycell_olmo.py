"""``tinycell.build``'s root with one more cell, of the family of three gated
delta-rule layers in four and full attention the fourth, each followed by a
dense gated MLP, every branch normed after it: the program's
``tiny-delta-mlp`` preset served whole in bfloat16, against
``benchmarks/olmo_hybrid_reference.py``."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-delta-mlp.tiny-closed"
#: the family's five per-layer metrics (and the accepted ones its cell lists)
OWN = ("decode_roofline.chatturns", "delta_step_roofline.chatturns",
       "delta_scan_roofline.chatturns", "delta_dev_pct.chatturns",
       "full_attn_dev_pct.chatturns")

#: Olmo-Hybrid-7B's published keys at the size of the ``tiny-delta-mlp``
#: preset; nothing cut.
CONFIG = {
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 48,
    "intermediate_size": 96, "num_hidden_layers": 8,
    "num_attention_heads": 3, "num_key_value_heads": 3,
    "hidden_act": "silu", "max_position_embeddings": 256,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention",
                    "linear_attention", "full_attention"] * 2,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 16, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "name": "tiny-delta-mlp",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-delta-mlp",
    "reduced": [],
    "reference": "olmo_hybrid_reference",
    "state_type": "float32",
    "deployment": "a test: the whole model in one process",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    # segments of 16 tokens = one block: every segment's end is a boundary
    # that gets a snapshot of the state, so the ladder's hits restore one
    "serve": {"model": "tiny-delta-mlp", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--slots", "4", "--prefill-chunk", "16"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # the limits only have to hold the cell as stated and to fail the
    # weights' control on echo_prompt, which reads 0.036 as stated and 0.12
    # with 8-bit weights at the test's one seed (tests/test_olmo_hybrid.py
    # reads both, the same cell in one process)
    "correct": {"limits": {"echo_prompt": 0.06, "echo_decode": 0.2,
                           "traffic_decode": 0.2, "traffic_prefill": 0.2}},
}
#: 2 attention layers x 3 KV heads x (16 + 16) values, in bfloat16
CACHE_BYTES = 2 * 3 * 32 * 2


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    for name in ("olmo_hybrid_reference.py", "olmo_hybrid_roofline.py"):
        shutil.copy(os.path.join(REPO, "benchmarks", name), data)
    with open(os.path.join(data, "configs", "tiny-delta-mlp.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-delta-mlp", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-delta-mlp.json",
         "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-delta-mlp", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: delta-rule layers and attention, an "
                            "MLP a layer, a matrix state a slot beside the "
                            "KV planes, from files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
