"""``tinycell.build``'s root with one more cell, of the family that generates
by masked denoising over blocks: the program's ``tiny-sdar-moe`` preset
(blocks of 4 in 2 denoise passes, the first of which writes the block
before on its way, QK norm, 8 experts top-2) served in bfloat16, against
``benchmarks/block_diffusion_reference.py``; its per-layer metrics read the
dispatch ledger's counts of passes, of tokens decided and of the routed
layers."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-sdar-moe.tiny-closed"

#: SDAR-30B-A3B-Chat's published keys at the size of the ``tiny-sdar-moe``
#: preset, with the three numbers the family's generate.py sets.
CONFIG = {
    "model_type": "sdar_moe", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 512,
    "block_length": 4, "denoising_steps": 2, "mask_token_id": 511,
    "name": "tiny-sdar-moe",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-sdar-moe",
    "reduced": [],
    "reference": "block_diffusion_reference",
    "layer_chips": 1,
    "deployment": "a test: the whole tiny model",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    "serve": {"model": "tiny-sdar-moe", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--slots", "4", "--prefill-chunk", "16"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # echo_prompt has the most positions (about 750) and is the steady one:
    # as stated it reads 0.015 on the CPU (seed 11), with int8 activations
    # 0.049, with 8-bit weights in the reference's place 0.077, with an int8
    # cache 0.033 (tests/test_block_diffusion.py, the same cell in one
    # process); the other three have a few hundred positions
    # (traffic_prefill 26) and only have to hold
    "correct": {"limits": {"echo_prompt": 0.03, "echo_decode": 0.1,
                           "traffic_decode": 0.1, "traffic_prefill": 0.2}},
}
#: 3 layers x 2 KV heads x (16 + 16) values, in bfloat16
CACHE_BYTES = 3 * 2 * 32 * 2
#: The per-layer metrics a CPU run of the cell reports: the ledger's.
LEDGER_METRICS = ("moe_held_share_pct.context", "moe_imbalance.context",
                  "tokens_per_row_pass.blockgen")


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(REPO, "benchmarks",
                             "block_diffusion_reference.py"), data)
    with open(os.path.join(data, "configs", "tiny-sdar-moe.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-sdar-moe", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-sdar-moe.json",
         "reduced": CONFIG["reduced"], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-sdar-moe", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: generation by blocks, from files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"].endswith(".blockgen") or m["name"] in (
                "moe_held_share_pct.context", "moe_imbalance.context"):
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
