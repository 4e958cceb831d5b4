"""``tinycell.build``'s root with one more cell, of Laguna-S-2.1's family:
full and window attention layers whose query-head counts differ, a gate a
head on attention's output, routed experts beside a shared one: the
program's ``tiny-laguna-ep2s`` preset (one of 2 chips that share each layer,
rings of 16 positions) served in bfloat16, against
``benchmarks/laguna_moe_reference.py`` given the same share; its per-layer
metrics read the dispatch ledger's counts of the routed layers (every other
one reads the device trace)."""

import json
import os
import shutil

import tinycell

REPO = tinycell.REPO
CELL = "tiny-laguna.tiny-closed"
#: the cell's own per-layer metrics
OWN = ("decode_roofline.codeturns", "moe_experts_roofline.codeturns",
       "window_attn_roofline.codeturns", "window_attn_dev_pct.codeturns",
       "full_attn_dev_pct.codeturns")

#: Laguna-S-2.1's published keys at the size of the ``tiny-laguna`` preset,
#: cut to a share of 2 as the repository's configuration is to one of 8.
CONFIG = {
    "model_type": "laguna", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 6, "num_key_value_heads": 3, "head_dim": 16,
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"] * 2,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "gating_types": ["per_head"] * 8,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 9, 9, 9] * 2,
    "moe_router_logit_softcapping": 0,
    "name": "tiny-laguna",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-laguna-ep2s",
    "reduced": ["num_experts", "vocab_size"],
    "reference": "laguna_moe_reference",
    "published_counts": {"num_experts": 16, "vocab_size": 1024},
    "layer_chips": 2, "chip_index": 0,
    "deployment": "a test: one of 2 chips that share each layer",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    # segments of 16 tokens: as wide as the preset's rings, so a prompt's
    # blocks are saved while the rings hold them and a hit restores a ring
    "serve": {"model": "tiny-laguna-ep2s", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--slots", "4", "--prefill-chunk", "16"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    # echo_prompt has the most positions (about 750) and is the steady one:
    # as stated it reads 0.112 on the CPU (seeds 11 and 12 alike), with
    # 8-bit weights in the reference's place 0.187 (0.167), with int8
    # activations 0.205 (0.188), with int8 planes 0.112 (tests/
    # test_laguna_moe.py, the same cell in one process).  Three times
    # tiny-mla-moe's numbers: seven routed layers where that has three, and
    # a model this narrow routes a token elsewhere on a rounding, with 2.5
    # times an expert's output behind each of its three choices.  Its limit
    # stands 1.25 x over the stated reading and 1.19 x under the weights'
    # control's smaller one.  The other three have 26-512 positions, read
    # 0.12-0.16 as stated and 0.06-0.30 under the controls, and only have
    # to hold.
    "correct": {"limits": {"echo_prompt": 0.14, "echo_decode": 0.3,
                           "traffic_decode": 0.3, "traffic_prefill": 0.3}},
}
#: 8 layers x 3 KV heads x (16 + 16) values, in bfloat16
CACHE_BYTES = 8 * 3 * 32 * 2


def build(root: str) -> str:
    tinycell.build(root)
    data = os.path.join(root, "benchmarks")
    for name in ("laguna_moe_reference.py", "laguna_moe_roofline.py"):
        shutil.copy(os.path.join(REPO, "benchmarks", name), data)
    with open(os.path.join(data, "configs", "tiny-laguna.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "tiny-laguna", "source": CONFIG["source"],
         "file": "benchmarks/configs/tiny-laguna.json",
         "reduced": CONFIG["reduced"], "why": "a test"})
    bench["workloads"].append(
        {"name": CELL, "config": "tiny-laguna", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: head counts by layer kind, a gate a "
                            "head, a shared expert beside a share of the "
                            "routed ones, from files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"] in OWN or m["name"] in (
                "moe_held_share_pct.context", "moe_imbalance.context"):
            m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
