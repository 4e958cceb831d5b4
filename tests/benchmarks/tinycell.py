"""Builds, in a directory of its own, a benchmark root whose cells are made
only of added files: the repository's data files copied as they are, plus a
tiny configuration, tiny mixes, and a BENCHMARK.json that names them.
``benchmarks/run.py --root <dir>`` then runs those cells on the CPU.

One cell is of a second model family, also from added files alone: the
program's ``tiny-moe`` preset served unquantised, whose configuration names
its own reference (``moe_reference.py``, copied from beside this file) and
whose per-layer metric reads counters that only its own file names."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_CONFIG = {
    "model_type": "tiny", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "name": "tiny", "source": "p2p_llm_tunnel_tpu/models/config.py tiny",
    "reduced": [],
    "precision": {"weights": "int8", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    "serve": {"model": "tiny", "max_seq": 256,
              "kv_block_tokens": 16,
              "args": ["--quant", "int8", "--slots", "4"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    "correct": {"limits": {"echo_prompt": 0.05, "echo_decode": 0.05,
                           "traffic_decode": 0.05, "traffic_prefill": 0.05},
                "why": "tiny on the CPU reads about 0.004"},
}

#: The published mixtral names for what the tiny-moe preset holds.
TINY_MOE_CONFIG = {
    "model_type": "mixtral", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "name": "tiny-moe",
    "source": "p2p_llm_tunnel_tpu/models/config.py tiny-moe",
    "reduced": [], "reference": "moe_reference",
    "precision": {"weights": "bfloat16", "activations": "bfloat16",
                  "kv_cache": "bfloat16"},
    "serve": {"model": "tiny-moe", "max_seq": 256, "kv_block_tokens": 16,
              "args": ["--slots", "4"],
              "env": {"TUNNEL_WARMUP_VIEW_CAP": "256"}},
    "correct": {"limits": {"echo_prompt": 0.05, "echo_decode": 0.05,
                           "traffic_decode": 0.05, "traffic_prefill": 0.05},
                "why": "tiny-moe on the CPU reads about 0.005"},
}
MOE_CELL = "tiny-moe.tiny-closed"

TINY_OPEN = {
    "name": "tiny-open", "loop": "open", "rate_rps": 10.0,
    "arrivals": {"dist": "exponential"}, "lead_s": 0.5, "tail_s": 0.5,
    "request_timeout_s": 30.0,
    "shared_prefix": {"documents": 2,
                      "tokens": {"dist": "uniform", "min": 40, "max": 60},
                      "popularity": {"dist": "zipf", "s": 1.0}, "warm": True},
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 40},
    "output_tokens": {"dist": "uniform", "min": 8, "max": 12},
    "who": "a test", "why": "open loop with shared documents, tiny",
}

TINY_CLOSED = {
    "name": "tiny-closed", "loop": "closed", "clients": 3,
    "requests_per_client": 4, "lead_s": 0.5, "tail_s": 0.0,
    "request_timeout_s": 30.0,
    "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
    "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
    "who": "a test", "why": "closed loop, tiny",
}


COUNTER_SHARE = '''"""A counter's growth over the window as a share (%) of the prompt tokens
the client sent in it."""


def read(ctx, counter: str):
    sent = 0
    by_index = {r.index: r for r in ctx.plan.all_requests()}
    for o in ctx.load.outcomes:
        if o.sent is not None and ctx.load.t0 <= o.sent < ctx.load.t1:
            sent += by_index[o.index].prompt_words
    if not sent or counter not in ctx.counters:
        return None
    return 100.0 * ctx.counters[counter] / sent
'''


COUNTER_RATIO = '''"""One counter's growth over the window as a share (%) of another's."""


def read(ctx, over: str, under: str):
    if not ctx.counters.get(under) or over not in ctx.counters:
        return None
    return 100.0 * ctx.counters[over] / ctx.counters[under]
'''


def build(root: str) -> str:
    data = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "layer_metrics", "end_to_end"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub),
                        os.path.join(data, sub))
    # the dense family's module, where a configuration that names no
    # reference finds it: a copy, as the data directories are
    shutil.copy(os.path.join(REPO, "benchmarks", "reference.py"), data)
    # a second family: its reference, its configuration, a reader and a
    # metric whose file names the counters it reads
    shutil.copy(os.path.join(HERE, "moe_reference.py"), data)
    for config in (TINY_CONFIG, TINY_MOE_CONFIG):
        with open(os.path.join(data, "configs", config["name"] + ".json"),
                  "w") as f:
            json.dump(config, f)
    with open(os.path.join(data, "layer_metrics", "counter_ratio.py"),
              "w") as f:
        f.write(COUNTER_RATIO)
    with open(os.path.join(data, "layer_metrics", "decode_fill_ctr_pct.json"),
              "w") as f:
        json.dump({"reader": "counter_ratio",
                   "args": {"over": "engine_decode_row_steps_total",
                            "under": "engine_decode_slot_steps_total"},
                   # the last is one the program does not publish: it
                   # is left out of what the reader sees, and fails nothing
                   "counters": ["engine_decode_row_steps_total",
                                "engine_decode_slot_steps_total",
                                "engine_no_such_counter_total"]}, f)
    for mix in (TINY_OPEN, TINY_CLOSED):
        with open(os.path.join(data, "traffic", mix["name"] + ".json"),
                  "w") as f:
            json.dump(mix, f)
    # an added end-to-end metric and an added per-layer metric, files only
    with open(os.path.join(data, "end_to_end", "ttft_p60_ms.json"), "w") as f:
        json.dump({"kind": "ttft_percentile", "percentile": 60}, f)
    with open(os.path.join(data, "layer_metrics", "late_p50_ms.json"),
              "w") as f:
        json.dump({"reader": "client_late", "args": {"percentile_of": 50}}, f)
    # ... and an added reader with the metric that uses it
    with open(os.path.join(data, "layer_metrics", "counter_share.py"),
              "w") as f:
        f.write(COUNTER_SHARE)
    with open(os.path.join(data, "layer_metrics", "prefix_hit_share.json"),
              "w") as f:
        json.dump({"reader": "counter_share",
                   "args": {"counter": "engine_prefix_hit_tokens_total"}}, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = ["tiny.tiny-open", "tiny.tiny-closed"]
    bench["configs"] = [
        {"name": c["name"], "source": c["source"],
         "file": f"benchmarks/configs/{c['name']}.json", "reduced": [],
         "why": "a test"} for c in (TINY_CONFIG, TINY_MOE_CONFIG)]
    bench["workloads"] = [
        {"name": cells[0], "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "a test"},
        {"name": cells[1], "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "a test"},
        {"name": MOE_CELL, "config": "tiny-moe", "traffic": "tiny-closed",
         "chips": 1, "why": "a test: a second family, from files alone"}]
    bench["end_to_end"] = [
        {"name": "ttft_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock", "workloads": [cells[0]]},
        {"name": "ttft_p60_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock", "workloads": [cells[0]]},
        {"name": "out_tok_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": [cells[1], MOE_CELL]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cells[1] if "closed" in m["name"]
                              else cells[0]]
    # the added reader's metric: no cell of the repository shares documents
    bench["per_layer"].append(
        {"name": "prefix_hit_share", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "KV stores",
         "moves": "ttft_p50_ms", "workloads": [cells[0]]})
    bench["per_layer"].append(
        {"name": "decode_fill_ctr_pct", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "scheduler + loop",
         "moves": "out_tok_per_s", "workloads": [MOE_CELL]})
    bench["per_layer"].append(
        {"name": "late_p50_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "load generator (benchmark's own)",
         "moves": "ttft_p50_ms", "workloads": [cells[0]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
