#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, for the program as the
configuration states it and for a control in a lower precision.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3,... \
        [--serve-args "--kv-quant int8"] [--weight-bits 4]

Not part of a run; made on the chip when a limit is set (``How correct is
decided``: read the sound runs' largest and the control's smallest, then
set the limit between them).  One stack and one reference process serve
every seed: the model is that of the first seed, and each seed draws its
own sequences, exactly as a run would.

``--serve-args`` switches on a lower-precision path of the program itself
(``--kv-quant int8``: an int8 cache where the configuration states bf16;
``--quant w8a8``: int8 activations where it states bf16;
``--prefill-act-quant``: int8 activations in prefill alone).  ``--weight-bits
<n>`` puts the reference itself in the program's place with its weights
rounded to ``n`` bits, fewer than the configuration states (4 where it
states int8): no stack is started, the number is the rounded reference
against the plain one.  The last line says for each seed whether ``correct``
would hold under the file's limits.
"""

from __future__ import annotations

import argparse
import json
import os
import shlex
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks import client, correctness, stack, traffic  # noqa: E402
from benchmarks.run import find_cell, load_json  # noqa: E402
from benchmarks.stack import say  # noqa: E402


def pretend(seq: dict, token: int = 3) -> None:
    """The probes ``correctness.ask_engine`` would have left, had a system
    answered ``token`` every time."""
    n = len(seq["prompt"])
    seq.update(tokens=list(seq["prompt"]), probes=[], parts=[])
    if seq["group"] == "ladder":
        seq["probes"] = [(r - 1, token) for r in correctness._rungs(seq)]
        seq["parts"] = ["traffic_prefill"] * len(seq["probes"])
        return
    seq["tokens"] += [token] * correctness.NEW_TOKENS
    if seq["group"] == "echo":
        seq["probes"] = [(t - 1, seq["tokens"][t]) for t in range(1, n)]
        seq["parts"] = ["echo_prompt"] * (n - 1)
    seq["probes"] += [(n - 1 + j, token)
                      for j in range(correctness.NEW_TOKENS)]
    seq["parts"] += [seq["group"] + "_decode"] * correctness.NEW_TOKENS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--serve-args", default="")
    ap.add_argument("--weight-bits", type=int, default=None)
    ap.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = load_json(os.path.join(args.root, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config_file = os.path.join(args.root, entry["file"])
    config = load_json(config_file)
    data = os.path.join(args.root, "benchmarks")
    mix = load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    platform = stack.platform_asked()
    vocab = int(config["vocab_size"])
    weight_seed = seeds[0] % 2147483629
    work = stack.work_dir(cell["name"] + ".control")
    max_seq = int(config["serve"]["max_seq"])
    groups = []
    for seed in seeds:
        plan = traffic.make_plan(mix, seed, bench["run_seconds"], vocab)
        groups.append(correctness.sequences(plan, seed, vocab, max_seq))
    flat = [s for g in groups for s in g]
    counted = None

    if args.weight_bits is not None:
        # the reference in the program's place, weights rounded: it scores
        # the probes a system would have been asked for, with token 3 as
        # every generated token
        for s in flat:
            pretend(s)
        rounded, _ = correctness.run_reference(
            config_file, data, weight_seed, flat, work, platform,
            weight_bits=args.weight_bits)
        for s, lp in zip(flat, rounded):
            s["system"] = list(lp)
    else:
        plan = traffic.make_plan(mix, seeds[0], bench["run_seconds"], vocab)
        # nothing is timed here: compile the warm-up on every core
        st = stack.for_config(config, work, platform, weight_seed,
                              extra_args=shlex.split(args.serve_args),
                              extra_env={"TUNNEL_WARMUP_PAR": "12"})
        try:
            st.start(900.0)
            client.send_warm(plan, "127.0.0.1", st.port)
            for fault in correctness.ask_engine(st.port, flat):
                say(f"fault: {fault}")
            counted = correctness.cache_bytes_counted(st.port, config)
        finally:
            st.stop()
    plain, stated = correctness.run_reference(config_file, data, weight_seed,
                                              flat, work, platform)
    if args.weight_bits is not None:
        counted = stated  # no stack, no pool: only the log-probabilities
    limits = config["correct"]["limits"]
    rows, at = [], 0
    for seed, group in zip(seeds, groups):
        numbers = correctness.compare(group, plain[at: at + len(group)])
        at += len(group)
        held = correctness.judge(numbers, limits, counted, stated,
                                 lambda line: None)
        rows.append({"seed": seed, "correct": held,
                     "cache_bytes_per_token": counted,
                     **{k: v["mean_abs"] for k, v in numbers.items()},
                     **{k + "_max": v["max_abs"]
                        for k, v in numbers.items()}})
        say(json.dumps(rows[-1]))
    print(json.dumps({"workload": args.workload,
                      "serve_args": args.serve_args,
                      "weight_bits": args.weight_bits, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
