#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate the system sustains.

    python benchmarks/sweep.py --workload <cell> --rates 2,3,4,5,6 --seconds 25

Not part of a run: made once when a cell is defined (and again by a later
benchmark PR when an optimisation has moved the knee).  One stack is
started; each rate is then offered for ``--seconds`` with the cell's own
mix, the system draining between rates.  A rate is sustained when the
requests of the window's last third wait no longer than those of its first
third (the queue does not grow) and every request completes.  The cell's
``rate_rps`` is then written by hand into its traffic file as 0.8 x the
highest sustained rate.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks import client, stack, stats, traffic  # noqa: E402
from benchmarks.run import find_cell, load_json  # noqa: E402
from benchmarks.stack import say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(REPO, entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    platform = stack.platform_asked()
    vocab = int(config["vocab_size"])
    st = stack.for_config(config, stack.work_dir(cell["name"] + ".sweep"),
                          platform, args.seed)
    rows = []
    try:
        st.start(900.0)
        stack.check_health(stack.healthz(st.port), platform)
        warmed = False
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            # a rate given several times is offered from several starting
            # points of its cycle: how far its numbers spread
            plan = traffic.make_plan(dict(mix, rate_rps=rate),
                                     args.seed + i, args.seconds, vocab)
            if not warmed:
                client.send_warm(plan, "127.0.0.1", st.port)
                warmed = True
            t0 = time.monotonic() + plan.lead_s
            load = asyncio.run(client.offer(plan, "127.0.0.1", st.port,
                                            args.seconds, t0))
            sample = sorted(load.sample(), key=lambda o: o.due)
            ttft = stats.ttft_values(sample, plan.timeout_s)
            third = max(1, len(sample) // 3)
            first, last = ttft[:third], ttft[-third:]
            row = {
                "rate_rps": rate, "requests": len(sample),
                "failed": sum(1 for o in load.outcomes if o.failed()),
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p90_ms": stats.percentile(ttft, 90),
                "ttft_first_third_p50_ms": stats.percentile(first, 50),
                "ttft_last_third_p50_ms": stats.percentile(last, 50),
                "tpot_p50_ms": stats.percentile(
                    stats.tpot_values(sample, plan.timeout_s), 50),
                "tpot_p90_ms": stats.percentile(
                    stats.tpot_values(sample, plan.timeout_s), 90),
                "drain_s": max(o.last_token or load.t1
                               for o in load.outcomes) - load.t1,
            }
            rows.append(row)
            say(json.dumps(row))
            st.check_alive()
    finally:
        st.stop()
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
