#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It stays off JAX: it starts ``tunnel signal``,
``tunnel serve --backend tpu`` (through serve_wrapper.py) and ``tunnel
proxy`` as children, offers the cell's traffic to the proxy's HTTP port,
and reads everything else from what those processes publish.  The last
line of its standard output is the result, one JSON object; a run that
cannot give one exits non-zero and prints none.

Without a TPU the serve process fails at start-up and so does the run,
unless ``JAX_PLATFORMS=cpu`` is set explicitly: then the same control flow
runs on the CPU as a rehearsal and every device metric is absent.
"""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmarks import client, correctness, stack, stats, traffic  # noqa: E402
from benchmarks.stack import BenchFailure, say  # noqa: E402

#: Length of the device trace's window inside the timed window.
TRACE_S = 3.0
#: A cold start at 7B: engine build plus every program compiled from
#: nothing, inside the 1200 s a first run may take.
READY_DEADLINE_S = 900.0
#: Counters read at the window's two ends in every cell: ``correct``'s
#: compile check and the readers that came with the first cells use them.
#: A per-layer metric's file names any other it reads (``"counters"``).
WINDOW_COUNTERS = ["engine_cold_compiles_total",
                   "engine_prefix_hit_tokens_total",
                   "engine_prefill_tokens_total", "engine_tokens_total"]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchFailure(f"BENCHMARK.json has no workload {name!r}")


def metrics_of(bench: dict, group: str, cell: str) -> List[dict]:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Context:
    """What the per-layer readers may read."""

    def __init__(self):
        self.cell = ""
        self.config: dict = {}
        self.mix: dict = {}
        self.plan: Optional[traffic.Plan] = None
        self.load: Optional[client.Load] = None
        self.spans: List[dict] = []
        self.counters: Dict[str, float] = {}
        self.polls: List[tuple] = []
        self.trace: Optional[dict] = None
        self.trace_span: Optional[tuple] = None
        self.device_kind: Optional[str] = None
        self.peaks: Optional[dict] = None


def metric_spec(data: str, name: str) -> dict:
    return load_json(os.path.join(data, "layer_metrics", name + ".json"))


def counters_of(data: str, per_layer: List[dict]) -> List[str]:
    """``WINDOW_COUNTERS`` and, after them, the counters that the cell's
    per-layer metrics name in their own files."""
    names = list(WINDOW_COUNTERS)
    for m in per_layer:
        for name in metric_spec(data, m["name"]).get("counters", []):
            if name not in names:
                names.append(name)
    return names


def read_counters(text: str, names: List[str]) -> Dict[str, float]:
    """The named counters of a ``/metrics`` exposition.  One that a metric's
    file names and this program does not publish is left out (its reader
    then finds nothing to read); ``WINDOW_COUNTERS`` have to be there."""
    out = {}
    for name in names:
        try:
            out[name] = stack.metric_value(text, name)
        except BenchFailure:
            if name in WINDOW_COUNTERS:
                raise
    return out


def _reader(data: str, name: str):
    """The reader module ``layer_metrics/<name>.py`` of the data root."""
    return correctness.load_module(
        os.path.join(os.path.abspath(data), "layer_metrics", name + ".py"))


def read_layer_metrics(ctx: Context, wanted: List[dict],
                       data: str) -> Dict[str, dict]:
    """Each per-layer metric through its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in wanted:
        spec = metric_spec(data, m["name"])
        value = _reader(data, spec["reader"]).read(ctx, **spec.get("args", {}))
        if value is None:
            say(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


async def _window_counters(port: int, names: List[str], t0: float, t1: float,
                           into: Dict[str, float]) -> None:
    async def read() -> Dict[str, float]:
        _, raw = await client.fetch("127.0.0.1", port, "GET", "/metrics")
        return read_counters(raw.decode(), names)

    await client._sleep_until(t0)
    first = await read()
    await client._sleep_until(t1)
    last = await read()
    into.update({n: last[n] - first[n] for n in first if n in last})


async def _trace_window(port: int, names: List[str], serve_pid: int,
                        start: float, length: float,
                        polls: List[tuple]) -> None:
    """Signal the serve process to record its device trace, and poll the
    counters a few times a second around it so that their values at the
    trace's own edges can be read off afterwards."""
    await client._sleep_until(start - 1.0)
    signalled = False
    while time.monotonic() < start + length + 2.0:
        now = time.monotonic()
        if not signalled and now >= start:
            os.kill(serve_pid, signal.SIGUSR1)
            signalled = True
        _, raw = await client.fetch("127.0.0.1", port, "GET", "/metrics")
        polls.append((time.monotonic(), read_counters(raw.decode(), names)))
        await asyncio.sleep(0.2)


def fetch_spans(port: int) -> List[dict]:
    trace = stack.get_json(port, "/healthz?trace=1")
    return [ev for ev in trace.get("traceEvents", [])
            if ev.get("ph") in ("X", "i")]


def reduce_trace(trace_dir: str, work: str) -> Optional[dict]:
    """The trace's reduction, in a process of its own (it imports JAX to
    read the file, on the CPU)."""
    out = os.path.join(work, "trace_summary.json")
    if os.path.exists(out):
        os.remove(out)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir,
         out], cwd=REPO, env=stack.child_env("cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, timeout=240)
    if proc.returncode != 0:
        raise BenchFailure("trace reduction failed:\n"
                           + proc.stdout.decode("utf-8", "replace")[-2000:])
    return load_json(out)


def main(argv=None) -> int:
    try:
        return run(argv)
    except BenchFailure as e:
        # no result line: the driver reads a failed run from the exit code
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 2


def run(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(args.root, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    config_file = os.path.join(args.root, conf_entry["file"])
    config = load_json(config_file)
    # Data files are found by name under the benchmark's directory of the
    # root that holds BENCHMARK.json (the checkout, unless a test says).
    data = os.path.join(args.root, "benchmarks")
    mix = load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    e2e = metrics_of(bench, "end_to_end", cell["name"])
    per_layer = metrics_of(bench, "per_layer", cell["name"])
    definitions = {m["name"]: load_json(os.path.join(
        data, "end_to_end", m["name"] + ".json")) for m in e2e}
    limits = config["correct"]["limits"]
    counter_names = counters_of(data, per_layer)

    platform = stack.platform_asked()
    weight_seed = args.seed % 2147483629
    serve = config["serve"]
    vocab = int(config["vocab_size"])
    work = stack.work_dir(cell["name"])
    trace_dir = os.path.join(work, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_s = min(TRACE_S, args.seconds / 2.0)

    plan = traffic.make_plan(mix, args.seed, args.seconds, vocab)
    if plan.max_context > int(serve["max_seq"]):
        raise BenchFailure(f"the mix reaches {plan.max_context} tokens, the "
                           f"configuration serves {serve['max_seq']}")
    say(f"cell {cell['name']}: seed {args.seed}, {args.seconds:g}s window, "
        f"{plan.loop} loop, platform {platform}, trace {args.trace}")

    trace_env = None
    if args.trace:
        os.makedirs(trace_dir, exist_ok=True)
        trace_env = {"TUNNEL_TRACE": "1", "TUNNEL_TRACE_BUFFER": "262144"}
    st = stack.for_config(
        config, work, platform, weight_seed,
        extra_env=dict(trace_env, BENCH_TRACE_DIR=trace_dir,
                       BENCH_TRACE_S=str(trace_s)) if args.trace else None,
        proxy_env=trace_env)
    ctx = Context()
    ctx.cell, ctx.config, ctx.mix, ctx.plan = cell["name"], config, mix, plan
    try:
        st.start(READY_DEADLINE_S)
        port = st.port
        healthz = stack.healthz(port)
        dev = stack.check_health(healthz, platform)
        stack.check_not_degraded(healthz)
        if platform == "tpu" and dev["count"] < cell["chips"]:
            raise BenchFailure(f"the cell asks for {cell['chips']} chips, "
                               f"JAX reports {dev['count']}")
        say(f"device: platform={dev['platform']} kind={dev['device_kind']!r}"
            f" count={dev['count']}; serve ready, warm-up of "
            f"{healthz.get('warmup_compile_s')}s")

        try:
            client.send_warm(plan, "127.0.0.1", port)
        except RuntimeError as e:
            raise BenchFailure(str(e))
        if plan.warm:
            say(f"sent {len(plan.warm)} shared documents once")

        t0 = time.monotonic() + plan.lead_s
        setup_s = t0 - _PROCESS_START

        async def on_window(w0: float, w1: float) -> None:
            jobs = [_window_counters(port, counter_names, w0, w1,
                                     ctx.counters)]
            if args.trace:
                jobs.append(_trace_window(
                    port, counter_names, st.serve.pid,
                    w0 + (args.seconds - trace_s) / 2.0, trace_s, ctx.polls))
            await asyncio.gather(*jobs)

        load = asyncio.run(client.offer(plan, "127.0.0.1", port,
                                        args.seconds, t0, on_window,
                                        traced=bool(args.trace)))
        ctx.load = load
        st.check_alive()
        say(f"window closed; {len(load.outcomes)} requests sent in all, "
            f"{len(load.sample())} in the sample")

        dev = stack.check_health(stack.healthz(port), platform)
        stack.check_no_stall(port)
        cold = ctx.counters["engine_cold_compiles_total"]
        say(f"compiles inside the window: {cold:.0f} (limit 0)")
        if args.trace:
            ctx.spans = fetch_spans(port)
            done = os.path.join(trace_dir, "done.json")
            waited = time.monotonic()
            while not os.path.exists(done):
                if time.monotonic() - waited > 120:
                    raise BenchFailure("the serve process wrote no trace")
                time.sleep(0.25)
            mark = load_json(done)
            ctx.trace_span = (mark["t0"], mark["t1"])

        seqs = correctness.sequences(plan, args.seed, vocab,
                                     int(serve["max_seq"]))
        faults = correctness.ask_engine(port, seqs)
        counted = correctness.cache_bytes_counted(port, config)
        say("the system has answered the sequences compared")
    finally:
        st.stop()

    # The serve process has let the chip go: the reference takes it.
    say("stack stopped; starting the reference")
    reference, stated = correctness.run_reference(
        config_file, data, weight_seed, seqs, work, platform)
    say("reference done")
    numbers = correctness.compare(seqs, reference)
    if args.trace:
        ctx.trace = reduce_trace(trace_dir, work)

    # ---- the result -----------------------------------------------------
    sample = load.sample()
    bad = [(o.index, o.failed()) for o in load.outcomes if o.failed()]
    for index, why in bad[:10]:
        say(f"request {index} failed: {why}")
    correct = correctness.judge(numbers, limits, counted, stated, say)
    for fault in faults:
        say(f"correct: {fault}")
    correct = correct and not faults and not bad and cold == 0

    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"]}
    peaks = [d["peak_bytes_in_use"] for d in dev["devices"]
             if d.get("peak_bytes_in_use") is not None]
    device["memory_peak_bytes"] = max(peaks) if peaks else None
    ctx.device_kind = dev["device_kind"]
    if platform == "tpu":
        table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
        if dev["device_kind"] not in table:
            raise BenchFailure("peaks.json has no entry for device kind "
                               f"{dev['device_kind']!r}")
        ctx.peaks = table[dev["device_kind"]]

    result = {"correct": bool(correct), "attempted": len(load.outcomes),
              "failed": len(bad), "metrics": {}, "device": device}
    try:
        if args.trace:
            result["metrics"] = read_layer_metrics(ctx, per_layer, data)
            if ctx.trace and ctx.trace.get("busy_s") is not None:
                device["busy_s"] = ctx.trace["busy_s"]
                device["window_s"] = ctx.trace["window_s"]
                result["breakdown"] = {
                    "device_ops": ctx.trace["device_ops"][:10],
                    "idle_gaps": ctx.trace["idle_gaps"][:10]}
        else:
            units = {m["name"]: m["unit"] for m in e2e}
            wanted = {k: d for k, d in definitions.items()
                      if d["kind"] != "setup"}
            values = stats.end_to_end(wanted, sample, load.outcomes, load.t0,
                                      load.t1, plan.timeout_s, say)
            values.update({k: setup_s for k, d in definitions.items()
                           if d["kind"] == "setup"})
            result["metrics"] = {k: {"value": v, "unit": units[k]}
                                 for k, v in values.items()}
    except ValueError as e:
        raise BenchFailure(str(e))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
