"""From a ``jax.profiler`` trace (``.xplane.pb``) to the device's numbers.

    python benchmarks/trace_reduce.py <trace dir> <out.json>

Reads the file with ``jax.profiler.ProfileData`` (nothing but JAX) and
writes one JSON object:

- ``window_s``: the length of the ``bench_window`` annotation the serve
  wrapper held open while it traced — a fixed wall length on the trace's
  own clock;
- ``busy_s``: seconds of that window in which an operation ran on the
  device (the union of the device's op intervals, clipped to the window,
  so idle time at the window's edges counts), averaged over the devices
  that ran anything;
- ``device_ops``: the operations that took most device time, by name;
- ``idle_gaps``: the longest gaps, each named by the programs on either
  side of it (the program has no host spans on this clock yet);
- ``programs``: for every XLA program (module) that ran whole inside the
  window: how often, its device seconds, and ``body_runs``, the number of
  times its innermost loop body ran — the largest count of any one op name
  inside its runs (an op name is unique in its computation, so it runs
  once for each pass of the loop around it).

The reduction knows nothing of the model: which programs are decode steps
and how many layers a body pass stands for is the per-layer reader's
business.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

WINDOW_NAME = "bench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_planes(path: str) -> Dict[str, Dict[str, List[tuple]]]:
    """plane name -> line name -> [(event name, start_s, end_s)]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[tuple]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = ev.start_ns / 1e9
                events.append((ev.name, start,
                               start + ev.duration_ns / 1e9))
    return planes


def find_window(planes) -> Optional[Interval]:
    for lines in planes.values():
        for events in lines.values():
            for name, start, end in events:
                if name == WINDOW_NAME:
                    return (start, end)
    return None


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(events: List[tuple], window: Interval) -> List[tuple]:
    w0, w1 = window
    return [(name, max(s, w0), min(e, w1)) for name, s, e in events
            if e > w0 and s < w1]


def _module_at(modules: List[tuple], t: float, side: str) -> str:
    """The program that ends (side 'before') or starts (side 'after')
    nearest to ``t``."""
    best, best_d = "window edge", None
    for name, s, e in modules:
        d = (t - e) if side == "before" else (s - t)
        if d >= -1e-9 and (best_d is None or d < best_d):
            best, best_d = name, d
    return best.split("(")[0]


def short_name(hlo: str) -> str:
    """``%fusion.7 = bf16[33,4096]{...} fusion(...)`` -> ``fusion.7
    bf16[33,4096]``: the instruction's name and the type of its result.
    A tuple result (a loop's carry) is left out."""
    name, _, rest = hlo.partition(" = ")
    name = name.strip().lstrip("%")
    if not rest or rest.startswith("("):
        return name[:80]
    return (name + " " + rest.split("{", 1)[0].split(" ", 1)[0])[:80]


def self_times(ops: List[tuple]) -> Dict[str, float]:
    """Device seconds by op name, a loop or a call counted without what
    runs nested inside it (its children are events of their own)."""
    by_name: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []  # [name, end, own seconds]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            by_name[name] += max(own, 0.0)

    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return by_name


def reduce_device(lines: Dict[str, List[tuple]], window: Interval) -> dict:
    ops = clip(lines.get(OPS_LINE, []), window)
    modules = lines.get(MODULES_LINE, [])
    busy = merge([(s, e) for _, s, e in ops])
    busy_s = sum(e - s for s, e in busy)
    by_name = self_times([(short_name(n), s, e) for n, s, e in ops])
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    named_gaps = [
        [f"after {_module_at(modules, s, 'before')} / before "
         f"{_module_at(modules, e, 'after')}", length]
        for length, s, e in gaps[:10]]
    # programs that ran whole inside the window
    programs: Dict[str, dict] = {}
    all_ops = sorted(lines.get(OPS_LINE, []), key=lambda ev: ev[1])
    starts = [ev[1] for ev in all_ops]
    for name, s, e in modules:
        if s < window[0] or e > window[1]:
            continue
        key = name.split("(")[0]
        prog = programs.setdefault(key, {"runs": 0, "device_s": 0.0,
                                         "body_runs": 0})
        prog["runs"] += 1
        prog["device_s"] += e - s
        counts: Dict[str, int] = collections.defaultdict(int)
        i = bisect.bisect_left(starts, s)
        while i < len(all_ops) and all_ops[i][1] < e:
            counts[all_ops[i][0]] += 1
            i += 1
        prog["body_runs"] += max(counts.values()) if counts else 0
    return {"busy_s": busy_s, "by_name": dict(by_name), "gaps": named_gaps,
            "programs": programs, "ops": len(ops)}


def reduce(planes) -> dict:
    window = find_window(planes)
    devices = {name: lines for name, lines in planes.items()
               if name.startswith(DEVICE_PREFIX) and lines.get(OPS_LINE)}
    if window is None or not devices:
        # no device ran anything (a CPU rehearsal): no device numbers
        return {"window_s": (window[1] - window[0]) if window else None,
                "busy_s": None, "device_ops": [], "idle_gaps": [],
                "programs": {}, "devices": sorted(devices)}
    per = {name: reduce_device(lines, window)
           for name, lines in devices.items()}
    by_name: Dict[str, float] = collections.defaultdict(float)
    programs: Dict[str, dict] = {}
    for d in per.values():
        for name, secs in d["by_name"].items():
            by_name[name] += secs / len(per)
        for name, prog in d["programs"].items():
            into = programs.setdefault(name, {"runs": 0, "device_s": 0.0,
                                              "body_runs": 0})
            for k in into:
                into[k] += prog[k]
    top = sorted(by_name.items(), key=lambda kv: kv[1], reverse=True)[:10]
    fullest = max(per.values(), key=lambda d: d["busy_s"])
    return {"window_s": window[1] - window[0],
            "busy_s": sum(d["busy_s"] for d in per.values()) / len(per),
            "device_ops": [[n, s] for n, s in top],
            "idle_gaps": fullest["gaps"],
            "programs": programs, "devices": sorted(per)}


def main(trace_dir: str, out_path: str) -> int:
    summary = reduce(read_planes(newest_xplane(trace_dir)))
    with open(out_path, "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
