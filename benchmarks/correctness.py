"""Decides ``correct``: the system's log-probabilities against the plain
float32 reference's, on the paths the cells time, and the width of the
cache the program says it keeps.

Three groups of seeded sequences go through the tunnel after the window,
on ``/v1/completions`` (not streamed) with ``logprobs``, eight requests at
a time:

- ``echo``: random prompts with ``echo`` on.  The prompt's
  log-probabilities check the whole-prompt prefill program
  (``echo_prompt``), the generated tokens' check decoding through the cache
  that prefill wrote (``echo_decode``).
- ``traffic``: requests of the run's own plan, cut to ``NEW_TOKENS`` output
  tokens.  They are admitted exactly as the window's traffic was (mux,
  chunked prefill, and in a mix with shared documents the prefix pool), so
  their generated tokens' log-probabilities check the decode program on
  the cache that chunked prefill wrote (``traffic_decode``).
- ``ladder``: every prefix of two long random prompts, eight tokens apart,
  each asked for one token.  A request's first token comes straight from
  the logits of the chunked-prefill program, and the prefixes reach it as
  traffic does, through the prefix pool; the program gives no prompt
  log-probabilities on that path, so the first tokens of many prefixes
  stand in for them (``traffic_prefill``).  The reference scores a whole
  ladder in one forward over the long prompt.

The reference (``reference_child.py``, a process of its own once the serve
process has let the chip go) makes the model from the seed itself;
log-probabilities are compared, never tokens.  The number compared is the
mean absolute difference over a group's positions, some hundreds of them:
steady from seed to seed, where a maximum is not.

An int8 cache reads like the bfloat16 one in every one of these numbers
(PERF.md section 2), so the cache is held to the configuration by its
width instead: the bytes a cached token takes, as the program's own
accounting of its prefix pool gives them on ``/healthz``, are those of the
stated type exactly.

What a cached token holds, which published keys the reference reads and
the reference itself belong to the configuration's model family: a module
the configuration file names (``"reference": "<module>"``, ``reference``
when absent), found beside the data files (``family``).

Limits and their reasons are in the configuration file.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import random
import re
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

from benchmarks import stack
from benchmarks.stack import BenchFailure
from benchmarks.traffic import Plan

HERE = os.path.dirname(os.path.abspath(__file__))

ECHO_SEQUENCES = 8
TRAFFIC_SEQUENCES = 8
NEW_TOKENS = 64
LADDERS = 2
LADDER_TOKENS = 512
LADDER_FIRST, LADDER_STEP = 32, 8
#: Requests in flight while the sequences are asked.
ASK_AT_ONCE = 8
NUMBERS = ("echo_prompt", "echo_decode", "traffic_decode", "traffic_prefill")

#: Bytes of one value of every type a configuration's ``precision`` or a
#: control may name.
TYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
              "int4": 0.5}
#: What every family's module gives (benchmarks/README.md, "A model family").
FAMILY_NAMES = ("REQUIRED_KEYS", "shapes_of", "make_weights",
                "forward_logprobs", "cache_bytes_per_token")


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    """A module found by its file's name under a data root (a family's
    reference, a per-layer reader), loaded once."""
    spec = importlib.util.spec_from_file_location(
        "benchmarks_file_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def family(config: Dict, data: str):
    """The module of the configuration's model family, ``<data>/<name>.py``:
    ``data`` is the benchmark's directory of the root that holds
    BENCHMARK.json, where the readers are found too.  It imports JAX: a run
    asks in the reference's process, never in the one that starts it."""
    name = config.get("reference", "reference")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise BenchFailure(f"a reference is named by a module's name, not "
                           f"{name!r}")
    path = os.path.join(os.path.abspath(data), name + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"the configuration names the reference {name!r}; "
                           f"there is no {path}")
    module = load_module(path)
    lacks = [n for n in FAMILY_NAMES if not hasattr(module, n)]
    if lacks:
        raise BenchFailure(f"{path} gives no {', '.join(lacks)}")
    return module


def check_published(config: Dict, fam) -> None:
    """The published keys that the family's reference reads are whole
    numbers in the file."""
    for key in ("hidden_size", "num_hidden_layers", "vocab_size",
                *fam.REQUIRED_KEYS):
        value = config.get(key)
        if not isinstance(value, int) or isinstance(value, bool):
            raise BenchFailure(
                f"the configuration's {key} is {value!r}: the reference "
                f"{os.path.basename(fam.__file__)} needs a whole number "
                f"there; a model of another family names its own "
                f"`reference`")


def _ids(text: str) -> List[int]:
    return [stack.word_id(w) for w in text.split()]


def sequences(plan: Plan, seed: int, vocab: int, max_seq: int) -> List[Dict]:
    rng = random.Random(int(seed) ^ 0x5EED)
    seqs = []
    for _ in range(ECHO_SEQUENCES):
        n = rng.randint(65, 128)  # one prefill width bucket
        seqs.append({"group": "echo",
                     "prompt": [rng.randrange(3, vocab) for _ in range(n)]})
    pool = [r for r in plan.all_requests()
            if r.prompt_words + NEW_TOKENS <= max_seq]
    if len(pool) < TRAFFIC_SEQUENCES:
        raise BenchFailure("the plan has too few requests that leave room "
                           f"for {NEW_TOKENS} tokens in {max_seq}")
    for req in rng.sample(pool, TRAFFIC_SEQUENCES):
        seqs.append({"group": "traffic", "prompt": _ids(req.prompt)})
    length = min(LADDER_TOKENS, max_seq // 2)
    for _ in range(LADDERS):
        seqs.append({"group": "ladder",
                     "prompt": [rng.randrange(3, vocab)
                                for _ in range(length)]})
    return seqs


def _rungs(seq: Dict) -> List[int]:
    return list(range(LADDER_FIRST, len(seq["prompt"]) + 1, LADDER_STEP))


def _complete(port: int, prompt: List[int], max_tokens: int,
              echo: bool) -> Dict:
    body = {"prompt": " ".join(f"w{t}" for t in prompt),
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
            "logprobs": 0, "echo": echo}
    status, raw = stack.http_json(port, "POST", "/v1/completions", body)
    if status != 200:
        raise BenchFailure(f"/v1/completions -> {status}: {raw[:300]!r}")
    resp = json.loads(raw)
    lp = resp["choices"][0]["logprobs"]
    return {"tokens": [stack.word_id(t) for t in lp["tokens"]],
            "values": lp["token_logprobs"],
            "completed": resp["usage"]["completion_tokens"]}


def ask_engine(port: int, seqs: List[Dict]) -> List[str]:
    """Fill every sequence with what the system said: ``tokens`` (what the
    reference forwards), ``probes`` (position, token: the reference's
    log-probability of ``token`` after ``tokens[..position]`` is wanted),
    and beside each probe the system's value and the part it belongs to.
    Returns the faults found in the responses themselves."""
    jobs = []
    for i, seq in enumerate(seqs):
        if seq["group"] == "ladder":
            jobs += [(i, n, 1, False) for n in _rungs(seq)]
        else:
            jobs.append((i, len(seq["prompt"]), NEW_TOKENS,
                         seq["group"] == "echo"))
        seq.update(tokens=list(seq["prompt"]), probes=[], system=[], parts=[])
    with ThreadPoolExecutor(ASK_AT_ONCE) as pool:
        answers = list(pool.map(
            lambda j: _complete(port, seqs[j[0]]["prompt"][: j[1]], j[2],
                                j[3]), jobs))
    faults = []
    for (i, n, asked, echo), got in zip(jobs, answers):
        seq = seqs[i]
        tokens, values = got["tokens"], got["values"]
        if echo:
            if tokens[:n] != seq["prompt"]:
                faults.append(f"sequence {i}: the echoed prompt differs")
            for t in range(1, n):
                seq["probes"].append((t - 1, tokens[t]))
                seq["system"].append(values[t])
                seq["parts"].append("echo_prompt")
            tokens, values = tokens[n:], values[n:]
        if len(tokens) != asked or got["completed"] != asked:
            faults.append(f"sequence {i}: {len(tokens)} tokens, asked "
                          f"{asked}")
            continue
        if seq["group"] == "ladder":
            seq["probes"].append((n - 1, tokens[0]))
            seq["system"].append(values[0])
            seq["parts"].append("traffic_prefill")
            continue
        seq["tokens"] = seq["prompt"] + tokens
        for j, (tok, value) in enumerate(zip(tokens, values)):
            seq["probes"].append((n - 1 + j, tok))
            seq["system"].append(value)
            seq["parts"].append(seq["group"] + "_decode")
    return faults


def run_reference(config_file: str, data: str, weight_seed: int,
                  seqs: List[Dict], work: str, platform: str,
                  weight_bits: Optional[int] = None) -> Tuple[List, int]:
    """The reference's log-probability at every probe of every sequence,
    and the bytes its family's module says a cached token takes
    (``cache_bytes_stated``, asked in the reference's process).
    ``weight_bits`` None: the weights as the configuration states them; a
    number: the reference's own weights rounded to that many bits (a
    control)."""
    spec = os.path.join(work, "reference_in.json")
    out = os.path.join(work, "reference_out.json")
    with open(spec, "w") as f:
        json.dump({"config": config_file, "data": data, "seed": weight_seed,
                   "platform": platform, "weight_bits": weight_bits,
                   "sequences": [{"tokens": s["tokens"],
                                  "probes": s["probes"]} for s in seqs]}, f)
    if os.path.exists(out):
        os.remove(out)
    log = os.path.join(work, "reference.log")
    with open(log, "wb") as lf:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "reference_child.py"),
             spec, out],
            cwd=stack.REPO, env=stack.child_env(platform), stdout=lf,
            stderr=subprocess.STDOUT, start_new_session=True)
        try:
            code = proc.wait(600)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise BenchFailure("the reference did not end in 600s")
    if code != 0 or not os.path.exists(out):
        with open(log, "rb") as lf:
            tail = lf.read().decode("utf-8", "replace")[-2000:]
        raise BenchFailure(f"the reference exited with code {code}:\n{tail}")
    with open(out) as f:
        said = json.load(f)
    return said["logprobs"], said["cache_bytes_per_token"]


def compare(seqs: List[Dict], reference: List[List[float]]) -> Dict[str, Dict]:
    """Per compared number: mean and largest absolute difference of the
    log-probabilities, and how many positions it covers."""
    diffs: Dict[str, List[float]] = {}
    for seq, ref in zip(seqs, reference):
        for part, got, want in zip(seq["parts"], seq["system"], ref):
            diffs.setdefault(part, []).append(abs(got - want))
    return {k: {"mean_abs": sum(v) / len(v), "max_abs": max(v), "n": len(v)}
            for k, v in diffs.items()}


def cache_bytes_stated(config: Dict, data: str) -> int:
    """Bytes that one cached token takes in every layer, in the type the
    configuration states for the cache: what its family's module says a
    token caches (keys and values of the KV heads, a latent, ...)."""
    fam = family(config, data)
    check_published(config, fam)
    return fam.cache_bytes_per_token(config)


def cache_bytes_counted(port: int, config: Dict) -> Optional[float]:
    """The same, by the program's own accounting of its prefix pool: bytes
    held over blocks used over a block's tokens.  None where the pool holds
    nothing to count."""
    block = int(config["serve"]["kv_block_tokens"])
    for _ in range(20):
        pool = stack.healthz(port).get("prefix_pool") or {}
        used, held = pool.get("blocks_used", 0), pool.get("kv_bytes", 0)
        if used and held % used == 0:  # not read between its two gauges
            return held / used / block
        time.sleep(0.1)
    return None


def judge(numbers: Dict[str, Dict], limits: Dict[str, float],
          counted: Optional[float], stated: int, say) -> bool:
    """Print every number compared beside its limit; all must hold."""
    ok = True
    for name in NUMBERS:
        if name not in numbers:
            say(f"correct: {name} was not compared")
            ok = False
            continue
        got, limit = numbers[name], limits[name]
        holds = got["mean_abs"] <= limit
        ok = ok and holds
        say(f"correct: {name} mean |dlogprob| {got['mean_abs']:.6f} over "
            f"{got['n']} positions (max {got['max_abs']:.6f}), limit "
            f"{limit:g}: {'holds' if holds else 'FAILS'}")
    if counted is None:
        say("correct: cache_bytes_per_token was not compared: the pool "
            "held nothing")
        return False
    holds = counted == stated
    say(f"correct: cache_bytes_per_token {counted:g} by the program's pool "
        f"accounting, {stated} in the type the configuration states, limit "
        f"0 apart: {'holds' if holds else 'FAILS'}")
    return ok and holds
