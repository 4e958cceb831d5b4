#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serve path still starts on the chip.

Drives the system's main path once, through the entry points a user calls:
three CLI processes on localhost — ``tunnel signal``, ``tunnel serve
--backend tpu`` and ``tunnel proxy`` — and plain HTTP against the proxy
port (no STUN, no network).  The model is mistral-7b at its full published
width (32 layers, 4096 wide, 32:8 heads of 128, FFN 14336, vocabulary
32000, window 4096), int8 weights made from the seed, the CLI's default
engine features, 32 slots, ``max_seq`` 1024.  The vocabulary comes in the
way a deployment brings it — ``--tokenizer`` — from a 32000-entry tokenizer
file this script writes from the same seed.

    python chip_smoke.py              one chip: serve phase, then every Pallas
                                      kernel against the einsum reference
    python chip_smoke.py --four-chip  one four-chip host: --tp 4 against
                                      --tp 1, then --replicas 4; nothing else
    python chip_smoke.py --rehearse-cpu [--four-chip]
                                      the same control flow at `tiny` size on
                                      the CPU (kernels interpreted); never
                                      prints "ok"

One process for each chip: this parent never imports JAX.  The serve
process holds the chip and reports the device in /healthz; the kernel phase
runs in its own child after the serve process has exited.  Without a TPU
the children fail at start-up, this script exits non-zero and prints no
result.  Any failed phase exits non-zero; nothing is skipped.

Last line of a passing run:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
#: Run-time files (logs, the tokenizer); chiprun_out/ is git-ignored and is
#: what the chip tool brings back.
WORK = os.path.join(REPO, "chiprun_out", "chip_smoke")

SEED = 0
VOCAB = 32000  # mistral-7b's published vocabulary
OUT_TOKENS = 64
N_STREAMS = 8
#: Largest prompt + generated context the serve phase sends, given to warmup
#: as TUNNEL_WARMUP_VIEW_CAP so it compiles the kv-view buckets this traffic
#: can reach (views 128/256/512: 6 decode + 9 chunk programs) and no more.
#: Every request's usage is held to it below.
VIEW_CAP = 384
#: One deadline for a cold start at this width: engine init plus ~15
#: programs compiled from nothing, with room to spare inside the script's
#: own 1200 s limit.
READY_DEADLINE_S = 840.0
TOTAL_DEADLINE_S = 1170.0
#: Prompt log-probabilities of --tp 4 against --tp 1: bf16 activations
#: (2^-8 relative) on logits of magnitude ~1-10, through 32 layers whose
#: partial sums the mesh adds in another order.
LOGPROB_ATOL = 0.15

_CHILDREN: List[subprocess.Popen] = []
_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"smoke[{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def fail(msg: str) -> None:
    raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(name: str, argv: List[str], env: Dict[str, str]) -> subprocess.Popen:
    log = open(os.path.join(WORK, f"{name}.log"), "wb")
    proc = subprocess.Popen(
        argv, cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
        start_new_session=True,
    )
    log.close()
    proc.smoke_name = name  # type: ignore[attr-defined]
    _CHILDREN.append(proc)
    return proc


def _stop(proc: subprocess.Popen, grace_s: float = 30.0) -> None:
    """SIGTERM (the serve peer drains), then SIGKILL the process group."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()


def _stop_all() -> None:
    for proc in reversed(_CHILDREN):
        _stop(proc, grace_s=5.0)
    _CHILDREN.clear()


def _tail(name: str, n: int = 40) -> str:
    try:
        with open(os.path.join(WORK, f"{name}.log"), "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode("utf-8", "replace")
    except OSError:
        return "(no log)"


def _log_has(name: str, needle: str) -> Optional[str]:
    try:
        with open(os.path.join(WORK, f"{name}.log"), "rb") as f:
            for line in f.read().decode("utf-8", "replace").splitlines():
                if needle in line:
                    return line
    except OSError:
        pass
    return None


def _child_env(platform: str, **extra: str) -> Dict[str, str]:
    """Children get their platform from this script's mode, never from the
    ambient environment: ``tpu`` (JAX fails at start-up without one) or,
    for the rehearsal, ``cpu``."""
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=REPO,
               PYTHONUNBUFFERED="1")
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# set-up: native codec, tokenizer, compile cache
# ---------------------------------------------------------------------------

def build_native() -> None:
    """Build native/build/*.so from native/*.cc when missing or older than a
    source — the libraries are git-ignored, so a fresh checkout has none.
    A missing compiler is a printed fact, not a silent difference."""
    srcs = [os.path.join(REPO, "native", f)
            for f in ("tunnel_frames.cc", "tunnel_arq.cc")]
    libs = [os.path.join(REPO, "native", "build", f)
            for f in ("libtunnelframes.so", "libtunnelarq.so")]
    newest_src = max(os.path.getmtime(p) for p in srcs)
    stale = any(not os.path.exists(p) or os.path.getmtime(p) < newest_src
                for p in libs)
    if stale:
        try:
            subprocess.run(
                [os.path.join(REPO, "scripts", "build-native.sh")],
                check=True, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
            say("native: built native/build/*.so from native/*.cc")
        except (OSError, subprocess.CalledProcessError) as e:
            out = getattr(e, "stdout", b"") or b""
            say(f"native: build failed ({e}); {out.decode()[-300:]!r}")
    else:
        say("native: native/build/*.so up to date")
    from p2p_llm_tunnel_tpu.protocol import native as frames_native
    from p2p_llm_tunnel_tpu.transport import arq

    say("codec (this tree): frames=%s arq=%s" % (
        "native" if frames_native.available() else "python",
        "native" if arq.native_available() else "python",
    ))


def write_tokenizer(path: str, vocab: int) -> None:
    """A ``vocab``-entry word-level tokenizer file, every id a distinct
    visible word (``w<id>``), loaded by the serve peer through
    ``--tokenizer`` exactly as a checkpoint's own tokenizer would be.  It
    gives the model its published vocabulary and lets a client read every
    generated token back."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    words = {"[UNK]": 0, "<s>": 1, "</s>": 2}
    for i in range(3, vocab):
        words[f"w{i}"] = i
    tok = Tokenizer(models.WordLevel(words, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    os.makedirs(path, exist_ok=True)
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "unk_token": "[UNK]", "bos_token": "<s>", "eos_token": "</s>",
        }, f)


def cache_entries() -> int:
    from p2p_llm_tunnel_tpu.utils.compile_cache import cache_dir

    try:
        return len(os.listdir(cache_dir()))
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# HTTP against the proxy port
# ---------------------------------------------------------------------------

def _request(port: int, method: str, path: str, body: Optional[dict] = None,
             timeout: float = 300.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body).encode()
        headers = {"content-type": "application/json"} if payload else {}
        conn.request(method, path, body=payload, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(port: int, path: str, ok_statuses=(200,)) -> dict:
    status, raw = _request(port, "GET", path)
    if status not in ok_statuses:
        fail(f"GET {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def post_json(port: int, path: str, body: dict) -> dict:
    status, raw = _request(port, "POST", path, body)
    if status != 200:
        fail(f"POST {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def sse_chat(port: int, prompt: str, max_tokens: int) -> dict:
    """One streamed /v1/chat/completions; returns text, usage, finish
    reason, whether the stream ended in [DONE], and client-side timings."""
    body = {
        "messages": [{"role": "user", "content": prompt}],
        "stream": True, "stream_options": {"include_usage": True},
        "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
    }
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600.0)
    t0 = time.monotonic()
    out = {"text": "", "usage": None, "finish": None, "done": False,
           "ttft_s": None, "events": 0}
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps(body),
                     headers={"content-type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            fail(f"chat stream -> {resp.status}: {resp.read()[:300]!r}")
        for raw in resp:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                out["done"] = True
                break
            obj = json.loads(data)
            out["events"] += 1
            if obj.get("usage"):
                out["usage"] = obj["usage"]
            for choice in obj.get("choices") or []:
                piece = (choice.get("delta") or {}).get("content") or ""
                if piece and out["ttft_s"] is None:
                    out["ttft_s"] = time.monotonic() - t0
                out["text"] += piece
                if choice.get("finish_reason"):
                    out["finish"] = choice["finish_reason"]
    finally:
        conn.close()
    out["wall_s"] = time.monotonic() - t0
    return out


def metric_value(text: str, name: str) -> float:
    """One unlabeled sample from a Prometheus exposition."""
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise SmokeFailure(f"/metrics carries no {name}")


# ---------------------------------------------------------------------------
# the three-process stack
# ---------------------------------------------------------------------------

class Stack:
    """signal + serve + proxy on localhost, as scripts/test-local.sh starts
    them, with the in-process engine in place of the mock upstream."""

    def __init__(self, tag: str, platform: str, serve_args: List[str],
                 serve_env: Dict[str, str]):
        self.tag = tag
        self.platform = platform
        self.serve_args = serve_args
        self.serve_env = serve_env
        self.port = 0
        self.ready_wall_s = 0.0
        self.procs: List[subprocess.Popen] = []

    def log(self, name: str) -> str:
        return f"{self.tag}-{name}"

    def start(self, deadline_s: float) -> None:
        cli = [sys.executable, "-m", "p2p_llm_tunnel_tpu.cli"]
        sig_port, self.port = _free_port(), _free_port()
        room = f"chip-smoke-{self.tag}-{os.getpid()}"
        url = f"ws://127.0.0.1:{sig_port}"
        # signal and proxy run no model: pinned to the CPU so that the serve
        # process is the only one that touches the chip.
        self.procs.append(_spawn(
            self.log("signal"), cli + ["signal", "--port", str(sig_port)],
            _child_env("cpu"),
        ))
        t0 = time.monotonic()
        serve = _spawn(
            self.log("serve"),
            cli + ["serve", "--signal", url, "--room", room,
                   "--backend", "tpu"] + self.serve_args,
            _child_env(self.platform, **self.serve_env),
        )
        self.procs.append(serve)
        # The serve peer joins the room once its engine is built and warm.
        # The proxy starts only then: alone in a room it would time out and
        # sit in reconnect back-off when the serve peer finally arrives.
        while not _log_has(self.log("signal"), "joined room"):
            if serve.poll() is not None:
                fail(f"serve exited with code {serve.returncode} before it "
                     f"was ready; its log ends:\n{_tail(self.log('serve'))}")
            if time.monotonic() - t0 > deadline_s:
                fail(f"serve not ready after {deadline_s:.0f}s; its log "
                     f"ends:\n{_tail(self.log('serve'))}")
            time.sleep(0.5)
        self.ready_wall_s = time.monotonic() - t0
        self.procs.append(_spawn(
            self.log("proxy"),
            cli + ["proxy", "--signal", url, "--room", room,
                   "--listen", f"127.0.0.1:{self.port}"],
            _child_env("cpu"),
        ))
        t1 = time.monotonic()
        while True:
            try:
                status, raw = _request(self.port, "GET", "/health", timeout=5)
                if status == 200 and raw.strip() == b"ok":
                    break
            except OSError:
                pass
            for p in self.procs:
                if p.poll() is not None:
                    fail(f"{p.smoke_name} exited with code {p.returncode}; "
                         f"its log ends:\n{_tail(p.smoke_name)}")
            if time.monotonic() - t1 > 60.0:
                fail("tunnel never answered /health; proxy log ends:\n"
                     + _tail(self.log("proxy")))
            time.sleep(0.5)

    def codec_lines(self) -> None:
        for name in ("serve", "proxy"):
            line = _log_has(self.log(name), "codec:")
            if line is None:
                fail(f"{name} never logged which codec it loaded")
            say(f"{name} {line[line.index('codec:'):]}")

    def stop(self) -> None:
        for proc in reversed(self.procs):
            _stop(proc)
            if proc in _CHILDREN:
                _CHILDREN.remove(proc)
        self.procs.clear()


def device_of(healthz: dict, want_platform: str) -> dict:
    dev = healthz.get("device")
    if not dev:
        fail("/healthz carries no device section")
    if dev["platform"] != want_platform:
        fail(f"the serve process reports platform {dev['platform']!r}, "
             f"this run needs {want_platform!r}")
    return dev


def check_health(healthz: dict) -> None:
    if healthz.get("engine_degraded_reason") is not None:
        fail(f"engine_degraded_reason = {healthz['engine_degraded_reason']!r}")
    fences = healthz["config"]["fences"]
    if fences:
        fail(f"config.fences is not empty: {fences}")


def words(rng: random.Random, n: int) -> str:
    return " ".join(f"w{rng.randrange(3, VOCAB)}" for _ in range(n))


# ---------------------------------------------------------------------------
# one chip: the serve phase
# ---------------------------------------------------------------------------

def serve_phase(size: dict) -> dict:
    from p2p_llm_tunnel_tpu.utils.compile_cache import cache_dir

    platform = size["platform"]
    entries_before = cache_entries()
    say(f"compile cache: {cache_dir()} holds {entries_before} entries")
    stack = Stack(
        "one", platform,
        ["--model", size["model"], "--quant", size["quant"],
         "--slots", "32", "--max-seq", "1024",
         "--tokenizer", os.path.join(WORK, "tokenizer")],
        {"TUNNEL_WARMUP_VIEW_CAP": str(VIEW_CAP), "TUNNEL_WARMUP_PAR": "4"},
    )
    stack.start(READY_DEADLINE_S)
    stack.codec_lines()
    port = stack.port

    models = get_json(port, "/v1/models")
    ids = [m.get("id") for m in models.get("data", [])]
    if size["model"] not in ids:
        fail(f"/v1/models does not list {size['model']}: {ids}")
    say(f"/v1/models lists {ids}")

    # After warmup, before any request: the set-up facts.
    healthz = get_json(port, "/healthz")
    dev = device_of(healthz, platform)
    warm_s = healthz["warmup_compile_s"]
    started = healthz["startup"]
    slowest = started["slowest_program"] or {"key": "-", "seconds": 0.0}
    say(f"device: platform={dev['platform']} kind={dev['device_kind']!r} "
        f"count={dev['count']}")
    say(f"set-up: process start to ready {stack.ready_wall_s:.1f}s, of which "
        f"warmup {warm_s:.1f}s for {started['programs']} programs "
        f"(slowest {slowest['key']} {slowest['seconds']:.1f}s; "
        f"{started['persistent_misses']} not in the compile cache)"
        f"; imports + engine init {stack.ready_wall_s - warm_s:.1f}s")

    # Two short prompts through the non-streamed Ollama route.  Shorter
    # than a cache page, so a repeat takes the same programs on the same
    # inputs: greedy output must repeat exactly, and differ between prompts.
    rng = random.Random(SEED)
    short_a, short_b = words(rng, 5), words(rng, 5)

    def generate(prompt: str) -> dict:
        return post_json(port, "/api/generate", {
            "model": size["model"], "prompt": prompt, "stream": False,
            "options": {"num_predict": 16, "temperature": 0},
        })

    first, again, other = generate(short_a), generate(short_a), generate(short_b)
    for r in (first, again, other):
        if not r.get("done") or r.get("eval_count") != 16:
            fail(f"/api/generate: done={r.get('done')} "
                 f"eval_count={r.get('eval_count')}, asked 16")
    if first["response"] != again["response"]:
        fail("the same greedy prompt returned two different texts:\n"
             f"  {first['response']!r}\n  {again['response']!r}")
    if first["response"] == other["response"]:
        fail("two different prompts returned the same text")
    say(f"/api/generate: 16/16 tokens; greedy repeat identical, other "
        f"prompt differs ({first['response'][:40]!r}...)")

    # Eight concurrent SSE streams, a few hundred prompt tokens each; the
    # first four share a 192-token prefix.
    shared = words(rng, 192)
    prompts = [f"{shared} {words(rng, 48)}" for _ in range(4)]
    prompts += [words(rng, 240) for _ in range(N_STREAMS - 4)]
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(N_STREAMS) as pool:
        streams = list(pool.map(
            lambda p: sse_chat(port, p, OUT_TOKENS), prompts))
    wall = time.monotonic() - t0
    for i, s in enumerate(streams):
        usage = s["usage"] or {}
        if not s["done"]:
            fail(f"stream {i} did not end in [DONE]")
        if usage.get("completion_tokens") != OUT_TOKENS:
            fail(f"stream {i}: {usage.get('completion_tokens')} tokens, "
                 f"asked {OUT_TOKENS}")
        if s["finish"] != "length":
            fail(f"stream {i}: finish_reason {s['finish']!r}")
        if usage["prompt_tokens"] + OUT_TOKENS > VIEW_CAP:
            fail(f"stream {i}: context {usage['prompt_tokens']}+{OUT_TOKENS} "
                 f"exceeds the warmup hint {VIEW_CAP}")
    total = sum(s["usage"]["completion_tokens"] for s in streams)
    ttfts = sorted(s["ttft_s"] for s in streams if s["ttft_s"] is not None)
    say(f"streams: {N_STREAMS}/{N_STREAMS} ended in [DONE] with "
        f"{OUT_TOKENS}/{OUT_TOKENS} tokens; prompt tokens "
        f"{[s['usage']['prompt_tokens'] for s in streams]}")
    say(f"smoke timings (not metrics): {total} tokens in {wall:.2f}s wall, "
        f"first visible delta {ttfts[0]:.2f}s..{ttfts[-1]:.2f}s")
    # A resent long prompt is served from cached pages plus a short tail —
    # other programs than the first time — so agreement is printed only.
    resent = sse_chat(port, prompts[0], OUT_TOKENS)
    say("resent stream 0 (prefix-cache hit): text "
        + ("identical" if resent["text"] == streams[0]["text"] else "differs"))

    healthz = get_json(port, "/healthz")
    check_health(healthz)
    metrics = _request(port, "GET", "/metrics")[1].decode()
    cold = metric_value(metrics, "engine_cold_compiles_total")
    if cold != 0:
        fail(f"engine_cold_compiles_total = {cold:.0f} after warmup")
    dev = device_of(healthz, platform)
    attention = healthz["config"]["attention"]
    for family in ("prefill", "chunk", "decode"):
        say(f"attention branch, {family}: "
            f"{', '.join(attention[family]) if family in attention else 'did not run'}")
    if "decode" not in attention or "chunk" not in attention:
        fail(f"decode and chunk prefill must have run: {attention}")
    say("health: engine_degraded_reason null, config.fences empty, "
        "0 cold compiles after warmup, prefix hit tokens "
        f"{metric_value(metrics, 'engine_prefix_hit_tokens_total'):.0f}")
    for d in dev["devices"]:
        say(f"device {d['id']}: bytes_in_use={d['bytes_in_use']} "
            f"peak_bytes_in_use={d['peak_bytes_in_use']}")
    stack.stop()
    entries_after = cache_entries()
    say(f"compile cache: {entries_after} entries "
        f"(+{entries_after - entries_before})")
    return dev


# ---------------------------------------------------------------------------
# one chip: every Pallas kernel against the einsum reference (own process)
# ---------------------------------------------------------------------------

def kernel_phase(size: dict) -> None:
    argv = [sys.executable, os.path.abspath(__file__), "--kernels-child"]
    if size["platform"] == "cpu":
        argv.append("--rehearse-cpu")
    proc = _spawn("kernels", argv, _child_env(size["platform"]))
    try:
        code = proc.wait(TOTAL_DEADLINE_S)
    except subprocess.TimeoutExpired:
        fail("kernel phase overran; its log ends:\n" + _tail("kernels"))
    for line in _tail("kernels", 200).splitlines():
        if line.startswith("kernel"):
            say(line)
    if code != 0:
        fail(f"kernel phase exited with code {code}; its log ends:\n"
             + _tail("kernels"))
    _CHILDREN.remove(proc)


def kernels_child(rehearse: bool) -> None:
    """Each kernel the TPU compiler accepts, once, against the einsum path
    of the same model functions on the same inputs: mistral-7b widths with
    the depth cut to two layers (the layer index still selects a cache
    plane), bf16, a cache of random content in each KV form."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_llm_tunnel_tpu.cli import require_tpu_backend
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import (
        chunk_prefill_into_cache,
        decode_attention_branch,
        decode_step,
        init_kv_cache,
        init_params,
        prefill,
        prefill_attention_branch,
        ragged_prefill_into_cache,
    )
    from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
        plan_ragged_group,
    )
    from p2p_llm_tunnel_tpu.utils.compile_cache import enable

    enable()
    require_tpu_backend(jax.default_backend(), "chip_smoke.py kernel phase")
    if rehearse:
        # head_dim 128: the kernels' gates want the lane width even when
        # interpreted.
        base = get_config("tiny", vocab_size=512, head_dim=128,
                          flash_interpret=True)
        rows, seq, view, t_flash, tail = 3, 256, 256, 128, 32
    else:
        base = get_config("mistral-7b", n_layers=2)
        rows, seq, view, t_flash, tail = 33, 1024, 512, 1024, 128
    ref_cfg = dataclasses.replace(base, flash=False)
    params = init_params(base, jax.random.PRNGKey(SEED), jnp.bfloat16)
    rng = np.random.default_rng(SEED)
    failures = []

    def report(name: str, got, want, ulps: int = 8) -> None:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        # bf16 tolerance, fixed before any chip run: 8 units of bf16
        # resolution (2^-8) of the largest reference logit.  Twice that
        # over a quantized cache: kernel and reference each quantize the
        # new rows, and a rounding tie moves a stored value by a whole
        # quantization step (1/7 of the row's largest value in int4).
        tol = ulps * 2.0**-8 * max(1.0, float(np.max(np.abs(want))))
        ok = np.isfinite(got).all() and err <= tol
        print(f"kernel {name}: max-abs error {err:.4g} (tolerance {tol:.4g})"
              f" {'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            failures.append(name)

    def random_cache(kv: Optional[str]):
        cache = init_kv_cache(base, rows, seq, jnp.bfloat16, quant=kv or "none")
        out = {}
        for name, leaf in cache.items():
            if leaf.dtype == jnp.int8:
                out[name] = jnp.asarray(
                    rng.integers(-128, 128, leaf.shape, dtype=np.int8))
            elif name.endswith("_scale"):
                # Dequantized magnitudes of order one in either form.
                unit = 1 / 4.0 if kv == "int4" else 1 / 73.0
                out[name] = jnp.asarray(
                    rng.uniform(0.5, 1.5, leaf.shape).astype(np.float32) * unit)
            else:
                out[name] = jnp.asarray(
                    rng.standard_normal(leaf.shape, np.float32), leaf.dtype)
        return out

    # -- whole-prompt prefill: flash_causal_attention -----------------------
    assert prefill_attention_branch(base, None, t_flash) == "pallas-flash"
    assert prefill_attention_branch(ref_cfg, None, t_flash) == "einsum"
    tokens = jnp.asarray(rng.integers(3, base.vocab_size, (2, t_flash)), jnp.int32)
    valid = jnp.arange(t_flash)[None, :] < jnp.asarray([[t_flash], [t_flash - 37]])
    run_prefill = jax.jit(prefill, static_argnums=(0,))
    got = run_prefill(base, params, tokens, valid)[0]
    want = run_prefill(ref_cfg, params, tokens, valid)[0]
    keep = np.asarray(valid)
    report(f"flash_causal_attention T={t_flash}",
           np.asarray(got, np.float32)[keep], np.asarray(want, np.float32)[keep])

    live = rows - 1  # the last row is the engine's scratch slot
    pos = jnp.asarray(np.linspace(1, view - 8, rows).astype(np.int32))
    toks = jnp.asarray(rng.integers(3, base.vocab_size, (rows,)), jnp.int32)
    run_decode = jax.jit(decode_step, static_argnums=(0,),
                         static_argnames=("kv_view",))
    run_chunk = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                        static_argnames=("kv_view",))
    run_ragged = jax.jit(
        ragged_prefill_into_cache, static_argnums=(0,),
        static_argnames=("block_q", "max_row_blocks", "interpret"))
    # Ragged group: every live prefill row a tail of another length, starts
    # on page boundaries — the shapes one mux iteration assembles.
    block_q, n_rows = 16, min(8, live)
    entries = [(i, 16 * (i + 1), tail - 5 * i) for i in range(n_rows)]
    tot = n_rows * tail
    plan = plan_ragged_group(entries, block_q, tot, rows - 1, tail // block_q)
    slot_of, start_of, qoff_of, _qlen, base_of, offs = plan
    flat = np.zeros((tot,), np.int32)
    padded = np.zeros((n_rows, tail), np.int32)
    sample_idx = np.zeros((n_rows,), np.int32)
    for i, ((_slot, _start, n), off) in enumerate(zip(entries, offs)):
        padded[i, :n] = rng.integers(3, base.vocab_size, n)
        flat[off:off + n] = padded[i, :n]
        sample_idx[i] = off + n - 1

    for kv in (None, "int8", "int4"):
        cache = random_cache(kv)
        ulps = 16 if kv else 8
        assert decode_attention_branch(ref_cfg, None, view, kv) == "einsum"
        if kv is None:
            # the default read of the plain cache: no option selects it
            assert decode_attention_branch(base, None, view) == "pallas-rows"
            ref_logits, _ = run_decode(
                ref_cfg, params, cache, toks, pos, kv_view=view)
            logits, _ = run_decode(base, params, cache, toks, pos, kv_view=view)
            report(f"decode_attention_rows kv=bf16 view={view}",
                   logits[:live], ref_logits[:live], ulps)
        got, _ = run_ragged(
            base, params, jnp.asarray(flat), jnp.asarray(slot_of),
            jnp.asarray(start_of), jnp.asarray(qoff_of), jnp.asarray(base_of),
            jnp.asarray(sample_idx), cache, block_q=block_q,
            max_row_blocks=tail // block_q,
            interpret=base.flash_interpret,
        )
        want, _ = run_chunk(
            ref_cfg, params, jnp.asarray(padded),
            jnp.asarray([n for _s, _st, n in entries], jnp.int32),
            jnp.asarray([st for _s, st, _n in entries], jnp.int32),
            cache, jnp.arange(n_rows), kv_view=seq,
        )
        report(f"ragged_prefill_attention kv={kv or 'bf16'} block_q={block_q}",
               got, want, ulps)
    # -- the rows kernel over planes of KV heads side by side (ISSUE 36): a
    # decode step of the window + full family, its full layers on the kernel
    swa = (get_config("tiny-swa-moe", flash_interpret=True) if rehearse else
           get_config("mimo-v2-flash-ep16s", n_experts=16, layer_chips=1,
                      moe_ffn_dim=256, ffn_dim=1024, vocab_size=1024))
    swa_ref = dataclasses.replace(swa, flash=False)
    assert decode_attention_branch(swa, None, view, None, seq) == "pallas-rows"
    assert decode_attention_branch(swa_ref, None, view, None, seq) == "einsum"
    swa_params = init_params(swa, jax.random.PRNGKey(SEED), jnp.bfloat16)
    cache = {name: jnp.asarray(rng.standard_normal(leaf.shape, np.float32),
                               leaf.dtype)
             for name, leaf in init_kv_cache(swa, rows, seq).items()}
    swa_toks = jnp.asarray(rng.integers(3, swa.vocab_size, (rows,)), jnp.int32)
    logits, _ = run_decode(swa, swa_params, cache, swa_toks, pos, kv_view=seq)
    want, _ = run_decode(swa_ref, swa_params, cache, swa_toks, pos, kv_view=seq)
    report(f"decode_attention_rows planes of {swa.kv_heads_of('full')} x "
           f"{swa.head_dim}/{swa.v_head_dim} seq={seq}", logits[:live],
           want[:live])
    # -- the grouped expert product (ISSUE 39) against ragged_dot: the middle
    # layer's groups of a stack of three, an empty group and a long one
    from p2p_llm_tunnel_tpu.ops.pallas_grouped_matmul import (
        grouped_matmul,
        visit_list,
    )

    held, k, n = (4, 128, 128) if rehearse else (16, 2048, 768)
    sizes = np.asarray(([3, 0, 150, 7] * (held // 4)), np.int32)
    m = int(sizes.sum()) + 24  # rows past the held groups
    lhs = jnp.asarray(rng.standard_normal((m, k), np.float32), jnp.bfloat16)
    experts = jnp.asarray(
        rng.standard_normal((3 * held, k, n), np.float32) * k ** -0.5,
        jnp.bfloat16)
    stacked = np.zeros(3 * held, np.int32)
    stacked[held:2 * held] = sizes
    for out in (jnp.float32, jnp.bfloat16):
        got = grouped_matmul(lhs, experts, visit_list(jnp.asarray(sizes), held),
                             out_dtype=out, interpret=rehearse)
        want = jax.lax.ragged_dot(lhs, experts, jnp.asarray(stacked),
                                  preferred_element_type=out)
        report(f"moe_grouped_rows [{m},{k}]x[{3 * held},{k},{n}] "
               f"-> {jnp.dtype(out).name}", got[:m - 24], want[:m - 24])
    if failures:
        raise SystemExit(f"kernels outside tolerance: {failures}")


# ---------------------------------------------------------------------------
# four chips: --tp 4 against --tp 1, then --replicas 4
# ---------------------------------------------------------------------------

def score(port: int, model: str, prompt: str) -> dict:
    """echo + logprobs on /v1/completions: the prompt's log-probabilities
    and 32 greedy tokens (the surface tests/test_echo_logprobs.py covers)."""
    resp = post_json(port, "/v1/completions", {
        "model": model, "prompt": prompt, "max_tokens": 32, "temperature": 0,
        "ignore_eos": True, "echo": True, "logprobs": 0,
    })
    lp = resp["choices"][0]["logprobs"]
    n_prompt = resp["usage"]["prompt_tokens"]
    return {
        "prompt_lps": lp["token_logprobs"][1:n_prompt],
        "greedy": lp["tokens"][n_prompt:],
    }


def four_chip_phase(size: dict) -> dict:
    platform = size["platform"]
    common = ["--model", size["model"], "--quant", size["quant"],
              "--slots", "32", "--max-seq", "1024",
              "--tokenizer", os.path.join(WORK, "tokenizer")]
    # Short prompts here: 128 covers them, so warmup compiles two views.
    env = {"TUNNEL_WARMUP_VIEW_CAP": "128", "TUNNEL_WARMUP_PAR": "4"}
    if platform == "cpu":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    prompt = words(random.Random(SEED), 48)
    scored = {}
    dev = {}
    mesh_tp = size["tp"]
    for tp in (mesh_tp, 1):
        stack = Stack(f"tp{tp}", platform, common + ["--tp", str(tp)], env)
        stack.start(READY_DEADLINE_S)
        healthz = get_json(stack.port, "/healthz")
        scored[tp] = score(stack.port, size["model"], prompt)
        after = get_json(stack.port, "/healthz")
        check_health(after)
        stack.stop()
        if tp == mesh_tp:
            dev = device_of(healthz, platform)
            if dev["count"] != 4:
                fail(f"--four-chip needs four devices, found {dev['count']}")
            used = [d["bytes_in_use"] for d in dev["devices"]]
            say(f"--tp {tp} after init: bytes_in_use per device {used}; device 0 "
                f"peak_bytes_in_use {dev['devices'][0]['peak_bytes_in_use']}")
            if platform == "tpu":
                if min(used) <= 0 or max(used) > 1.25 * min(used):
                    fail(f"--tp 4: per-device bytes not within 25 %: {used}")
            say(f"--tp {tp} attention branches: {after['config']['attention']}")
    a, b = scored[mesh_tp]["prompt_lps"], scored[1]["prompt_lps"]
    if len(a) != len(b) or not a:
        fail(f"prompt logprob lists differ in length: {len(a)} vs {len(b)}")
    worst = max(abs(x - y) for x, y in zip(a, b))
    agree = sum(x == y for x, y in
                zip(scored[mesh_tp]["greedy"], scored[1]["greedy"]))
    say(f"--tp {mesh_tp} vs --tp 1: {len(a)} prompt log-probabilities, max-abs "
        f"difference {worst:.4g} (tolerance {LOGPROB_ATOL}); greedy agreement "
        f"{agree}/{len(scored[1]['greedy'])} tokens (printed, not asserted)")
    if not worst <= LOGPROB_ATOL:
        fail(f"--tp {mesh_tp} prompt log-probabilities disagree with one chip")

    stack = Stack("dp4", platform, common + ["--replicas", "4"], env)
    stack.start(READY_DEADLINE_S)
    rng = random.Random(SEED + 1)
    with concurrent.futures.ThreadPoolExecutor(16) as pool:
        streams = list(pool.map(
            lambda p: sse_chat(stack.port, p, 32),
            [words(rng, 40) for _ in range(16)]))
    for i, s in enumerate(streams):
        if not s["done"] or (s["usage"] or {}).get("completion_tokens") != 32:
            fail(f"--replicas 4: stream {i} incomplete: {s['usage']}")
    healthz = get_json(stack.port, "/healthz")
    check_health(healthz)
    rdev = device_of(healthz, platform)
    stack.stop()
    placed = rdev.get("engines")
    say(f"--replicas 4: 16/16 streams complete; engines resident on devices "
        f"{placed}; bytes_in_use per device "
        f"{[d['bytes_in_use'] for d in rdev['devices']]}")
    if placed != [[0], [1], [2], [3]]:
        fail("--replicas 4: every replica's weights and cache (where its "
             f"last dispatch left it) must sit on its own device: {placed}")
    if platform == "tpu":
        used = [d["bytes_in_use"] for d in rdev["devices"]]
        if min(used) < 0.5 * max(used):
            fail(f"--replicas 4: a device holds no replica: {used}")
    return dev


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip paths: --tp 4 against "
                         "--tp 1, then --replicas 4")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the same control flow at tiny size on the CPU; "
                         "proves nothing about the chip and never prints ok")
    ap.add_argument("--kernels-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.kernels_child:
        kernels_child(args.rehearse_cpu)
        return 0

    if not os.path.isdir(os.path.join(REPO, "p2p_llm_tunnel_tpu")):
        print("chip_smoke.py: no p2p_llm_tunnel_tpu package beside this "
              "script; it drives the repository's own entry points",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    os.makedirs(WORK, exist_ok=True)
    if args.rehearse_cpu:
        # tiny has two kv heads, so its mesh rehearsal is --tp 2.
        size = {"platform": "cpu", "model": "tiny", "quant": "none", "tp": 2}
    else:
        size = {"platform": "tpu", "model": "mistral-7b", "quant": "int8",
                "tp": 4}

    def overrun() -> None:
        print(f"chip_smoke.py: still running after {TOTAL_DEADLINE_S:.0f}s; "
              "giving up", file=sys.stderr, flush=True)
        _stop_all()
        os._exit(3)

    timer = threading.Timer(TOTAL_DEADLINE_S, overrun)
    timer.daemon = True
    timer.start()
    try:
        build_native()
        write_tokenizer(os.path.join(WORK, "tokenizer"), VOCAB)
        say(f"model {size['model']} ({size['quant']} weights, seed {SEED}), "
            f"children on JAX_PLATFORMS={size['platform']}")
        if args.four_chip:
            dev = four_chip_phase(size)
        else:
            dev = serve_phase(size)
            before = cache_entries()
            kernel_phase(size)
            # Small eager programs here compile in about the second below
            # which JAX caches nothing, so a warm run can still add a few.
            after = cache_entries()
            say(f"compile cache: {after} entries "
                f"(+{after - before} in the kernel phase)")
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        timer.cancel()
        _stop_all()
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"]}
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal_ok": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
