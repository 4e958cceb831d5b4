"""The deployed layout on localhost: ``tunnel signal`` + ``tunnel serve
--backend tpu`` + ``tunnel proxy``, three processes, HTTP against the proxy.

Copied in shape from chip_smoke.py (launcher, tokenizer writer, health
checks) so that later changes to that script cannot move the benchmark.
The parent never imports JAX: the serve process alone holds the chip.  The
serve process is the program's own CLI, started through
``serve_wrapper.py``, which adds only the weight seed and the profiler
switch.
"""

from __future__ import annotations

import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot give a result; the command exits non-zero."""


_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"bench[{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def work_dir(cell: str) -> str:
    """Run-time files of a cell (logs, tokenizer, trace): inside the
    checkout, git-ignored."""
    path = os.path.join(REPO, ".bench_work", cell)
    os.makedirs(path, exist_ok=True)
    return path


def cache_dir() -> str:
    """JAX's persistent compilation cache: one fixed directory inside the
    checkout, whatever the environment says, so that two checkouts share
    nothing and the second run of a cell finds every program."""
    return os.path.join(REPO, ".jax_cache")


def child_env(platform: str, **extra: str) -> Dict[str, str]:
    # PYTHONHASHSEED: with Python's per-process hash randomisation every
    # serve process orders its sets and dicts its own way and schedules a
    # little differently for its whole life; runs of one seed then differ
    # by several percent from process to process while windows inside one
    # process agree to a tenth of a percent (PERF.md, PR 24).
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=REPO,
               PYTHONUNBUFFERED="1", PYTHONHASHSEED="0",
               JAX_COMPILATION_CACHE_DIR=cache_dir())
    # a size cap set for some other cache would evict this one's programs
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    env.update(extra)
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_native() -> None:
    """Build native/build/*.so where missing or stale: the libraries are
    git-ignored, so a fresh checkout has none.  A build that fails fails the
    run: the peers would fall back to their Python codec, and the run would
    time another tunnel than the one deployed."""
    srcs = [os.path.join(REPO, "native", f)
            for f in ("tunnel_frames.cc", "tunnel_arq.cc")]
    libs = [os.path.join(REPO, "native", "build", f)
            for f in ("libtunnelframes.so", "libtunnelarq.so")]
    script = os.path.join(REPO, "scripts", "build-native.sh")
    if not all(os.path.exists(p) for p in srcs + [script]):
        raise BenchFailure("the program's native/ sources are not in this "
                           "directory: nothing to measure")
    newest = max(os.path.getmtime(p) for p in srcs)
    if all(os.path.exists(p) and os.path.getmtime(p) >= newest for p in libs):
        return
    try:
        subprocess.run([script], check=True, stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT)
        say("native: built native/build/*.so")
    except (OSError, subprocess.CalledProcessError) as e:
        out = getattr(e, "stdout", b"") or b""
        raise BenchFailure(f"native: build failed ({e}):\n"
                           + out.decode("utf-8", "replace")[-1500:])


def write_tokenizer(path: str, vocab: int) -> None:
    """A ``vocab``-entry word-level tokenizer file, every id a distinct
    visible word (``w<id>``, none special), which the serve peer loads through
    ``--tokenizer`` as it would a checkpoint's own.  It gives the model its
    published vocabulary and lets the client read every token back."""
    marker = os.path.join(path, f"vocab-{vocab}.ok")
    if os.path.exists(marker):
        return
    from tokenizers import Tokenizer, models, pre_tokenizers

    # No special entries: a token the tokenizer would skip on decoding is
    # a token an SSE client cannot see or count.
    words = {f"w{i}": i for i in range(vocab)}
    tok = Tokenizer(models.WordLevel(words, unk_token="w0"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    # written aside and moved into place whole: another run may be making
    # the same directory at this moment
    aside = f"{path}.{os.getpid()}"
    os.makedirs(aside, exist_ok=True)
    tok.save(os.path.join(aside, "tokenizer.json"))
    with open(os.path.join(aside, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    open(os.path.join(aside, os.path.basename(marker)), "w").close()
    try:
        os.rename(aside, path)
    except OSError:  # the other run was first
        shutil.rmtree(aside, ignore_errors=True)
        if not os.path.exists(marker):
            raise BenchFailure(f"{path} is there without {marker}: remove it")


def word_id(word: str) -> int:
    """The id behind a decoded token of ``write_tokenizer``'s vocabulary."""
    word = word.strip()
    if word.startswith("w") and word[1:].isdigit():
        return int(word[1:])
    raise BenchFailure(f"cannot read a token id from {word!r}")


def http_json(port: int, method: str, path: str, body: Optional[dict] = None,
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


def get_json(port: int, path: str, statuses=(200,)) -> dict:
    status, raw = http_json(port, "GET", path)
    if status not in statuses:
        raise BenchFailure(f"GET {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def healthz(port: int) -> dict:
    """``/healthz`` answers 503 with the same body while it is degraded."""
    return get_json(port, "/healthz", statuses=(200, 503))


def metric_value(text: str, name: str) -> float:
    """One unlabeled sample from a Prometheus exposition."""
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise BenchFailure(f"/metrics carries no {name}")


def counters(port: int, names: List[str]) -> Dict[str, float]:
    text = http_json(port, "GET", "/metrics")[1].decode()
    return {n: metric_value(text, n) for n in names}


class Stack:
    """signal + serve + proxy on localhost."""

    def __init__(self, work: str, platform: str, serve_args: List[str],
                 serve_env: Dict[str, str],
                 proxy_env: Optional[Dict[str, str]] = None):
        self.proxy_env = proxy_env or {}
        self.work = work
        self.platform = platform
        self.serve_args = serve_args
        self.serve_env = serve_env
        self.port = 0
        self.procs: List[subprocess.Popen] = []
        self.serve: Optional[subprocess.Popen] = None

    def _log(self, name: str) -> str:
        return os.path.join(self.work, f"{name}.log")

    def _spawn(self, name: str, argv: List[str],
               env: Dict[str, str]) -> subprocess.Popen:
        with open(self._log(name), "wb") as log:
            proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=log,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
        proc.bench_name = name  # type: ignore[attr-defined]
        self.procs.append(proc)
        return proc

    def tail(self, name: str, n: int = 40) -> str:
        try:
            with open(self._log(name), "rb") as f:
                lines = f.read().splitlines()[-n:]
            return b"\n".join(lines).decode("utf-8", "replace")
        except OSError:
            return "(no log)"

    def log_has(self, name: str, needle: str) -> Optional[str]:
        try:
            with open(self._log(name), "rb") as f:
                for line in f.read().decode("utf-8", "replace").splitlines():
                    if needle in line:
                        return line
        except OSError:
            pass
        return None

    def start(self, deadline_s: float) -> None:
        cli = [sys.executable, "-m", "p2p_llm_tunnel_tpu.cli"]
        sig_port, self.port = free_port(), free_port()
        room = f"bench-{os.getpid()}"
        url = f"ws://127.0.0.1:{sig_port}"
        # signal and proxy run no model: pinned to the CPU, so the serve
        # process is the only one that touches the chip
        self._spawn("signal", cli + ["signal", "--port", str(sig_port)],
                    child_env("cpu"))
        t0 = time.monotonic()
        self.serve = self._spawn(
            "serve",
            [sys.executable, os.path.join(HERE, "serve_wrapper.py"), "serve",
             "--signal", url, "--room", room, "--backend", "tpu"]
            + self.serve_args,
            child_env(self.platform, **self.serve_env))
        # the serve peer joins the room once its engine is built and warm;
        # the proxy starts only then, or it would sit in reconnect back-off
        while not self.log_has("signal", "joined room"):
            if self.serve.poll() is not None:
                raise BenchFailure(
                    f"serve exited with code {self.serve.returncode} before "
                    f"it was ready; its log ends:\n{self.tail('serve')}")
            if time.monotonic() - t0 > deadline_s:
                raise BenchFailure(
                    f"serve not ready after {deadline_s:.0f}s; its log "
                    f"ends:\n{self.tail('serve')}")
            time.sleep(0.25)
        self._spawn("proxy",
                    cli + ["proxy", "--signal", url, "--room", room,
                           "--listen", f"127.0.0.1:{self.port}"],
                    child_env("cpu", **self.proxy_env))
        t1 = time.monotonic()
        while True:
            try:
                status, raw = http_json(self.port, "GET", "/health", timeout=5)
                if status == 200 and raw.strip() == b"ok":
                    return
            except OSError:
                pass
            self.check_alive()
            if time.monotonic() - t1 > 60.0:
                raise BenchFailure("the tunnel never answered /health; proxy "
                                   "log ends:\n" + self.tail("proxy"))
            time.sleep(0.25)

    def check_alive(self) -> None:
        for p in self.procs:
            if p.poll() is not None:
                raise BenchFailure(
                    f"{p.bench_name} exited with code {p.returncode}; its "
                    f"log ends:\n{self.tail(p.bench_name)}")

    def stop(self) -> None:
        """SIGTERM (the serve peer drains), then SIGKILL the process
        group, and wait: no process outlives the run."""
        for proc in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 20.0
        for proc in reversed(self.procs):
            try:
                proc.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait()
        self.procs.clear()


def platform_asked() -> str:
    """The chip, unless the CPU is asked for by name (a rehearsal)."""
    return "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu" else "tpu"


def for_config(config: dict, work: str, platform: str, weight_seed: int,
               extra_args: Optional[List[str]] = None,
               extra_env: Optional[Dict[str, str]] = None,
               proxy_env: Optional[Dict[str, str]] = None) -> Stack:
    """The stack that serves a configuration file's ``serve`` section, with
    the native codec built and the tokenizer of its vocabulary written."""
    serve, vocab = config["serve"], int(config["vocab_size"])
    build_native()
    tok_dir = os.path.join(REPO, ".bench_work", f"tokenizer-{vocab}")
    write_tokenizer(tok_dir, vocab)
    env = dict(serve.get("env", {}), BENCH_WEIGHT_SEED=str(weight_seed))
    env.update(extra_env or {})
    return Stack(work, platform,
                 ["--model", serve["model"], "--tokenizer", tok_dir,
                  "--max-seq", str(serve["max_seq"])] + serve["args"]
                 + list(extra_args or []), env, proxy_env)


def check_health(healthz: dict, want_platform: str) -> dict:
    """The serve process runs where the run says, with no option fenced
    off; returns the ``device`` section."""
    dev = healthz.get("device")
    if not dev:
        raise BenchFailure("/healthz carries no device section")
    if dev["platform"] != want_platform:
        raise BenchFailure(
            f"the serve process reports platform {dev['platform']!r}; this "
            f"run needs {want_platform!r}")
    fences = healthz["config"]["fences"]
    if fences:
        raise BenchFailure(f"config.fences is not empty: {fences}")
    return dev


def check_not_degraded(healthz: dict) -> None:
    """Before any load: the engine calls itself sound."""
    reason = healthz.get("engine_degraded_reason")
    if reason is not None:
        raise BenchFailure(f"engine_degraded_reason = {reason!r} before "
                           "the first request")


def check_no_stall(port: int) -> None:
    """After the load.  ``/healthz`` then says ``degraded`` for three
    things it does not tell apart in ``engine_degraded_reason``, so the
    counters are read instead.  A decode stall (the watchdog saw no token
    for its whole budget) is a fault and fails the run.  The other two are
    the server's opinion of the traffic and are printed: its SLO alert
    (first tokens later than its default 2 s objective: what a cell above
    the knee is for) and its memory-thrash detector (the prefix pool turns
    over faster than conversations end: what unshared traffic does to it).
    Neither changes how a single peer serves."""
    seen = counters(port, ["engine_watchdog_stalls_total",
                           "engine_thrash_trips_total"])
    if seen["engine_watchdog_stalls_total"]:
        raise BenchFailure("the decode-stall watchdog tripped "
                           f"{seen['engine_watchdog_stalls_total']:.0f} "
                           "time(s)")
    say(f"health: 0 decode stalls; memory-thrash detector trips: "
        f"{seen['engine_thrash_trips_total']:.0f}")
