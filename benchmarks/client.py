"""Raw HTTP/1.1 + SSE client on asyncio, and the loops that offer the load.

Standard library only (copied in shape from scripts/loadgen.py, whose
closed-loop driver and nearest-rank arithmetic are not used).  One process,
one thread, one event loop: the load comes from a single core, and how late
it ran is reported with every run.
"""

from __future__ import annotations

import asyncio
import json
import re
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.stats import Outcome
from benchmarks.traffic import Plan, Request


_WORD = re.compile(r"w\d+")


async def _read_headers(reader) -> Tuple[int, Dict[str, str]]:
    status_line = await reader.readline()
    if not status_line:
        raise ConnectionError("empty response")
    status = int(status_line.decode("latin-1").split(" ", 2)[1])
    headers: Dict[str, str] = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers


async def _iter_body(reader, headers):
    """Body chunks of a chunked or content-length response."""
    if "chunked" in headers.get("transfer-encoding", "").lower():
        while True:
            size = int((await reader.readline()).strip().split(b";")[0], 16)
            if size == 0:
                await reader.readline()
                return
            data = await reader.readexactly(size)
            await reader.readexactly(2)
            yield data
    else:
        n = int(headers.get("content-length", "0") or "0")
        if n:
            yield await reader.readexactly(n)


def _http_request(host: str, port: int, method: str, path: str,
                  body: Optional[bytes], extra: str = "") -> bytes:
    head = (f"{method} {path} HTTP/1.1\r\nhost: {host}:{port}\r\n"
            f"connection: close\r\n{extra}")
    if body is not None:
        head += ("content-type: application/json\r\n"
                 f"content-length: {len(body)}\r\n")
    return head.encode() + b"\r\n" + (body or b"")


async def fetch(host: str, port: int, method: str, path: str,
                payload: Optional[dict] = None,
                timeout: float = 120.0) -> Tuple[int, bytes]:
    """One whole request and response (not streamed)."""
    body = None if payload is None else json.dumps(payload).encode()

    async def inner():
        reader, writer = await asyncio.open_connection(host, port)
        try:
            writer.write(_http_request(host, port, method, path, body))
            await writer.drain()
            status, headers = await _read_headers(reader)
            data = b""
            async for chunk in _iter_body(reader, headers):
                data += chunk
            return status, data
        finally:
            writer.close()

    return await asyncio.wait_for(inner(), timeout)


def trace_id_of(index: int) -> str:
    """The trace id a traced run gives request ``index``: the program's
    spans of that request then carry it (header ``x-tunnel-trace``)."""
    return f"{index & 0xFFFFFFFFFFFFFFFF:016x}"


async def _stream(host: str, port: int, req: Request, out: Outcome,
                  traced: bool) -> None:
    payload = {
        "prompt": req.prompt,
        "stream": True, "stream_options": {"include_usage": True},
        "max_tokens": req.max_tokens, "temperature": 0, "ignore_eos": True,
    }
    extra = (f"x-tunnel-trace: {trace_id_of(req.index)}/0\r\n"
             if traced else "")
    wire = _http_request(host, port, "POST", "/v1/completions",
                         json.dumps(payload).encode(), extra)
    reader, writer = await asyncio.open_connection(host, port)
    try:
        out.sent = time.monotonic()
        writer.write(wire)
        await writer.drain()
        out.status, headers = await _read_headers(reader)
        buf = b""
        async for chunk in _iter_body(reader, headers):
            now = time.monotonic()
            if out.status != 200:
                continue
            buf += chunk
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data:"):
                    continue
                data = event[5:].strip()
                if data == b"[DONE]":
                    out.done = True
                    continue
                obj = json.loads(data)
                err = obj.get("error")
                if isinstance(err, dict):
                    out.error = f"error event {err.get('code')}"
                    continue
                usage = obj.get("usage")
                if usage:
                    out.usage_prompt = usage.get("prompt_tokens")
                    out.usage_completion = usage.get("completion_tokens")
                for choice in obj.get("choices") or ():
                    if choice.get("finish_reason"):
                        out.finish = choice["finish_reason"]
                    text = choice.get("text")
                    if not text:
                        continue
                    # the tokenizer is word-level and every entry is
                    # w<id>: a delta's tokens are its words
                    n = len(_WORD.findall(text))
                    if n == 0:
                        continue
                    if out.first_token is None:
                        out.first_token = now
                    out.last_token = now
                    out.tokens_seen += n
                    out.token_times.append((now, n))
    finally:
        writer.close()


async def one_request(host: str, port: int, req: Request, out: Outcome,
                      timeout_s: float, traced: bool = False) -> None:
    """Send one streamed completion request; whatever goes wrong is
    recorded in ``out`` and never raised."""
    try:
        await asyncio.wait_for(_stream(host, port, req, out, traced),
                               timeout_s)
    except asyncio.TimeoutError:
        out.error = f"no end after {timeout_s:g}s"
    except (ConnectionError, asyncio.IncompleteReadError, OSError,
            ValueError) as e:
        out.error = f"{type(e).__name__}: {e}"


def send_warm(plan: Plan, host: str, port: int) -> None:
    """Set-up the traffic itself needs: each shared document once."""
    for req in plan.warm:
        out = Outcome(req.index, time.monotonic(), req.max_tokens)
        asyncio.run(one_request(host, port, req, out, plan.timeout_s))
        if out.failed():
            raise RuntimeError(f"warming document {req.document}: "
                               f"{out.failed()}")


async def _sleep_until(when: float) -> None:
    while True:
        left = when - time.monotonic()
        if left <= 0:
            return
        await asyncio.sleep(left)


class Load:
    """What a run of a plan gave: every request's outcome, and the window."""

    def __init__(self):
        self.outcomes: List[Outcome] = []
        self.t0 = 0.0
        self.t1 = 0.0

    def sample(self) -> List[Outcome]:
        return [o for o in self.outcomes if o.in_window]


async def offer(plan: Plan, host: str, port: int, seconds: float,
                t0: float, on_window=None, traced: bool = False) -> Load:
    """Offer the plan's load with the window opening at ``t0`` (monotonic
    seconds).  Open loop: every request is sent at its due time whatever
    the system does.  Closed loop: each client sends its next request when
    the last has ended, until the window closes.  Returns when every
    request that was sent has ended."""
    load = Load()
    load.t0, load.t1 = t0, t0 + seconds
    tasks = []
    if on_window is not None:
        tasks.append(asyncio.ensure_future(on_window(load.t0, load.t1)))

    if plan.loop == "open":
        async def timed(req: Request, out: Outcome):
            await _sleep_until(out.due)
            await one_request(host, port, req, out, plan.timeout_s, traced)

        for req in plan.requests:
            out = Outcome(req.index, t0 + req.due, req.max_tokens)
            out.in_window = load.t0 <= out.due < load.t1
            load.outcomes.append(out)
            tasks.append(asyncio.ensure_future(timed(req, out)))
    else:
        async def client(seq: List[Request], start: float):
            await _sleep_until(start)
            i = 0
            while time.monotonic() < load.t1:
                req = seq[i % len(seq)]
                i += 1
                out = Outcome(req.index, time.monotonic(), req.max_tokens)
                load.outcomes.append(out)
                await one_request(host, port, req, out, plan.timeout_s, traced)
                # a request belongs to the window if it ended inside it
                out.in_window = (load.t0 <= time.monotonic() < load.t1)

        for seq, start in zip(plan.clients, plan.client_starts):
            tasks.append(asyncio.ensure_future(client(seq, t0 + start)))
    await asyncio.gather(*tasks)
    return load
