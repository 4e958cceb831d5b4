"""The ``tunnel`` CLI: serve / proxy / signal subcommands + retry supervisor.

Reference parity (tunnel/src/cli.rs, tunnel/src/main.rs):
- flag > env > default precedence for every option (cli.rs:13-68); env names
  TUNNEL_SIGNAL / TUNNEL_ROOM / TUNNEL_UPSTREAM / TUNNEL_LISTEN kept
- defaults: signal ``wss://signal-server.fly.dev``, listen ``127.0.0.1:8000``,
  advertise ``/`` (cli.rs, README table)
- ``run_with_retry``: infinite reconnect attempts with exponential backoff
  ``2·2^(attempt-1)`` seconds capped at 60, where Ctrl+C interrupts both the
  running tunnel and the backoff sleep (main.rs:14-16, :111-159)

Beyond the reference: ``--backend tpu`` runs the in-process JAX engine
instead of forwarding to an HTTP upstream (the BASELINE.json north star),
``--transport udp|tcp`` picks the P2P data plane, and ``signal`` hosts the
rendezvous server (the reference keeps that in TypeScript; ours is also
importable in-process).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import random
import sys
import time
from typing import Optional

from p2p_llm_tunnel_tpu.utils.logging import get_logger, init_logging

log = get_logger(__name__)

INITIAL_BACKOFF = 2.0  # main.rs:14
MAX_BACKOFF = 60.0  # main.rs:16

DEFAULT_SIGNAL = os.environ.get("TUNNEL_SIGNAL", "wss://signal-server.fly.dev")
DEFAULT_LISTEN = os.environ.get("TUNNEL_LISTEN", "127.0.0.1:8000")
DEFAULT_UPSTREAM = os.environ.get("TUNNEL_UPSTREAM", "http://127.0.0.1:11434")


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    return os.environ.get(name, default)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="tunnel", description="P2P LLM tunnel, TPU-native edition"
    )
    sub = ap.add_subparsers(dest="command", required=True)

    def common(p: argparse.ArgumentParser) -> None:
        p.add_argument("--signal", default=DEFAULT_SIGNAL,
                       help="signaling server URL (env TUNNEL_SIGNAL)")
        p.add_argument("--room", default=_env("TUNNEL_ROOM"),
                       help="rendezvous room name (env TUNNEL_ROOM)")
        p.add_argument("--transport", choices=("udp", "tcp"),
                       default=_env("TUNNEL_TRANSPORT", "udp"),
                       help="P2P data plane (default udp hole-punch)")
        # NAT traversal aids (reference cli.rs:72-77 TURN surface):
        p.add_argument("--stun", default=_env("TUNNEL_STUN"),
                       help="STUN server host[:port] for a server-reflexive "
                            "candidate (env TUNNEL_STUN; e.g. "
                            "stun.l.google.com:19302)")
        p.add_argument("--relay", default=_env("TUNNEL_RELAY"),
                       help="relay host[:port] to fall back to when hole "
                            "punching fails (env TUNNEL_RELAY)")
        p.add_argument("--relay-secret", default=_env("TUNNEL_RELAY_SECRET"),
                       help="shared credential for an authenticated relay "
                            "(env TUNNEL_RELAY_SECRET) — the --turn-user/"
                            "--turn-pass surface of the reference")
        # Observability (ISSUE 6): request-scope span recording — both
        # peers emit spans (proxy ingress, serve dispatch, engine
        # lifecycle), so the knobs live on the shared surface.
        p.add_argument("--trace",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_TRACE", "") == "1",
                       help="record request-scope spans (utils/tracing "
                            "SPAN_CATALOG) into a bounded ring buffer; "
                            "export as Chrome trace-event JSON via GET "
                            "/healthz?trace=1, summarize with "
                            "scripts/traceview.py (env TUNNEL_TRACE=1; "
                            "off by default — pure host bookkeeping, but "
                            "zero is zero)")
        p.add_argument("--trace-sample", type=float,
                       default=float(_env("TUNNEL_TRACE_SAMPLE", "1.0")),
                       help="fraction of traces to record under --trace, "
                            "decided deterministically per trace id so "
                            "every layer of one request agrees (env "
                            "TUNNEL_TRACE_SAMPLE; 1.0 = all)")
        p.add_argument("--trace-buffer", type=int,
                       default=int(_env("TUNNEL_TRACE_BUFFER", "4096")),
                       help="span ring-buffer capacity under --trace "
                            "(env TUNNEL_TRACE_BUFFER)")

    serve = sub.add_parser("serve", help="provider peer: expose an LLM")
    common(serve)
    serve.add_argument("--upstream", default=DEFAULT_UPSTREAM,
                       help="upstream LLM base URL (env TUNNEL_UPSTREAM)")
    serve.add_argument("--advertise", default=_env("TUNNEL_ADVERTISE", "/"),
                       help="path prefix advertised to the peer (default /)")
    serve.add_argument("--backend", choices=("http", "tpu"),
                       default=_env("TUNNEL_BACKEND", "http"),
                       help="http = forward to --upstream; tpu = in-process JAX engine")
    serve.add_argument("--model", default=_env("TUNNEL_MODEL", "tiny"),
                       help="model preset for --backend tpu")
    serve.add_argument("--slots", type=int,
                       default=int(_env("TUNNEL_SLOTS", "8")),
                       help="continuous-batching slots (tpu backend)")
    serve.add_argument("--max-seq", type=int,
                       default=int(_env("TUNNEL_MAX_SEQ", "512")),
                       help="max context length (tpu backend)")
    serve.add_argument("--decode-steps", type=int,
                       default=int(_env("TUNNEL_DECODE_STEPS", "8")),
                       help="decode steps per device call (tpu backend)")
    serve.add_argument("--decode-steps-eager", type=int,
                       default=int(_env("TUNNEL_DECODE_STEPS_EAGER", "4")),
                       help="smaller decode burst used while requests are "
                            "waiting so an admission is never stuck behind "
                            "a full burst (0 = no adaptation)")
    serve.add_argument("--prefill-rows", type=int,
                       default=int(_env("TUNNEL_PREFILL_ROWS", "8")),
                       help="at most this many rows per batched-prefill "
                            "dispatch: admissions are chunked to it; a "
                            "prompt's first segment that arrives alone or "
                            "beside one other runs as one or two rows, not "
                            "padded to this many")
    serve.add_argument("--dtype", default=_env("TUNNEL_DTYPE", "bfloat16"),
                       help="activation/weight dtype for the in-process "
                            "engine (bfloat16|float32)")
    serve.add_argument("--max-waiting", type=int,
                       default=int(_env("TUNNEL_MAX_WAITING", "64")),
                       help="admission control: max requests buffered in "
                            "the engine's waiting queue before new work is "
                            "shed with HTTP 429 + Retry-After (0 = "
                            "unbounded; tpu backend)")
    serve.add_argument("--fair-admission",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_FAIR_ADMISSION", "1") == "1",
                       help="tenant-fair admission (default ON): weighted-"
                            "fair ordering across x-tunnel-tenant "
                            "identities plus per-tenant waiting-queue "
                            "share caps, so one hot API key is shed (429 "
                            "tenant_overlimit) before it starves others; "
                            "degenerates to plain FIFO with one tenant "
                            "(--no-fair-admission or "
                            "TUNNEL_FAIR_ADMISSION=0 disables)")
    serve.add_argument("--tenant-weights",
                       default=_env("TUNNEL_TENANT_WEIGHTS", ""),
                       help="fairness weights as name=weight,name=weight "
                            "(unlisted tenants weigh 1.0); a weight-4 "
                            "tenant gets 4x the contended queue share and "
                            "admission stride (env TUNNEL_TENANT_WEIGHTS)")
    serve.add_argument("--max-inflight", type=int,
                       default=int(_env("TUNNEL_MAX_INFLIGHT", "256")),
                       help="admission control at the tunnel layer: max "
                            "concurrently-dispatched requests before 429 "
                            "(0 = unbounded)")
    serve.add_argument("--drain-timeout", type=float,
                       default=float(_env("TUNNEL_DRAIN_TIMEOUT", "0")),
                       help="seconds a SIGTERM drain waits for in-flight "
                            "streams before abandoning them; past it a "
                            "postmortem bundle is captured (trigger "
                            "'drain') and the tunnel closes anyway "
                            "(0 = wait forever, the historical behavior; "
                            "env TUNNEL_DRAIN_TIMEOUT)")
    serve.add_argument("--stream-grace-s", type=float,
                       default=float(_env("TUNNEL_STREAM_GRACE_S", "5")),
                       help="mid-stream continuity (ISSUE 13): how long a "
                            "token stream whose tunnel link died parks in "
                            "the detached-stream registry — engine "
                            "generation still running, replay journal "
                            "still filling — awaiting a RES_RESUME from "
                            "the reattached proxy before the generation "
                            "is cancelled and the client gets the typed "
                            "peer_lost terminal (0 disables resume "
                            "entirely: legacy wire, legacy failure mode; "
                            "env TUNNEL_STREAM_GRACE_S)")
    serve.add_argument("--stream-journal-bytes", type=int,
                       default=int(_env("TUNNEL_STREAM_JOURNAL_BYTES",
                                        str(512 * 1024))),
                       help="per-stream replay-journal cap in bytes: "
                            "response bytes retained until the proxy's "
                            "FLOW grants ack them, so a resume can splice "
                            "at the delivered offset; also the journal's "
                            "backpressure bound while detached (memory "
                            "cost: up to this many bytes per in-flight "
                            "resumable stream; keep it above the 256 KiB "
                            "flow-credit window or resumes of a lagging "
                            "client fall back to peer_lost; env "
                            "TUNNEL_STREAM_JOURNAL_BYTES)")
    serve.add_argument("--postmortem-dir",
                       default=_env("TUNNEL_POSTMORTEM_DIR",
                                    "artifacts/postmortem"),
                       help="directory postmortem black-box bundles are "
                            "archived into on a watchdog trip, SLO "
                            "breach, drain timeout, or engine crash "
                            "(also served at GET /healthz?postmortem=1; "
                            "empty string disables archiving; env "
                            "TUNNEL_POSTMORTEM_DIR)")
    serve.add_argument("--watchdog-budget", type=float,
                       default=float(_env("TUNNEL_WATCHDOG_BUDGET", "60")),
                       help="decode-stall watchdog: mark the engine "
                            "degraded (surfaced via /healthz) when no "
                            "decode progress happens for this many seconds "
                            "while requests are in flight (0 = off; tpu "
                            "backend)")
    serve.add_argument("--tp", type=int, default=int(_env("TUNNEL_TP", "1")),
                       help="tensor-parallel degree over the device mesh")
    serve.add_argument("--ckpt", default=_env("TUNNEL_CKPT"),
                       help="orbax checkpoint path (default: random init)")
    serve.add_argument("--quant",
                       choices=("none", "int8", "w8a8", "int4", "a8"),
                       default=_env("TUNNEL_QUANT", "none"),
                       help="weight quantization: int8 halves decode HBM "
                            "traffic; w8a8 also quantizes activations "
                            "(int8 MXU dots); int4 packs two weights per "
                            "byte with per-group scales, halving the "
                            "weight stream again; a8 rounds activations "
                            "to int8 over the weights as they are (a "
                            "precision control, not a serving mode)")
    serve.add_argument("--quant-group-size", type=int,
                       default=int(_env("TUNNEL_QUANT_GROUP_SIZE", "128")),
                       help="int4 scale group size (contracted positions "
                            "per f32 scale; must be even)")
    serve.add_argument("--kv-quant", choices=("none", "int8", "int4"),
                       default=_env("TUNNEL_KV_QUANT", "none"),
                       help="KV-cache quantization (int8 halves, int4 "
                            "quarters the long-context KV read term; int4 "
                            "composes with the prefix cache, chunked "
                            "prefill AND spec decode — byte-aligned pool "
                            "pages + spliced verify bursts leave /healthz "
                            "config.fences empty)")
    serve.add_argument("--prefill-act-quant",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_PREFILL_ACT_QUANT", "") == "1",
                       help="with --quant int8: run PREFILL activations "
                            "int8 too (2x MXU rate where prefill is "
                            "compute-bound); decode stays weight-only "
                            "(--no-prefill-act-quant overrides the env)")
    serve.add_argument("--ragged-prefill",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_RAGGED_PREFILL", "") == "1",
                       help="ragged grouped flash-prefill kernel: every "
                            "chunk-prefill dispatch (mux segments, "
                            "prefix-cache tails) packs the group's "
                            "variable-length tails into ONE Pallas "
                            "launch — no pad buckets, no per-(tail,view) "
                            "programs, the warmup grid collapses; token "
                            "streams are byte-identical to the chunked "
                            "path (off by default pending on-chip "
                            "measurement)")
    serve.add_argument("--prefill-chunk", type=int,
                       default=int(_env("TUNNEL_PREFILL_CHUNK", "0")),
                       help="chunked prefill: prompts longer than this many "
                            "tokens advance one segment of this size per "
                            "engine step, interleaved with decode (0 = "
                            "whole-prompt prefill)")
    serve.add_argument("--mux",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_MUX", "1") == "1",
                       help="iteration-level prefill/decode multiplexing "
                            "(default ON): each engine "
                            "step runs one decode burst plus a budgeted "
                            "slice of chunked-prefill segments, with "
                            "prefix-grouped admission deduping shared "
                            "prompt prefixes across the queue; outputs are "
                            "byte-identical to the legacy rhythm; disable "
                            "with --no-mux or TUNNEL_MUX=0")
    serve.add_argument("--mux-budget-tokens", type=int,
                       default=int(_env("TUNNEL_MUX_BUDGET_TOKENS", "0")),
                       help="fixed per-iteration prefill token budget "
                            "under --mux (0 = adaptive controller)")
    serve.add_argument("--prefix-pool-blocks", type=int,
                       default=int(_env("TUNNEL_PREFIX_POOL_BLOCKS", "128")),
                       help="prefix-cache pool capacity in KV blocks "
                            "(block 0 is scratch)")
    serve.add_argument("--spill-pages", type=int,
                       default=int(_env("TUNNEL_SPILL_PAGES", "0")),
                       help="pinned host-RAM spill tier capacity in KV "
                            "pages (0 = off); cold pages migrate out of "
                            "HBM under pressure and splice back on reuse")
    serve.add_argument("--role",
                       choices=["both", "prefill", "decode"],
                       default=_env("TUNNEL_ROLE", "both"),
                       help="disaggregated serving role: 'prefill' peers "
                            "take proxy export probes and ship KV pages "
                            "over the tunnel; 'decode' peers splice "
                            "shipped pages and stream tokens; 'both' "
                            "(default) serves classic single-engine")
    serve.add_argument("--conv-cache",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_CONV_CACHE", "1") == "1",
                       help="cross-request conversation cache (default ON "
                            "with --prefix-cache): finished streams' KV — "
                            "prompt AND generated tokens — is saved into "
                            "the prefix pool, so a returning user's next "
                            "turn re-prefills only its new tail; disable "
                            "with --no-conv-cache or TUNNEL_CONV_CACHE=0")
    serve.add_argument("--prefix-evict", choices=("cost", "lru"),
                       default=_env("TUNNEL_PREFIX_EVICT", "cost"),
                       help="pool page eviction policy: cost (GreedyDual — "
                            "pages weigh their recompute cost, tokens x "
                            "live per-token prefill ms, so deep "
                            "conversations outlive cheap one-shot prompts "
                            "under pressure) or lru")
    serve.add_argument("--prefix-cache",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_PREFIX_CACHE", "1") == "1",
                       help="automatic prefix caching (default ON): "
                            "reuse prompt-prefix KV across "
                            "requests (shared system prompts, resent "
                            "conversations); pure latency optimization, "
                            "outputs unchanged; disable with "
                            "--no-prefix-cache or TUNNEL_PREFIX_CACHE=0")
    serve.add_argument("--spec-ngram", type=int,
                       default=int(_env("TUNNEL_SPEC_NGRAM", "0")),
                       help="prompt-lookup speculative decoding: match "
                            "length (0 = off); exact-greedy verification, "
                            "output identical to plain decode")
    serve.add_argument("--spec-k", type=int,
                       default=int(_env("TUNNEL_SPEC_K", "4")),
                       help="speculative proposal length per step")
    serve.add_argument("--spec-k-max", type=int,
                       default=int(_env("TUNNEL_SPEC_K_MAX", "0")),
                       help="adaptive verify-burst cap: when > --spec-k, "
                            "each dispatch picks K from a warmed "
                            "power-of-two ladder up to this cap, steered "
                            "by the per-slot acceptance EMA (0 = fixed K)")
    serve.add_argument("--prefix-cache-dir",
                       default=_env("TUNNEL_PREFIX_CACHE_DIR"),
                       help="persist the prefix-cache block pool here: warm "
                            "prompt KV survives serve restarts (loaded at "
                            "startup when compatible, saved at shutdown)")
    serve.add_argument("--sp", type=int, default=int(_env("TUNNEL_SP", "1")),
                       help="sequence-parallel degree for prefill "
                            "(long-context)")
    serve.add_argument("--sp-mode", choices=("ring", "ulysses"),
                       default=_env("TUNNEL_SP_MODE", "ring"),
                       help="SP strategy: ring (ppermute KV rotation) or "
                            "ulysses (all_to_all; supports sliding windows)")
    serve.add_argument("--ep", type=int, default=int(_env("TUNNEL_EP", "1")),
                       help="expert-parallel degree for MoE models")
    serve.add_argument("--tokenizer", default=_env("TUNNEL_TOKENIZER"),
                       help="a checkpoint's tokenizer (default: byte-level). "
                            "A local directory is read with the tokenizers "
                            "library alone: tokenizer.json, "
                            "tokenizer_config.json, and chat_template.jinja, "
                            "additional_chat_templates/*.jinja or an older "
                            "layout's special_tokens_map.json where present. "
                            "transformers is imported (18-25 s) only for a "
                            "hub name, a directory without tokenizer.json, a "
                            "tokenizer class other than "
                            "PreTrainedTokenizerFast / Llama / Qwen2, an "
                            "auto_map, or added or special tokens that "
                            "tokenizer.json does not hold; the start-up log "
                            "names the loader and the reason")
    serve.add_argument("--replicas", type=int,
                       default=int(_env("TUNNEL_REPLICAS", "1")),
                       help="data-parallel engine replicas behind a router, "
                            "one per device round-robin")
    serve.add_argument("--coordinator",
                       default=_env("TUNNEL_COORDINATOR")
                       or _env("MEGASCALE_COORDINATOR_ADDRESS"),
                       help="multi-host: jax.distributed coordinator "
                            "host:port; run the same serve command on "
                            "every host (env TUNNEL_COORDINATOR)")
    serve.add_argument("--num-processes", type=int,
                       default=int(_env("TUNNEL_NUM_PROCESSES", "0")),
                       help="multi-host: total process count")
    serve.add_argument("--process-id", type=int,
                       default=int(_env("TUNNEL_PROCESS_ID", "-1")),
                       help="multi-host: this process's rank")
    serve.add_argument("--dp-dcn", type=int,
                       default=int(_env("TUNNEL_DP_DCN", "1")),
                       help="data-parallel degree ACROSS hosts (DCN tier); "
                            "tp/sp/ep stay slice-local on ICI "
                            "(parallel/distributed.py)")

    # SLO burn-rate engine (ISSUE 9, utils/slo.py): declarative objectives
    # evaluated over multi-window burn rates, published as slo_* series
    # and the /healthz "slo" section; a burning objective marks the peer
    # degraded so fabric routing steers around it.
    serve.add_argument("--slo",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_SLO", "1") == "1",
                       help="evaluate SLO burn rates (default ON): TTFT "
                            "and availability objectives over fast (~5 "
                            "min) / slow (~1 h) windows; verdicts land in "
                            "/metrics (slo_* labeled series) and the "
                            "/healthz slo section, and a burning "
                            "objective degrades the peer's health state "
                            "(--no-slo or TUNNEL_SLO=0 disables)")
    serve.add_argument("--slo-ttft-ms", type=float,
                       default=float(_env("TUNNEL_SLO_TTFT_MS", "2000")),
                       help="TTFT objective threshold: the ttft objective "
                            "counts a request good when its engine TTFT "
                            "is within this many ms (env "
                            "TUNNEL_SLO_TTFT_MS)")
    serve.add_argument("--slo-ttft-target", type=float,
                       default=float(_env("TUNNEL_SLO_TTFT_TARGET",
                                          "0.99")),
                       help="required good fraction for the ttft "
                            "objective (0.99 = TTFT p99 must meet the "
                            "threshold; env TUNNEL_SLO_TTFT_TARGET)")
    serve.add_argument("--slo-availability-target", type=float,
                       default=float(_env("TUNNEL_SLO_AVAIL_TARGET",
                                          "0.999")),
                       help="required fraction of requests answered "
                            "without shed/error (env "
                            "TUNNEL_SLO_AVAIL_TARGET)")
    serve.add_argument("--fabric",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_FABRIC", "") == "1",
                       help="join the room as a role-tagged `serve` peer of "
                            "a multi-peer fabric (ISSUE 8): the room holds "
                            "one proxy and up to N serve peers, this peer "
                            "always answers the proxy's targeted offer; "
                            "pair with `proxy --peers N` (env "
                            "TUNNEL_FABRIC=1; default off = classic 2-peer "
                            "room)")

    proxy = sub.add_parser("proxy", help="consumer peer: local HTTP port")
    common(proxy)
    proxy.add_argument("--listen", default=DEFAULT_LISTEN,
                       help="local HTTP listen addr (env TUNNEL_LISTEN)")
    proxy.add_argument("--peers", type=int,
                       default=int(_env("TUNNEL_PEERS", "1")),
                       help="multi-peer fabric (ISSUE 8): fan requests "
                            "across up to this many serve peers joined to "
                            "the room with `serve --fabric` — health-routed "
                            "least-loaded dispatch, per-peer circuit "
                            "breakers, transparent re-dispatch of "
                            "not-yet-streaming requests when a peer dies "
                            "(1 = classic single-peer tunnel, byte-"
                            "identical to before; env TUNNEL_PEERS)")
    proxy.add_argument("--peer-probe-s", type=float,
                       default=float(_env("TUNNEL_PEER_PROBE_S", "15")),
                       help="fabric health probing: tunneled GET /healthz "
                            "per peer at this interval feeds the "
                            "live/degraded/draining routing states "
                            "(0 = RTT-only health; applies with "
                            "--peers > 1; env TUNNEL_PEER_PROBE_S)")
    proxy.add_argument("--trust-tenant-header",
                       action=argparse.BooleanOptionalAction,
                       default=_env("TUNNEL_TRUST_TENANT_HEADER", "") == "1",
                       help="honor a client-sent x-tunnel-tenant at this "
                            "listener (default OFF: a public listener "
                            "trusting the label lets one client mint a "
                            "fresh tenant per request, sidestepping its "
                            "fair-share cap; identities otherwise come "
                            "from x-api-key fingerprints or the room "
                            "fallback — enable only behind a trusted "
                            "edge that stamps the header; env "
                            "TUNNEL_TRUST_TENANT_HEADER=1)")

    sig = sub.add_parser("signal", help="run the rendezvous server")
    sig.add_argument("--listen", default="127.0.0.1")
    sig.add_argument("--port", type=int, default=8787)
    sig.add_argument("--stun-port", type=int,
                     default=int(_env("TUNNEL_STUN_PORT", "0")),
                     help="also answer STUN binding requests on this UDP "
                          "port (0 = disabled)")

    rly = sub.add_parser("relay", help="run the UDP pairing relay "
                                       "(TURN-equivalent fallback)")
    rly.add_argument("--listen", default="0.0.0.0")
    rly.add_argument("--port", type=int, default=3479)
    rly.add_argument("--secret", default=_env("TUNNEL_RELAY_SECRET"),
                     help="require HMAC-authenticated JOINs with this shared "
                          "credential (env TUNNEL_RELAY_SECRET)")
    return ap


# ---------------------------------------------------------------------------
# retry supervisor (main.rs:111-159)
# ---------------------------------------------------------------------------

async def run_with_retry(name: str, attempt_fn, *, max_attempts: int = 0,
                         stop: "Optional[asyncio.Event]" = None) -> None:
    """Run ``attempt_fn()`` forever, reconnecting with capped backoff.

    ``max_attempts=0`` means infinite (the reference hardcodes infinite).
    Cancellation (SIGINT) aborts both the running attempt and the backoff
    sleep — matching main.rs:119-125, :148-155.

    ``stop`` (optional) is the graceful-drain switch: once set, no new
    attempt starts and a backoff sleep ends early — so SIGTERM during a
    reconnect loop (dead signal server, flaky WAN) exits promptly instead
    of retrying forever.  An attempt already serving handles the same
    event itself (run_serve's drain path).
    """
    import time as _time

    attempt = 0
    while True:
        if stop is not None and stop.is_set():
            log.info("%s: drain requested; not reconnecting", name)
            return
        attempt += 1
        started = _time.monotonic()
        try:
            log.info("%s: connecting (attempt %d)", name, attempt)
            await attempt_fn()
            log.info("%s ended cleanly", name)
            return
        except asyncio.CancelledError:
            raise
        except Exception as e:
            log.warning("%s failed: %s", name, e)
        if _time.monotonic() - started > MAX_BACKOFF:
            # The session ran healthily before dying — treat the next
            # reconnect as fresh rather than compounding hours-old failures.
            attempt = 1
        if max_attempts and attempt >= max_attempts:
            raise RuntimeError(f"{name}: giving up after {attempt} attempts")
        backoff = min(INITIAL_BACKOFF * (2 ** (attempt - 1)), MAX_BACKOFF)
        # Jitter (ISSUE 8 / tunnelcheck TC11): a fleet of serve peers
        # killed by the same fault must not re-dial the signal server in
        # lockstep — the reference's bare exponential synchronizes herds.
        backoff *= 1.0 + random.uniform(0.0, 0.25)
        log.info("%s: reconnecting in %.0fs", name, backoff)
        if stop is None:
            await asyncio.sleep(backoff)  # CancelledError propagates → Ctrl+C
        else:
            # Backoff that a drain can interrupt.
            try:
                await asyncio.wait_for(stop.wait(), backoff)
            except asyncio.TimeoutError:
                pass


# ---------------------------------------------------------------------------
# subcommand bodies
# ---------------------------------------------------------------------------

async def _serve_once(args, drain: "Optional[asyncio.Event]" = None) -> None:
    from p2p_llm_tunnel_tpu.endpoints.serve import http_backend, run_serve
    from p2p_llm_tunnel_tpu.transport import connect

    backend = None
    if args.backend == "tpu":
        try:
            backend = await _engine_backend(args)
        except Exception as e:
            # The retry supervisor exists for tunnels that die; an engine
            # that cannot start (no device, a compiler refusal, out of
            # memory) will not start on the next dial either.
            log.exception("engine start-up failed")
            raise SystemExit(f"engine start-up failed: {e}") from e
        if backend is None:
            # Multi-host follower rank: the replay loop above ran to
            # completion (leader stopped); nothing to serve here.
            return
    global _TUNNEL_TIMED
    tunnel_t0 = None if _TUNNEL_TIMED else time.monotonic()
    _TUNNEL_TIMED = True
    channel, signaling = await connect(
        args.signal, args.room, args.transport,
        stun_server=args.stun, relay=args.relay,
        relay_secret=args.relay_secret,
        # --fabric: join role-tagged as one of N serve peers (ISSUE 8);
        # this peer always answers the proxy's targeted offer.
        role="serve" if getattr(args, "fabric", False) else None,
    )
    try:
        kwargs = dict(
            max_inflight=getattr(args, "max_inflight", 0), drain=drain,
            drain_timeout=getattr(args, "drain_timeout", 0.0),
            stream_grace_s=getattr(args, "stream_grace_s", -1.0),
            stream_journal_bytes=getattr(args, "stream_journal_bytes", 0),
            tunnel_t0=tunnel_t0,
        )
        if backend is not None:
            await run_serve(channel, backend=backend, **kwargs)
        else:
            await run_serve(channel, args.upstream, args.advertise, **kwargs)
    finally:
        channel.close()
        # Clean close sends `bye` on signaling — peers learn of the drain
        # instead of waiting out their dead-peer timers.
        await signaling.close()


def require_tpu_backend(platform: str, who: str) -> None:
    """A chip-serving entry point (``serve --backend tpu``, chip_smoke.py) means
    the TPU: refuse to start on whatever else JAX fell back to, naming it.
    The CPU is served only when the caller asked for it explicitly with
    ``JAX_PLATFORMS=cpu`` (tests and rehearsals).  SystemExit, not an
    Exception the retry supervisor would swallow and re-dial on forever."""
    if platform == "tpu":
        return
    asked = os.environ.get("JAX_PLATFORMS", "")
    if platform == "cpu" and asked.split(",")[0].strip() == "cpu":
        return
    raise SystemExit(
        f"{who}: JAX's default backend is {platform!r}, not 'tpu' "
        f"(JAX_PLATFORMS={asked!r}); set JAX_PLATFORMS=cpu to run on the "
        "CPU on purpose"
    )


_BACKEND = None
#: The first session's signaling connect has been timed (startup.tunnel).
_TUNNEL_TIMED = False
#: Engines constructed by this process — the Ctrl+C path snapshots their
#: prefix pools (asyncio.run tears down before any engine.stop() runs).
_ENGINES: list = []


async def _engine_backend(args):
    """Start (once) the in-process engine(s) and return the request handler.

    The engine outlives individual tunnel sessions: reconnects re-use the
    warm engine (weights + compiled programs) rather than re-initialising.
    """
    global _BACKEND
    if _BACKEND is not None:
        return _BACKEND
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.utils.flight import global_compile_watch as watch

    # The start-up journal (ISSUE 40): imports, tokenizer, backend,
    # engine_build and warmup tile startup.process in this order.
    import jax

    if args.tokenizer:
        from p2p_llm_tunnel_tpu.engine.tokenizer import HFTokenizer
    watch.add_span("startup.imports", t0=watch.process_began()[0])
    tokenizer = None
    if args.tokenizer:
        with watch.startup_phase("startup.tokenizer") as attrs:
            tokenizer = HFTokenizer(args.tokenizer)
            attrs["entries"] = tokenizer.vocab_size
            attrs["loader"] = tokenizer.loader
        log.info("tokenizer: %s, %d entries, loaded by %s%s", args.tokenizer,
                 tokenizer.vocab_size, tokenizer.loader,
                 f" ({tokenizer.fallback_reason})"
                 if tokenizer.fallback_reason else "")
    t_backend = time.monotonic()
    mesh = None
    if args.coordinator:
        # Multi-host: join the runtime FIRST (jax.devices() becomes global),
        # then build the DCN-aware mesh — dp across hosts, tp/sp/ep on ICI.
        # A partial flag set must error loudly, not silently start an
        # independent single-host server on every pod host.
        if args.num_processes <= 0 or args.process_id < 0:
            raise SystemExit(
                "--coordinator requires --num-processes > 0 and "
                "--process-id >= 0 (or TUNNEL_NUM_PROCESSES / "
                "TUNNEL_PROCESS_ID)"
            )
        from p2p_llm_tunnel_tpu.parallel.distributed import (
            init_distributed,
            make_hybrid_mesh,
        )

        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
        mesh = make_hybrid_mesh(
            tp=args.tp, dp_dcn=args.dp_dcn, sp=args.sp, ep=args.ep
        )
    # First backend touch — after the multi-host join, which must precede
    # it.  Replica placement must use THIS host's devices: after a join,
    # jax.devices() is global and mostly non-addressable here.
    require_tpu_backend(jax.default_backend(), "serve --backend tpu")
    devices = jax.local_devices()
    log.info(
        "engine device: platform=%s kind=%s local_devices=%d; compile "
        "cache at %s", devices[0].platform, devices[0].device_kind,
        len(devices), jax.config.jax_compilation_cache_dir,
    )
    watch.add_span(
        "startup.backend", t0=t_backend, platform=devices[0].platform,
        device_kind=devices[0].device_kind, devices=len(devices),
    )

    def make_engine(seed: int) -> InferenceEngine:
        # Each replica snapshots into its own subdirectory — one shared dir
        # would have every save clobber the previous replica's pool.
        pfx_dir = args.prefix_cache_dir
        if pfx_dir and args.replicas > 1:
            pfx_dir = os.path.join(pfx_dir, f"replica-{seed}")
        # Replica i lives on device i (round-robin).  default_device makes
        # the weights and cache materialize there (never on device 0
        # first), but leaves them UNcommitted: the engine loop dispatches
        # outside this context and would run every replica on device 0.
        # A single engine stays uncommitted on the default device; a mesh
        # engine is placed by its shardings.
        device = devices[seed % len(devices)]
        with jax.default_device(device):
            engine = InferenceEngine(
                tokenizer=tokenizer,
                mesh=mesh,
                engine_cfg=EngineConfig(
                    model=args.model,
                    num_slots=args.slots,
                    max_seq=args.max_seq,
                    dtype=args.dtype,
                    decode_steps=args.decode_steps,
                    decode_steps_eager=args.decode_steps_eager,
                    prefill_rows=args.prefill_rows,
                    tp=args.tp,
                    sp=args.sp,
                    sp_mode=args.sp_mode,
                    ep=args.ep,
                    ckpt_path=args.ckpt,
                    quant=args.quant,
                    quant_group_size=args.quant_group_size,
                    kv_quant=args.kv_quant,
                    prefill_act_quant=args.prefill_act_quant,
                    prefix_cache=args.prefix_cache,
                    prefix_cache_dir=pfx_dir,
                    prefix_pool_blocks=args.prefix_pool_blocks,
                    spill_pages=args.spill_pages,
                    conv_cache=args.conv_cache and args.prefix_cache,
                    prefix_evict=args.prefix_evict,
                    spec_ngram=args.spec_ngram,
                    spec_k=args.spec_k,
                    spec_k_max=args.spec_k_max,
                    prefill_chunk=args.prefill_chunk,
                    ragged_prefill=args.ragged_prefill,
                    mux=args.mux,
                    mux_budget_tokens=args.mux_budget_tokens,
                    max_waiting=args.max_waiting,
                    fair_admission=args.fair_admission,
                    tenant_weights=args.tenant_weights,
                    watchdog_budget_s=args.watchdog_budget,
                    seed=seed,
                    role=getattr(args, "role", "both"),
                )
            )
        if args.replicas > 1:
            engine.commit_to(device)
        return engine

    if args.replicas > 1:
        from p2p_llm_tunnel_tpu.engine.router import ReplicaRouter, router_backend

        if args.coordinator and args.num_processes > 1:
            raise SystemExit(
                "--replicas > 1 is a single-host data-parallel mode; "
                "multi-host runs shard ONE engine over the global mesh"
            )
        log.info("starting %d engine replicas: model=%s slots=%d",
                 args.replicas, args.model, args.slots)
        with watch.startup_phase("startup.engine_build"):
            router = ReplicaRouter(
                [make_engine(i) for i in range(args.replicas)], args.model
            )
            _ENGINES.extend(router.engines)
            await router.start()
        # Pre-compile every decode variant BEFORE serving: a first-hit
        # compile inside the live loop would stall the event loop past the
        # transport's 15 s dead-peer timeout and kill the tunnel.
        for eng in router.engines:
            await eng.warmup()
        _BACKEND = router_backend(router)
    else:
        from p2p_llm_tunnel_tpu.engine.api import engine_backend

        log.info("starting TPU engine: model=%s slots=%d", args.model, args.slots)
        t_build = time.monotonic()
        engine = make_engine(0)
        _ENGINES.append(engine)
        spmd = getattr(engine, "_spmd", None)  # tests inject fake engines
        if spmd is not None and spmd.rank != 0:
            # Follower host (PARITY A8): no tunnel endpoint here — rank 0
            # owns the tunnel and broadcasts every dispatch's host inputs;
            # this process replays them until the leader stops.  Returns
            # None so _serve_once skips connecting.
            log.info("multi-host follower rank %d: replaying rank-0 "
                     "dispatches", spmd.rank)
            await asyncio.to_thread(engine.spmd_follower_loop)
            return None
        await engine.start()
        watch.add_span("startup.engine_build", t0=t_build)
        # See replica branch: compile all decode variants before traffic.
        await engine.warmup()
        _BACKEND = engine_backend(engine, args.model)
    watch.mark_ready()
    return _BACKEND


async def _proxy_once(args) -> None:
    from p2p_llm_tunnel_tpu.endpoints.proxy import run_proxy
    from p2p_llm_tunnel_tpu.transport import connect

    host, _, port = args.listen.rpartition(":")
    if args.peers > 1:
        await _proxy_fabric_once(args, host or "127.0.0.1", int(port))
        return
    channel, signaling = await connect(args.signal, args.room, args.transport,
                                       stun_server=args.stun, relay=args.relay,
                                       relay_secret=args.relay_secret)
    try:
        # Untagged requests inherit the room as tenant: one proxy
        # connection = one accountable identity at the serve peer.
        await run_proxy(channel, host or "127.0.0.1", int(port),
                        tenant_fallback=args.room or "",
                        trust_tenant_header=args.trust_tenant_header)
    finally:
        channel.close()
        await signaling.close()


async def _proxy_fabric_once(args, host: str, port: int) -> None:
    """One fabric session (ISSUE 8): a role-tagged proxy fanning requests
    across up to ``--peers`` serve peers.

    Supervision split: each serve peer's own ``run_with_retry`` redials the
    room when its channel dies (a fresh peer-joined re-admits it here), so
    a single peer death does NOT end this session — only the signaling
    socket's death does, raising out to the caller's retry loop.
    """
    from p2p_llm_tunnel_tpu.endpoints.proxy import ProxyState, run_proxy_fabric
    from p2p_llm_tunnel_tpu.transport.fabric import run_fabric_dialer

    state = ProxyState(
        tenant_fallback=args.room or "",
        trust_tenant_header=args.trust_tenant_header,
        probe_interval=args.peer_probe_s,
        fabric=True,
    )
    dialer = asyncio.create_task(run_fabric_dialer(
        args.signal, args.room, args.transport, state,
        max_peers=args.peers, stun_server=args.stun,
        relay=args.relay, relay_secret=args.relay_secret,
    ))
    try:
        await run_proxy_fabric(state, host, port)
    finally:
        dialer.cancel()
        try:
            await dialer
        except asyncio.CancelledError:
            pass
        except Exception as e:
            # The dialer's own failure IS the root cause (e.g. signaling
            # refused the join: "room is full: a proxy peer is already
            # present") — surface it to the retry supervisor instead of
            # the generic "fabric supervision ended".
            log.warning("proxy fabric dialer failed: %s", e)
            raise


async def _amain(args) -> None:
    if args.command == "signal":
        from p2p_llm_tunnel_tpu.signaling.server import SignalServer

        if args.stun_port:
            from p2p_llm_tunnel_tpu.transport.stun import start_stun_server

            await start_stun_server(args.listen, args.stun_port)
        await SignalServer(args.listen, args.port).serve_forever()
        return

    if args.command == "relay":
        from p2p_llm_tunnel_tpu.transport.relay import run_relay_server

        await run_relay_server(args.listen, args.port, args.secret)
        return

    if not args.room:
        raise SystemExit("--room (or TUNNEL_ROOM) is required")
    # The native libraries are built, not shipped (native/build is
    # git-ignored): say which codec this peer runs.
    from p2p_llm_tunnel_tpu.protocol import native as frames_native
    from p2p_llm_tunnel_tpu.transport import arq

    log.info(
        "codec: frames=%s arq=%s (scripts/build-native.sh builds the "
        "native ones)",
        "native" if frames_native.available() else "python",
        "native" if arq.native_available() else "python",
    )
    if getattr(args, "trace", False):
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        global_tracer.configure(
            enabled=True, capacity=args.trace_buffer,
            sample=args.trace_sample,
        )
        log.info(
            "request tracing on: buffer=%d sample=%.3f (export: GET "
            "/healthz?trace=1)", args.trace_buffer, args.trace_sample,
        )
        # The flight ring rides the same export: give it the journal's
        # room, so a traced run's records reach back as far as its spans
        # (1,024 records are under a minute of a busy loop, ISSUE 57).
        from p2p_llm_tunnel_tpu.utils.flight import global_flight

        global_flight.configure(
            capacity=max(global_flight.capacity, args.trace_buffer))
    if args.command == "serve":
        if args.backend == "tpu":
            # Before the first compile: a second start of the same
            # configuration loads its programs instead of compiling them.
            from p2p_llm_tunnel_tpu.utils.compile_cache import enable

            enable()
        from p2p_llm_tunnel_tpu.utils.flight import global_blackbox
        from p2p_llm_tunnel_tpu.utils.slo import (
            default_objectives,
            global_slo,
        )

        # Postmortem black box (ISSUE 12): where bundles archive on a
        # watchdog trip / SLO breach / drain timeout / engine crash.  The
        # in-memory ring serves GET /healthz?postmortem=1 either way.
        global_blackbox.configure(directory=args.postmortem_dir or "")
        global_slo.configure(
            enabled=args.slo,
            objectives=default_objectives(
                ttft_ms=args.slo_ttft_ms,
                ttft_target=args.slo_ttft_target,
                availability_target=args.slo_availability_target,
            ),
        )
        if args.slo:
            log.info(
                "slo engine on: ttft p%g <= %gms, availability >= %g%%",
                args.slo_ttft_target * 100, args.slo_ttft_ms,
                args.slo_availability_target * 100,
            )
        # Graceful drain: the FIRST SIGTERM stops admission and lets
        # in-flight streams finish (run_serve returns cleanly, the retry
        # supervisor sees a clean end); a SECOND SIGTERM force-exits via
        # the default handler.  SIGINT keeps the immediate-interrupt path.
        import os as _os
        import signal as _signal

        drain = asyncio.Event()

        def _drain_now() -> None:
            if drain.is_set():
                log.warning("second SIGTERM: exiting immediately")
                _signal.signal(_signal.SIGTERM, _signal.SIG_DFL)
                _os.kill(_os.getpid(), _signal.SIGTERM)
            log.info("SIGTERM: draining (finishing in-flight requests)")
            drain.set()

        loop = asyncio.get_running_loop()
        try:
            loop.add_signal_handler(_signal.SIGTERM, _drain_now)
        except (NotImplementedError, RuntimeError):
            pass  # non-main thread / platforms without signal support
        try:
            await run_with_retry(
                "serve", lambda: _serve_once(args, drain), stop=drain
            )
        finally:
            try:
                loop.remove_signal_handler(_signal.SIGTERM)
            except (NotImplementedError, RuntimeError, ValueError):
                pass
    elif args.command == "proxy":
        await run_with_retry("proxy", lambda: _proxy_once(args))


def main(argv: Optional[list] = None) -> None:
    t_main = time.monotonic()  # startup.process falls back to this line
    from p2p_llm_tunnel_tpu.utils.flight import global_compile_watch

    global_compile_watch.process_began(t_main)
    init_logging()
    import signal as _signal

    # SIGTERM (docker stop, systemd, supervisors) takes the same graceful
    # path as Ctrl+C — prefix-pool snapshots must survive orchestrated
    # restarts, not just interactive ones.  And a process launched as a
    # background job of a non-interactive shell inherits SIGINT=ignore
    # (POSIX); restore the default so Ctrl+C-equivalents work there too.
    got_sig = {"num": None}

    def _graceful(signum, frame):
        got_sig["num"] = signum
        raise KeyboardInterrupt

    _signal.signal(_signal.SIGTERM, _graceful)
    if _signal.getsignal(_signal.SIGINT) == _signal.SIG_IGN:
        _signal.signal(_signal.SIGINT, _graceful)
    def _save_snapshots() -> None:
        # Warm prompt KV must survive BOTH exit paths — Ctrl+C and a
        # clean SIGTERM drain (asyncio.run tears engines down before any
        # engine.stop() would run).
        for eng in _ENGINES:
            try:
                eng.save_prefix_snapshot()
            except Exception as e:  # best-effort on the exit path
                log.warning("prefix snapshot on shutdown failed: %s", e)

    args = build_parser().parse_args(argv)
    try:
        asyncio.run(_amain(args))
        _save_snapshots()
    except KeyboardInterrupt:
        log.info("interrupted, shutting down")
        _save_snapshots()
        if got_sig["num"] == _signal.SIGTERM:
            # Die BY SIGTERM so supervisors (systemd SuccessExitStatus,
            # docker) see a normal stop, not exit code 130.
            _signal.signal(_signal.SIGTERM, _signal.SIG_DFL)
            os.kill(os.getpid(), _signal.SIGTERM)
        sys.exit(130)


if __name__ == "__main__":
    main()
