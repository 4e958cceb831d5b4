#!/usr/bin/env python
"""End-to-end benchmark: tokens/sec and TTFT through the tunnel.

Measures the BASELINE.json metric — decode throughput and p50 time-to-first-
token for concurrent OpenAI SSE streams, measured at the HTTP client, through
the full stack:

    client → proxy endpoint → tunnel frames → serve endpoint → JAX engine
           ← SSE chunks     ← RES_BODY/token ←

Prints ONE JSON line on stdout for a run that measured something:
{"metric", "value", "unit", "vs_baseline", ...extras}.  ``vs_baseline`` is
against the driver target of 1800 tok/s llama3-8b (BASELINE.md); the
reference itself publishes no numbers (SURVEY.md §6).

One attempt at the model asked for (BENCH_MODEL, default llama3-8b), on the
device JAX finds.  It runs in a child process with a deadline, so a compile
that never returns is killed; a failed attempt exits non-zero.  Without a
TPU the attempt fails, unless the caller asked for the CPU explicitly with
JAX_PLATFORMS=cpu (`make bench-smoke`): such a row names its platform and
carries ``vs_baseline`` and ``mfu`` null.

One process for each chip: this parent never imports JAX, the serving child
is the only process that touches the chip, and the load generator it spawns
is pinned to the CPU.

Env knobs: BENCH_MODEL, BENCH_CLIENTS, BENCH_MAX_TOKENS, BENCH_SLOTS,
BENCH_MAX_SEQ, BENCH_DTYPE, BENCH_DECODE_STEPS (decode burst size),
BENCH_QUANT (none|int8|w8a8|int4; default int8), BENCH_QUANT_GROUP (int4
scale group size, default 128), BENCH_BUDGET_S (overall wall budget,
default 480), BENCH_PROFILE_DIR (write a jax.profiler trace of the
measure window).
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import subprocess
import sys
import threading
import time

TARGET_TOK_S = 1800.0  # BASELINE.md: Llama-3 8B / v5e-1 target
T_START = time.monotonic()

#: Published per-chip peaks, keyed by JAX's ``device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM.  A TPU
#: kind missing here is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


#: The bench result-row schema (ISSUE 9): exactly the keys every
#: successful attempt's JSON row carries, pinned here AND statically
#: cross-checked against the row-builder dict by tests/test_bench_smoke.py
#: — CI appends `make bench-smoke` rows to trend files, so a silently
#: renamed/dropped key would corrupt every downstream reader.  _finalize()
#: may ADD the driver-facing ``no_tpu`` key; it is optional by contract.
RESULT_ROW_KEYS = (
    "platform", "metric", "value", "unit", "vs_baseline",
    "ttft_p50_ms", "ttft_p99_ms", "ttft_p999_ms",
    "ttfb_p50_ms", "ttfb_p99_ms", "ttfb_p999_ms",
    "engine_ttft_p50_ms", "engine_ttft_p99_ms",
    "queue_wait_p50_ms", "prefill_exec_p50_ms",
    "prefill_p50_ms", "decode_fetch_p50_ms",
    "mfu", "model", "quant", "quant_group_size", "prefill_act_quant",
    "kv_quant", "flash_decode", "flash_sgrid", "fused_decode_layer",
    "ragged_prefill",
    "decode_kernels_per_step", "prefix_cache", "spec_ngram",
    "spec_k", "spec_accept_rate",
    "mux", "mux_budget_tokens", "mux_prefill_chunk",
    "shared_prefix_tokens", "prefix_hit_tokens", "prefix_dedup_hits",
    "pages_used", "pages_free", "conversation_hit_rate",
    "spill_pages", "spill_tier_hit_rate", "spill_pagein_p50_ms",
    # ISSUE 20 add-only extension: the disaggregated A/B.  `disagg` is
    # the topology knob (two-engine prefill/decode fabric vs the
    # single-engine loopback), the counters are the page wire motion,
    # and kv_export_p50_ms is the TTFT split's transfer leg — the
    # queue_wait/prefill_exec decomposition above carries the local
    # legs, so a disagg-on/off twin pair splits TTFT end to end.
    "disagg", "pages_shipped", "pages_spliced", "page_xfer_bytes",
    "disagg_handoffs", "disagg_fallbacks", "affinity_hits",
    "kv_export_p50_ms",
    "warmup_compile_s", "warmup_programs", "warmup_compile_max_s",
    "clients", "engine_tok_s", "engine_tokens", "visible_tokens",
    "wall_s",
)


def _log(msg: str) -> None:
    print(f"bench[{time.monotonic() - T_START:7.1f}s]: {msg}",
          file=sys.stderr, flush=True)


def _budget_s() -> float:
    return float(os.environ.get("BENCH_BUDGET_S", "480"))


_CLIENT_MOD = None


def _one_client(port: int, prompt: str, max_tokens: int, results: list, idx: int):
    """The SSE client (token/TTFT definitions) lives in ONE place —
    scripts/bench_clients.py — used both by the out-of-process load
    generator and by this module's warmup / BENCH_INPROC_CLIENTS paths, so
    the metric definition cannot drift between them."""
    global _CLIENT_MOD
    if _CLIENT_MOD is None:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "scripts", "bench_clients.py",
        )
        spec = importlib.util.spec_from_file_location("bench_clients", path)
        _CLIENT_MOD = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_CLIENT_MOD)
    return _CLIENT_MOD.one_client(port, prompt, max_tokens, results, idx)


def _model_params(model: str) -> int:
    """Approximate parameter count, for the end-to-end MFU figure."""
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config(model)
    l, dm, h, kh, hd, f, v = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
        cfg.head_dim, cfg.ffn_dim, cfg.vocab_size,
    )
    params = v * dm + l * (dm * (h + 2 * kh) * hd + h * hd * dm + 3 * dm * f)
    if not cfg.tie_embeddings:
        params += dm * v
    return params


def _peak_bf16_flops(platform: str, device_kind: str):
    """The chip's published bf16 peak from DEVICE_PEAKS; None on the CPU
    (an explicit rehearsal has no MFU), an error for an unknown TPU."""
    if platform != "tpu":
        return None
    if device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peak for device kind {device_kind!r}: add it "
            "to bench.DEVICE_PEAKS with its source"
        )
    return DEVICE_PEAKS[device_kind]["bf16_flops"]


async def _run_attempt(model: str) -> dict:
    import jax

    from p2p_llm_tunnel_tpu.endpoints.proxy import run_proxy
    from p2p_llm_tunnel_tpu.endpoints.serve import run_serve
    from p2p_llm_tunnel_tpu.engine.api import engine_backend
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.transport.loopback import loopback_pair
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    clients = int(os.environ.get("BENCH_CLIENTS", "32"))
    max_tokens = int(os.environ.get("BENCH_MAX_TOKENS", "128"))
    slots = int(os.environ.get("BENCH_SLOTS", "32"))
    max_seq = int(os.environ.get("BENCH_MAX_SEQ", "512"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    decode_steps = int(os.environ.get("BENCH_DECODE_STEPS", "16"))
    eager_steps = int(os.environ.get("BENCH_DECODE_STEPS_EAGER", "4"))
    prefill_rows = int(os.environ.get("BENCH_PREFILL_ROWS", "8"))
    quant = os.environ.get("BENCH_QUANT", "int8")
    quant_group = int(os.environ.get("BENCH_QUANT_GROUP", "128"))
    # Effective only with int8 weights (the engine ignores it otherwise);
    # record what actually ran, not what was asked for.
    pf8 = (os.environ.get("BENCH_PREFILL_ACT_QUANT", "1") == "1"
           and quant == "int8")
    kv_quant = os.environ.get("BENCH_KV_QUANT", "none")
    # BENCH_FLASH_SGRID implies flash decode; as of ISSUE 4 BOTH flags
    # route to the s-grid kernel family, which composes with every
    # kv_quant mode (in-VMEM dequant) — the legacy plane kernel is no
    # longer reachable, so the old "int8 cache forces the einsum path
    # under bare BENCH_FLASH_DECODE" carve-out is gone.
    flash_sgrid = os.environ.get("BENCH_FLASH_SGRID", "0") == "1"
    flash_decode = (
        flash_sgrid or os.environ.get("BENCH_FLASH_DECODE", "0") == "1"
    )
    # The fused decode-layer kernel (ISSUE 4): supersedes the flash
    # selection when set — rope + KV quant + cache append + attention in
    # one program per layer.
    fused_decode = os.environ.get("BENCH_FUSED_DECODE", "0") == "1"
    # Ragged grouped prefill (ISSUE 15): one flat-packed Pallas launch
    # per admission group instead of the chunk[t, view] program family —
    # the warmup_programs / warmup_compile_s fields in the row are the
    # cold-start axis its sweep twins compare.
    ragged_prefill = os.environ.get("BENCH_RAGGED_PREFILL", "0") == "1"
    # Automatic prefix caching — on by default here AND in the serve CLI
    # (TUNNEL_PREFIX_CACHE), so the benched config is the deployed default.
    # The bench prompts share a prefix the way real traffic shares system
    # prompts; the result JSON records the knob + hit counts so the number
    # is interpretable, and the sweep's pfx-off row isolates its effect.
    prefix_cache = os.environ.get("BENCH_PREFIX_CACHE", "1") == "1"
    # Chunked prefill: off by default (bench prompts are short); the
    # long-context sweep configs turn it on.
    prefill_chunk = int(os.environ.get("BENCH_PREFILL_CHUNK", "0"))
    spec_ngram = int(os.environ.get("BENCH_SPEC_NGRAM", "0"))
    # Fused K-token verify burst width (ISSUE 17); BENCH_SPEC_K_MAX > K
    # additionally enables the adaptive power-of-two K ladder.
    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    spec_k_max = int(os.environ.get("BENCH_SPEC_K_MAX", "0"))
    # Iteration-level prefill/decode multiplexing + prefix-grouped
    # admission (ISSUE 5) — on by default here AND in the serve CLI
    # (TUNNEL_MUX), so the benched config is the deployed default; the
    # sweep's mux-off twins isolate its effect.
    mux = os.environ.get("BENCH_MUX", "1") == "1"
    mux_budget = int(os.environ.get("BENCH_MUX_BUDGET", "0"))
    # Cross-request conversation cache (ISSUE 14) — on by default here AND
    # in the serve CLI (TUNNEL_CONV_CACHE); needs the prefix pool.  The
    # row records pool occupancy + the conversation hit rate so multi-turn
    # reuse is a trend axis.
    conv_cache = os.environ.get("BENCH_CONV_CACHE", "1") == "1"
    prefix_evict = os.environ.get("BENCH_PREFIX_EVICT", "cost")
    # Host-RAM KV spill tier (ISSUE 16) — off by default (the default
    # bench pool never fills); the memory-pressure sweep configs size it.
    spill_pages = int(os.environ.get("BENCH_SPILL_PAGES", "0"))
    # Disaggregated prefill/decode A/B (ISSUE 20): BENCH_DISAGG=1 builds
    # the two-engine fabric — a prefill-role peer exporting KV pages and
    # a decode-role peer splicing them — behind run_proxy_fabric with
    # prefix-affinity routing, instead of the single-engine loopback.
    # Needs the prefix pool on both peers (the engine fences role=* back
    # to "both" without it), so a pool-less config runs undisaggregated
    # and the row says so.
    disagg = os.environ.get("BENCH_DISAGG", "0") == "1"
    if disagg and not prefix_cache:
        _log("BENCH_DISAGG=1 needs BENCH_PREFIX_CACHE=1; "
             "running undisaggregated")
        disagg = False
    # Cold-shared-prefix herd (the ISSUE 5 TTFT workload): prepend this
    # many tokens of IDENTICAL templated text to every measured client's
    # prompt — but not the warm client's, so the herd hits the prefix
    # machinery cold, the way a restart or a template rollout does.
    shared_prefix_tokens = int(
        os.environ.get("BENCH_SHARED_PREFIX_TOKENS", "0")
    )
    if model == "tiny":
        # tiny is the CPU rehearsal size; keep it light — but
        # an EXPLICIT env override wins, so CPU herd experiments (the
        # ISSUE 5 32-client TTFT A/B) can use the real fan-out.
        if "BENCH_CLIENTS" not in os.environ:
            clients = min(clients, 8)
        if "BENCH_SLOTS" not in os.environ:
            slots = min(slots, 8)
        if "BENCH_MAX_TOKENS" not in os.environ:
            max_tokens = 32

    prompt = "Benchmark this tunnel with a steady stream of tokens."
    # Long-prompt runs (chunked-prefill / long-context configs): repeat the
    # base text to ~BENCH_PROMPT_TOKENS byte-tokens.
    want_tokens = int(os.environ.get("BENCH_PROMPT_TOKENS", "0"))
    if want_tokens > 0:
        reps = max(1, want_tokens // (len(prompt) + 1))
        prompt = " ".join([prompt] * reps)
    # Measured clients may carry a shared templated prefix the warm client
    # never saw (see shared_prefix_tokens above): the herd then exercises
    # cold prefix dedup, not a pool pre-warmed by the warmup request.
    measure_prompt = prompt
    if shared_prefix_tokens > 0:
        blurb = ("You are a helpful assistant serving through a "
                 "peer-to-peer tunnel; answer with care and cite the "
                 "system policy where relevant. ")
        reps = max(1, -(-shared_prefix_tokens // len(blurb)))
        measure_prompt = (blurb * reps)[:shared_prefix_tokens] + prompt

    _log(
        f"attempt model={model} clients={clients} max_tokens={max_tokens} "
        f"slots={slots} decode_steps={decode_steps} quant={quant} "
        f"prefill_act_quant={pf8} flash_decode={flash_decode}"
    )
    t0 = time.monotonic()
    from p2p_llm_tunnel_tpu.engine.tokenizer import NumericTokenizer
    from p2p_llm_tunnel_tpu.models.config import get_config

    # Keep the preset's REAL vocabulary (llama3: 128256) so the embed and
    # lm_head matmuls — ~12% of 8B decode HBM traffic — are benched at true
    # size.  NumericTokenizer renders EVERY sampled id as visible text, so
    # each decoded token crosses the tunnel as a RES_BODY-framed SSE chunk
    # and the headline number can be counted CLIENT-side (VERDICT r3
    # item 3: the r3 run measured with the tunnel idle).
    ecfg_kw = dict(
        model=model, num_slots=slots, max_seq=max_seq, dtype=dtype,
        decode_steps=decode_steps, decode_steps_eager=eager_steps,
        prefill_rows=prefill_rows, quant=quant,
        quant_group_size=quant_group,
        prefill_act_quant=pf8, flash_decode=flash_decode,
        flash_sgrid=flash_sgrid, fused_decode_layer=fused_decode,
        kv_quant=kv_quant, prefix_cache=prefix_cache,
        prefill_chunk=prefill_chunk, spec_ngram=spec_ngram,
        spec_k=spec_k, spec_k_max=spec_k_max,
        ragged_prefill=ragged_prefill,
        mux=mux, mux_budget_tokens=mux_budget,
        conv_cache=conv_cache and prefix_cache,
        prefix_evict=prefix_evict,
        spill_pages=spill_pages,
    )
    engine = InferenceEngine(
        engine_cfg=EngineConfig(
            role="decode" if disagg else "both", **ecfg_kw,
        ),
        tokenizer=NumericTokenizer(vocab_size=get_config(model).vocab_size),
    )
    # The prefill half of the disaggregated pair: EVERY numerics-relevant
    # knob identical (same ecfg_kw — the pin check + byte-identity depend
    # on it), only the role differs.
    pre_engine = None
    if disagg:
        # Two full engines in one process: on one chip that is two weight
        # sets.  Say so, rather than let the allocator fail mid-build.
        mem = jax.local_devices()[0].memory_stats() or {}
        if 2 * mem.get("bytes_in_use", 0) > mem.get("bytes_limit", 1 << 62):
            raise SystemExit(
                f"BENCH_DISAGG=1: the decode engine holds "
                f"{mem['bytes_in_use'] / 2**30:.1f} GiB of this device's "
                f"{mem['bytes_limit'] / 2**30:.1f} GiB, so a second full "
                f"engine of {model} does not fit in one process on one "
                "chip; use a smaller model or run undisaggregated"
            )
        pre_engine = InferenceEngine(
            engine_cfg=EngineConfig(role="prefill", **ecfg_kw),
            tokenizer=NumericTokenizer(
                vocab_size=get_config(model).vocab_size
            ),
        )
    _log(f"engine init (weights on device) took {time.monotonic() - t0:.1f}s")
    await engine.start()
    if pre_engine is not None:
        await pre_engine.start()

    # Warmup hints (see engine._warmup_views / _warm_aot_parallel): the
    # bench KNOWS its maximum reachable context — the server's OWN chat
    # rendering of the longest client prompt, tokenized by the engine's
    # OWN tokenizer (no BOS: see below), +max_tokens — so warmup can skip kv-view
    # buckets the traffic cannot hit, and AOT-compile the rest in
    # parallel.  Every program warmed is compile time before the first
    # request; both hints cut it to what this traffic can reach.
    from p2p_llm_tunnel_tpu.engine.api import render_chat_prompt

    # No BOS adjustment: the chat route prefills exactly
    # tok.encode(render_chat_prompt(...)) — and the counts must be EXACT,
    # not conservative: the prefill hint warms the bucket of precisely
    # this length, and a +1 landing on a bucket boundary would warm the
    # next bucket up while live traffic dispatches the lower one.
    worst = render_chat_prompt(
        [{"role": "user", "content": f"{measure_prompt} ({clients - 1})"}]
    )
    worst_toks = len(engine.tokenizer.encode(worst))
    ctx_cap = worst_toks + max_tokens
    os.environ.setdefault("TUNNEL_WARMUP_VIEW_CAP", str(ctx_cap))
    os.environ.setdefault("TUNNEL_WARMUP_PAR", "4")
    if engine.ecfg.prefill_chunk == 0:
        # Both prompt shapes the run prefills: the warm client (no " (i)"
        # suffix) and the measured clients.  Chunked-prefill configs —
        # including mux, which defaults a segment width in — skip the
        # hint: their prompts take the segment path instead.
        warm_prompt = render_chat_prompt([{"role": "user", "content": prompt}])
        warm_toks = len(engine.tokenizer.encode(warm_prompt))
        os.environ.setdefault(
            "TUNNEL_WARMUP_PREFILL_TOKENS", f"{warm_toks},{worst_toks}"
        )

    t0 = time.monotonic()
    await engine.warmup()
    if pre_engine is not None:
        # Same hint env vars: the prefill peer prefills the same prompt
        # shapes; its decode programs are dead weight but warmup is the
        # only place the shared compile cache gets populated.
        await pre_engine.warmup()
    _log(f"decode warmup (view x steps compiles) took {time.monotonic() - t0:.1f}s")
    # Cold-start breakdown (ISSUE 12): captured NOW — the post-warmup
    # global_metrics.reset() below wipes the gauges, and cold start
    # deserves trend datapoints of its own: total wall, program count, and
    # the slowest single program.
    warmup_compile_s = round(
        global_metrics.gauge("engine_warmup_compile_s"), 2
    )
    warmup_programs = int(global_metrics.gauge("engine_warmup_programs"))
    warmup_compile_max_s = round(
        global_metrics.gauge("engine_warmup_compile_max_s"), 2
    )

    ready: asyncio.Future = asyncio.get_running_loop().create_future()
    serve_tasks = []
    if disagg:
        # Two serve peers behind one fabric proxy (mirrors
        # testing/local_stack._amain_disagg): the decode peer is the
        # measured engine; the prefill peer exists to ship KV pages.
        from p2p_llm_tunnel_tpu.endpoints.proxy import (
            ProxyState,
            run_proxy_fabric,
        )

        state = ProxyState(fabric=True)
        for pid, eng in (("prefill-0", pre_engine), ("decode-0", engine)):
            serve_ch, proxy_ch = loopback_pair()
            serve_tasks.append(asyncio.create_task(run_serve(
                serve_ch, backend=engine_backend(eng, model),
            )))
            await state.admit(proxy_ch, pid)
        proxy_task = asyncio.create_task(
            run_proxy_fabric(state, "127.0.0.1", 0, ready=ready)
        )
    else:
        serve_ch, proxy_ch = loopback_pair()
        serve_tasks.append(asyncio.create_task(
            run_serve(serve_ch, backend=engine_backend(engine, model))
        ))
        proxy_task = asyncio.create_task(
            run_proxy(proxy_ch, "127.0.0.1", 0, ready=ready)
        )
    port = await asyncio.wait_for(ready, 30.0)

    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    profiling = False
    try:
        # Warmup with ONE client: compiles the (bucketed) batched-prefill
        # program and the k-step decode program — the measurement fan-out
        # reuses both, so no compile lands inside the timed window.
        t0 = time.monotonic()
        warm: list = []
        await _one_client(port, prompt, 4, warm, -1)
        _log(f"warmup (compiles) took {time.monotonic() - t0:.1f}s")
        global_metrics.reset()

        if profile_dir:
            jax.profiler.start_trace(profile_dir)
            profiling = True
        # The client fan-out runs in its OWN process so the server stack
        # (proxy + tunnel + serve + engine host path) never competes with
        # client-side SSE parsing for this interpreter — the reference is
        # always load-tested from external processes too (curl in
        # scripts/test-tunnel.sh).  BENCH_INPROC_CLIENTS=1 restores the
        # old in-process fan-out for debugging.
        tokens_before = global_metrics.counter("engine_tokens_total")
        t_start = time.monotonic()
        if os.environ.get("BENCH_INPROC_CLIENTS") == "1":
            results: list = []
            await asyncio.gather(
                *(
                    _one_client(port, f"{measure_prompt} ({i})", max_tokens,
                                results, i)
                    for i in range(clients)
                )
            )
            wall = time.monotonic() - t_start
        else:
            repo = os.path.dirname(os.path.abspath(__file__))
            cfg = json.dumps({
                "port": port, "clients": clients,
                "max_tokens": max_tokens, "prompt": measure_prompt,
            })
            # One process for each chip: this process holds it, so the
            # load generator is pinned to the CPU.
            proc = await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(repo, "scripts", "bench_clients.py"),
                cfg,
                stdout=asyncio.subprocess.PIPE,
                env=dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu"),
            )
            out, _ = await proc.communicate()
            if proc.returncode != 0:
                raise RuntimeError(f"loadgen exited rc={proc.returncode}")
            payload = json.loads(out.decode().strip().splitlines()[-1])
            results = payload["results"]
            wall = payload["wall_s"]  # child-side fan-out wall (excludes spawn)
        engine_tokens = global_metrics.counter("engine_tokens_total") - tokens_before
        _log(f"measured {engine_tokens:.0f} tokens in {wall:.1f}s")
    finally:
        if profiling:
            jax.profiler.stop_trace()
            _log(f"profiler trace written to {profile_dir}")
        proxy_task.cancel()
        for t in serve_tasks:
            t.cancel()
        for t in (*serve_tasks, proxy_task):
            try:
                await t
            except (asyncio.CancelledError, RuntimeError):
                pass
        await engine.stop()
        if pre_engine is not None:
            await pre_engine.stop()

    # Headline tok/s counts tokens RECEIVED BY THE HTTP CLIENTS as SSE
    # deltas — every one crossed the tunnel as a RES_BODY frame, so frame
    # mux + flow control + SSE emission are inside the measurement.  The
    # engine counter is reported alongside as a cross-check (they differ
    # only by surplus tokens decoded past a request's eviction).
    visible_tokens = sum(r["tokens"] for r in results)
    ttfts = sorted(r["ttft_s"] for r in results if r["ttft_s"] is not None)
    tok_s = visible_tokens / wall if wall > 0 else 0.0
    ttft_p50_ms = statistics.median(ttfts) * 1000.0 if ttfts else None

    def _pct_ms(xs, p):
        """Client-side percentile in ms via the registry's shared
        nearest-rank estimator (ISSUE 6: herd rows carry the p99/p999
        tails next to p50 — goodput per DistServe is defined against
        per-request SLOs, which live in the tail, not the median).  With
        a herd smaller than 1/(1-p) this reports the max — honest, and
        the row's `clients` field says so."""
        from p2p_llm_tunnel_tpu.utils.metrics import nearest_rank

        if not xs:
            return None
        return round(nearest_rank(xs, p) * 1000.0, 1)
    dev0 = jax.local_devices()[0]
    peak_flops = _peak_bf16_flops(dev0.platform, dev0.device_kind)
    admissions = global_metrics.counter("engine_admissions_total")
    conv_hit_rate = (
        round(global_metrics.counter("engine_conv_hits_total") / admissions, 4)
        if admissions > 0 else None
    )
    # Spill-tier effectiveness (ISSUE 16): of the page-in attempts the
    # scheduler issued, the fraction that spliced cleanly (the rest fell
    # back to tail re-prefill).  None when the tier never moved a page.
    spill_ins = global_metrics.counter("engine_spill_pageins_total")
    spill_in_fails = global_metrics.counter(
        "engine_spill_pagein_failures_total"
    )
    spill_hit_rate = (
        round(spill_ins / (spill_ins + spill_in_fails), 4)
        if (spill_ins + spill_in_fails) > 0 else None
    )
    row = {
        # The backend the measurement ACTUALLY ran on — _finalize() nulls
        # vs_baseline and mfu off this, so a CPU rehearsal can never
        # masquerade as a TPU datapoint.
        "platform": dev0.platform,
        "metric": "e2e_decode_tok_s",
        "value": round(tok_s, 2),
        "unit": "tok/s",
        "vs_baseline": round(tok_s / TARGET_TOK_S, 4),
        "ttft_p50_ms": round(ttft_p50_ms, 1) if ttft_p50_ms is not None else None,
        # Tail percentiles next to p50 (ISSUE 6, first slice of the
        # 1k-client ingress item): client-side TTFT tails plus the proxy's
        # first-byte tails from the upgraded registry reservoirs.
        "ttft_p99_ms": _pct_ms(ttfts, 99),
        "ttft_p999_ms": _pct_ms(ttfts, 99.9),
        "ttfb_p50_ms": round(global_metrics.percentile("proxy_ttfb_ms", 50), 1),
        "ttfb_p99_ms": round(global_metrics.percentile("proxy_ttfb_ms", 99), 1),
        "ttfb_p999_ms": round(
            global_metrics.percentile("proxy_ttfb_ms", 99.9), 1
        ),
        # Client TTFT waits for the first VISIBLE SSE delta; with random
        # weights the byte decoder buffers invisible UTF-8 fragments, so the
        # engine's submit→first-token histogram is the accurate lower bound.
        "engine_ttft_p50_ms": round(global_metrics.percentile("engine_ttft_ms", 50), 1),
        "engine_ttft_p99_ms": round(
            global_metrics.percentile("engine_ttft_ms", 99), 1
        ),
        # TTFT decomposition (ISSUE 5): queue wait (submit -> slot) +
        # prefill execution (slot -> first token, incl. dedup park time).
        "queue_wait_p50_ms": round(
            global_metrics.percentile("engine_queue_wait_ms", 50), 1
        ),
        "prefill_exec_p50_ms": round(
            global_metrics.percentile("engine_prefill_exec_ms", 50), 1
        ),
        "prefill_p50_ms": round(global_metrics.percentile("engine_prefill_ms", 50), 1),
        "decode_fetch_p50_ms": round(
            global_metrics.percentile("engine_decode_fetch_ms", 50), 1
        ),
        # End-to-end utilisation: tok/s x 2 x params over the chip's
        # published bf16 peak (DEVICE_PEAKS) — not a kernel's roofline.
        "mfu": (round(tok_s * 2 * _model_params(model) / peak_flops, 4)
                if peak_flops else None),
        "model": model,
        "quant": quant,
        "quant_group_size": quant_group if quant == "int4" else None,
        "prefill_act_quant": pf8,
        "kv_quant": kv_quant,
        "flash_decode": flash_decode,
        "flash_sgrid": flash_sgrid,
        "fused_decode_layer": fused_decode,
        # EFFECTIVE knob (the engine fences it off untileable shapes /
        # sp>1 meshes): a row claiming the requested value would
        # misattribute its warmup_* fields.
        "ragged_prefill": engine.ecfg.ragged_prefill,
        "decode_kernels_per_step": global_metrics.gauge(
            "engine_decode_kernels_per_step"
        ),
        # EFFECTIVE knobs, read back from the engine: kv_quant=int4
        # disables prefix cache / spec decode internally, and a row that
        # claims the requested value would misattribute the number.
        "prefix_cache": engine._prefix is not None,
        "spec_ngram": engine.ecfg.spec_ngram,
        # ISSUE 17: the verify burst width and the measured acceptance
        # rate (accepted/proposed over the whole measurement window) —
        # the two numbers that make a spec-on row's tok/s interpretable.
        "spec_k": engine.ecfg.spec_k,
        "spec_accept_rate": round(
            global_metrics.counter("engine_spec_accepted_tokens_total")
            / max(1.0, global_metrics.counter(
                "engine_spec_proposed_tokens_total")), 3
        ),
        # EFFECTIVE mux knobs (the engine may disable/default them) plus
        # the herd-shape knob, so every mux row is self-describing.
        "mux": engine.ecfg.mux,
        "mux_budget_tokens": engine.ecfg.mux_budget_tokens,
        "mux_prefill_chunk": engine.ecfg.prefill_chunk,
        "shared_prefix_tokens": shared_prefix_tokens,
        "prefix_hit_tokens": global_metrics.counter(
            "engine_prefix_hit_tokens_total"
        ),
        "prefix_dedup_hits": global_metrics.counter(
            "engine_prefix_dedup_hits_total"
        ),
        # Block-paged pool occupancy + conversation-cache reuse (ISSUE 14):
        # pages at measurement end, and the fraction of admissions whose
        # prefix match reached into finished-stream (conversation) pages.
        "pages_used": int(
            global_metrics.gauge("engine_prefix_pool_blocks_used")
        ),
        "pages_free": int(
            global_metrics.gauge("engine_prefix_pool_blocks_free")
        ),
        "conversation_hit_rate": conv_hit_rate,
        # Host-RAM spill tier (ISSUE 16): shadow residency at measurement
        # end, page-in success rate, and the splice latency median.
        "spill_pages": int(global_metrics.gauge("engine_spill_pages")),
        "spill_tier_hit_rate": spill_hit_rate,
        "spill_pagein_p50_ms": round(
            global_metrics.percentile("engine_spill_pagein_ms", 50), 1
        ),
        # Disaggregated A/B (ISSUE 20): topology knob + page wire motion
        # (both engines share this process's registry, so shipped counts
        # the prefill peer and spliced the decode peer) + the transfer
        # leg of the TTFT split — queue_wait/prefill_exec above are the
        # local legs.
        "disagg": disagg,
        "pages_shipped": int(
            global_metrics.counter("engine_pages_shipped_total")
        ),
        "pages_spliced": int(
            global_metrics.counter("engine_pages_spliced_total")
        ),
        "page_xfer_bytes": int(
            global_metrics.counter("engine_page_xfer_bytes_total")
        ),
        "disagg_handoffs": int(
            global_metrics.counter("proxy_disagg_handoffs_total")
        ),
        "disagg_fallbacks": int(
            global_metrics.counter("proxy_disagg_fallbacks_total")
        ),
        "affinity_hits": int(
            global_metrics.counter("proxy_affinity_hits_total")
        ),
        "kv_export_p50_ms": (
            round(global_metrics.percentile("engine_page_export_ms", 50), 1)
            if disagg else None
        ),
        # Cold-start breakdown (ISSUE 12): captured before the
        # post-warmup metrics reset above.
        "warmup_compile_s": warmup_compile_s,
        "warmup_programs": warmup_programs,
        "warmup_compile_max_s": warmup_compile_max_s,
        "clients": clients,
        "engine_tok_s": round(engine_tokens / wall, 2) if wall > 0 else 0.0,
        "engine_tokens": engine_tokens,
        "visible_tokens": visible_tokens,
        "wall_s": round(wall, 2),
    }
    drift = set(row).symmetric_difference(RESULT_ROW_KEYS)
    if drift:
        # Schema drift is a bug in THIS file: the builder and the pinned
        # key list must move together (tests/test_bench_smoke.py also
        # cross-checks them statically).
        raise RuntimeError(
            f"bench result-row schema drift: {sorted(drift)} — update "
            "RESULT_ROW_KEYS and the schema test in lockstep"
        )
    return row


def _attempt_main(model: str, deadline_s: float) -> None:
    """Child-process entry: run the attempt, print its JSON, hard-exit on
    overrun (a compile that never returns can't be cancelled
    cooperatively).  This is the one process that touches the chip."""

    def watchdog():
        time.sleep(deadline_s)
        _log(f"attempt {model}: watchdog fired after {deadline_s:.0f}s")
        os._exit(3)

    threading.Thread(target=watchdog, daemon=True).start()
    import jax

    from p2p_llm_tunnel_tpu.cli import require_tpu_backend
    from p2p_llm_tunnel_tpu.utils.compile_cache import enable as enable_cache

    # Persistent compilation cache: init/decode/prefill programs compile
    # once per CONFIG, not once per process.
    cache_dir = enable_cache()
    require_tpu_backend(jax.default_backend(), "bench.py")
    dev0 = jax.local_devices()[0]
    _log(f"device: platform={dev0.platform} kind={dev0.device_kind} "
         f"count={len(jax.local_devices())}; compile cache at {cache_dir}")
    result = asyncio.run(_run_attempt(model))
    print(json.dumps(_finalize(result)), flush=True)


def _finalize(result: dict) -> dict:
    """Null the chip-only fields of any non-TPU measurement.

    The target (1800 tok/s, BASELINE.md) and the MFU peak are defined on
    TPU hardware only, so a CPU-platform row — an explicit
    JAX_PLATFORMS=cpu rehearsal — gets a top-level ``no_tpu`` flag with
    ``vs_baseline`` and ``mfu`` null; the raw tok/s stays for CPU-vs-CPU
    trend reading.  Nothing from an earlier chip run is attached."""
    if result.get("platform") != "tpu":
        result["no_tpu"] = True
        result["vs_baseline"] = None
        result["mfu"] = None
    return result


def main() -> None:
    if os.environ.get("BENCH_SINGLE"):
        _attempt_main(
            os.environ["BENCH_SINGLE"],
            float(os.environ.get("BENCH_SINGLE_DEADLINE", "420")),
        )
        return

    # This parent stays off JAX (one process for each chip): it only
    # spawns the serving child and enforces the budget from outside.
    budget = _budget_s()
    model = os.environ.get("BENCH_MODEL", "llama3-8b")
    _log(f"spawning attempt: {model} (deadline {budget:.0f}s)")
    env = dict(os.environ, BENCH_SINGLE=model,
               BENCH_SINGLE_DEADLINE=str(budget - 10))
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, timeout=budget + 30,
        )
    except subprocess.TimeoutExpired:
        # Child stuck past even its own watchdog (e.g. a native call
        # holding the GIL).
        _log(f"attempt {model} overran its {budget:.0f}s budget")
        sys.exit(1)
    lines = proc.stdout.decode().strip().splitlines()
    if proc.returncode != 0 or not lines:
        _log(f"attempt {model} failed (rc={proc.returncode})")
        sys.exit(proc.returncode or 1)
    print(lines[-1])


if __name__ == "__main__":
    main()
