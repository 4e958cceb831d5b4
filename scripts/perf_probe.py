#!/usr/bin/env python
"""Decode-loop micro-probe: isolate the engine's jitted decode burst.

Measures, on the real chip and without the tunnel stack:
- engine init time (weights on device)
- decode-burst compile time
- steady-state per-burst wall time → implied tok/s upper bound
- XLA cost analysis (bytes accessed / flops) and memory analysis of the
  compiled burst, to verify where HBM traffic goes (VERDICT r3 item 1:
  is the int8 dequant materializing a bf16 weight copy?)

Env knobs: PP_MODEL, PP_QUANT (int8|w8a8|int4|none), PP_GROUP (int4 scale
group size, default 128), PP_KV_QUANT (none|int8|int4), PP_FUSED=1 (the
fused decode-layer kernel, ISSUE 4), PP_SLOTS, PP_STEPS, PP_MAX_SEQ,
PP_ITERS, PP_POS (starting cache position), PP_PIPELINE=1 (dispatch burst
n before fetching n-1, like the engine loop).

Besides wall times and XLA cost analysis, reports the burst program's
KERNEL/LAUNCH COUNTS from the TPU-lowered StableHLO (utils/hlo.py) —
works from any CPU host, so the fused kernel's launch-collapse (and any
regression re-splitting the layer body) is measurable without a chip
window.  ``kernels_per_layer_step`` is the major-kernel count of the
layer-scan body; ``layer_body_ops`` is the unfused-op upper bound.

The int4 acceptance probe (ISSUE 2): with PP_QUANT=int4 on the 8B shape
the cost analysis must report ≤ 4.5 GB HBM bytes-accessed/step (vs ~7.85
GB for int8) — i.e. XLA reads PACKED bytes from HBM and never
materializes the bf16 weight copy.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from p2p_llm_tunnel_tpu.utils.compile_cache import enable as _enable_cache

_enable_cache()


def main() -> None:
    model = os.environ.get("PP_MODEL", "llama3-8b")
    quant = os.environ.get("PP_QUANT", "int8")
    slots = int(os.environ.get("PP_SLOTS", "32"))
    steps = int(os.environ.get("PP_STEPS", "16"))
    max_seq = int(os.environ.get("PP_MAX_SEQ", "512"))
    iters = int(os.environ.get("PP_ITERS", "6"))
    pos0 = int(os.environ.get("PP_POS", "32"))
    pipeline = os.environ.get("PP_PIPELINE", "1") == "1"
    kv_view = int(os.environ.get("PP_VIEW", str(max_seq)))
    group = int(os.environ.get("PP_GROUP", "128"))
    kv_quant = os.environ.get("PP_KV_QUANT", "none")
    fused = os.environ.get("PP_FUSED", "0") == "1"

    from p2p_llm_tunnel_tpu.engine import sampling
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer
    from p2p_llm_tunnel_tpu.models.config import get_config

    print(
        f"probe: model={model} quant={quant} slots={slots} steps={steps} "
        f"max_seq={max_seq} backend={jax.default_backend()}",
        file=sys.stderr, flush=True,
    )
    t0 = time.monotonic()
    eng = InferenceEngine(
        engine_cfg=EngineConfig(
            model=model, num_slots=slots, max_seq=max_seq,
            decode_steps=steps, quant=quant, quant_group_size=group,
            kv_quant=kv_quant, fused_decode_layer=fused,
        ),
        tokenizer=ByteTokenizer(vocab_size=get_config(model).vocab_size),
    )
    jax.block_until_ready(eng.params)
    t_init = time.monotonic() - t0
    print(f"init: {t_init:.1f}s", file=sys.stderr, flush=True)

    rows = slots + 1
    # Mirrors engine._warm_samp exactly (same dtypes incl. seed/bias_on)
    # so the probed program hashes identically to the served one.
    samp = sampling.SamplingParams(
        temperature=jnp.zeros((rows,), jnp.float32),
        top_k=jnp.zeros((rows,), jnp.int32),
        top_p=jnp.ones((rows,), jnp.float32),
        freq_pen=jnp.zeros((rows,), jnp.float32),
        pres_pen=jnp.zeros((rows,), jnp.float32),
        logprobs=jnp.zeros((rows,), jnp.int32),
        seed=jnp.zeros((rows,), jnp.uint32),
        bias_on=jnp.zeros((rows,), bool),
    )
    tokens = jnp.full((rows,), 5, jnp.int32)
    positions = jnp.full((rows,), pos0, jnp.int32)
    counts = jnp.zeros((rows, eng.mcfg.vocab_size), jnp.int32)
    bias = jnp.zeros((rows, eng.mcfg.vocab_size), jnp.float32)
    ovm = jnp.zeros((rows,), bool)
    ovt = jnp.full((rows,), 5, jnp.int32)
    ovp = jnp.full((rows,), pos0, jnp.int32)
    key = jax.random.PRNGKey(0)

    # Expected weight stream per decode step (every leaf read once):
    # packed q/scale bytes summed over the param tree.  The cost-analysis
    # "bytes accessed" below must be in this ballpark × steps (+ KV terms);
    # a ~3x overshoot means XLA materialized a dequantized weight copy
    # (the r3 int8 suspicion — fusion must keep reads at the packed size).
    weight_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(eng.params)
    )
    print(
        f"param bytes (read once per step): {weight_bytes / 1e9:.2f} GB",
        file=sys.stderr, flush=True,
    )

    # Cost/memory analysis of the burst program (non-donating lower to keep
    # the analysis side-effect-free).
    try:
        lowered = jax.jit(eng._decode_fn, static_argnums=(11, 12)).lower(
            eng.params, eng.kv_cache, tokens, positions, counts, bias, ovm,
            ovt, ovp, samp, key, kv_view, steps,
        )
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        interesting = {
            k: v for k, v in ca.items()
            if k in ("flops", "bytes accessed", "transcendentals",
                     "bytes accessed operand 0 {}", "optimal_seconds")
        }
        print(f"cost_analysis: {interesting}", file=sys.stderr, flush=True)
        try:
            ma = compiled.memory_analysis()
            print(
                "memory_analysis: "
                f"arg={getattr(ma, 'argument_size_in_bytes', '?')} "
                f"out={getattr(ma, 'output_size_in_bytes', '?')} "
                f"temp={getattr(ma, 'temp_size_in_bytes', '?')} "
                f"alias={getattr(ma, 'alias_size_in_bytes', '?')}",
                file=sys.stderr, flush=True,
            )
        except Exception as e:  # pragma: no cover - diagnostics only
            print(f"memory_analysis unavailable: {e}", file=sys.stderr)
    except Exception as e:  # pragma: no cover - diagnostics only
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)

    # Kernel/launch counts of the REAL TPU burst program, cross-lowered
    # from this host (utils/hlo.py) — next to bytes-accessed, so both the
    # byte-traffic and the launch-count terms of the decode roofline are
    # visible off-chip.  One recipe, owned by the engine
    # (decode_launch_report): the probe re-implementing the jit signature
    # here is the TC02 stale-signature incident class.
    report = None
    try:
        report = eng.decode_launch_report(view=kv_view, steps=steps)
        if report is not None:
            print(
                "launch counts: "
                f"kernels_per_layer_step={report['layer_body_major']} "
                f"layer_body_ops={report['layer_body_ops']} "
                f"layer_body_pallas={report['layer_body_pallas']} "
                f"total_major={report['total_major']} "
                f"total_ops={report['total_ops']}",
                file=sys.stderr, flush=True,
            )
        else:
            print("launch counts unavailable (TPU lowering failed)",
                  file=sys.stderr)
    except Exception as e:  # pragma: no cover - diagnostics only
        print(f"launch counts unavailable: {e}", file=sys.stderr)

    t0 = time.monotonic()
    out = eng._jit_decode(
        eng.params, eng.kv_cache, tokens, positions, counts, bias, ovm, ovt,
        ovp, samp, key, kv_view, steps,
    )
    jax.block_until_ready(out)
    t_compile = time.monotonic() - t0
    print(f"compile+first burst: {t_compile:.1f}s", file=sys.stderr, flush=True)
    sampled, _lp, tokens, positions, counts, kv = out

    times = []
    if pipeline:
        in_flight = None
        for i in range(iters + 1):
            t0 = time.monotonic()
            if i < iters:
                cur = eng._jit_decode(
                    eng.params, kv, tokens, positions, counts, bias, ovm,
                    ovt, ovp, samp, jax.random.fold_in(key, i), kv_view,
                    steps,
                )
                sampled, _lp, tokens, positions, counts, kv = cur
            if in_flight is not None:
                np.asarray(jax.device_get(in_flight))
                times.append(time.monotonic() - t0)
            in_flight = sampled if i < iters else None
    else:
        for i in range(iters):
            t0 = time.monotonic()
            sampled, _lp, tokens, positions, counts, kv = eng._jit_decode(
                eng.params, kv, tokens, positions, counts, bias, ovm, ovt,
                ovp, samp, jax.random.fold_in(key, i), kv_view, steps,
            )
            np.asarray(jax.device_get(sampled))
            times.append(time.monotonic() - t0)

    times = sorted(times)
    med = times[len(times) // 2]
    per_step_ms = med * 1000.0 / steps
    tok_s = slots * steps / med
    result = {
        "model": model, "quant": quant, "kv_quant": kv_quant,
        "fused_decode_layer": fused, "slots": slots, "steps": steps,
        "param_gb": round(weight_bytes / 1e9, 2),
        "kernels_per_layer_step": (
            report["layer_body_major"] if report else None
        ),
        "layer_body_ops": report["layer_body_ops"] if report else None,
        "max_seq": max_seq, "kv_view": kv_view, "init_s": round(t_init, 1),
        "compile_s": round(t_compile, 1),
        "burst_ms_median": round(med * 1000.0, 1),
        "per_step_ms": round(per_step_ms, 2),
        "tok_s_upper_bound": round(tok_s, 1),
        "all_burst_ms": [round(t * 1000.0, 1) for t in times],
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
