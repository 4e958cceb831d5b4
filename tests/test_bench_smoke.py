"""`make bench-smoke` schema stability (ISSUE 9): the bench result-row
keys are a CONTRACT — CI appends smoke rows to trend files, so a renamed
or dropped key corrupts every downstream reader silently.

Fast and engine-free: the row-builder dict in bench._run_attempt is
cross-checked STATICALLY (ast) against bench.RESULT_ROW_KEYS, and both
against the list pinned here — three copies that must move in lockstep,
so drift in any one of them fails loudly.  (_run_attempt itself also
raises at runtime on drift; `make bench-smoke` exercises that path on a
real tiny CPU run.)
"""

from __future__ import annotations

import ast
import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The pinned schema.  Changing it is an intentional, reviewed act: update
#: bench.RESULT_ROW_KEYS, the row builder, and THIS list together.
PINNED_ROW_KEYS = (
    "platform", "metric", "value", "unit", "vs_baseline",
    "ttft_p50_ms", "ttft_p99_ms", "ttft_p999_ms",
    "ttfb_p50_ms", "ttfb_p99_ms", "ttfb_p999_ms",
    "engine_ttft_p50_ms", "engine_ttft_p99_ms",
    "queue_wait_p50_ms", "prefill_exec_p50_ms",
    "prefill_p50_ms", "decode_fetch_p50_ms",
    "mfu", "model", "quant", "quant_group_size", "prefill_act_quant",
    "kv_quant", "flash_decode", "flash_sgrid", "fused_decode_layer",
    # ISSUE 15 add-only extension: the ragged grouped-prefill knob
    # (effective, engine-read) — its on/off sweep twins compare the
    # warmup_* cold-start fields and prefill_exec_p50_ms.
    "ragged_prefill",
    "decode_kernels_per_step", "prefix_cache", "spec_ngram",
    # ISSUE 17 add-only extension: the fused spec-verify burst width and
    # the measured acceptance rate (accepted/proposed over the window).
    "spec_k", "spec_accept_rate",
    "mux", "mux_budget_tokens", "mux_prefill_chunk",
    "shared_prefix_tokens", "prefix_hit_tokens", "prefix_dedup_hits",
    # ISSUE 14 add-only extension: block-paged pool occupancy + the
    # conversation-cache hit rate (fraction of admissions matching
    # finished-stream pages).
    "pages_used", "pages_free", "conversation_hit_rate",
    # ISSUE 16 add-only extension: host-RAM spill-tier residency, page-in
    # success rate (rest fell back to tail re-prefill), splice latency.
    "spill_pages", "spill_tier_hit_rate", "spill_pagein_p50_ms",
    # ISSUE 20 add-only extension: the disaggregated prefill/decode A/B
    # — the topology knob, the KV-page wire-motion counters, and the
    # transfer leg (kv_export_p50_ms) of the TTFT split.
    "disagg", "pages_shipped", "pages_spliced", "page_xfer_bytes",
    "disagg_handoffs", "disagg_fallbacks", "affinity_hits",
    "kv_export_p50_ms",
    # ISSUE 12 add-only extension: the cold-start compile breakdown
    # (warmup total / program count / slowest single program).
    "warmup_compile_s", "warmup_programs", "warmup_compile_max_s",
    "clients", "engine_tok_s", "engine_tokens", "visible_tokens",
    "wall_s",
)


def _bench_module():
    spec = importlib.util.spec_from_file_location(
        "bench_schema_under_test", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _builder_dict_keys() -> list:
    """The literal keys of the `row = {...}` dict inside _run_attempt,
    extracted statically — the builder cannot drift from the pinned list
    without this test noticing, and nothing heavy ever runs."""
    tree = ast.parse(open(os.path.join(REPO, "bench.py")).read())
    for node in ast.walk(tree):
        if (isinstance(node, ast.AsyncFunctionDef)
                and node.name == "_run_attempt"):
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1
                        and isinstance(sub.targets[0], ast.Name)
                        and sub.targets[0].id == "row"
                        and isinstance(sub.value, ast.Dict)):
                    return [
                        k.value for k in sub.value.keys
                        if isinstance(k, ast.Constant)
                    ]
    raise AssertionError("bench._run_attempt row dict not found")


def test_result_row_keys_pinned():
    bench = _bench_module()
    assert tuple(bench.RESULT_ROW_KEYS) == PINNED_ROW_KEYS


def test_row_builder_matches_declared_schema():
    keys = _builder_dict_keys()
    assert len(keys) == len(set(keys)), "duplicate keys in the row builder"
    assert tuple(keys) == PINNED_ROW_KEYS


def test_finalize_cpu_row_is_never_a_chip_datapoint():
    """_finalize may ADD the no_tpu key but must never rename or drop a
    row key.  A CPU row — an explicit JAX_PLATFORMS=cpu rehearsal — keeps
    the full schema with the chip-only fields nulled (vs_baseline, mfu)
    and carries nothing from an earlier chip run."""
    bench = _bench_module()
    row = {k: 0 for k in PINNED_ROW_KEYS}
    row["platform"] = "cpu"
    out = bench._finalize(dict(row))
    assert set(out) == set(PINNED_ROW_KEYS) | {"no_tpu"}
    assert out["no_tpu"] is True
    assert out["vs_baseline"] is None and out["mfu"] is None
    assert json.dumps(out)  # the row stays a single serializable JSON line


def test_finalize_leaves_a_chip_row_alone():
    bench = _bench_module()
    row = {k: 0 for k in PINNED_ROW_KEYS}
    row.update(platform="tpu", vs_baseline=0.5, mfu=0.1)
    assert bench._finalize(dict(row)) == row


def test_mfu_peak_comes_from_the_device_table():
    """The bf16 peak is keyed by device_kind with its source; the CPU has
    no MFU and an unknown TPU kind is an error, not a default."""
    import pytest

    bench = _bench_module()
    assert bench._peak_bf16_flops("tpu", "TPU v5 lite") == 197e12
    assert bench._peak_bf16_flops("cpu", "cpu") is None
    with pytest.raises(RuntimeError, match="TPU v9"):
        bench._peak_bf16_flops("tpu", "TPU v9")
