"""``tiny-mla-moe`` through the engine (the programs alone are
tests/test_mla_moe.py): the routed layers' counts against a host-side
recount, the counters, the ledger and the records, the refusals, and
/healthz.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import init_kv_cache
from tests import mla_moe_plain as plain
from tests.mla_moe_tiny import (
    MAX_SEQ,
    ROWS,
    _chunk_prefill,
    _decode_step,
    _prefill_into_cache,
    _prompt,
    _whole,
    model,
)
from tests.moe_records import dispatches_closed


# ---- the engine -----------------------------------------------------------------

def _engine(model_name="tiny-mla-moe-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def _routing(cfg, params, seq):
    """For each expert layer, the experts [T, k] the plain reference's own
    forward over ``seq`` routes each position to."""
    lo, _ = cfg.experts_held
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(seq)]
        for group in ("dense_blocks", "blocks"):
            for i in range(f32[group]["wq"].shape[0]):
                w = jax.tree.map(lambda a: a[i], f32[group])
                x = plain.attention(cfg, w, x)
                h = plain._norm(x, w["mlp_norm"], cfg.norm_eps)
                if group == "dense_blocks":
                    x = x + plain._swiglu(h, w["w_gate"], w["w_up"],
                                          w["w_down"])
                    continue
                chosen.append(np.asarray(moe.route(cfg, w, h)[0]))
                x = x + plain.routed_layer(cfg, w, h, first_held=lo)
    return chosen


def _recount(cfg, chosen, positions):
    """What the routed layers count of ``positions`` in one call."""
    lo, n = cfg.experts_held
    made = held = fullest = touched = 0
    for top_i in chosen:
        here = top_i[positions]
        local = here[(here >= lo) & (here < lo + n)] - lo
        per = np.bincount(local, minlength=n)
        made, held = made + here.size, held + local.size
        fullest, touched = fullest + per.max(), touched + int((per > 0).sum())
    return [made, held, fullest, touched]


def test_each_program_counts_what_a_host_side_recount_does(model):
    """Padding rows and parked rows count for nothing."""
    cfg, params = model
    prompt = _prompt(9, 27)
    chosen = _routing(cfg, params, prompt)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    tok = jnp.zeros((2, 32), jnp.int32).at[0, :27].set(jnp.array(prompt))
    park = ROWS - 1
    *_, stats = _prefill_into_cache(
        cfg, params, tok, jnp.array([27, 1]), cache, jnp.array([1, park]),
        stat_rows=jnp.array([True, False]))
    assert list(stats) == _recount(cfg, chosen, np.arange(27))
    _, cache = _whole(cfg, params, cache, prompt[:16], 1)
    tail = jnp.zeros((2, 16), jnp.int32).at[0, :10].set(
        jnp.array(prompt[16:26]))
    _, cache, stats = _chunk_prefill(
        cfg, params, tail, jnp.array([10, 1]), jnp.array([16, 0]), cache,
        jnp.array([1, park]), kv_view=32, stat_rows=jnp.array([True, False]))
    assert list(stats) == _recount(cfg, chosen, np.arange(16, 26))
    tokens = jnp.zeros((ROWS,), jnp.int32).at[1].set(prompt[26])
    positions = jnp.full((ROWS,), MAX_SEQ).at[1].set(26)
    _, _, stats = _decode_step(cfg, params, cache, tokens, positions,
                              kv_view=32, with_stats=True)
    assert list(stats) == _recount(cfg, chosen, np.array([26]))


def test_the_counters_and_the_ledger_carry_the_counts():
    """One request through the engine (chunked prefill, then decode bursts):
    the counters grow by what the dispatch records carry, the prefill
    record by a host-side recount, the decode records by their live rows
    and steps."""
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
    from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

    names = ("engine_moe_assignments_total",
             "engine_moe_assignments_held_total",
             "engine_moe_expert_tokens_max_total",
             "engine_moe_experts_touched_total")
    keys = ("moe_assignments", "moe_held", "moe_expert_tokens_max",
            "moe_experts_touched")
    assert all(n in METRICS_CATALOG for n in names)
    prompt = _prompt(9, 37)

    async def main():
        eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16)
        assert eng._moe_counts
        await eng.start()
        try:
            before = [global_metrics.counter(n) for n in names]
            toks = [ev.token_id async for ev in eng.generate(
                prompt, max_new_tokens=5, stop_ids=())]
            await dispatches_closed(eng)
            grew = [global_metrics.counter(n) - b
                    for n, b in zip(names, before)]
        finally:
            await eng.stop()
        return eng, toks, grew

    global_tracer.clear()
    global_tracer.configure(enabled=True, sample=1.0, capacity=65536)
    try:
        eng, toks, grew = asyncio.run(asyncio.wait_for(main(), 300))
        records = global_tracer.records()
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()
    cfg = eng.mcfg
    assert len(toks) == 5
    segs = [r for r in records if r.name == "engine.prefill_segment"]
    bursts = [r for r in records if r.name == "engine.decode_burst"]
    assert len(segs) == 1 and bursts
    chosen = _routing(cfg, eng.params, prompt)
    assert [segs[0].attrs[k] for k in keys] == _recount(
        cfg, chosen, np.arange(len(prompt)))
    per_position = cfg.n_experts_per_tok * len(chosen)
    for r in bursts:
        a = r.attrs
        assert a["moe_assignments"] == a["live_rows"] * a["steps"] * per_position
        assert 0 < a["moe_expert_tokens_max"] <= a["moe_held"] <= \
            a["moe_assignments"]
    assert [sum(r.attrs[k] for r in segs + bursts) for k in keys] == grew
    assert 0 < grew[1] < grew[0]  # a share holds some of them, not all


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged-dot", "kernel"])
def test_the_records_moe_and_the_kernel_counter_are_held_to_each_other(
        kernel):
    """(ISSUE 39) Every decode and prefill record of a share says which
    grouped product its program ran; the counter grows by the records that
    say the kernel; the kernel (interpreted here) emits ``ragged_dot``'s
    tokens."""
    from tests import moe_records

    def run(interpret):
        eng = _engine(
            model_cfg=get_config("tiny-mla-moe-ep2s", flash_interpret=interpret,
                                 vocab_size=259),
            mux=True, prefix_cache=True, prefix_pool_blocks=16)
        return (eng,) + moe_records.run_traced(eng, _prompt(9, 37), 5)

    eng, toks, grew, records = run(kernel)
    moe_records.check(eng, grew, records, kernel)
    if kernel:
        assert toks == run(False)[1]


REFUSED = {
    "quant-int8": dict(quant="int8"),
    "quant-int4": dict(quant="int4"),
    "quant-w8a8": dict(quant="w8a8"),
    "kv-int4": dict(kv_quant="int4"),
    "tp": dict(tp=2), "sp": dict(sp=2), "ep": dict(ep=2),
    "ragged-prefill": dict(ragged_prefill=True),
    "spec-ngram": dict(spec_ngram=2),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_family_lacks_is_refused_at_start_up(case):
    with pytest.raises(ValueError, match="cannot be served with --"):
        _engine("tiny-mla-moe", **REFUSED[case])


def test_healthz_names_the_cache_form_and_the_share():
    eng = _engine(prefix_cache=True, prefix_pool_blocks=8)
    section = eng._model_section()
    cfg = eng.mcfg
    assert section["cache"] == {
        "form": "latent", "values_per_token_layer": 40,
        "bytes_per_token": cfg.n_layers * 40 * 4}
    assert section["layers"] == {"held": 4, "of": 4}
    assert section["experts"] == {"held": 4, "first": 0, "of": 8}
    # (a CPU backend: the grouped products are ragged_dot's)
    assert set(section["expert_products"]) == {"decode", "chunk_prefill"}
    assert section["expert_products"]["decode"] == moe.RAGGED
    assert section["vocab_rows"] == {"held": cfg.vocab_size,
                                     "of": 2 * cfg.vocab_size}
    assert eng._prefix_block_bytes == 16 * cfg.n_layers * 40 * 4
    assert eng._prefix_snapshot_meta()["page"] == [
        ["c", [32], "float32"], ["kr", [16], "float32"]]
    dense = _engine("tiny")._model_section()
    assert dense["cache"]["form"] == "kv_heads"
    assert dense["experts"] == {"held": 0, "first": 0, "of": 0}
