"""``tiny-delta-mlp`` through the engine (the programs alone are
tests/test_olmo_hybrid.py): a prefix hit that restores a snapshot, held to
the benchmark's plain reference, the dispatch records' state rows, and the
refusals.
"""

from __future__ import annotations

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.ops.pallas_delta_step import DELTA_STEP_KERNEL
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE
from tests.olmo_hybrid_tiny import ATOL, UPDATES, _decoding, _prompt, _want


# ---- through the engine ------------------------------------------------------------

def _engine(model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model="tiny-delta-mlp", num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def _generate(eng, prompts, new=8):
    async def main():
        await eng.start()
        try:
            out = []
            for prompt in prompts:
                events = [ev async for ev in eng.generate(
                    prompt, max_new_tokens=new, logprobs=1, stop_ids=())]
                out.append(([ev.token_id for ev in events],
                            [ev.logprob for ev in events]))
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


def test_a_prefix_hit_restores_a_snapshot_and_decodes_as_the_unshared_run():
    """Two prompts that share their first 48 tokens, one after the other
    (chunk prefill in segments of 16, the pool, decode bursts): the second
    restores the snapshot of state at 48 and its generated tokens and their
    log-probabilities are those of an engine with no pool, and the
    reference's; the state's counters count for this state as for
    Mamba-2's."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    base = _prompt(9, 60)
    prompts = [base, base[:48] + _prompt(10, 11)]
    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                  prefill_chunk=16)
    assert not eng.config_fences
    hit = global_metrics.counter("engine_prefix_hit_tokens_total")
    restores = global_metrics.counter("engine_state_restores_total")
    saves = global_metrics.counter("engine_state_snapshots_total")
    moved = global_metrics.counter("engine_state_bytes_total")
    shared = _generate(eng, prompts)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit == 48
    assert global_metrics.counter("engine_state_restores_total") - restores \
        == 1
    assert global_metrics.counter("engine_state_snapshots_total") > saves
    assert global_metrics.counter("engine_state_bytes_total") > moved
    alone = _generate(_engine(mux=True, prefix_cache=False,
                              prefill_chunk=16), prompts[1:])
    assert shared[1][0] == alone[0][0]
    np.testing.assert_allclose(shared[1][1], alone[0][1], atol=ATOL)
    tokens, values = shared[1]
    want = _want(eng.params, prompts[1] + tokens)
    n = len(prompts[1])
    np.testing.assert_allclose(
        values, [want[n - 1 + j, t] for j, t in enumerate(tokens)], atol=ATOL)
    # /healthz names the layer, the head, the delta state a slot (heads, key
    # and value widths, type, bytes) and the snapshots' room in bytes
    said = eng._model_section()
    assert said["layer"] == {"mixers": {"L": 6, "*": 2}, "mlp_width": 96}
    assert said["head"] == "its own"
    state = said["cache"]["kinds"]["state"]
    per_slot = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    assert per_slot == 6 * (3 * 16 * 24 * 4 + 3 * 168 * 4)
    assert {k: state[k] for k in (
        "rule", "layers", "heads", "key_width", "value_width", "type",
        "held_as", "bytes_per_slot", "update")} == {
        "rule": "gated delta", "layers": 6, "heads": 3, "key_width": 16,
        "value_width": 24, "type": "float32", "held_as": [1, 384],
        "bytes_per_slot": per_slot, "update": "elementwise"}
    assert state["snapshots"] == {
        "room": 16, "held": len(eng._snapshots), "bytes_each": per_slot,
        "bytes": 16 * per_slot}
    assert said["cache"]["kinds"]["attention"]["kv_heads"] == 3
    assert eng._snap_pool["delta"].shape[:2] == (6, 17)


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_the_dispatch_records_carry_state_rows(update):
    """``engine.decode_burst`` and ``engine.prefill_segment`` records name
    the rows whose state the dispatch read and wrote and their bytes, as a
    Mamba-2 model's do; a burst's ``state_update`` names the branch's answer
    (as /healthz does) and ``engine_decode_state_kernel_steps_total`` counts
    the steps that took the kernel."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics
    from tests.moe_records import tracing

    name = "engine_decode_state_kernel_steps_total"
    with tracing() as tracer:
        eng = _engine(_decoding(get_config("tiny-delta-mlp"), update),
                      mux=True, prefill_chunk=16)
        before = global_metrics.counter(name)
        steps = global_metrics.counter("engine_decode_steps_total")
        _generate(eng, [_prompt(12, 20)], new=4)
        grew = global_metrics.counter(name) - before
        steps = global_metrics.counter("engine_decode_steps_total") - steps
        records = tracer.records()
    row = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    segs = [r for r in records if r.name == "engine.prefill_segment"]
    bursts = [r for r in records if r.name == "engine.decode_burst"]
    assert [r.attrs["tokens"] for r in segs] == [16, 4] and bursts
    for r in segs:
        assert (r.attrs["state_rows"], r.attrs["state_bytes"]) == (1, 2 * row)
    for r in bursts:
        a = r.attrs
        assert a["state_rows"] == a["live_rows"] * a["steps"]
        assert a["state_bytes"] == 2 * row * a["state_rows"]
    want = DELTA_STEP_KERNEL if update == "kernel" else ELEMENTWISE
    assert {r.attrs["state_update"] for r in bursts} == {want}
    assert eng._model_section()["cache"]["kinds"]["state"]["update"] == want
    assert steps > 0 and grew == (steps if update == "kernel" else 0)


@pytest.mark.parametrize("case", [
    dict(quant="int8"), dict(kv_quant="int4"), dict(spec_ngram=2),
    dict(ragged_prefill=True), dict(tp=2)], ids=lambda c: next(iter(c)))
def test_what_the_family_lacks_is_refused_for_this_model_too(case):
    with pytest.raises(ValueError, match="a dense MLP a layer"):
        _engine(**case)
