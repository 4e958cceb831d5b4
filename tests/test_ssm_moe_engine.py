"""``tiny-ssm-moe`` through the engine (the programs alone are
tests/test_ssm_moe.py): snapshots of state beside the prefix pool's pages
against the plain reference, tests/ssm_moe_plain.py, the records and counters
of state traffic, what /healthz says, and what is refused.
"""

from __future__ import annotations

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE, SSM_STEP_KERNEL
from tests import ssm_moe_plain as plain
from tests.moe_records import dispatches_closed
from tests.ssm_moe_tiny import ATOL, UPDATES, _prompt


# ---- the engine -------------------------------------------------------------------

def _engine(model_name="tiny-ssm-moe-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def _generate(eng, prompts, new=10, between=None):
    async def main():
        await eng.start()
        try:
            out = []
            for prompt in prompts:
                events = [ev async for ev in eng.generate(
                    prompt, max_new_tokens=new, logprobs=1, stop_ids=())]
                out.append(([ev.token_id for ev in events],
                            [ev.logprob for ev in events]))
                if between is not None:
                    between()
            await dispatches_closed(eng)
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


def _held_to_the_reference(eng, prompts, outs):
    for prompt, (tokens, values) in zip(prompts, outs):
        want = np.asarray(plain.forward_logprobs(
            eng.mcfg, eng.params, prompt + tokens))
        n = len(prompt)
        np.testing.assert_allclose(
            values, [want[n - 1 + j, t] for j, t in enumerate(tokens)],
            atol=ATOL)


def test_a_prefix_hit_restores_the_snapshot_at_or_before_it(chunk=32):
    """Prompts that share their first blocks, one after another through the
    engine (chunk prefill in segments of 32, the pool, decode bursts).  A
    later one hits the longest pooled boundary that has a snapshot of the
    state, one every 32 tokens here: the prompt of 77 whose rows reach 64
    hits 64; the one of 55 whose rows reach 48 falls back to 32 (with
    ``--prefill-chunk 16`` it hits 48: the tiny cell below).
    Every generated token's log-probability is the plain reference's: a
    restored state reads as a cold prefill does.  Slots are reused
    throughout: a finished request's state carries nothing over."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=chunk)
    assert not eng.config_fences
    assert set(eng._pool) == {"k", "v"}  # pages hold rows, never state
    assert {k: v.shape[:2] for k, v in eng._snap_pool.items()} == {
        "ssm": (3, 17), "conv": (3, 17)}  # scratch + 32 x 16 tokens / 32
    base = _prompt(9, 70)
    prompts = [base, base[:55], base[:64] + _prompt(10, 13),
               base[:40] + _prompt(11, 9)]
    seen = [global_metrics.counter("engine_prefix_hit_tokens_total")]
    restores = global_metrics.counter("engine_state_restores_total")
    outs = _generate(eng, prompts, between=lambda: seen.append(
        global_metrics.counter("engine_prefix_hit_tokens_total")))
    assert [b - a for a, b in zip(seen, seen[1:])] == [0, 32, 64, 32]
    assert global_metrics.counter("engine_state_restores_total") - restores \
        == 3
    _held_to_the_reference(eng, prompts, outs)
    # the pool's bytes are the rows'; the snapshots are counted apart
    assert eng._prefix_block_bytes == 16 * 2 * 2 * 32 * 4
    assert global_metrics.gauge("engine_state_snapshots") == len(
        eng._snapshots) > 0
    assert global_metrics.gauge("engine_state_snapshot_bytes") == len(
        eng._snapshots) * ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)


def test_the_snapshots_are_evicted_least_recently_used_first():
    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        PrefixIndex,
        StateSnapshots,
    )

    snaps = StateSnapshots(3)  # scratch + two
    a, b, c = snaps.allocate(b"a"), snaps.allocate(b"b"), None
    assert {a, b} == {1, 2} and snaps.allocate(b"a") == a
    c = snaps.allocate(b"c")  # b was the least recently used
    assert c == b and b"b" not in snaps and b"a" in snaps and len(snaps) == 2
    assert snaps.lookup(b"b") is None and snaps.evictions == 1
    # a match ends at the longest pooled boundary that has a snapshot
    index = PrefixIndex(4, 16)
    index.snapshots = StateSnapshots(4)
    ids = list(range(1, 18))
    keys = index.block_keys(ids)
    index.allocate(keys[:3])
    assert index.match(ids) == (0, [])
    index.snapshots.allocate(keys[1])
    hist, pool_ids = index.match(ids)
    assert hist == 8 and len(pool_ids) == 2
    index.snapshots.allocate(keys[3])  # past the pooled rows: no use yet
    assert index.match(ids)[0] == 8


def test_an_echoed_prompt_and_a_whole_prompt_prefill_leave_a_snapshot():
    """The whole-prompt path (an echo request) of 64 tokens ends on a block
    boundary: its state is snapshotted, and the next request hits all 64."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=16)
    prompt = _prompt(12, 64)

    async def main():
        await eng.start()
        try:
            events = [ev async for ev in eng.generate(
                prompt, max_new_tokens=2, logprobs=1, echo_logprobs=True,
                stop_ids=())]
            hit = global_metrics.counter("engine_prefix_hit_tokens_total")
            later = prompt + _prompt(13, 5)
            more = [ev async for ev in eng.generate(
                later, max_new_tokens=4, logprobs=1, stop_ids=())]
            await asyncio.sleep(0.2)
            return events, more, global_metrics.counter(
                "engine_prefix_hit_tokens_total") - hit
        finally:
            await eng.stop()

    events, more, hit = asyncio.run(asyncio.wait_for(main(), 300))
    assert hit == 64
    want = np.asarray(plain.forward_logprobs(eng.mcfg, eng.params, prompt))
    np.testing.assert_allclose(
        events[0].prompt_logprobs[1:64],
        [want[t - 1, tok] for t, tok in enumerate(prompt)][1:], atol=ATOL)
    later = prompt + _prompt(13, 5)
    _held_to_the_reference(eng, [later], [([e.token_id for e in more],
                                           [e.logprob for e in more])])


def test_the_records_and_the_counters_carry_the_state_read_and_written():
    """One cold request and one that hits through the engine: every prefill
    and decode record says how many rows' state it read and wrote and the
    bytes, from the host's own counts; ``engine.state_snapshot`` and
    ``engine.state_restore`` say theirs; ``engine_state_bytes_total`` grows
    by exactly the records' sum, the other two counters by the events."""
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
    from tests.moe_records import tracing

    names = ("engine_state_bytes_total", "engine_state_snapshots_total",
             "engine_state_restores_total")
    assert all(n in METRICS_CATALOG for n in names)
    assert "engine_state_snapshots" in METRICS_CATALOG
    assert "engine_state_snapshot_bytes" in METRICS_CATALOG
    prompt = _prompt(9, 37)
    with tracing() as tracer:
        eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                      prefill_chunk=16)
        before = [global_metrics.counter(n) for n in names]
        _generate(eng, [prompt, prompt[:35]], new=5)
        grew = [global_metrics.counter(n) - b for n, b in zip(names, before)]
        records = tracer.records()
    row = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    assert row == eng._state_row_bytes
    segs = [r for r in records if r.name == "engine.prefill_segment"]
    bursts = [r for r in records if r.name == "engine.decode_burst"]
    saved = [r for r in records if r.name == "engine.state_snapshot"]
    restored = [r for r in records if r.name == "engine.state_restore"]
    # 16 + 16 + 5 cold, then 32 restored and a tail of 3
    assert [r.attrs["tokens"] for r in segs] == [16, 16, 5, 3] and bursts
    for r in segs:
        assert (r.attrs["state_rows"], r.attrs["state_bytes"]) == (1, 2 * row)
    for r in bursts:
        a = r.attrs
        assert a["state_rows"] == a["live_rows"] * a["steps"]
        assert a["state_bytes"] == 2 * row * a["state_rows"]
    assert [r.attrs["boundary"] for r in saved] == [16, 32]
    assert [r.attrs["tokens_skipped"] for r in restored] == [32]
    assert all(r.attrs["bytes"] == row for r in saved + restored)
    assert grew == [
        sum(r.attrs["state_bytes"] for r in segs + bursts)
        + row * len(saved + restored), len(saved), len(restored)]
    # a model without a state counts none
    dense = _engine("tiny")
    assert dense._state_row_bytes == 0 and dense._snapshots is None


def test_the_records_moe_and_the_kernel_counter_are_held_to_each_other(
        kernel=True):
    """Every decode and prefill record of a share says which grouped product
    its program ran (two an expert here); the counter grows by the records
    that say the kernel; the kernel (interpreted here, over the stack of all
    layers' experts) emits ``ragged_dot``'s tokens (over a layer's slice)."""
    from tests import moe_records

    def run(interpret):
        eng = _engine(
            model_cfg=get_config("tiny-ssm-moe-ep2s", flash_interpret=interpret,
                                 vocab_size=259),
            mux=True, prefix_cache=True, prefix_pool_blocks=16,
            prefill_chunk=16)
        return (eng,) + moe_records.run_traced(eng, _prompt(9, 37), 5)

    eng, toks, grew, records = run(kernel)
    moe_records.check(eng, grew, records, kernel)
    plain_eng, plain_toks, plain_grew, plain_records = run(False)
    moe_records.check(plain_eng, plain_grew, plain_records, False)
    assert toks == plain_toks


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_the_state_kernels_counter_the_records_and_healthz_name_one_branch(
        update):
    """``engine_decode_state_kernel_steps_total`` grows by the steps of the
    bursts whose program took the state kernel and by none under the
    elementwise branch; every burst's record and /healthz name that branch;
    a parked slot's state is the same to the bit after the bursts."""
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
    from tests.moe_records import tracing

    name = "engine_decode_state_kernel_steps_total"
    assert name in METRICS_CATALOG
    want = SSM_STEP_KERNEL if update == "kernel" else ELEMENTWISE
    with tracing() as tracer:
        eng = _engine(model_cfg=get_config(
            "tiny-ssm-moe-ep2s", vocab_size=259, **UPDATES[update]))
        # slot 1 is never admitted: what lies there is a parked row's
        eng.kv_cache["ssm"] = eng.kv_cache["ssm"].at[:, 1].set(0.25)
        before = global_metrics.counter(name)
        steps = global_metrics.counter("engine_decode_steps_total")
        _generate(eng, [_prompt(9, 21)], new=7)
        grew = global_metrics.counter(name) - before
        steps = global_metrics.counter("engine_decode_steps_total") - steps
        bursts = [r for r in tracer.records()
                  if r.name == "engine.decode_burst"]
    assert bursts and steps == sum(r.attrs["steps"] for r in bursts)
    assert {r.attrs["state_update"] for r in bursts} == {want}
    assert grew == (steps if update == "kernel" else 0)
    state = eng._model_section()["cache"]["kinds"]["state"]
    assert state["update"] == want == eng._state_update
    np.testing.assert_array_equal(np.asarray(eng.kv_cache["ssm"][:, 1]), 0.25)
    assert float(jnp.abs(eng.kv_cache["ssm"][:, 0]).max()) > 0
    # a model without such a state has no branch, no attr and no count
    dense = _engine("tiny")
    assert dense._state_update is None


REFUSED = {
    "quant-int8": dict(quant="int8"),
    "quant-int4": dict(quant="int4"),
    "quant-w8a8": dict(quant="w8a8"),
    "kv-int4": dict(kv_quant="int4"),
    "tp": dict(tp=2), "sp": dict(sp=2), "ep": dict(ep=2),
    "ragged-prefill": dict(ragged_prefill=True),
    "spec-ngram": dict(spec_ngram=2),
    "ckpt": dict(ckpt_path="/nowhere"),
    "spill-pages": dict(prefix_cache=True, spill_pages=4),
    "role": dict(prefix_cache=True, role="prefill"),
    "prefix-cache-dir": dict(prefix_cache=True, prefix_cache_dir="/nowhere"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_family_lacks_is_refused_at_start_up(case):
    with pytest.raises(ValueError, match=r"a recurrent state beside the KV "
                                         r"planes.* cannot be served with --"):
        _engine("tiny-ssm-moe", **REFUSED[case])


def test_healthz_names_the_planes_the_state_and_a_slots_bytes():
    eng = _engine(prefix_cache=True, prefix_pool_blocks=8, mux=True,
                  prefill_chunk=16)
    section = eng._model_section()
    cache = section["cache"]
    assert cache["form"] == "kv_heads+state"
    assert cache["kinds"]["attention"] == {
        "layers": 2, "kv_heads": 2, "key_width": 16, "value_width": 16,
        "positions_per_slot": 128, "bytes_per_token_layer": 64 * 4}
    per_slot = 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert cache["kinds"]["state"] == {
        "layers": 3, "heads": 4, "head_width": 8, "state_width": 16,
        "type": "float32", "conv_positions": 3, "conv_width": 96,
        "conv_type": "float32", "bytes_per_slot": per_slot,
        "update": ELEMENTWISE,
        # 8 blocks x 16 tokens / a chunk of 16 (ISSUE 46: said in bytes)
        "snapshots": {"room": 8, "held": 0, "bytes_each": per_slot,
                      "bytes": 8 * per_slot}}
    # one mixer a layer, no multiplier, a head of its own (ISSUE 46)
    assert section["layer"] == {"mixers": {"M": 3, "E": 2, "*": 2},
                                "mlp_width": 0}
    assert section["multipliers"] == {
        "embedding": 1.0, "residual": 1.0, "attention_scores": 0.25,
        "logits_divisor": 1.0}
    assert section["head"] == "its own"
    # two statements: what the pool holds for a token, what a slot holds
    assert cache["bytes_per_token"] == 2 * 64 * 4
    assert cache["bytes_per_slot"] == 2 * 64 * 4 * 128 + per_slot
    rows = eng.ecfg.num_slots + 1
    assert sum(int(a.size) * a.dtype.itemsize
               for a in eng.kv_cache.values()) == rows * cache["bytes_per_slot"]
    assert eng._prefix_block_bytes == 16 * cache["bytes_per_token"]
    assert section["layers"] == {"held": 7, "of": 7}
    assert section["experts"] == {"held": 4, "first": 0, "of": 8}
    assert section["expert_products"]["decode"] == moe.RAGGED
    assert section["vocab_rows"] == {"held": eng.mcfg.vocab_size,
                                     "of": 2 * eng.mcfg.vocab_size}
