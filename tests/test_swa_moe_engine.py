"""``tiny-swa-moe`` through the engine (the programs alone are
tests/test_swa_moe.py): prefix hits against the plain reference,
tests/swa_moe_plain.py, what is saved while the rings hold it, the counts of
cache rows by layer kind in records and counters, the refusals, the branch
decode takes, and /healthz.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_step,
    init_kv_cache,
    init_params,
)
from tests import swa_moe_plain as plain
from tests.swa_moe_tiny import ATOL, RING, WINDOW, _generate, _prompt


# ---- the engine -----------------------------------------------------------------

def _engine(model_name="tiny-swa-moe-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def test_a_prefix_hit_through_the_engine_reads_like_the_reference():
    """Prompts that share their first blocks, one after another through the
    engine (chunk prefill in segments of 16, the pool, decode bursts): the
    later ones hit the pool, are restored into rings, and every generated
    token's log-probability is the plain reference's."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=16)
    assert eng._ring == RING and not eng.config_fences
    base = _prompt(9, 70)
    prompts = [base, base[:55], base[:64] + _prompt(10, 13)]
    hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
    outs = _generate(eng, prompts)
    # 48 of 55 tokens (whole blocks short of the prompt), then 64 of 77
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit0 \
        == 48 + 64
    for prompt, (tokens, values) in zip(prompts, outs):
        want = np.asarray(plain.forward_logprobs(
            eng.mcfg, eng.params, prompt + tokens))
        n = len(prompt)
        np.testing.assert_allclose(
            values, [want[n - 1 + j, t] for j, t in enumerate(tokens)],
            atol=ATOL)


def test_a_prompt_longer_than_the_ring_saves_no_block_with_a_hole():
    """A whole-prompt prefill (the echo path) of 70 tokens leaves the rings
    holding the last 16: its early blocks cannot be saved whole, a chain
    with a hole matches nothing, so nothing is saved."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=16)

    async def main():
        await eng.start()
        try:
            saved = global_metrics.counter("engine_prefix_saved_blocks_total")
            events = [ev async for ev in eng.generate(
                _prompt(12, 70), max_new_tokens=2, logprobs=1,
                echo_logprobs=True, stop_ids=())]
            await asyncio.sleep(0.2)
            return events, global_metrics.counter(
                "engine_prefix_saved_blocks_total") - saved
        finally:
            await eng.stop()

    events, saved = asyncio.run(asyncio.wait_for(main(), 300))
    assert len(events) == 2 and saved == 0
    want = np.asarray(plain.forward_logprobs(eng.mcfg, eng.params,
                                             _prompt(12, 70)))
    plps = events[0].prompt_logprobs
    np.testing.assert_allclose(
        plps[1:70], [want[t - 1, tok] for t, tok in
                     enumerate(_prompt(12, 70))][1:], atol=ATOL)


@pytest.mark.parametrize("ring,hit", [(48, 48), (16, 32)])
def test_a_finished_stream_is_saved_while_the_rings_hold_it(ring, hit):
    """The conversation cache: a stream of 40 + 20 tokens ends with its
    prompt's two whole blocks saved; its third block (positions 32-47, the
    prompt's end and generated tokens) is saved from the rings where they
    still hold it (48 positions) and not where they have moved on (16), so
    the next turn restores 48 tokens or 32, and reads like the reference
    either way."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = InferenceEngine(
        engine_cfg=EngineConfig(
            model="tiny-swa-moe-ep2s", num_slots=2, max_seq=128,
            dtype="float32", decode_steps=2, mux=True, prefix_cache=True,
            conv_cache=True, prefix_pool_blocks=32, prefill_chunk=16),
        model_cfg=get_config("tiny-swa-moe-ep2s", vocab_size=259,
                             ring_positions=ring))
    assert eng._ring == ring and not eng.config_fences
    first = _prompt(13, 40)
    names = ("engine_conv_saved_pages_total", "engine_prefix_hit_tokens_total")

    async def main():
        await eng.start()
        try:
            before = [global_metrics.counter(n) for n in names]
            said = [ev.token_id async for ev in eng.generate(
                first, max_new_tokens=20, stop_ids=())]
            await asyncio.sleep(0.3)  # the end-of-iteration drain
            turn = first + said + _prompt(14, 7)
            events = [ev async for ev in eng.generate(
                turn, max_new_tokens=6, logprobs=1, stop_ids=())]
            return turn, events, [global_metrics.counter(n) - b
                                  for n, b in zip(names, before)]
        finally:
            await eng.stop()

    turn, events, (saved, restored) = asyncio.run(
        asyncio.wait_for(main(), 300))
    assert (saved, restored) == ((hit - 32) // 16, hit)
    tokens = [ev.token_id for ev in events]
    want = np.asarray(plain.forward_logprobs(eng.mcfg, eng.params,
                                             turn + tokens))
    np.testing.assert_allclose(
        [ev.logprob for ev in events],
        [want[len(turn) - 1 + j, t] for j, t in enumerate(tokens)], atol=ATOL)


def test_the_records_and_the_counters_carry_the_rows_read_by_kind():
    """One request through the engine: every prefill and decode record says
    what its attention had to read of the cache, by layer kind, from the
    rows' positions; the counters grow by exactly the records' sums, and a
    host-side recount gives the prefill records' numbers."""
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
    from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

    names = ("engine_kv_rows_full_total", "engine_kv_rows_window_total",
             "engine_kv_rows_window_read_total")
    assert all(n in METRICS_CATALOG for n in names)
    prompt = _prompt(9, 37)
    global_tracer.clear()
    global_tracer.configure(enabled=True, sample=1.0, capacity=65536)
    try:
        eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                      prefill_chunk=16)
        before = [global_metrics.counter(n) for n in names]
        (tokens, _), = _generate(eng, [prompt], new=5)
        grew = [global_metrics.counter(n) - b for n, b in zip(names, before)]
        records = global_tracer.records()
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()
    assert len(tokens) == 5
    segs = [r for r in records if r.name == "engine.prefill_segment"]
    bursts = [r for r in records if r.name == "engine.decode_burst"]
    assert [r.attrs["tokens"] for r in segs] == [16, 16, 5] and bursts
    starts = [0, 16, 32]
    for r, start in zip(segs, starts):
        seen = range(start, start + r.attrs["tokens"])
        assert r.attrs["kv_rows_full"] == 2 * sum(p + 1 for p in seen)
        assert r.attrs["kv_rows_window"] == 5 * sum(
            min(p + 1, WINDOW) for p in seen)
        # a prefill dispatch gathers its window by position: read = need
        assert r.attrs["kv_rows_window_read"] == r.attrs["kv_rows_window"]
    for r in bursts:
        a = r.attrs
        assert a["kv_rows_window"] == 5 * WINDOW * a["live_rows"] * a["steps"]
        assert a["kv_rows_full"] >= 2 * 37 * a["live_rows"] * a["steps"]
        # the einsum reads the whole ring a live row, step and layer
        assert a["kv_rows_window_read"] == \
            5 * RING * a["live_rows"] * a["steps"]
    assert [sum(r.attrs[k] for r in segs + bursts)
            for k in ("kv_rows_full", "kv_rows_window",
                      "kv_rows_window_read")] == grew
    # a model with one kind of layer counts it all as that kind
    dense = _engine("tiny")
    assert dense._attn_kinds == (False, False) and dense._ring == 0


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
            model_cfg=get_config("tiny-swa-moe-ep2s", flash_interpret=interpret,
                                 vocab_size=259),
            mux=True, prefix_cache=True, prefix_pool_blocks=16,
            prefill_chunk=16)
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
    "ckpt": dict(ckpt_path="/nowhere"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_family_lacks_is_refused_at_start_up(case):
    with pytest.raises(ValueError, match=r"window rings beside full planes"
                                         r".* cannot be served with --"):
        _engine("tiny-swa-moe", **REFUSED[case])


def _two_chips(cpu_devices):
    from jax.sharding import Mesh

    return Mesh(np.asarray(cpu_devices[:2]).reshape(1, 2), ("dp", "tp"))


#: (what the code can observe) -> the full layers' decode read (ISSUE 36).
#: model, config fields, KV quant, a tp mesh?, the answer.
BRANCHES = {
    "bf16-planes-interpreting":
        ("tiny-swa-moe", dict(flash_interpret=True), None, False,
         "pallas-rows"),
    "the-cells-planes-on-a-tpu-backend":  # rows of 768 and 512: whole tiles
        ("mimo-v2-flash-ep16s", dict(flash_force=True), None, False,
         "pallas-rows"),
    "int8-planes":
        ("tiny-swa-moe", dict(flash_interpret=True), "int8", False, "einsum"),
    "a-cpu-backend":
        ("mimo-v2-flash-ep16s", {}, None, False, "einsum"),
    "a-tp-mesh":
        ("tiny-swa-moe", dict(flash_interpret=True), None, True, "einsum"),
    "a-key-row-that-is-no-whole-lane-tile":  # 1 x 192; the values' 128 is
        ("mimo-v2-flash-ep16s", dict(flash_force=True, n_kv_heads=1), None,
         False, "einsum"),
    "a-value-row-that-is-no-whole-lane-tile":  # 4 x 192 = 768, 4 x 80 = 320
        ("mimo-v2-flash-ep16s", dict(flash_force=True, v_head_dim=80), None,
         False, "einsum"),
    "the-tiny-presets-rows-on-a-tpu-backend":  # 24 and 16 wide
        ("tiny-swa-moe", dict(flash_force=True), None, False, "einsum"),
    "the-reference":
        ("tiny-swa-moe", dict(flash_interpret=True, flash=False), None,
         False, "einsum"),
}


@pytest.mark.parametrize("case", sorted(BRANCHES))
def test_the_branch_is_decided_by_what_the_code_observes(case, cpu_devices):
    """No flag and no model name: the backend, the mesh, the planes' type
    and a row's width decide whether a full layer's decode read is the rows
    kernel; the plan follows (one decode entry a step count at ``max_seq``,
    or the view ladder), and so does what ``decode_step`` traces.  Whole
    prompts and chunks keep the einsum either way."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.models.transformer import (
        decode_attention_branch,
        decode_branch_coverage,
        prefill_attention_branch,
    )
    from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import ROWS_KERNEL

    name, fields, kv, tp, want = BRANCHES[case]
    cfg = get_config(name, **fields)
    mesh = _two_chips(cpu_devices) if tp else None
    seq = 8192 if name.startswith("mimo") else 512
    assert decode_attention_branch(cfg, mesh, 128, kv, seq) == want
    assert prefill_attention_branch(cfg, None, 512) == "einsum"
    ring = cfg.ring_default(seq, 512 if name.startswith("mimo") else 0)
    covers = decode_branch_coverage(cfg, want, ring)
    assert covers.startswith(want) and ("window layers" in covers) == (
        want != "einsum")
    # the rings follow the full layers where they tile (ISSUE 56): mimo's
    # 640 slots of 1,536 and 1,024 values do, the tiny preset's 16 do not
    assert ring == (640 if name.startswith("mimo") else RING)
    assert ("rows of the ring" in covers) == (
        want != "einsum" and name.startswith("mimo"))
    assert ("einsum over the ring" in covers) == (
        want != "einsum" and not name.startswith("mimo"))
    if name.startswith("mimo"):
        return  # the share at its size is tests/test_tpu_compile.py's
    # what decode_step traces
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = init_kv_cache(cfg, 2, seq, jnp.float32, quant=kv)
    row = jnp.zeros((2,), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, c: decode_step(
        cfg, p, c, row, row, kv_view=128, mesh=mesh))(params, cache))
    assert (ROWS_KERNEL in text) == (want == "pallas-rows")
    if tp:
        return  # the engine refuses --tp for this family at start-up
    # the plan
    eng = InferenceEngine(model_cfg=cfg, engine_cfg=EngineConfig(
        model=name, num_slots=2, max_seq=seq, dtype="float32", decode_steps=4,
        decode_steps_eager=2, kv_quant=kv or "none"))
    entries = [shape for kind, shape in eng.warmup_plan() if kind == "decode"]
    views = [seq] if want == "pallas-rows" else [128, 256, 512]
    assert eng._decode_reads_rows() == (want == "pallas-rows")
    assert entries == [(v, k) for v in views for k in (2, 4)]
    assert eng._attention_branch("decode", (128, 2)) == want


def test_healthz_names_both_kinds_of_plane_and_a_slots_bytes():
    eng = _engine(prefix_cache=True, prefix_pool_blocks=8, mux=True,
                  prefill_chunk=16)
    section = eng._model_section()
    cache = section["cache"]
    assert cache["form"] == "window_rings+full"
    assert cache["ring_positions"] == RING and cache["window"] == WINDOW
    assert cache["kinds"]["full"] == {
        "layers": 2, "kv_heads": 1, "key_width": 24, "value_width": 16,
        "positions_per_slot": 128, "bytes_per_token_layer": 40 * 4}
    assert cache["kinds"]["window"] == {
        "layers": 5, "kv_heads": 2, "key_width": 24, "value_width": 16,
        "positions_per_slot": RING, "bytes_per_token_layer": 80 * 4}
    # two statements: what the pool holds for a token, what a slot holds
    assert cache["bytes_per_token"] == (2 * 40 + 5 * 80) * 4
    assert cache["bytes_per_slot"] == (2 * 40 * 128 + 5 * 80 * RING) * 4
    rows = eng.ecfg.num_slots + 1
    assert sum(int(a.size) * a.dtype.itemsize
               for a in eng.kv_cache.values()) == rows * cache["bytes_per_slot"]
    assert eng._prefix_block_bytes == 16 * cache["bytes_per_token"]
    assert section["layers"] == {"held": 7, "of": 7}
    assert section["experts"] == {"held": 4, "first": 0, "of": 8}
    # (a CPU backend: the grouped products are ragged_dot's)
    assert set(section["expert_products"]) == {"decode", "chunk_prefill"}
    assert section["expert_products"]["decode"] == moe.RAGGED
    assert section["vocab_rows"] == {"held": eng.mcfg.vocab_size,
                                     "of": 2 * eng.mcfg.vocab_size}
    assert eng._prefix_snapshot_meta()["page"] == [
        ["k", [24], "float32"], ["v", [16], "float32"],
        ["wk", [48], "float32"], ["wv", [32], "float32"]]
