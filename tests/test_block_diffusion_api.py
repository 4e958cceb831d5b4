"""``tiny-sdar-moe`` as the engine's users see it (the block carry itself:
tests/test_block_diffusion_engine.py): the API's stream in order and its
counts, an int8 cache served for the cache control, the counters against the
records, and the refusals.
"""

from __future__ import annotations

import asyncio
import contextlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import get_config
from tests import block_diffusion_plain as plain
from tests.block_diffusion_tiny import (
    ATOL,
    BLOCK,
    _check_against_reference,
    _engine,
    _generate,
    _prompt,
)


def test_the_api_streams_in_order_and_counts_what_was_asked():
    """(e) ``/v1/completions``, streamed and not: ``usage.completion_tokens``
    is ``max_tokens`` whether or not it ends a group, and the chat route
    answers."""
    from p2p_llm_tunnel_tpu.engine.api import EngineAPI
    from p2p_llm_tunnel_tpu.protocol.frames import RequestHeaders

    eng = _engine()
    api = EngineAPI(eng, "tiny-sdar-moe")

    async def post(path, body):
        req = RequestHeaders(1, "POST", path, {})
        status, _, chunks = await api.handle(req, json.dumps(body).encode())
        return status, b"".join([c async for c in chunks]).decode()

    async def main():
        await eng.start()
        try:
            out = []
            for new in (5, 6):
                body = {"prompt": "fill these blocks", "max_tokens": new,
                        "ignore_eos": True, "logprobs": 0}
                out.append(await post("/v1/completions", body))
                out.append(await post("/v1/completions", dict(
                    body, stream=True,
                    stream_options={"include_usage": True})))
            out.append(await post("/v1/chat/completions", {
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 3, "ignore_eos": True}))
            return out
        finally:
            await eng.stop()

    outs = asyncio.run(asyncio.wait_for(main(), 300))
    for new, (plain_out, streamed) in zip((5, 6), zip(outs[0:4:2],
                                                     outs[1:4:2])):
        status, raw = plain_out
        resp = json.loads(raw)
        assert status == 200 and resp["usage"]["completion_tokens"] == new
        assert len(resp["choices"][0]["logprobs"]["tokens"]) == new
        status, raw = streamed
        events = [json.loads(line[6:]) for line in raw.splitlines()
                  if line.startswith("data: {")]
        assert status == 200
        assert events[-1]["usage"]["completion_tokens"] == new
        text = "".join(ev["choices"][0]["text"] for ev in events
                       if ev.get("choices"))
        # greedy: the stream is the plain answer, piece by piece
        assert text == resp["choices"][0]["text"]
    status, raw = outs[4]
    assert status == 200
    assert json.loads(raw)["usage"]["completion_tokens"] == 3


def test_an_int8_cache_is_served_for_the_cache_control():
    """The benchmark's cache control (``--kv-quant int8``): committed rows
    are held as int8 with a scale a token, layer and KV head, chunk prefill
    and the pool likewise; the numbers stay near the reference's (an 8-bit
    grid over 16 columns: a per cent of a key) and are not the plain
    cache's."""
    eng = _engine(kv_quant="int8")
    assert eng.kv_cache["k"].dtype == jnp.int8 and "k_scale" in eng.kv_cache
    prompt = _prompt(80, 37)
    (events,) = _generate(eng, [(prompt, 12, False)])
    tokens = [ev.token_id for ev in events]
    want = np.asarray(plain.denoise_logprobs(eng.mcfg, eng.params,
                                             prompt + tokens))
    off = [abs(ev.logprob - want[len(prompt) + j, ev.token_id])
           for j, ev in enumerate(events)]
    assert len(events) == 12 and ATOL < max(off) < 0.1


@contextlib.contextmanager
def _tracing():
    from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

    global_tracer.clear()
    global_tracer.configure(enabled=True, sample=1.0, capacity=65536)
    try:
        yield global_tracer
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()


def test_counters_are_the_sums_of_the_records():
    """(f) ``engine_block_*_total``, ``engine_tokens_total`` and
    ``engine_kv_rows_full_total`` grow by what the ``engine.decode_burst``
    records of the same run add up to; a token is counted once it is
    delivered, a pass once whatever it carries.  The schedule: every pass
    decides a group (none decides nothing), and about every other one
    writes the block before on its way."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    names = ("engine_block_row_passes_total",
             "engine_block_commit_row_passes_total",
             "engine_block_tokens_decided_total", "engine_tokens_total",
             "engine_decode_row_steps_total",
             "engine_block_fused_commits_total")
    eng = _engine()
    jobs = [(_prompt(70, 18), 9, False), (_prompt(71, 35), 14, False),
            (_prompt(72, 9), 6, True)]
    with _tracing() as tracer:
        before = [global_metrics.counter(n) for n in names]
        kv0 = global_metrics.counter("engine_kv_rows_full_total")
        _generate(eng, jobs, together=True)
        grew = [global_metrics.counter(n) - b for n, b in zip(names, before)]
        kv = global_metrics.counter("engine_kv_rows_full_total") - kv0
        records = tracer.records()
    bursts = [r.attrs for r in records if r.name == "engine.decode_burst"]
    assert bursts and all(a["block"] == BLOCK and a["attn"] == "einsum"
                          for a in bursts)
    passes = sum(a["row_passes_denoise"] + a["row_passes_commit"]
                 for a in bursts)
    assert grew[0] == passes
    assert grew[1] == sum(a["row_passes_commit"] for a in bursts) == 0
    assert grew[2] == grew[3] == sum(a["tokens_decided"] for a in bursts)
    assert grew[2] == 9 + 14 + 6
    assert grew[5] == sum(a["row_commits_fused"] for a in bursts)
    # a block of 4 is written by the first of the 2 passes on the block
    # after it, a row's first block has none behind it and its last is
    # left unwritten: 18 + 9 tokens end in block 6 of blocks 4.., 35 + 14
    # in block 12 of blocks 8.., the echoed 9 + 6 in block 3 of blocks 0..
    assert grew[5] == 2 + 4 + 3
    assert all(0 <= a["row_commits_fused"] <= a["row_passes_denoise"]
               for a in bursts)
    # a real row's pass is a row-step of a dispatch; rows that ended inside
    # a burst stop being accounted, so the passes never exceed them
    assert 0 < passes <= grew[4] == sum(a["live_rows"] * a["steps"]
                                        for a in bursts)
    # two passes fill four positions: a row's passes yield at most 4/2
    assert grew[2] / passes <= BLOCK / 2
    chunks = [r.attrs for r in records if r.name == "engine.prefill_segment"]
    assert kv == sum(a["kv_rows_full"] for a in bursts + chunks)
    # every pass's 4 queries of the current block see base + 4 positions in
    # each of 3 layers (the block behind rides the same read)
    for a in bursts:
        assert a["kv_rows_full"] % (BLOCK * BLOCK * 3 * a["steps"]) == 0
        assert a["kv_rows_window"] == 0
    section = eng._model_section()["generation"]
    assert section == {"block_length": 4, "denoise_steps": 2,
                       "remasking": "sequential", "mask_token_id": 258}


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged-dot", "kernel"])
def test_the_records_moe_and_the_kernel_counter_are_held_to_each_other(
        kernel):
    """(ISSUE 39) Every pass burst and chunk-prefill record says which
    grouped product its program ran, the counter grows by the records that
    say the kernel, and the kernel's passes (interpreted here) give the
    reference's log-probabilities as ``ragged_dot``'s do."""
    from tests import moe_records

    cfg = get_config("tiny-sdar-moe", flash_interpret=kernel,
                     vocab_size=259)
    eng = _engine(cfg)
    prompt = _prompt(73, 22)
    with moe_records.tracing():
        before = moe_records.global_metrics.counter(moe_records.COUNTER)
        (events,) = _generate(eng, [(prompt, 7, False)])
        grew = moe_records.global_metrics.counter(
            moe_records.COUNTER) - before
        records = [r for r in moe_records.global_tracer.records()
                   if r.name in ("engine.decode_burst",
                                 "engine.prefill_segment")]
    _check_against_reference(eng, prompt, events)
    moe_records.check(eng, grew, records, kernel)


@pytest.mark.parametrize("option,named", [
    ({"spec_ngram": 3}, "--spec-ngram"),
    ({"kv_quant": "int4"}, "--kv-quant int4"),
    ({"tp": 2}, "--tp 2"), ({"sp": 2}, "--sp 2"), ({"ep": 2}, "--ep 2"),
    ({"ragged_prefill": True}, "--ragged-prefill"),
    ({"quant": "int8"}, "--quant int8"),
])
def test_what_the_family_lacks_is_refused_by_name(option, named):
    """(g) Refused at start-up, by name, before any weight is made."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    with pytest.raises(ValueError) as err:
        InferenceEngine(engine_cfg=EngineConfig(
            model="tiny-sdar-moe", num_slots=2, max_seq=64, **option))
    assert named in str(err.value) and "generation by blocks" in str(err.value)
