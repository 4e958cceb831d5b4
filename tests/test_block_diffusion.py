"""Generation by blocks (``tiny-sdar-moe``: SDAR at a size the CPU runs:
blocks of 4 filled in 2 denoise passes of 2, a block's commit riding the
first pass on the block after it, QK norm, 8 experts top-2) against its
plain reference, tests/block_diffusion_plain.py:
the model's programs through the cache, the engine's block carry for every
prompt remainder, ``max_tokens`` that ends inside a group, a chunk
boundary, a prefix-pool hit, ``echo``, rows out of phase with each other,
the API's stream, the counters against the records, and the refusals.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.block_decode import block_decode_step
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_attention_branch,
    init_kv_cache,
    init_params,
    prefill,
    prefill_attention_branch,
)
from tests import block_diffusion_plain as plain
from tests.moe_records import dispatches_closed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK, GROUP = 4, 2
ROWS, MAX_SEQ = 3, 128
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a grouped product over sorted rows, a softmax in two parts
# over the cache prefix and the block) differ in the last places of a
# float32: 2e-6 is what they read, 1e-4 leaves the CPU's threads their
# order.  A bfloat16 product anywhere (8 bits of mantissa: 4e-3 a term)
# reads two orders of magnitude above it.
ATOL = 1e-4


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-sdar-moe", vocab_size=259, mask_token_id=258)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view",))
_block_step = jax.jit(block_decode_step, static_argnums=(0,),
                      static_argnames=("kv_view", "with_stats"))


def _chunk(cfg, params, cache, ids, start, slot, width=16):
    tok = jnp.zeros((1, width), jnp.int32).at[0, :len(ids)].set(
        jnp.array(ids, jnp.int32))
    return _chunk_prefill(cfg, params, tok, jnp.array([len(ids)]),
                          jnp.array([start]), cache, jnp.array([slot]),
                          kv_view=MAX_SEQ)[1]


def _row(values, slot, parked):
    """``values`` in row ``slot`` of ROWS rows, the others parked."""
    out = np.full((ROWS,) + np.shape(values), parked, np.int32)
    out[slot] = values
    return jnp.asarray(out)


# ---- the model's programs ----------------------------------------------------

def test_prefill_under_the_block_causal_mask_is_the_plain_forward(model):
    """Whole-prompt ``prefill`` (the einsum under ``j // 4 <= i // 4``;
    the causal flash kernel and the rows kernel are declined for this
    family by their own gates)."""
    cfg, params = model
    ids = _prompt(1, 20)
    tok = jnp.array([ids + [0] * 12], jnp.int32)
    logits, _, _ = prefill(cfg, params, tok, jnp.arange(32)[None] < 20)
    np.testing.assert_allclose(
        logits[0, :20], plain.forward(cfg, params, ids), atol=ATOL)
    forced = get_config("tiny-sdar-moe", flash_force=True, head_dim=128)
    assert prefill_attention_branch(forced, None, 128) == "einsum"
    assert decode_attention_branch(forced, None, 128) == "einsum"


@pytest.mark.parametrize("cut", [16, 28])
def test_chunk_prefill_and_block_passes_against_the_reference(model, cut):
    """36 prompt tokens prefilled as two segments (the second starts at
    ``cut``: inside the first pool block's successor or past it), then the
    passes of three blocks given the reference's own tokens: every pass's
    logits, both offsets, all columns; a pass with nothing pending leaves
    the cache bit-for-bit as it found it, and the first pass on a block
    writes the 4 rows a layer of the block before it."""
    cfg, params = model
    ids = _prompt(2, 48)
    want = np.asarray(plain.denoise_logprobs(cfg, params, ids))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    cache = _chunk(cfg, params, cache, ids[:cut], 0, 1, width=32)
    cache = _chunk(cfg, params, cache, ids[cut:36], cut, 1, width=32)
    for base in (36, 40, 44):
        blk = _row(ids[base - BLOCK:base + BLOCK], 1, 0)  # [behind | block]
        at = _row(base, 1, MAX_SEQ)
        for decided in (0, 2):
            behind = decided == 0 and base > 36
            logits, after = _block_step(
                cfg, params, cache, blk, at, _row(decided, 1, 0),
                _row(behind, 1, 0).astype(bool), kv_view=MAX_SEQ)
            np.testing.assert_allclose(
                _logprobs(logits[1]),
                want[base + decided: base + decided + GROUP], atol=ATOL)
            for key in cache:
                changed = np.argwhere(np.asarray(after[key] != cache[key]))
                if not behind:  # (b) nothing written
                    assert not len(changed), key
                    continue
                assert set(changed[:, 1]) == {1}, key  # the row's own slot
                assert set(changed[:, 2]) == set(range(base - BLOCK, base)), \
                    key
            cache = after


FUSED_CASES = {
    # rows: (tokens held before the pending block, the pending block is
    # there, offsets decided of the current block)
    "a-block-behind": [(16, True, 0)],
    "out-of-phase": [(24, True, 0), (12, False, 2), (8, False, 0)],
    "the-mask-id-behind": [(16, True, 0)],
    "int8-cache": [(16, True, 0), (20, False, 0)],
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_the_fused_pass_is_the_commit_and_then_the_first_denoise_pass(
        model, case):
    """One pass over ``[pending | current]`` against the two-pass form
    computed here: the pending block forwarded clean by itself (chunk
    prefill of its 4 tokens at its position: the same block-causal forward,
    another program), then the pass on the current block with nothing
    pending.  Equal logits at the deciding offsets and equal K/V written,
    for a row with a block behind it beside rows without (one of them in
    its second pass), a pending token equal to ``mask_token_id`` (a decided
    token, read as itself) and the int8 cache control (the current block's
    queries read the pending K/V quantised in both forms)."""
    cfg, params = model
    quant = case == "int8-cache"
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant=quant)
    rows = FUSED_CASES[case]
    toks = np.zeros((ROWS, 2 * BLOCK), np.int32)
    base = np.full((ROWS,), MAX_SEQ, np.int32)
    decided = np.zeros((ROWS,), np.int32)
    behind = np.zeros((ROWS,), bool)
    seqs = []
    for slot, (held, pend, dec) in enumerate(rows):
        ids = _prompt(90 + slot, held + 2 * BLOCK)
        if case == "the-mask-id-behind":
            ids[held + 1] = cfg.mask_token_id
        cache = _chunk(cfg, params, cache, ids[:held], 0, slot, width=32)
        if not pend:  # the block behind is in the cache already
            cache = _chunk(cfg, params, cache, ids[held:held + BLOCK], held,
                           slot, width=BLOCK)
        toks[slot] = ids[held:]
        base[slot], decided[slot], behind[slot] = held + BLOCK, dec, pend
        seqs.append(ids)
    args = (jnp.asarray(toks), jnp.asarray(base), jnp.asarray(decided))
    fused, wrote = _block_step(cfg, params, cache, *args,
                               jnp.asarray(behind), kv_view=MAX_SEQ)
    two = cache
    for slot, (held, pend, _dec) in enumerate(rows):
        if pend:
            two = _chunk(cfg, params, two, seqs[slot][held:held + BLOCK],
                         held, slot, width=BLOCK)
    alone, same = _block_step(cfg, params, two, *args,
                              jnp.zeros((ROWS,), bool), kv_view=MAX_SEQ)
    for key in two:
        assert bool((same[key] == two[key]).all()), key
    live = len(rows)
    # (the int8 grid: a value that rounds the other way moves a key by a
    # 127th of its row's largest, and the logits by what that is worth)
    np.testing.assert_allclose(fused[:live], alone[:live],
                               atol=2e-2 if quant else ATOL)
    for key in two:
        a, b = np.asarray(wrote[key]), np.asarray(two[key])
        if a.dtype == np.int8:
            assert np.abs(a.astype(np.int32) - b).max() <= 1, key
        else:
            np.testing.assert_allclose(a, b, atol=ATOL, err_msg=key)
        changed = np.argwhere(a != np.asarray(cache[key]))
        for slot, (held, pend, _dec) in enumerate(rows):
            at = set(changed[changed[:, 1] == slot][:, 2])
            assert at == (set(range(held, held + BLOCK)) if pend else set()), \
                (key, slot)
    # and the reference, which keeps its separate commit forward
    for slot, (held, _pend, dec) in enumerate([] if quant else rows):
        want = np.asarray(plain.denoise_logprobs(cfg, params, seqs[slot]))
        first = held + BLOCK + dec
        np.testing.assert_allclose(_logprobs(fused[slot]),
                                   want[first:first + GROUP], atol=ATOL)


def test_a_token_equal_to_the_mask_id_is_a_token(model):
    """Whether an offset is decided is kept by offset: a decided token that
    happens to be ``mask_token_id`` conditions the next group as itself
    (here it is the same embedding, so the check is that nothing else
    changes), and an undecided offset's stale token is never read; nor is
    the half before the block where nothing is pending."""
    cfg, params = model
    ids = _prompt(3, 16)
    cache = _chunk(cfg, params, init_kv_cache(cfg, ROWS, MAX_SEQ,
                                              jnp.float32), ids, 0, 0)
    at, dec = _row(16, 0, MAX_SEQ), _row(2, 0, 0)
    none = jnp.zeros((ROWS,), bool)
    stale = [1, 2, 3, 4]
    a, _ = _block_step(cfg, params, cache, _row(stale + [7, 9, 11, 13], 0, 0),
                       at, dec, none, kv_view=MAX_SEQ)
    b, _ = _block_step(cfg, params, cache,
                       _row([5, 6, 7, 8, 7, 9, 200, 201], 0, 0), at, dec,
                       none, kv_view=MAX_SEQ)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    c, _ = _block_step(cfg, params, cache, _row(stale + [7, 8, 11, 13], 0, 0),
                       at, dec, none, kv_view=MAX_SEQ)
    assert float(jnp.abs(a[0] - c[0]).max()) > 1e-3


# ---- the engine -----------------------------------------------------------------

def _engine(model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    kw = {"num_slots": 3, "decode_steps": 2, "prefill_chunk": 16, **kw}
    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model="tiny-sdar-moe", max_seq=MAX_SEQ, dtype="float32", mux=True,
        prefix_cache=True, prefix_pool_blocks=32, **kw))


def _generate(eng, jobs, together=False, tops=3):
    """``jobs``: [(prompt, max_tokens, echo)] -> their events, one after
    another or all at once."""
    async def one(prompt, new, echo, wait=0.0):
        await asyncio.sleep(wait)
        return [ev async for ev in eng.generate(
            prompt, max_new_tokens=new, logprobs=tops, echo_logprobs=echo,
            stop_ids=())]

    async def main():
        await eng.start()
        try:
            if together:
                out = await asyncio.gather(*(
                    one(*job, wait=0.05 * i) for i, job in enumerate(jobs)))
            else:
                out = [await one(*job) for job in jobs]
            await dispatches_closed(eng)
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


def _check_against_reference(eng, prompt, events):
    """Every generated token's log-probability and its alternatives', and
    under echo every prompt token's, are the reference's for the sequence
    the engine produced."""
    tokens = [ev.token_id for ev in events]
    want = np.asarray(plain.denoise_logprobs(eng.mcfg, eng.params,
                                             prompt + tokens))
    n = len(prompt)
    for j, ev in enumerate(events):
        assert abs(ev.logprob - want[n + j, ev.token_id]) < ATOL, (n, j)
        for tok, value in ev.top_logprobs:
            assert abs(value - want[n + j, tok]) < ATOL, (n, j, tok)
    plps = events[0].prompt_logprobs
    if plps is not None:
        assert len(plps) == n
        np.testing.assert_allclose(
            plps[1:], [want[q, prompt[q]] for q in range(1, n)], atol=ATOL)


def test_every_remainder_and_an_end_inside_a_group():
    """(a) Prompts of every ``n mod 4``, through chunk prefill of their
    whole blocks (one and two segments of 16) and the decode passes, with
    ``max_tokens`` odd and even so that a request ends inside a group and
    inside a block; a prompt shorter than a block is not prefilled at all.
    (e) The stream is in position order and as long as asked."""
    eng = _engine()
    jobs = [(_prompt(20 + n, n), new, False)
            for n, new in ((20, 10), (21, 9), (22, 7), (23, 6), (40, 5),
                           (3, 6), (33, 1))]
    for (prompt, new, _), events in zip(jobs, _generate(eng, jobs)):
        assert len(events) == new and events[-1].finish_reason == "length"
        assert all(ev.finish_reason is None for ev in events[:-1])
        _check_against_reference(eng, prompt, events)


def test_a_prefix_pool_hit_ends_on_a_block_boundary():
    """(a) A prompt that shares its first 32 tokens with an earlier one
    restores them from the pool (pool blocks are 16 tokens = 4 blocks, so a
    hit ends where a block ends) and reads like the reference from there."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine()
    assert eng._prefix_block % BLOCK == 0
    base = _prompt(9, 50)
    jobs = [(base, 6, False), (base[:32] + _prompt(10, 13), 7, False),
            (base[:49], 5, False)]
    hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
    outs = _generate(eng, jobs)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit0 \
        == 32 + 48
    for (prompt, _new, _), events in zip(jobs, outs):
        _check_against_reference(eng, prompt, events)


def test_a_finished_stream_is_saved_as_far_as_it_is_committed():
    """The conversation cache saves a finished stream's whole pool blocks
    below the block of its last token (a pass on a block follows the commit
    of the one before it; the last block may not be committed): a next turn
    that resends the conversation hits them and reads like the reference."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(conv_cache=True)
    first = _prompt(30, 21)

    async def main():
        await eng.start()
        try:
            said = [ev async for ev in eng.generate(
                first, max_new_tokens=30, logprobs=1, stop_ids=())]
            await asyncio.sleep(0.2)
            hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
            turn = first + [ev.token_id for ev in said] + _prompt(31, 6)
            events = [ev async for ev in eng.generate(
                turn, max_new_tokens=7, logprobs=3, stop_ids=())]
            return turn, events, global_metrics.counter(
                "engine_prefix_hit_tokens_total") - hit0
        finally:
            await eng.stop()

    turn, events, hit = asyncio.run(asyncio.wait_for(main(), 300))
    # 51 tokens held, the last at position 50 in block 12: 48 committed
    assert hit == 48
    _check_against_reference(eng, turn, events)


def test_echo_runs_the_prompt_through_the_decode_passes():
    """(c) ``echo``: the prompt's tokens are forced outcomes of the same
    passes, scored by the same definition, for every remainder; generation
    goes on from the cache those passes committed."""
    eng = _engine()
    jobs = [(_prompt(40 + n, n), 5, True) for n in (12, 13, 14, 15, 2)]
    for (prompt, _new, _), events in zip(jobs, _generate(eng, jobs)):
        assert len(events) == 5
        _check_against_reference(eng, prompt, events)


def test_rows_out_of_phase_get_what_they_get_alone():
    """(d) Rows admitted at different passes of each other's blocks, with
    different remainders, echoed and not: one dispatch mixes rows with a
    block that awaits its commit and rows without, first and second passes,
    and every row reads like the reference."""
    jobs = [(_prompt(60, 21), 12, False), (_prompt(61, 34), 9, False),
            (_prompt(62, 11), 8, True), (_prompt(63, 19), 11, False)]
    eng = _engine(decode_steps=3)
    for (prompt, new, _), events in zip(jobs, _generate(eng, jobs, True)):
        assert len(events) == new
        _check_against_reference(eng, prompt, events)


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


def test_the_published_shape_is_the_preset():
    """The preset carries the published widths and the cut only drops
    layers; what a cached token takes follows."""
    whole, cut = get_config("sdar-30b-a3b"), get_config("sdar-30b-a3b-pp7s")
    assert (whole.dim, whole.n_heads, whole.n_kv_heads, whole.head_dim,
            whole.n_experts, whole.n_experts_per_tok, whole.expert_dim,
            whole.vocab_size, whole.n_layers) == (
        2048, 32, 4, 128, 128, 8, 768, 151936, 48)
    assert whole.qk_norm and whole.block_length == 4
    assert whole.denoise_steps == 2 and whole.mask_token_id == 151669
    assert cut.n_layers == 7 and cut.published_layers == 48
    assert cut.experts_held == (0, 128) and cut.vocab_size == 151936
    assert 7 * 2 * cut.n_kv_heads * cut.head_dim * 2 == 14336


# ---- the benchmark's copy of the family -----------------------------------------

def _tiny_file():
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_bd

    return tinycell_bd


def test_the_benchmarks_reference_is_the_same_model(model):
    """benchmarks/block_diffusion_reference.py draws the program's weights
    from the seed and computes the plain reference's distributions: row
    ``p`` is position ``p + 1``'s, the last row a position past the
    sequence, which its padding cannot move."""
    from benchmarks import block_diffusion_reference as bench

    tiny = _tiny_file()
    config = dict(tiny.CONFIG, vocab_size=259, mask_token_id=258)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, 11)
    cfg, _ = model
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.bfloat16)
    names = {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
             "q_norm": "q_norm", "k_norm": "k_norm", "router": "router",
             "gate": "moe_gate", "up": "moe_up", "down": "moe_down"}
    for theirs, ours in names.items():
        np.testing.assert_array_equal(
            np.asarray(weights["layers"][theirs], np.float32),
            np.asarray(params["blocks"][ours], np.float32), err_msg=theirs)
    for name in ("embed", "lm_head"):
        np.testing.assert_array_equal(np.asarray(weights[name], np.float32),
                                      np.asarray(params[name], np.float32))
    ids = _prompt(5, 24)
    want = np.asarray(plain.denoise_logprobs(cfg, params, ids))
    got = np.asarray(bench.forward_logprobs(shapes, weights, ids))
    np.testing.assert_allclose(got, want[1:], atol=ATOL)
    padded = np.asarray(bench.forward_logprobs(shapes, weights,
                                               ids + [0] * 8))
    np.testing.assert_allclose(padded[:24], got, atol=ATOL)
    assert bench.cache_bytes_per_token(config) == tiny.CACHE_BYTES
    with pytest.raises(ValueError):
        bench.forward_logprobs(shapes, weights, ids[:22])


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row["config"] for row in map(json.loads, f)
                         if row["name"] == "SDAR-30B-A3B-Chat")
    assert body["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        assert body[key] == (7 if key == "num_hidden_layers" else value), key
    assert body["published_counts"] == {"num_hidden_layers": 48}
    assert body["layer_chips"] == 1
    from benchmarks import block_diffusion_reference as bench

    assert bench.cache_bytes_per_token(body) == 14336
    shapes = bench.shapes_of(body)
    cut = get_config(body["serve"]["model"])
    assert (shapes["layers"], shapes["experts"], shapes["top_k"],
            shapes["expert_ffn"], shapes["vocab"], shapes["kv"]) == (
        cut.n_layers, cut.n_experts, cut.n_experts_per_tok, cut.expert_dim,
        cut.vocab_size, cut.n_kv_heads)
    assert (shapes["block"], shapes["group"], shapes["mask"]) == (
        cut.block_length, cut.block_length // cut.denoise_steps,
        cut.mask_token_id)
    assert (shapes["eps"], shapes["theta"]) == (cut.norm_eps, cut.rope_theta)
    for key in ("block_length", "denoising_steps", "remasking", "remainder",
                "echo", "mask_token_id", "qk_norm", "rotary", "slots",
                "max_seq", "prefix_pool_blocks", "tokenizer",
                "in_place_prediction", "commit_pass"):
        assert key in body["assumed"], key


TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
    "activations": ({"quant": "a8"}, None),
    "kv_cache": ({"kv_quant": "int8"}, None),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_bd.py's cell (``tiny-sdar-moe`` in bfloat16
    against benchmarks/block_diffusion_reference.py) through the engine in
    this process: what ``correct`` compares, as stated and with each stated
    precision lowered.  The echoed prompts run through the decode passes,
    the ladder's prefixes reach the first decode pass through chunk prefill
    and the pool.  (Through signal + serve + proxy:
    tests/benchmarks/test_bm_bd_rehearsal.py, ``slow``.)"""
    from test_mla_moe import _ask_in_process

    tiny = _tiny_file()
    from benchmarks import block_diffusion_reference as bench
    from benchmarks import correctness, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    engine_args, weight_bits = TINY_CELL_MODES[mode]
    config, seed = tiny.CONFIG, 11
    limits = config["correct"]["limits"]
    vocab = config["vocab_size"]
    plan = traffic.make_plan(
        {"name": "t", "loop": "closed", "clients": 3,
         "requests_per_client": 4, "lead_s": 0.5, "tail_s": 0.0,
         "request_timeout_s": 30.0,
         "prompt_tokens": {"dist": "uniform", "min": 8, "max": 24},
         "output_tokens": {"dist": "uniform", "min": 8, "max": 16}},
        seed, 3, vocab)
    seqs = correctness.sequences(plan, seed, vocab, 256)
    shapes = bench.shapes_of(config)
    weights = bench.make_weights(shapes, seed)
    stated = bench.cache_bytes_per_token(config)
    if weight_bits is None:
        class Words(ByteTokenizer):
            vocab_size = vocab

        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16,
                **engine_args),
            tokenizer=Words())
        assert eng.mcfg.mask_token_id == config["mask_token_id"]
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
    else:  # the reference in the program's place, its weights rounded
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "control", os.path.join(REPO, "benchmarks", "control.py"))
        control = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(control)
        counted = stated
        for seq in seqs:
            control.pretend(seq)
            pad = seq["tokens"] + [0] * (-len(seq["tokens"]) % 4)
            lp = np.asarray(bench.forward_logprobs(
                shapes, weights, pad, weight_bits=weight_bits))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        pad = seq["tokens"] + [0] * (-len(seq["tokens"]) % 4)
        lp = np.asarray(bench.forward_logprobs(shapes, weights, pad))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    print("\n".join(said))
    assert held is (mode == "stated"), "\n".join(said)
    if mode == "kv_cache":  # by its width alone
        # int8 values and one float32 scale a KV head beside each plane
        assert counted == 3 * 2 * (32 + 2 * 4)
        assert stated == tiny.CACHE_BYTES
    elif mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
