"""The family that mixes window and full attention layers over routed experts
(``tiny-swa-moe``: MiMo-V2-Flash at a size the CPU runs, rings of 16
positions) against its plain reference, tests/swa_moe_plain.py: the three
serving programs through rings that wrap, the prefix pool's pages of two
kinds of leaf, the shares of a layer, what a slot and a pooled token hold,
the counts of cache rows by layer kind, the benchmark's own copy of the
reference and its configuration file, and the tiny cell in one process.
"""

from __future__ import annotations

import asyncio
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe, swa
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    _act,
    chunk_prefill_into_cache,
    decode_step,
    init_kv_cache,
    init_params,
    prefill,
    prefill_into_cache,
)
from p2p_llm_tunnel_tpu.ops.attention import (
    masked_attention,
    ring_positions,
    window_mask,
)
from tests import swa_moe_plain as plain
from tests.moe_records import dispatches_closed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, MAX_SEQ, RING, WINDOW = 4, 96, 16, 8
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a grouped product over sorted rows, a softmax over ring
# slots in another order than positions) differ in the last places of a
# float32.
ATOL = 2e-4


@pytest.fixture(scope="module", params=["tiny-swa-moe", "tiny-swa-moe-ep2s"])
def model(request):
    cfg = get_config(request.param)
    return cfg, init_params(cfg, jax.random.PRNGKey(11), jnp.float32)


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# (one trace a shape: an eager scan is traced anew at every call)
_prefill_into_cache = jax.jit(prefill_into_cache, static_argnums=(0,))
_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view", "return_all_logits"))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view",))


def _whole(cfg, params, cache, prompt, slot):
    width = 16 * -(-len(prompt) // 16)
    tok = jnp.zeros((1, width), jnp.int32).at[0, :len(prompt)].set(
        jnp.array(prompt))
    return _prefill_into_cache(cfg, params, tok, jnp.array([len(prompt)]),
                               cache, jnp.array([slot]))


def _chunk(cfg, params, cache, prompt, start, end, slot, width=16,
           view=MAX_SEQ):
    """Positions [start, end) of ``prompt`` as one padded segment of
    ``width``, beside a padding row on the scratch slot."""
    n = end - start
    tok = jnp.zeros((2, width), jnp.int32).at[0, :n].set(
        jnp.array(prompt[start:end]))
    return _chunk_prefill(
        cfg, params, tok, jnp.array([n, 1]), jnp.array([start, 0]), cache,
        jnp.array([slot, ROWS - 1]), kv_view=view, return_all_logits=True)


def _decode(cfg, params, cache, slot, token, position, view=MAX_SEQ):
    tokens = jnp.zeros((ROWS,), jnp.int32).at[slot].set(token)
    positions = jnp.full((ROWS,), MAX_SEQ).at[slot].set(position)
    logits, cache = _decode_step(cfg, params, cache, tokens, positions,
                                 kv_view=view)
    return logits[slot], cache


def test_the_presets_cache_is_rings_beside_full_planes(model):
    cfg, _ = model
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    assert cfg.attn_kinds == ("full", "window", "window", "window", "window",
                              "full", "window")
    assert cfg.layer_kinds == ("dense",) + ("moe",) * 6
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, ROWS, MAX_SEQ, 1 * 24), "v": (2, ROWS, MAX_SEQ, 1 * 16),
        "wk": (5, ROWS, RING, 2 * 24), "wv": (5, ROWS, RING, 2 * 16)}
    assert [(r.attn, r.ffn, r.attn_first, r.ffn_first, r.n)
            for r in swa.layer_runs(cfg)] == [
        ("full", "dense", 0, 0, 1), ("window", "moe", 0, 0, 4),
        ("full", "moe", 1, 4, 1), ("window", "moe", 4, 5, 1)]


def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params = model
    prompt = _prompt(3, 43)
    want = np.asarray(plain.forward_logprobs(cfg, params, prompt))
    tok = jnp.array([prompt + [0] * 5])
    valid = jnp.arange(48)[None, :] < 43
    logits, rows, _ = prefill(cfg, params, tok, valid)
    np.testing.assert_allclose(_logprobs(logits[0, :43]), want, atol=ATOL)
    assert rows["full"][0].shape == (2, 1, 48, 24)
    assert rows["window"][1].shape == (5, 1, 48, 32)
    # into the cache: a full layer keeps every position, a ring the last 16
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    last, cache = _whole(cfg, params, cache, prompt, 1)
    np.testing.assert_allclose(_logprobs(last[0]), want[42], atol=ATOL)
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 1, :43]),
                                  np.asarray(rows["full"][0][:, 0, :43]))
    for p in range(43):
        held = np.asarray(cache["wk"][:, 1, p % RING])
        fresh = np.asarray(rows["window"][0][:, 0, p])
        assert (held == fresh).all() == (p >= 43 - RING), p


@pytest.mark.parametrize("cuts", [
    (0, 16, 32, 48), (0, 12, 24, 36, 48), (0, 7, 23, 37, 41), (0, 16, 19)],
    ids=["aligned", "seams-inside-segments", "ragged", "short-tail"])
def test_chunked_prefill_and_decode_through_the_rings(model, cuts):
    """Segments of at most 16 tokens against rings of 16: every segment
    after the first reads its window out of a ring that has wrapped or will
    within the segment (a seam inside the tail), and 30 decode steps wrap
    the rings twice more.  Log-probabilities of every position against the
    plain reference, which has no cache."""
    cfg, params = model
    n = cuts[-1]
    seq = _prompt(5, n + 30)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    for start, end in zip(cuts, cuts[1:]):
        logits, cache = _chunk(cfg, params, cache, seq, start, end, 2)
        np.testing.assert_allclose(_logprobs(logits[0, :end - start]),
                                   want[start:end], atol=ATOL)
    for p in range(n, n + 30):
        logits, cache = _decode(cfg, params, cache, 2, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)
    # the scratch row's padding and the parked rows left slot 0 untouched
    assert not np.asarray(cache["wk"][:, 0]).any()


def test_a_segment_wider_than_a_ring_keeps_its_last_positions(model):
    """A tail of 40 real tokens into rings of 16: attention inside the
    segment needs no ring, and the ring is left holding the last 16."""
    cfg, params = model
    seq = _prompt(6, 60)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    logits, cache = _chunk(cfg, params, cache, seq, 0, 40, 1, width=48)
    np.testing.assert_allclose(_logprobs(logits[0, :40]), want[:40],
                               atol=ATOL)
    for p in range(40, 60):
        logits, cache = _decode(cfg, params, cache, 1, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)


def test_an_int8_plane_reads_like_the_plain_one_and_is_narrower(model):
    cfg, params = model
    seq = _prompt(8, 50)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32, quant="int8")
    assert {k: (v.shape[2:], str(v.dtype)) for k, v in cache.items()} == {
        "k": ((MAX_SEQ, 24), "int8"), "k_scale": ((MAX_SEQ, 1), "float32"),
        "v": ((MAX_SEQ, 16), "int8"), "v_scale": ((MAX_SEQ, 1), "float32"),
        "wk": ((RING, 48), "int8"), "wk_scale": ((RING, 2), "float32"),
        "wv": ((RING, 32), "int8"), "wv_scale": ((RING, 2), "float32")}
    assert swa.RING_KEYS == {"wk", "wv", "wk_scale", "wv_scale"}
    got = []
    for start in (0, 16, 32):
        logits, cache = _chunk(cfg, params, cache, seq, start, start + 16, 0)
        got.append(_logprobs(logits[0]))
    for p in range(48, 50):
        logits, cache = _decode(cfg, params, cache, 0, seq[p], p)
        got.append(_logprobs(logits)[None])
    err = np.abs(np.concatenate(got) - want).mean()
    # (a model this narrow routes a token elsewhere on a rounding)
    assert 1e-5 < err < 0.3, err  # rounded, and only that
    with pytest.raises(ValueError, match="no KV quant mode 'int4'"):
        init_kv_cache(cfg, ROWS, MAX_SEQ, quant="int4")


# ---- the attention op ------------------------------------------------------------

def test_the_sink_joins_the_denominator_and_carries_no_value():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 3, 4, 6))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 5, 2, 6))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 5, 2, 3))
    sink = jnp.array([0.5, -1.0, 2.0, 0.0])
    pos = jnp.array([[2, 3, 4]])
    mask = window_mask(pos, jnp.arange(5)[None, :], window=3)
    assert mask[0].tolist() == [[True, True, True, False, False],
                                [False, True, True, True, False],
                                [False, False, True, True, True]]
    got = masked_attention(q, k, v, mask, 0.4, sink=sink)
    assert got.shape == (1, 3, 4, 3)  # values narrower than keys
    kk, vv = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) * 0.4
    e = jnp.where(mask[:, None], jnp.exp(s), 0.0)
    p = e / (e.sum(-1, keepdims=True) + jnp.exp(sink)[None, :, None, None])
    want = jnp.einsum("bhts,bshd->bthd", p, vv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    assert float(p.sum(-1).max()) < 1.0  # a head gives its keys less than all
    plainly = masked_attention(q, k, v, mask, 0.4)
    assert float(jnp.abs(plainly - got).max()) > 1e-2


def test_a_ring_slot_is_named_by_the_position_it_holds():
    held = ring_positions(jnp.array([0, 5, 16, 37]), 16)
    assert held[0].tolist() == [0] + [-16 + r for r in range(1, 16)]
    assert held[1].tolist()[:6] == [0, 1, 2, 3, 4, 5] and held[1, 6] == -10
    assert held[2].tolist() == [16] + list(range(1, 16))
    assert sorted(held[3].tolist()) == list(range(22, 38))
    # queries blocked a few at a time give what one block gives
    from p2p_llm_tunnel_tpu.ops import attention as A

    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 64, 4, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 2, 4))
    pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
    mask = window_mask(pos, pos, 9)
    whole = masked_attention(q, k, v, mask, 0.3)
    old, A._SCORE_BLOCK_BYTES = A._SCORE_BLOCK_BYTES, 2 * 4 * 64 * 4 * 16
    try:
        blocked = masked_attention(q, k, v, mask, 0.3)
    finally:
        A._SCORE_BLOCK_BYTES = old
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-6)


# ---- the shares ------------------------------------------------------------------

def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def test_the_shares_add_up():
    """The held-experts parts of both shares equal the uncut reference
    layer (no shared expert in this family: nothing is counted twice)."""
    whole = get_config("tiny-swa-moe")
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.dim))
    w = _layer(init_params(whole, jax.random.PRNGKey(11), jnp.float32))
    want = plain.routed_layer(whole, w, h.reshape(-1, whole.dim))
    total, seen = 0.0, 0
    for chip in range(2):
        cfg = replace(get_config("tiny-swa-moe-ep2s"), chip_index=chip)
        part = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
        lo, held = cfg.experts_held
        np.testing.assert_array_equal(
            np.asarray(part["blocks"]["moe_up"][0]),
            np.asarray(w["moe_up"][lo:lo + held]))
        # attention is held whole on every chip
        np.testing.assert_array_equal(
            np.asarray(part["attn_window"]["wk"]),
            np.asarray(init_params(whole, jax.random.PRNGKey(11),
                                   jnp.float32)["attn_window"]["wk"]))
        with jax.default_matmul_precision("highest"):
            out, stats = moe.moe_mlp(cfg, _layer(part), h,
                                     lambda x: _act(cfg, x))
        total = total + out.reshape(-1, whole.dim)
        seen += int(stats[1])
        assert int(stats[0]) == 18 * cfg.n_experts_per_tok
    assert seen == 18 * whole.n_experts_per_tok  # every assignment held once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


# ---- the prefix pool: a page with two kinds of leaves ------------------------------

def test_a_page_of_two_kinds_is_restored_into_a_ring(model):
    """Saved segment by segment while the rings hold each block, a prefix
    of 48 tokens is restored into another slot: the full layers whole, the
    window layers' last 16 positions into the ring, and a tail and decode
    steps over the restored slot read what the plain reference gives."""
    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        init_pool,
        make_batch_copy_ops,
        pad_rows,
    )

    cfg, params = model
    block, nmax = 16, MAX_SEQ // 16
    seq = _prompt(7, 48) + _prompt(8, 30)
    want = np.asarray(plain.forward_logprobs(cfg, params, seq))
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    pool = init_pool(cache, block, 8)
    # a pooled token holds its keys and values of every layer, window too
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 8, block, 24), "v": (2, 8, block, 16),
        "wk": (5, 8, block, 48), "wv": (5, 8, block, 32)}
    copy_in, copy_out = make_batch_copy_ops(
        block, nmax, 2, layerwise_keys=frozenset(cache),
        ring_keys=swa.RING_KEYS)
    ids = [5, 2, 7]
    for i in range(3):  # each block saved while the ring holds it
        _, cache = _chunk(cfg, params, cache, seq, 16 * i, 16 * i + 16, 0)
        pool = copy_out(pool, cache, *pad_rows([(0, [ids[i]], [i])], 2,
                                               nmax, 0))
    # slot 2 held another sequence's ring before the hit
    _, cache = _chunk(cfg, params, cache, _prompt(9, 16), 0, 16, 2)
    cache = copy_in(cache, pool, *pad_rows([(2, ids, [0, 1, 2])], 2, nmax,
                                           None))
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 2, :48]),
                                  np.asarray(cache["k"][:, 0, :48]))
    # the ring holds positions 32..47 (block 2), where slot 0's does
    np.testing.assert_array_equal(np.asarray(cache["wk"][:, 2]),
                                  np.asarray(cache["wk"][:, 0]))
    np.testing.assert_array_equal(np.asarray(cache["wv"][:, 2]),
                                  np.asarray(pool["wv"][:, 7]))
    logits, cache = _chunk(cfg, params, cache, seq, 48, 57, 2)
    np.testing.assert_allclose(_logprobs(logits[0, :9]), want[48:57],
                               atol=ATOL)
    for p in range(57, 78):
        logits, cache = _decode(cfg, params, cache, 2, seq[p], p)
        np.testing.assert_allclose(_logprobs(logits), want[p], atol=ATOL)


# ---- the engine -----------------------------------------------------------------

def _engine(model_name="tiny-swa-moe-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def _generate(eng, prompts, new=10):
    async def main():
        await eng.start()
        try:
            out = []
            for prompt in prompts:
                events = [ev async for ev in eng.generate(
                    prompt, max_new_tokens=new, logprobs=1, stop_ids=())]
                out.append(([ev.token_id for ev in events],
                            [ev.logprob for ev in events]))
            await dispatches_closed(eng)
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


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


def test_the_ring_is_sized_by_the_segment():
    share = get_config("mimo-v2-flash-ep16s")
    assert share.ring_default(8192, 512) == 640  # window + segment
    assert share.ring_default(8192, 128) == 256
    assert share.ring_default(8192) == 256
    assert share.ring_default(512, 512) == 512  # never more than the slot
    assert get_config("tiny-swa-moe").ring_default(128, 16) == RING  # as preset
    assert get_config("mistral-7b").attn_kinds == ("window",) * 32
    assert get_config("tiny-gemma").attn_kinds == ("window", "full")
    assert get_config("tiny").attn_kinds == ("full", "full")


def test_the_published_preset_and_its_share():
    whole, share = get_config("mimo-v2-flash"), get_config("mimo-v2-flash-ep16s")
    assert (whole.n_layers, whole.n_experts, whole.vocab_size) == (
        48, 256, 152576)
    assert whole.attn_kinds.count("full") == 9
    assert whole.attn_kinds.count("window") == 39
    assert whole.attn_kinds[:6] == ("full",) + ("window",) * 4 + ("full",)
    assert whole.attn_kinds[-1] == "full"
    assert whole.experts_held == (0, 256)
    assert share.experts_held == (0, 16) and share.n_layers == 7
    assert share.vocab_size * 8 == whole.vocab_size  # eighths, two chips each
    assert share.layer_kinds == ("dense",) + ("moe",) * 6
    same = {f: getattr(whole, f) for f in (
        "dim", "n_heads", "n_kv_heads", "window_kv_heads", "head_dim",
        "v_head_dim", "ffn_dim", "moe_ffn_dim", "n_experts",
        "n_experts_per_tok", "n_shared_experts", "sliding_window",
        "rotary_dim", "rope_theta", "window_rope_theta", "value_scale",
        "window_sink", "router_bias", "routed_scale", "router_score")}
    assert same == {f: getattr(share, f) for f in same}
    assert (whole.head_dim, whole.v_head_dim, whole.rotary_dim) == (192, 128, 64)
    assert (whole.n_kv_heads, whole.window_kv_heads) == (4, 8)
    # the cut's parameters, by the shapes the program would draw
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 3.42e9 < count < 3.44e9


# ---- the benchmark's copy and its configuration ---------------------------------

def _tiny_file():
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import tinycell_swa

    return tinycell_swa


@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_benchmarks_reference_is_the_same_model(share):
    """benchmarks/swa_moe_reference.py draws the program's weights from the
    seed and computes the plain reference's forward, whole and as a share."""
    from benchmarks import swa_moe_reference as bench

    config = dict(_tiny_file().CONFIG)
    if not share:
        config.update(n_routed_experts=8, layer_chips=1,
                      published_counts={"n_routed_experts": 8})
    cfg = get_config("tiny-swa-moe-ep2s" if share else "tiny-swa-moe")
    shapes = bench.shapes_of(config)
    assert shapes["rotary"] == cfg.rotary_dim == 8
    weights = bench.make_weights(shapes, 5)
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.bfloat16)
    pairs = [(weights["full"][k], params["attn_full"][k])
             for k in ("wq", "wk", "wv", "wo")]
    pairs += [(weights["window"][k], params["attn_window"][k])
              for k in ("wq", "wk", "wv", "wo", "sink")]
    pairs += [(weights["moe"][a], params["blocks"][b]) for a, b in (
        ("gate", "moe_gate"), ("up", "moe_up"), ("down", "moe_down"),
        ("router", "router"), ("bias", "router_bias"))]
    pairs += [(weights["dense"]["down"], params["dense_ffn"]["w_down"]),
              (weights["embed"], params["embed"]),
              (weights["lm_head"], params["lm_head"])]
    for mine, theirs in pairs:
        np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                      np.asarray(theirs, np.float32))
    tokens = _prompt(3, 37)
    got = np.asarray(bench.forward_logprobs(shapes, weights, tokens))
    want = np.asarray(plain.forward_logprobs(cfg, params, tokens))
    np.testing.assert_allclose(got, want, atol=ATOL)
    rounded = np.asarray(bench.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=8))
    assert 1e-3 < np.abs(rounded - want).mean() < 0.5
    assert bench.cache_bytes_per_token(config) == _tiny_file().CACHE_BYTES


def test_the_configuration_file_keeps_the_published_keys():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo-v2-flash.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row["config"] for row in map(json.loads, f)
                         if row["name"] == "MiMo-V2-Flash")
    reduced = {"num_hidden_layers": 7, "n_routed_experts": 16,
               "vocab_size": 19072}
    assert set(body["reduced"]) == set(reduced)
    for key, value in published.items():
        assert body[key] == reduced.get(key, value), key
    assert body["published_counts"] == {k: published[k] for k in reduced}
    assert body["layer_chips"] * body["n_routed_experts"] == \
        published["n_routed_experts"]
    assert 8 * body["vocab_size"] == published["vocab_size"]
    from benchmarks import swa_moe_reference as bench

    # 2 full layers x 4 heads + 5 window layers x 8 heads, 192 + 128 values
    assert bench.cache_bytes_per_token(body) == 2 * 2560 + 5 * 5120 == 30720
    shapes = bench.shapes_of(body)
    assert (shapes["held"], shapes["experts"], len(shapes["attn"])) == (
        16, 256, 7)
    share = get_config(body["serve"]["model"])
    assert share.experts_held == (shapes["first_held"], shapes["held"])
    assert share.vocab_size == shapes["vocab"]
    assert share.attn_kinds == shapes["attn"]
    assert share.layer_kinds == shapes["ffn"]
    assert (share.rotary_dim, share.value_scale) == (
        shapes["rotary"], shapes["value_scale"])
    # what a slot holds at the file's serve shape: the second statement
    chunk = int(body["serve"]["args"][body["serve"]["args"].index(
        "--prefill-chunk") + 1])
    ring = share.ring_default(body["serve"]["max_seq"], chunk)
    assert ring == 640
    assert 2 * 2560 * body["serve"]["max_seq"] + 5 * 5120 * ring == 58327040


# ---- the tiny cell, in one process ----------------------------------------------

TINY_CELL_MODES = {
    "stated": ({}, None),
    "weights": ({}, 8),
    "activations": ({"quant": "a8"}, None),
    "kv_cache": ({"kv_quant": "int8"}, None),
}


@pytest.mark.parametrize("mode", sorted(TINY_CELL_MODES))
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_swa.py's cell (the ``tiny-swa-moe-ep2s``
    share in bfloat16 against benchmarks/swa_moe_reference.py given the same
    share) through the engine in this process: what ``correct`` compares,
    as stated and with each stated precision lowered.  The ladder's
    prefixes reach the chunk program through the pool and the rings.
    (Through signal + serve + proxy: tests/benchmarks/
    test_bm_swa_rehearsal.py, ``slow``.)"""
    from test_mla_moe import _ask_in_process

    tiny = _tiny_file()
    from benchmarks import correctness, swa_moe_reference as bench, traffic
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
            lp = np.asarray(bench.forward_logprobs(
                shapes, weights, seq["tokens"], weight_bits=weight_bits))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = np.asarray(bench.forward_logprobs(shapes, weights, seq["tokens"]))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    assert held is (mode == "stated"), "\n".join(said)
    if mode == "kv_cache":  # by its width alone
        # int8 values and one float32 scale a KV head beside each plane
        assert counted == (2 * 1 + 5 * 2) * (40 + 2 * 4)
        assert stated == tiny.CACHE_BYTES
        assert all(numbers[n]["mean_abs"] <= limits[n]
                   for n in correctness.NUMBERS), said
    elif mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
