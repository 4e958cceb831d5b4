"""The row ladder of chunk prefill (ISSUE 30): a dispatch is padded to the
smallest rung that holds the rows it carries, never to ``prefill_rows``
whatever it carries.

Tiny engines on the CPU.  What a rung may not change: a row's sampled token,
its log-probabilities and the cache rows it writes (to float32 rounding: two
programs of different shapes tile the same products differently).  What it must change:
the dispatch record's ``rows_padded`` / ``positions``, the positions counter,
and the programs ``warmup_plan()`` holds — exactly those dispatch can pick.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_tunnel_tpu.engine.engine import (
    EngineConfig,
    InferenceEngine,
    chunk_row_ladder,
)
from p2p_llm_tunnel_tpu.engine.scheduler import GenRequest, RunningSlot
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics
from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

CHUNK = 32   # segment width; the prefix-tail widths are 16 and 32
TAIL = 16
ROWS = 8
SLOTS = 8

LADDERS = {1: (1,), 2: (1, 2), 3: (1, 2, 3), 8: (1, 2, 4, 8)}


FIRST_VIEW = 128  # the smallest kv-view bucket: where first segments run


def rungs(prefill_rows, t, view=FIRST_VIEW):
    """What a dispatch at width ``t`` and kv-view ``view`` pads to: first
    segments (the segment width at its smallest view) take the ladder's two
    lowest rungs or its top; later segments and prefix tails its top (a rung
    is a program, and a program is 0.7 s of every warm start: PERF.md
    section 6, PR 30)."""
    ladder = LADDERS[prefill_rows]
    if t == CHUNK and view == FIRST_VIEW:
        return ladder[:2] + ladder[2:][-1:]
    return ladder[-1:]


def make_engine(model="tiny", **over):
    cfg = dict(
        model=model, num_slots=SLOTS, max_seq=128, dtype="float32", seed=0,
        mux=True, prefix_cache=True, prefill_chunk=CHUNK, prefill_rows=ROWS,
        decode_steps=4, decode_steps_eager=2,
    )
    cfg.update(over)
    return InferenceEngine(engine_cfg=EngineConfig(**cfg))


def smallest_rung(rungs, n):
    return min(r for r in rungs if r >= n)


# -- the rung function ------------------------------------------------------


@pytest.mark.parametrize("prefill_rows", sorted(LADDERS))
def test_ladder_is_powers_of_two_then_prefill_rows(prefill_rows):
    assert chunk_row_ladder(prefill_rows) == LADDERS[prefill_rows]


@pytest.mark.parametrize(
    "prefill_rows,n",
    [(pr, n) for pr in sorted(LADDERS) for n in range(1, pr + 1)],
)
def test_rung_is_the_smallest_that_holds_the_rows(prefill_rows, n):
    rung = smallest_rung(chunk_row_ladder(prefill_rows), n)
    assert n <= rung <= prefill_rows
    assert rung < 2 * n or rung == prefill_rows  # never twice the rows
    assert rung == prefill_rows or rung & (rung - 1) == 0


# -- a row's result does not depend on the rung ----------------------------


def _run(slot, ids, rid, **req):
    return RunningSlot(
        GenRequest(request_id=rid, prompt_ids=list(ids), max_new_tokens=4,
                   **req),
        slot=slot, cache_len=0,
    )


def _ids(seed, n, vocab):
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, vocab - 1, size=n)]


def _dispatch(engine, rows, t):
    first, lp, _ = engine._dispatch_chunk_rows(rows, t)
    first, lp = jax.device_get((first, lp))
    return np.asarray(first), jax.tree.map(np.asarray, lp)


def _slot_rows(engine, slot, lo, hi):
    """Every cache plane's rows [lo, hi) of ``slot`` (planes are laid out
    [layers, rows, positions, ...] in every family)."""
    return [np.asarray(leaf[:, slot, lo:hi])
            for leaf in jax.tree.leaves(engine.kv_cache)]


@pytest.fixture(scope="module", params=["tiny", "tiny-qwen", "tiny-mla-moe"])
def engine(request):
    return make_engine(request.param)


#: kind -> (width dispatched at, start, tokens in the row, sampled?)
KINDS = {
    "segment": (CHUNK, 0, CHUNK, False),
    "final_segment": (CHUNK, CHUNK, 7, True),
    "prefix_tail": (TAIL, 2 * TAIL, 5, True),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_row_alone_equals_row_beside_seven(engine, kind):
    t, start, n, sample = KINDS[kind]
    vocab = engine.mcfg.vocab_size
    prompt = _ids(7, start + n, vocab)
    target = _run(3, prompt, 1, logprobs=3 if sample else 0)
    if start:
        # the history the row attends to: written once, alone
        _dispatch(engine, [(target, 0, prompt[:start], False)],
                  CHUNK if start % CHUNK == 0 else TAIL * 2)
    snap = jax.tree.map(np.asarray, engine.kv_cache)
    row = (target, start, prompt[start:], sample)

    alone = rungs(ROWS, t)[0]  # 1 for a first or early segment, 8 for a tail
    engine._programs_ready.clear()
    first_a, lp_a = _dispatch(engine, [row], t)
    assert f"chunk[{alone},{t}," in "".join(engine._programs_ready)
    rows_a = _slot_rows(engine, 3, start, start + n)

    engine.kv_cache = jax.tree.map(jnp.asarray, snap)
    others = [
        _run(s, _ids(20 + s, 3 + 4 * s, vocab), 10 + s)
        for s in range(SLOTS) if s != 3
    ]
    beside = [(o, 0, o.request.prompt_ids[:t], True) for o in others]
    beside.insert(3, row)
    engine._programs_ready.clear()
    first_b, lp_b = _dispatch(engine, beside, t)
    assert f"chunk[8,{t}," in "".join(engine._programs_ready)
    rows_b = _slot_rows(engine, 3, start, start + n)

    assert first_a.shape == (alone,) and first_b.shape == (ROWS,)
    assert first_a[0] == first_b[3]
    for a, b in zip(rows_a, rows_b):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    if sample:
        for a, b in zip(lp_a, lp_b):
            if a.dtype.kind == "f":
                np.testing.assert_allclose(a[0], b[3], rtol=1e-5, atol=1e-4)
            else:
                np.testing.assert_array_equal(a[0], b[3])
    else:
        assert lp_a is None


# -- the record and the counter say the rung --------------------------------


@pytest.fixture(scope="module")
def tiny():
    return make_engine("tiny")


@pytest.mark.parametrize("n", range(1, ROWS + 1))
def test_record_and_counter_say_the_rung(tiny, n):
    vocab = tiny.mcfg.vocab_size
    rows = [(_run(s, _ids(40 + s, 5 + s, vocab), 50 + s), 0,
             _ids(40 + s, 5 + s, vocab), True) for s in range(n)]
    rung = smallest_rung(rungs(ROWS, CHUNK), n)
    before = global_metrics.counter("engine_prefill_positions_total")
    global_tracer.configure(enabled=True, sample=1.0, capacity=1024)
    try:
        first, _lp = _dispatch(tiny, rows, CHUNK)
        attrs = tiny._last_dispatch.attrs
    finally:
        global_tracer.configure(enabled=False)
        global_tracer.clear()
    assert first.shape == (rung,)
    assert (attrs["rows"], attrs["rows_padded"]) == (n, rung)
    assert attrs["positions"] == rung * CHUNK == rung * attrs["t"]
    assert attrs["tokens"] == sum(5 + s for s in range(n))
    assert global_metrics.counter("engine_prefill_positions_total") - before \
        == rung * CHUNK


# -- the plan holds what dispatch can pick, and nothing else ----------------


@pytest.mark.parametrize("prefill_rows", sorted(LADDERS))
@pytest.mark.parametrize("prefix_cache", [True, False])
def test_plan_holds_exactly_what_dispatch_can_pick(prefill_rows,
                                                   prefix_cache):
    eng = make_engine(prefill_rows=prefill_rows, prefix_cache=prefix_cache,
                      num_slots=max(2, prefill_rows), max_seq=256)
    planned = {s for kind, s in eng.warmup_plan() if kind == "chunk"}
    picked = set()

    def stub(params, kv, bias, tokens, lengths, starts, slots, samp, key,
             view):
        picked.add((*tokens.shape, view))
        return jnp.zeros(tokens.shape[:1], jnp.int32), None, kv

    eng._jit_chunk_prefill = stub  # every row count, width and view there is
    vocab = eng.mcfg.vocab_size
    widths = {CHUNK} | (set(eng._chunk_buckets) if prefix_cache else set())
    for t in widths:
        for view in eng._view_buckets():
            for n in range(1, prefill_rows + 1):
                rows = [(_run(s, _ids(s, 3, vocab), s), view - t,
                         _ids(s, 3, vocab), False) for s in range(n)]
                eng._dispatch_chunk_rows(rows, t)
    assert planned == picked
    for r, t, view in planned:
        assert r in rungs(prefill_rows, t, view)
    assert {r for r, t, v in planned if (t, v) == (CHUNK, FIRST_VIEW)} == \
        set(LADDERS[prefill_rows][:2]) | {prefill_rows}
    assert {r for r, t, v in planned if (t, v) != (CHUNK, FIRST_VIEW)} == \
        {prefill_rows}


# -- a herd over every rung compiles nothing after warm-up ------------------


async def _collect(engine, prompt, max_new=4):
    return [ev.token_id async for ev in
            engine.generate(prompt, max_new_tokens=max_new, stop_ids=())]


def test_mux_herd_over_every_rung_hits_zero_cold_compiles(monkeypatch):
    """Arrivals of 1, 2, 3, 5 and 8 prompts at once: every dispatch pads to
    the smallest rung it may, all three rungs run, and none of them compiles
    after ``warmup()`` said the grid was complete."""
    monkeypatch.setenv("TUNNEL_WARMUP_VIEW_CAP", "60")

    async def run():
        eng = make_engine()
        vocab = eng.mcfg.vocab_size
        await eng.start()
        await eng.warmup()
        cold0 = global_metrics.counter("engine_cold_compiles_total")
        global_tracer.configure(enabled=True, sample=1.0, capacity=65536)
        try:
            outs = []
            for wave, n in enumerate((1, 2, 3, 5, 8)):
                herd = [_ids(100 * wave + i, 9 + 6 * i, vocab)
                        for i in range(n)]
                outs += await asyncio.gather(*(_collect(eng, p) for p in herd))
            await asyncio.sleep(0.2)
            segs = [r.attrs for r in global_tracer.records()
                    if r.name == "engine.prefill_segment"]
        finally:
            global_tracer.configure(enabled=False)
            global_tracer.clear()
        cold = global_metrics.counter("engine_cold_compiles_total") - cold0
        await eng.stop()
        return outs, segs, cold

    outs, segs, cold = asyncio.run(run())
    assert len(outs) == 19 and all(len(o) == 4 for o in outs)
    assert cold == 0, f"{cold} compiles after warm-up"
    for a in segs:
        assert a["rows_padded"] == smallest_rung(
            rungs(ROWS, a["t"], a["view"]), a["rows"])
        assert a["positions"] == a["rows_padded"] * a["t"]
    assert {a["rows"] for a in segs} >= {1, 2, 3, 5, 8}
    assert {a["rows_padded"] for a in segs} == {1, 2, ROWS}
