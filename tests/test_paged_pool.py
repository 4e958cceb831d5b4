"""ISSUE 14: block-paged quantized KV pool + cross-request conversation cache.

Four contracts:

1. **Page alignment unfences int4**: the packed int4 KV cache composes
   with chunked prefill and the prefix pool (page-aligned whole-byte
   writes), and pooled pages are BYTE-STABLE — a pool round-trip returns
   exactly the bytes the chunk path wrote, so pool-on and pool-off token
   streams are identical (the PR 5 mux-identity bar, extended to int4).
2. **Cost-aware eviction is deterministic**: GreedyDual victims follow
   recompute cost + LRU tiebreak; a seeded random operation sequence
   produces identical state across two runs (the `make chaos` two-run
   idiom, host-pure here).
3. **Page reservations never leak**: admission-time grants return to zero
   on EVERY death path — deadline evict, client cancel, owner-death
   waiter promotion — because generate()'s finally releases them.
4. **Conversation reuse**: a turn-2 prompt that resends turn-1's whole
   conversation matches through the finished stream's pages and prefills
   only its new tail.

Pure-host index tests run in tier-1; jit-compiling engine/model tests are
slow-tier like the rest of the prefix-cache suite.
"""

import asyncio
import random

import numpy as np
import pytest

from p2p_llm_tunnel_tpu.engine.prefix_cache import PrefixIndex
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics


# ---------------------------------------------------------------------------
# cost-aware eviction (fast, host-pure)
# ---------------------------------------------------------------------------

def _key(n: int) -> bytes:
    return n.to_bytes(16, "big")


def test_cost_evict_prefers_cheap_page():
    idx = PrefixIndex(16, 4, evict="cost")  # 3 usable pages
    idx.allocate([_key(1)], costs=[100.0])
    idx.allocate([_key(2)], costs=[1.0])
    idx.allocate([_key(3)], costs=[50.0])
    # Pool full: the cheap page (key 2) is the GreedyDual victim even
    # though key 1 is older.
    idx.allocate([_key(4)], costs=[10.0])
    assert idx.id_of(_key(2)) is None
    assert idx.id_of(_key(1)) is not None
    assert idx.id_of(_key(3)) is not None
    assert idx.evictions == 1


def test_cost_evict_clock_ages_out_stale_expensive_pages():
    """The GreedyDual clock: after enough cheap churn, an untouched
    expensive page eventually loses to fresh inserts (plain cost-max
    would pin it forever)."""
    idx = PrefixIndex(16, 3, evict="cost")  # 2 usable pages
    idx.allocate([_key(1)], costs=[10.0])  # prio 10
    n = 2
    # Each churn evicts the cheaper page and raises the clock; once the
    # clock passes 10, a fresh cost-1 insert (prio clock+1) outranks the
    # stale expensive page and it gets evicted.
    for _ in range(20):
        idx.allocate([_key(n)], costs=[1.0])
        n += 1
        if idx.id_of(_key(1)) is None:
            break
    assert idx.id_of(_key(1)) is None, "expensive page never aged out"


def test_lru_evict_mode_keeps_plain_order():
    idx = PrefixIndex(16, 3, evict="lru")
    idx.allocate([_key(1)], costs=[1000.0])
    idx.allocate([_key(2)], costs=[1.0])
    idx.allocate([_key(3)], costs=[1.0])  # evicts key 1 (oldest), not cheap
    assert idx.id_of(_key(1)) is None
    assert idx.id_of(_key(2)) is not None


def test_cost_evict_two_run_identity_seeded():
    """Two runs of a seeded random (insert | touch) sequence end with
    IDENTICAL index state and eviction counts — the determinism the
    chaos-gate idiom demands of every policy this engine serves with."""

    def run(seed: int):
        rng = random.Random(seed)
        idx = PrefixIndex(16, 9, evict="cost")
        prompts = [
            list(range(s, s + 16 * rng.randint(1, 5))) for s in range(12)
        ]
        for _ in range(200):
            p = rng.choice(prompts)
            if rng.random() < 0.5:
                idx.match(p)
            else:
                missing = idx.missing(p)
                idx.allocate(
                    [k for _, k in missing],
                    costs=[(i + 1) * 16.0 for i, _ in missing],
                    conv=rng.random() < 0.3,
                )
        return idx.export_state(), idx.evictions, idx.conv_hits

    assert run(5) == run(5)
    assert run(19) == run(19)
    # Different seeds should actually exercise different paths.
    assert run(5) != run(19)


def test_reserve_evicts_under_pressure_and_release_balances():
    idx = PrefixIndex(16, 5, evict="cost")  # 4 usable pages
    idx.allocate([_key(i) for i in range(1, 5)],
                 costs=[1.0, 2.0, 3.0, 4.0])
    assert idx.free_blocks == 0
    granted = idx.reserve(2)
    assert granted == 2
    assert idx.free_blocks >= 2  # evicted the two cheapest
    assert idx.evictions == 2
    assert idx.reserved_pages == 2
    idx.release(2)
    assert idx.reserved_pages == 0
    # Grants are capped at the pool size; release never goes negative.
    assert idx.reserve(100) == 4
    idx.release(1000)
    assert idx.reserved_pages == 0


def test_export_import_roundtrip_keeps_cost_and_conv_tags():
    idx = PrefixIndex(16, 6, evict="cost")
    idx.allocate([_key(1), _key(2)], costs=[10.0, 20.0])
    idx.allocate([_key(3)], costs=[5.0], conv=True)
    state = idx.export_state()
    idx2 = PrefixIndex(16, 6, evict="cost")
    idx2.import_state(state)
    assert idx2.export_state() == state
    # The conversation tag survived: matching through key 3's block must
    # count as a conversation hit.
    assert state[-1][3] == 1


def test_import_state_accepts_legacy_two_field_entries():
    """Pre-ISSUE-14 snapshots carry [hex, idx] pairs; they load as
    cost-0, non-conversation pages instead of being dropped."""
    idx = PrefixIndex(16, 4)
    idx.import_state([[_key(1).hex(), 1], [_key(2).hex(), 2]])
    assert idx.used_blocks == 2
    assert idx.id_of(_key(1)) == 1
    assert idx.free_blocks == 1


# ---------------------------------------------------------------------------
# engine-level composition + leak gates (slow: jit compiles)
# ---------------------------------------------------------------------------

def _cfg(**kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig

    base = dict(model="tiny", num_slots=4, max_seq=128, dtype="float32",
                min_prefill_bucket=16, decode_steps=4)
    base.update(kw)
    return EngineConfig(**base)


def _herd(cfg, prompts, max_new=6):
    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    async def main():
        eng = InferenceEngine(engine_cfg=cfg)
        await eng.start()
        try:
            async def gen(p):
                out = []
                async for ev in eng.generate(p, max_new_tokens=max_new,
                                             stop_ids=()):
                    out.append(ev.token_id)
                return out
            return await asyncio.gather(*(gen(p) for p in prompts)), eng
        finally:
            await eng.stop()

    return asyncio.run(main())


@pytest.mark.slow
def test_int4_hero_composition_identity_and_unfenced():
    """ISSUE 14 acceptance: kv_quant=int4 with prefix cache, chunked
    prefill, and mux ALL enabled runs with an EMPTY fence list and emits
    token streams byte-identical to the unpooled non-mux engine at the
    same segment width (pooled pages hold exactly the bytes the unpooled
    chunk path computes)."""
    prompts = [list(range(1, 70)) + [300 + i] for i in range(4)]
    plain, _ = _herd(_cfg(kv_quant="int4", mux=False, prefix_cache=False,
                          prefill_chunk=32), prompts)
    pooled, eng = _herd(_cfg(kv_quant="int4", mux=True, prefix_cache=True,
                             prefill_chunk=32), prompts)
    assert pooled == plain
    assert eng.config_fences == []
    assert eng._prefix is not None and eng.ecfg.prefill_chunk == 32
    assert eng._prefix.hits > 0  # real page reuse happened


@pytest.mark.slow
@pytest.mark.parametrize("kv_quant", ["none", "int8", "int4"])
def test_pool_on_off_identity_every_kv_mode(kv_quant):
    """Pool on vs pool off (same chunk width, conv cache off) is a pure
    latency optimization at EVERY kv mode — byte-identical streams."""
    prompts = [list(range(1, 52)) + [400 + i] for i in range(3)]
    off, _ = _herd(_cfg(kv_quant=kv_quant, mux=True, prefix_cache=False,
                        prefill_chunk=16), prompts)
    on, _ = _herd(_cfg(kv_quant=kv_quant, mux=True, prefix_cache=True,
                       prefill_chunk=16), prompts)
    assert on == off, f"pool changed the stream under kv_quant={kv_quant}"


@pytest.mark.slow
def test_int4_pool_roundtrip_bytes_stable():
    """Pool pages are alignment-stable under int4: copy_out pages of a
    chunk-prefilled slot, wipe the slot, copy_in — the packed cache bytes
    and scale planes come back bit-identical (the shippable-page
    substrate the disaggregation roadmap item presupposes)."""
    import jax.numpy as jnp

    from p2p_llm_tunnel_tpu.engine.prefix_cache import (
        init_pool,
        make_batch_copy_ops,
        pad_rows,
    )
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.transformer import (
        chunk_prefill_into_cache,
        init_kv_cache,
        init_params,
    )
    import jax

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    block, nblocks, rows = 16, 5, 2
    cache = init_kv_cache(cfg, 2, 64, jnp.float32, quant="int4")
    toks = jnp.zeros((2, 32), jnp.int32).at[0, :].set(
        jnp.arange(1, 33, dtype=jnp.int32))
    _, cache = chunk_prefill_into_cache(
        cfg, params, toks, jnp.asarray([32, 1], jnp.int32),
        jnp.asarray([0, 0], jnp.int32), cache,
        jnp.asarray([0, 1], jnp.int32), kv_view=64,
    )
    pool = init_pool(cache, block, nblocks)
    assert pool["k"].shape[2] == block // 2  # packed page unit
    assert pool["k_scale"].shape[2] == block
    copy_in, copy_out = make_batch_copy_ops(
        block, 2, rows, packed_keys=frozenset({"k", "v"}))
    entry = [(0, [1, 2], [0, 1])]  # slot 0's two pages -> pool ids 1, 2
    slots, pids, bnos = pad_rows(entry, rows, 2, scratch=0)
    pool = copy_out(pool, cache, slots, pids, bnos)
    orig = {k: np.asarray(v).copy() for k, v in cache.items()}
    wiped = {k: jnp.zeros_like(v) for k, v in cache.items()}
    slots, pids, bnos = pad_rows(entry, rows, 2, scratch=None)
    restored = copy_in(wiped, pool, slots, pids, bnos)
    for key in orig:
        unit = 32 // 2 if key in ("k", "v") else 32
        np.testing.assert_array_equal(
            np.asarray(restored[key])[:, 0, :unit],
            orig[key][:, 0, :unit],
            err_msg=f"pool round-trip corrupted {key}",
        )


@pytest.mark.slow
def test_page_reservation_leak_gate_death_paths():
    """Pages reserved at admission return to the free pool on every death
    path: deadline eviction, client cancel mid-stream, and owner-death
    waiter promotion (the mux prefix-group path)."""
    import time

    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    cfg = _cfg(mux=True, prefix_cache=True, conv_cache=True,
               prefill_chunk=16, num_slots=2)

    async def main():
        eng = InferenceEngine(engine_cfg=cfg)
        await eng.start()
        try:
            shared = list(range(1, 40))

            # (a) deadline eviction: an already-expired-at-submit request
            # raises; a mid-flight one gets evicted by the scheduler.
            with pytest.raises(Exception):
                async for _ in eng.generate(
                    shared + [99], max_new_tokens=4,
                    deadline=time.monotonic() + 0.001, stop_ids=(),
                ):
                    await asyncio.sleep(0.05)

            # (b) client cancel mid-stream.
            gen = eng.generate(shared + [98], max_new_tokens=64,
                               stop_ids=())
            async for _ in gen:
                break
            await gen.aclose()

            # (c) owner-death promotion: two requests share a cold
            # prefix; cancel the FIRST (the group owner) immediately so
            # the waiter is promoted and finishes alone.
            owner = eng.generate(shared + [97], max_new_tokens=8,
                                 stop_ids=())
            waiter_task = asyncio.create_task(
                _collect(eng, shared + [96], 4))
            it = owner.__aiter__()
            task = asyncio.create_task(it.__anext__())
            await asyncio.sleep(0)
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            await owner.aclose()
            out = await waiter_task
            assert len(out) == 4  # the promoted waiter completed

            # Let the loop settle, then assert the gates.
            await asyncio.sleep(0.2)
            assert eng._page_reserved == {}, eng._page_reserved
            assert eng._prefix.reserved_pages == 0
            assert (eng._prefix.used_blocks + eng._prefix.free_blocks
                    == eng.ecfg.prefix_pool_blocks - 1)
            return eng
        finally:
            await eng.stop()

    asyncio.run(main())


async def _collect(eng, prompt, n):
    out = []
    async for ev in eng.generate(prompt, max_new_tokens=n, stop_ids=()):
        out.append(ev.token_id)
    return out


@pytest.mark.slow
def test_conversation_cache_turn2_prefills_tail_only():
    """ISSUE 14 acceptance: a returning conversation's turn-2 request —
    full turn-1 history resent plus a new tail — matches the finished
    stream's pages and prefills ONLY the tail (measured via the prefill
    token counter), with the reuse visible in the conv_* metrics."""
    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    global_metrics.reset()
    cfg = _cfg(kv_quant="int4", mux=True, prefix_cache=True,
               conv_cache=True)

    async def main():
        eng = InferenceEngine(engine_cfg=cfg)
        await eng.start()
        try:
            p1 = list(range(1, 49))  # 48 tokens = 3 pages
            g1 = await _collect(eng, p1, 20)
            t1 = global_metrics.counter("engine_prefill_tokens_total")
            p2 = p1 + g1[:-1] + [250, 251, 252]
            await _collect(eng, p2, 4)
            t2 = global_metrics.counter(
                "engine_prefill_tokens_total") - t1
            return eng, len(p2), t2
        finally:
            await eng.stop()

    eng, p2len, t2 = asyncio.run(main())
    # Turn 1 pooled 4 pages (48 prompt + 19 generated = 67 tokens); the
    # turn-2 prefill must cover only the un-pooled tail, not the history.
    assert t2 < p2len / 2, f"turn 2 prefilled {t2} of {p2len}"
    assert eng._prefix.conv_hits >= 1
    assert eng._prefix.conv_hit_tokens >= 16
    assert global_metrics.counter("engine_conv_hits_total") >= 1
    assert global_metrics.counter("engine_conv_saved_pages_total") >= 1


@pytest.mark.slow
def test_fences_registry_and_published_info():
    """The composition-fence registry: the hero config and int4+spec record
    NOTHING (ISSUE 17: verify bursts splice whole bytes); what is fenced is
    published for /healthz via the metrics info store."""
    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    def fences(**kw):
        async def main():
            eng = InferenceEngine(engine_cfg=_cfg(**kw))
            return eng.config_fences
        return asyncio.run(main())

    hero = fences(kv_quant="int4", mux=True, prefix_cache=True,
                  conv_cache=True)
    assert hero == []
    assert global_metrics.info("config_fences") == []

    assert fences(kv_quant="int4", spec_ngram=2) == []
    # conv_cache without the pool is fenced with a reason, not silent.
    conv = fences(conv_cache=True, prefix_cache=False)
    assert [f["knob"] for f in conv] == ["conv_cache"]
    assert global_metrics.info("config_fences") == conv


def test_engine_rejects_unknown_kv_quant_and_gates_int4():
    """A cache form the engine does not know is refused at start-up, by
    name; the packed int4 cache composes with the prefix cache, chunked
    prefill (ISSUE 14: page-aligned writes) and speculation (ISSUE 17)
    as asked, with nothing fenced."""
    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    with pytest.raises(ValueError, match="kv_quant"):
        InferenceEngine(engine_cfg=_cfg(kv_quant="int2"))
    eng = InferenceEngine(engine_cfg=_cfg(
        kv_quant="int4", prefix_cache=True, prefill_chunk=16, spec_ngram=2))
    assert eng._prefix is not None
    assert eng.ecfg.prefill_chunk == 16
    assert eng.ecfg.spec_ngram == 2
    assert eng.config_fences == []


def test_int4_alignment_pass_covers_mux_defaulted_chunk():
    """The page-alignment pass runs AFTER mux picks the default segment
    width, so an odd EFFECTIVE chunk (odd min_prefill_bucket > 128, or a
    user-set odd width) is rounded up — not crashed into
    chunk_prefill_into_cache's even-width guard at serve time."""
    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    # User-set odd width under int4: rounded up to even.
    eng = InferenceEngine(engine_cfg=_cfg(kv_quant="int4", mux=True,
                                          prefill_chunk=31))
    assert eng.ecfg.prefill_chunk == 32
    # Odd page size with the pool on: fenced with a recorded reason.
    eng = InferenceEngine(engine_cfg=_cfg(kv_quant="int4", mux=True,
                                          min_prefill_bucket=15,
                                          prefix_cache=True))
    assert eng.ecfg.prefill_chunk % 2 == 0
    assert [f["knob"] for f in eng.config_fences] == ["prefix_cache"]


@pytest.mark.slow
def test_int4_prefix_pool_snapshot_roundtrip(tmp_path):
    """The packed int4 pool snapshots and restores (page-shaped leaves +
    cost/conv index fields), and a restored pool serves real matches."""
    from p2p_llm_tunnel_tpu.engine.engine import InferenceEngine

    cfg = _cfg(kv_quant="int4", mux=True, prefix_cache=True,
               conv_cache=True, prefix_cache_dir=str(tmp_path))
    prompt = list(range(1, 49))

    async def first():
        eng = InferenceEngine(engine_cfg=cfg)
        await eng.start()
        try:
            await _collect(eng, prompt, 4)
        finally:
            await eng.stop()

    asyncio.run(first())

    async def second():
        eng = InferenceEngine(engine_cfg=cfg)
        assert eng._prefix.used_blocks > 0  # snapshot restored
        hist, _ids = eng._prefix.match(prompt + [7])
        return hist

    assert asyncio.run(second()) >= 32
