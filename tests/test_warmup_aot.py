"""Parallel AOT warmup (engine._warm_aot_parallel) equivalence tests.

The warmup's phase A AOT-compiles every warm program from concurrent
threads via ``jit.lower(...).compile()`` and relies on the persistent
compilation cache to hand those executables back to the serial execute
pass (and to live dispatch).  That only works if the AOT-lowered programs
hash IDENTICALLY to the ones live dispatch builds — any aval drift
(shape/dtype/static-arg mismatch in _decode_warm_args/_chunk_warm_args)
silently doubles compile work before the first request is served.

The hash-identity proof: warm up engine A with the AOT phase ON, snapshot
the persistent-cache file set, then warm up an identically-configured
engine B with the AOT phase OFF — B's serial compiles must ALL hit the
persistent cache, i.e. add zero new files.
"""

import asyncio
import os

import pytest

import jax

from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

pytestmark = pytest.mark.slow

ECFG = dict(
    model="tiny", num_slots=4, max_seq=256, dtype="float32", seed=0,
    decode_steps=4, decode_steps_eager=2, prefill_rows=2,
    prefix_cache=True,
)


async def _collect(engine, prompt, max_new=8):
    out = []
    async for ev in engine.generate(prompt, max_new_tokens=max_new,
                                    stop_ids=()):
        out.append(ev.token_id)
    return out


def _cache_files(path):
    return {f for f in os.listdir(path)}


@pytest.fixture()
def persistent_cache(tmp_path, monkeypatch):
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # The cache object binds its directory at first use: without a reset a
    # SECOND test in the same process keeps writing to the first test's
    # (already-asserted) tmp dir and its own stays empty.
    from jax._src import compilation_cache

    compilation_cache.reset_cache()
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
    compilation_cache.reset_cache()


PROMPT = ByteTokenizer().encode("hello aot")


def test_aot_programs_hash_identical_to_dispatch(persistent_cache,
                                                 monkeypatch):
    monkeypatch.setenv("TUNNEL_WARMUP_VIEW_CAP", "100")
    # Covers the prefill-hint path: the live generate below prefills
    # len(PROMPT) tokens, so its bucket must be AOT-warmed too.
    monkeypatch.setenv("TUNNEL_WARMUP_PREFILL_TOKENS", str(len(PROMPT)))

    marks = {}

    async def run(par):
        monkeypatch.setenv("TUNNEL_WARMUP_PAR", par)
        eng = InferenceEngine(
            engine_cfg=EngineConfig(**ECFG), tokenizer=ByteTokenizer()
        )
        await eng.start()
        await eng.warmup()
        marks[f"warm{par}"] = _cache_files(persistent_cache)
        toks = await _collect(eng, PROMPT)
        await eng.stop()
        return toks

    toks_a = asyncio.run(run("2"))
    files_after_a = _cache_files(persistent_cache)
    assert marks["warm2"], "AOT warmup wrote nothing to the cache"
    # Live dispatch (prefill wave + decode bursts + prefix insert) must
    # hit only pre-warmed programs — any new cache file means a warm-args
    # builder drifted from its live call and a fresh compile landed on
    # the serving path.
    live_new = files_after_a - marks["warm2"]
    assert not live_new, (
        f"live dispatch compiled {len(live_new)} programs warmup missed"
    )

    toks_b = asyncio.run(run("0"))
    new = _cache_files(persistent_cache) - files_after_a
    assert not new, (
        f"serial warmup compiled {len(new)} programs the AOT phase "
        f"missed or mis-hashed"
    )
    assert toks_a == toks_b


def test_warmup_view_cap():
    """Cap arithmetic mirrors _kv_view_bucket's pipelining pad."""
    eng = InferenceEngine(
        engine_cfg=EngineConfig(**{**ECFG, "prefix_cache": False}),
        tokenizer=ByteTokenizer(),
    )
    # max_seq 256 -> full bucket list [128, 256].
    assert eng._view_buckets() == [128, 256]
    # No cap: everything.
    assert eng._warmup_views() == [128, 256]
    # cap 100 + 2*4+1 pad = 109 -> bucket 128 only.
    os.environ["TUNNEL_WARMUP_VIEW_CAP"] = "100"
    try:
        assert eng._warmup_views() == [128]
        # cap 140 -> need 149 -> bucket 256: keeps both.
        os.environ["TUNNEL_WARMUP_VIEW_CAP"] = "140"
        assert eng._warmup_views() == [128, 256]
    finally:
        del os.environ["TUNNEL_WARMUP_VIEW_CAP"]


def test_ragged_mux_herd_hits_zero_cold_compiles(persistent_cache,
                                                 monkeypatch):
    """ISSUE 15 acceptance: under the RAGGED prefill path the warmup
    grid is the collapsed one — decode view×steps plus ONE ragged
    flat-bucket program (warmup_plan: the whole chunk[t, view] family
    gone) — and it is still COMPLETE: a multiplexed shared-prefix herd
    with short-tail, multi-segment, prefix-hit, and mid-decode
    admissions adds ZERO fresh compiles, and the engine's cold-compile
    counter stays at zero."""
    monkeypatch.setenv("TUNNEL_WARMUP_VIEW_CAP", "100")
    monkeypatch.setenv("TUNNEL_WARMUP_PAR", "2")
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    tok = ByteTokenizer()

    async def run():
        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                **{**ECFG, "mux": True, "ragged_prefill": True}
            ),
            tokenizer=tok,
        )
        assert eng.ecfg.ragged_prefill, eng.config_fences
        assert [k for k, _s in eng.warmup_plan() if k == "chunk"] == []
        await eng.start()
        await eng.warmup()
        warmed = _cache_files(persistent_cache)
        cold0 = global_metrics.counter("engine_cold_compiles_total")
        shared = list(range(1, 81))  # 5 pooled blocks of 16
        herd = [shared + [100 + i] for i in range(3)]  # short tails
        herd.append(list(range(1, 91)))  # multi-segment (90 > chunk 64)
        outs = await asyncio.gather(*(_collect(eng, p) for p in herd))
        # Mid-decode admission + a warm prefix-hit tail.
        outs.append(await _collect(eng, shared + [200]))
        cold = global_metrics.counter("engine_cold_compiles_total") - cold0
        await eng.stop()
        return outs, warmed, cold

    outs, warmed, cold = asyncio.run(run())
    assert warmed, "warmup wrote nothing to the persistent cache"
    assert all(len(o) == 8 for o in outs)
    assert cold == 0, f"{cold} mid-serve cold compiles under ragged mux"
    live_new = _cache_files(persistent_cache) - warmed
    assert not live_new, (
        f"ragged multiplexed herd compiled {len(live_new)} programs "
        f"warmup missed"
    )


def test_mux_herd_hits_zero_cold_compiles(persistent_cache, monkeypatch):
    """ISSUE 5 warmup coverage: under the MULTIPLEXED serving loop, every
    program the scheduler can reach — both burst sizes x every view
    bucket, the chunk program at the (defaulted) segment width x every
    view a padded tail can bucket to (the cap + prefill_chunk term of
    _warmup_views), the prefix copy ops, and every row rung a dispatch
    pads to (chunk_row_ladder: the budget controller picks how many rows a
    dispatch carries, the ladder which warmed shape holds them; the herd
    over all rungs of prefill_rows=8 is tests/test_prefill_row_ladder.py)
    — is compiled by warmup(); a multiplexed
    shared-prefix herd with multi-segment, short-tail, and mid-decode
    admissions then adds ZERO fresh compiles."""
    monkeypatch.setenv("TUNNEL_WARMUP_VIEW_CAP", "100")
    monkeypatch.setenv("TUNNEL_WARMUP_PAR", "2")
    tok = ByteTokenizer()

    async def run():
        eng = InferenceEngine(
            engine_cfg=EngineConfig(**{**ECFG, "mux": True}),
            tokenizer=tok,
        )
        await eng.start()
        await eng.warmup()
        warmed = _cache_files(persistent_cache)
        shared = list(range(1, 81))  # 5 pooled blocks of 16
        herd = [shared + [100 + i] for i in range(3)]  # short tails
        herd.append(list(range(1, 91)))  # multi-segment (90 > chunk 64)
        outs = await asyncio.gather(*(_collect(eng, p) for p in herd))
        # Mid-decode admission: the budget controller's interleave path.
        outs.append(await _collect(eng, shared + [200]))
        await eng.stop()
        return outs, warmed

    outs, warmed = asyncio.run(run())
    assert warmed, "warmup wrote nothing to the persistent cache"
    assert all(len(o) == 8 for o in outs)
    live_new = _cache_files(persistent_cache) - warmed
    assert not live_new, (
        f"multiplexed herd compiled {len(live_new)} programs warmup missed"
    )


def test_mux_spec_herd_hits_zero_cold_compiles(persistent_cache,
                                               monkeypatch):
    """ISSUE 17 acceptance: warmup_plan() enumerates the fused spec-verify
    program per (view, K) — the whole adaptive power-of-two K ladder, not
    just the configured burst width — so a multiplexed spec-on herd of
    repetitive prompts (the ngram proposer fires constantly, so verify
    bursts really dispatch) serves with engine_cold_compiles_total == 0
    and adds no fresh persistent-cache entries."""
    monkeypatch.setenv("TUNNEL_WARMUP_VIEW_CAP", "100")
    monkeypatch.setenv("TUNNEL_WARMUP_PAR", "2")
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    tok = ByteTokenizer()
    rep = list(b"the cat sat on the mat. the cat sat on the mat. the cat")

    async def run():
        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                **{**ECFG, "mux": True, "spec_ngram": 3, "spec_k": 2,
                   "spec_k_max": 4}
            ),
            tokenizer=tok,
        )
        spec_shapes = [s for kind, s in eng.warmup_plan() if kind == "spec"]
        assert spec_shapes, "warmup plan lost the spec-verify programs"
        # Every view bucket appears with every K bucket of the ladder
        # (adaptive mode: powers of two up to the cap, down to K=1).
        assert {k for _v, k in spec_shapes} == {1, 2, 4}
        await eng.start()
        await eng.warmup()
        warmed = _cache_files(persistent_cache)
        cold0 = global_metrics.counter("engine_cold_compiles_total")
        spec0 = global_metrics.counter("engine_spec_proposed_tokens_total")
        herd = [rep + [100 + i] for i in range(3)]
        outs = await asyncio.gather(
            *(_collect(eng, p, max_new=24) for p in herd))
        # Mid-decode admission while verify bursts are in flight.
        outs.append(await _collect(eng, rep + [200], max_new=24))
        cold = global_metrics.counter("engine_cold_compiles_total") - cold0
        fired = (global_metrics.counter("engine_spec_proposed_tokens_total")
                 - spec0)
        await eng.stop()
        return outs, warmed, cold, fired

    outs, warmed, cold, fired = asyncio.run(run())
    assert warmed, "warmup wrote nothing to the persistent cache"
    assert all(len(o) == 24 for o in outs)
    assert fired > 0, "the spec-on herd never dispatched a verify burst"
    assert cold == 0, f"{cold} mid-serve cold compiles under mux+spec"
    live_new = _cache_files(persistent_cache) - warmed
    assert not live_new, (
        f"mux+spec herd compiled {len(live_new)} programs warmup missed"
    )


def test_rows_kernel_herd_warms_one_decode_program_a_step_count(
        persistent_cache, monkeypatch):
    """ISSUE 33: where decode's attention is the rows kernel (here in
    interpret mode) the plan holds ONE decode entry a step count, at
    max_seq — the view axis is gone — and is still complete: a multiplexed
    herd whose rows decode across the old ladder's edge at 128 asks
    _dispatch_decode for no program outside it (no fresh compile, no cache
    file, ``engine_cold_compiles_total`` unmoved).  Chunk programs keep
    their views, and the einsum path's plan is what it was."""
    from dataclasses import replace

    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    monkeypatch.setenv("TUNNEL_WARMUP_PAR", "2")
    tok = ByteTokenizer()
    mcfg = replace(
        get_config("tiny", vocab_size=tok.vocab_size), flash_interpret=True
    )
    cfg = {**ECFG, "mux": True}

    def decode_entries(eng):
        return [s for kind, s in eng.warmup_plan() if kind == "decode"]

    plain = InferenceEngine(engine_cfg=EngineConfig(**cfg), tokenizer=tok)
    assert decode_entries(plain) == [(128, 2), (128, 4), (256, 2), (256, 4)]

    async def run():
        eng = InferenceEngine(
            model_cfg=mcfg, engine_cfg=EngineConfig(**cfg), tokenizer=tok)
        assert decode_entries(eng) == [(256, 2), (256, 4)]
        assert [e for e in eng.warmup_plan() if e[0] != "decode"] == \
            [e for e in plain.warmup_plan() if e[0] != "decode"]
        await eng.start()
        await eng.warmup()
        warmed = _cache_files(persistent_cache)
        cold0 = global_metrics.counter("engine_cold_compiles_total")
        herd = [list(range(1, 101)), list(range(1, 91)), [5, 6, 7]]
        outs = await asyncio.gather(
            *(_collect(eng, p, max_new=48) for p in herd))
        outs.append(await _collect(eng, list(range(1, 121)), max_new=24))
        cold = global_metrics.counter("engine_cold_compiles_total") - cold0
        ready = {k for k in eng._programs_ready if k.startswith("decode")}
        branches = eng.attention_branches["decode"]
        await eng.stop()
        return outs, warmed, cold, ready, branches

    outs, warmed, cold, ready, branches = asyncio.run(run())
    assert warmed, "warmup wrote nothing to the persistent cache"
    assert [len(o) for o in outs] == [48, 48, 48, 24]
    assert branches == ["pallas-rows"]
    assert ready == {"decode[256,2]", "decode[256,4]"}
    assert cold == 0, f"{cold} mid-serve cold compiles on the kernel path"
    live_new = _cache_files(persistent_cache) - warmed
    assert not live_new, (
        f"the kernel-path herd compiled {len(live_new)} programs warmup "
        f"missed"
    )
