"""Prompt-lookup speculative decoding: exact-greedy acceptance.

The contract is absolute: speculation is a pure latency optimization —
token output must be IDENTICAL to plain decode (greedy acceptance only
admits tokens greedy decoding would have produced), for greedy rows,
stochastic rows (which accept nothing and sample their own stream), stop
sequences, and token limits alike.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_step,
    init_kv_cache,
    init_params,
    prefill_into_cache,
    spec_verify_into_cache,
)
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

# The full acceptance suite is compile-heavy (JAX jit of engine/model
# programs) and stays slow-tier (VERDICT r4 item 8) — but the core
# greedy-equivalence contract runs in tier-1 (ISSUE 17 satellite):
# test_greedy_spec_equivalence_tier1 below is deliberately UNMARKED so a
# spec regression fails `make test`, not only the slow runs.
slow = pytest.mark.slow


def _cfg(**kw):
    base = dict(model="tiny", num_slots=4, max_seq=128, dtype="float32",
                seed=0)
    base.update(kw)
    return EngineConfig(**base)


async def _collect(engine, prompt, max_new=24, **kw):
    out = []
    async for ev in engine.generate(prompt, max_new_tokens=max_new,
                                    stop_ids=(), **kw):
        out.append(ev.token_id)
    return out


#: Highly repetitive prompt: the ngram proposer should fire constantly.
REP = list(b"the cat sat on the mat. the cat sat on the mat. the cat")


def test_greedy_spec_equivalence_tier1():
    """Tier-1 (ISSUE 17 satellite): greedy token streams are byte-identical
    spec-on vs spec-off at EVERY kv_quant mode — including int4, which was
    fenced off speculation before verify bursts spliced whole bytes.  The
    horizon is short (the verify path fires on every proposal whether or
    not anything is accepted), so this runs in `make test` and catches a
    spec regression without waiting for the slow tier."""
    async def run(spec, kv):
        engine = InferenceEngine(
            engine_cfg=_cfg(spec_ngram=3 if spec else 0, spec_k=4,
                            kv_quant=kv, max_seq=256))
        await engine.start()
        try:
            global_metrics.reset()
            out = await _collect(engine, REP, max_new=32)
            proposed = global_metrics.counter(
                "engine_spec_proposed_tokens_total")
            return out, proposed
        finally:
            await engine.stop()

    for kv in ("none", "int8", "int4"):
        plain, _ = asyncio.run(run(False, kv))
        spec, proposed = asyncio.run(run(True, kv))
        assert spec == plain, f"speculation changed greedy output (kv={kv})"
        assert proposed > 0, f"verify path never fired (kv={kv})"
    assert global_metrics.gauge("engine_spec_hist_entries") == 0


@pytest.mark.parametrize("kv_quant", [False, "int8", "int4"])
def test_spec_verify_matches_sequential_decode_steps(kv_quant):
    """The whole-model contract behind greedy spec/plain equivalence:
    one spec_verify_into_cache call returns the same logits AND leaves
    bitwise-identical cache planes as T sequential decode_steps.  Row 0
    starts at an ODD position — the unaligned-int4 splice path must still
    land whole-byte writes."""
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    rng = np.random.RandomState(0)
    b, s, t = 3, 256, 4
    lens = [7, 12, 250]

    cache = init_kv_cache(cfg, b, s, jnp.float32, quant=kv_quant)
    toks = jnp.zeros((b, s), jnp.int32)
    for i, n in enumerate(lens):
        toks = toks.at[i, :n].set(
            jnp.asarray(rng.randint(1, 200, size=n), jnp.int32))
    _, cache = prefill_into_cache(
        cfg, params, toks, jnp.array(lens), cache, jnp.arange(b))
    positions = jnp.array(lens, jnp.int32)
    burst = jnp.asarray(rng.randint(1, 200, size=(b, t)), jnp.int32)

    sc = cache
    seq_logits = []
    for i in range(t):
        lg, sc = decode_step(cfg, params, sc, burst[:, i],
                             positions + i, kv_view=s)
        seq_logits.append(lg)
    seq_logits = jnp.stack(seq_logits, axis=1)

    logits, oc = spec_verify_into_cache(
        cfg, params, burst, positions, cache, kv_view=s)

    l_err = np.abs(np.asarray(logits) - np.asarray(seq_logits)).max()
    assert l_err < 2e-3, l_err
    assert np.array_equal(np.argmax(np.asarray(logits), -1),
                          np.argmax(np.asarray(seq_logits), -1))
    for key in ("k", "v"):
        assert np.array_equal(np.asarray(oc[key]), np.asarray(sc[key])), key
    for key in oc:
        np.testing.assert_allclose(np.asarray(oc[key]), np.asarray(sc[key]),
                                   atol=2e-5)


def test_spec_composes_with_hero_config_no_fences():
    """ISSUE 17 acceptance: spec_ngram under int4 weights + int4 KV +
    mux + the prefix cache leaves the config_fences registry EMPTY —
    the last composition fence is gone.  Construction-time check: fences
    are registered at engine init."""
    engine = InferenceEngine(engine_cfg=_cfg(
        spec_ngram=3, spec_k=4, spec_k_max=8, quant="int4",
        kv_quant="int4", mux=True, prefix_cache=True, max_seq=256))
    assert engine.config_fences == [], engine.config_fences
    assert engine.ecfg.spec_ngram == 3
    # The warmup plan carries the spec-verify ladder for the combo.
    assert [s for k, s in engine.warmup_plan() if k == "spec"]


@slow
def test_greedy_equivalence_and_acceptance():
    # Acceptance needs the GREEDY STREAM (not just the prompt) to repeat
    # its own n-grams: the random tiny model's trajectory settles into a
    # cycle only after ~3 dozen tokens (the r2-r8 numerics work — int4,
    # fused decode, mux — shifted where the cycle starts, which is what
    # silently broke this test at the old 24-token horizon).  96 tokens
    # reaches the cycle with margin while equivalence still binds every
    # token.
    async def run(spec):
        engine = InferenceEngine(
            engine_cfg=_cfg(spec_ngram=3 if spec else 0, spec_k=4,
                            max_seq=256))
        await engine.start()
        try:
            global_metrics.reset()
            out = await _collect(engine, REP, max_new=96)
            accepted = global_metrics.counter(
                "engine_spec_accepted_tokens_total")
            return out, accepted
        finally:
            await engine.stop()

    plain, _ = asyncio.run(run(False))
    spec, accepted = asyncio.run(run(True))
    assert spec == plain, "speculation changed greedy output"
    assert accepted > 0, "repetitive stream never accepted a proposal"


@slow
def test_stochastic_rows_identical_under_spec():
    """Seeded stochastic requests accept nothing — their samples must be
    bit-identical with and without speculation in the engine."""
    async def run(spec):
        engine = InferenceEngine(
            engine_cfg=_cfg(spec_ngram=3 if spec else 0))
        await engine.start()
        try:
            return await _collect(engine, REP, temperature=0.8, seed=9)
        finally:
            await engine.stop()

    assert asyncio.run(run(True)) == asyncio.run(run(False))


@slow
def test_mixed_batch_and_stops_under_spec():
    """Concurrent greedy + stochastic + string-stop requests under spec:
    every stream equals its plain-engine counterpart."""
    async def run(spec):
        engine = InferenceEngine(
            engine_cfg=_cfg(spec_ngram=3 if spec else 0))
        await engine.start()
        try:
            outs = await asyncio.gather(
                _collect(engine, REP),
                _collect(engine, REP, temperature=1.1, seed=4),
                _collect(engine, list(b"xyxyxyxyxyxy"), max_new=10),
                _collect(engine, REP, max_new=3),
            )
            return outs
        finally:
            await engine.stop()

    assert asyncio.run(run(True)) == asyncio.run(run(False))


@slow
def test_spec_respects_stop_ids_and_logprobs_fallback():
    async def run():
        engine = InferenceEngine(engine_cfg=_cfg(spec_ngram=3))
        await engine.start()
        try:
            # stop token mid-acceptance: surplus accepted tokens dropped.
            plain = InferenceEngine(engine_cfg=_cfg())
            await plain.start()
            a = []
            async for ev in engine.generate(REP, max_new_tokens=20):
                a.append((ev.token_id, ev.finish_reason))
            b = []
            async for ev in plain.generate(REP, max_new_tokens=20):
                b.append((ev.token_id, ev.finish_reason))
            assert a == b
            # a logprobs request sends the batch down the plain path and
            # still gets its logprobs.
            evs = []
            async for ev in engine.generate(REP, max_new_tokens=4,
                                            stop_ids=(), logprobs=2):
                evs.append(ev)
            assert all(ev.logprob is not None for ev in evs)
            await plain.stop()
        finally:
            await engine.stop()

    asyncio.run(run())
