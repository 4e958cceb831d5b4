"""Ragged grouped flash-prefill kernel (ISSUE 15 tentpole): planner
properties, interpret-mode oracles vs the chunked path across
(group shapes × ragged lengths × kv quants × window/softcap), the int4
packed-write alignment property against the ISSUE 14 page/segment byte
boundaries, engine-level token-stream identity ragged-on vs ragged-off,
the float64 golden-logits anchor through the ragged kernel, the
warmup-plan collapse, and the cross-lowered grouped-launch evidence
(one tpu_custom_call per layer per group — utils/hlo.py).
"""

import asyncio
import os
import sys
import types
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import ModelConfig, get_config
from p2p_llm_tunnel_tpu.models.quant import pack_int4, unpack_int4
from p2p_llm_tunnel_tpu.models.transformer import (
    _quant_kv,
    _quant_kv4,
    chunk_prefill_into_cache,
    init_kv_cache,
    init_params,
    ragged_prefill_into_cache,
)
from p2p_llm_tunnel_tpu.ops.attention import history_attention
from p2p_llm_tunnel_tpu.ops.pallas_prefill_attention import (
    plan_ragged_group,
    ragged_prefill_attention,
)
from p2p_llm_tunnel_tpu.ops.rope import apply_rope

THETA = 10000.0

#: Ragged group exercising every descriptor shape at once: history + tail,
#: zero-history, multi-block odd-length tail, exactly-one-block tail.
ENTRIES = [(0, 32, 20), (1, 0, 7), (2, 16, 33), (3, 0, 16)]


# ---------------------------------------------------------------------------
# planner properties (fast tier)
# ---------------------------------------------------------------------------

def test_plan_ragged_group_packs_blocks_and_descriptors():
    slot_of, start_of, qoff_of, qlen_of, base_of, offs = plan_ragged_group(
        ENTRIES, 16, 128, scratch_slot=9
    )
    # Rows land at block-aligned flat offsets in order, no overlap.
    assert offs == [0, 32, 48, 96]
    # Row 2 (len 33) owns blocks 3..5, base pointing at its first block.
    assert list(slot_of[3:6]) == [2, 2, 2]
    assert list(qoff_of[3:6]) == [0, 16, 32]
    assert list(base_of[3:6]) == [3, 3, 3]
    assert list(qlen_of[3:6]) == [33, 33, 33]
    # Pad blocks: scratch slot, zero length, self-based (masking to zero).
    assert slot_of[-1] == 9 and qlen_of[-1] == 0 and base_of[-1] == 7


def test_plan_rejects_misaligned_start_and_overflow():
    # The ISSUE 14 alignment contract: starts must be block multiples —
    # an odd/misaligned start would shear the cache-append block maps
    # (and, packed int4, corrupt a neighbour's nibble).
    with pytest.raises(ValueError, match="multiple of the q-block"):
        plan_ragged_group([(0, 13, 8)], 16, 64, scratch_slot=1)
    with pytest.raises(ValueError, match="overflows"):
        plan_ragged_group([(0, 0, 60), (1, 0, 60)], 16, 96, scratch_slot=2)
    with pytest.raises(ValueError, match="tail_len"):
        plan_ragged_group([(0, 0, 0)], 16, 64, scratch_slot=1)


def test_kernel_rejects_odd_block_under_int4():
    l, b, s, kh, d = 1, 2, 64, 2, 32
    kc = jnp.zeros((l, b, s // 2, kh, d), jnp.int8)
    sc = jnp.zeros((l, b, s, kh), jnp.float32)
    nqb = 2
    desc = jnp.zeros((nqb,), jnp.int32)
    with pytest.raises(ValueError, match="even block_q"):
        ragged_prefill_attention(
            jnp.zeros((2 * 9, 4, d), jnp.float32),
            jnp.zeros((2 * 9, kh, d), jnp.float32),
            jnp.zeros((2 * 9, kh, d), jnp.float32),
            kc, kc, sc, sc, desc, desc, desc, desc,
            jnp.asarray(0), block_q=9, rope_theta=THETA, kv_quant="int4",
            interpret=True,
        )


# ---------------------------------------------------------------------------
# kernel-level oracle: rope → quant → append → history_attention (slow)
# ---------------------------------------------------------------------------

def _kernel_case(kv_quant, window=None, softcap=None, seed=0, s=128, bq=16,
                 tot=128):
    """Run the ragged kernel over ENTRIES[:3] and return everything the
    oracle checks need."""
    rng = np.random.default_rng(seed)
    l, b, kh, g, d = 2, 4, 2, 2, 32
    h = kh * g
    layer = 1
    entries = ENTRIES[:3]
    slot_of, start_of, qoff_of, qlen_of, base_of, offs = plan_ragged_group(
        entries, bq, tot, scratch_slot=3
    )
    hist_k = rng.standard_normal((l, b, s, kh, d)).astype(np.float32)
    hist_v = rng.standard_normal((l, b, s, kh, d)).astype(np.float32)
    if kv_quant is None:
        kc, vc = jnp.asarray(hist_k), jnp.asarray(hist_v)
        ksc = vsc = None
    else:
        qfn = _quant_kv4 if kv_quant == "int4" else _quant_kv
        kq, ks = qfn(jnp.asarray(hist_k))
        vq, vs = qfn(jnp.asarray(hist_v))
        if kv_quant == "int4":
            kc, vc = pack_int4(kq, axis=2), pack_int4(vq, axis=2)
        else:
            kc, vc = kq, vq
        ksc, vsc = ks, vs
    q = np.zeros((tot, h, d), np.float32)
    kn = np.zeros((tot, kh, d), np.float32)
    vn = np.zeros((tot, kh, d), np.float32)
    for (slot, start, ln), off in zip(entries, offs):
        q[off:off + ln] = rng.standard_normal((ln, h, d))
        kn[off:off + ln] = rng.standard_normal((ln, kh, d))
        vn[off:off + ln] = rng.standard_normal((ln, kh, d))
    outs = ragged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
        kc, vc, ksc, vsc,
        jnp.asarray(slot_of), jnp.asarray(start_of), jnp.asarray(qoff_of),
        jnp.asarray(base_of), jnp.asarray(layer),
        block_q=bq, rope_theta=THETA, kv_quant=kv_quant,
        window=window, softcap=softcap, interpret=True,
    )
    return (entries, offs, layer, (hist_k, hist_v), (q, kn, vn),
            (kc, vc, ksc, vsc), outs)


def _oracle_row(kv_quant, layer, slot, start, ln, off, hists, news,
                window, softcap):
    """Per-row reference: rope at global positions, quantize-roundtrip
    through the cache precision, scatter, attend via history_attention —
    exactly what chunk_prefill_into_cache composes."""
    hist_k, hist_v = hists
    q, kn, vn = news
    pos = start + np.arange(ln)
    q_r = apply_rope(jnp.asarray(q[off:off + ln])[None],
                     jnp.asarray(pos)[None], THETA)
    kn_r = apply_rope(jnp.asarray(kn[off:off + ln])[None],
                      jnp.asarray(pos)[None], THETA)[0]
    vn_r = jnp.asarray(vn[off:off + ln])
    kc_l = jnp.asarray(hist_k)[layer, slot]
    vc_l = jnp.asarray(hist_v)[layer, slot]
    if kv_quant is None:
        kd = kc_l.at[pos].set(kn_r)
        vd = vc_l.at[pos].set(vn_r)
    else:
        qfn = _quant_kv4 if kv_quant == "int4" else _quant_kv
        hq_k, hs_k = qfn(kc_l)
        hq_v, hs_v = qfn(vc_l)
        nq_k, ns_k = qfn(kn_r)
        nq_v, ns_v = qfn(vn_r)
        kd = (hq_k.astype(jnp.float32) * hs_k[..., None]).at[pos].set(
            nq_k.astype(jnp.float32) * ns_k[..., None])
        vd = (hq_v.astype(jnp.float32) * hs_v[..., None]).at[pos].set(
            nq_v.astype(jnp.float32) * ns_v[..., None])
    want = history_attention(
        q_r, kd[None], vd[None], jnp.asarray([start]),
        window=window, softcap=softcap,
    )[0]
    return np.asarray(want), kn_r


@pytest.mark.parametrize("kv_quant", [None, "int8", "int4"])
def test_ragged_kernel_matches_history_attention_oracle(kv_quant):
    """Fast-tier core oracle: one interpret run covering history + tail,
    zero-history, and multi-block ragged rows in ONE grouped launch."""
    entries, offs, layer, hists, news, _caches, outs = _kernel_case(kv_quant)
    attn = np.asarray(outs[0])
    for (slot, start, ln), off in zip(entries, offs):
        want, _ = _oracle_row(kv_quant, layer, slot, start, ln, off,
                              hists, news, None, None)
        np.testing.assert_allclose(
            attn[off:off + ln], want, rtol=2e-4, atol=2e-4,
            err_msg=f"slot={slot} kv={kv_quant}",
        )


@pytest.mark.slow
@pytest.mark.parametrize("kv_quant", [None, "int8", "int4"])
@pytest.mark.parametrize("kw", [dict(window=48), dict(softcap=20.0)])
@pytest.mark.parametrize("s", [128, 512])
def test_ragged_kernel_oracle_windows_softcap_multiblock(kv_quant, kw, s):
    """s=512 exercises multi-block history with the frontier clamp; the
    window/softcap variants pin the masking/score paths."""
    entries, offs, layer, hists, news, _caches, outs = _kernel_case(
        kv_quant, s=s, seed=3, **kw
    )
    attn = np.asarray(outs[0])
    for (slot, start, ln), off in zip(entries, offs):
        want, _ = _oracle_row(
            kv_quant, layer, slot, start, ln, off, hists, news,
            kw.get("window"), kw.get("softcap"),
        )
        np.testing.assert_allclose(
            attn[off:off + ln], want, rtol=2e-4, atol=2e-4,
            err_msg=f"slot={slot} kv={kv_quant} s={s} {kw}",
        )


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_ragged_append_bytes_exact_and_page_aligned(kv_quant):
    """The int4 packed-write alignment property (ISSUE 14/15): the
    grouped append lands the EXACT bytes the chunk path's quantize +
    pack_int4 scatter would, on whole-byte page/segment boundaries —
    other slots, other layers, and each row's history region untouched.
    Bit-exact: rope feeds round(), and the kernel reproduces apply_rope's
    expression graph precisely so the nibble never flips."""
    entries, offs, layer, hists, news, caches, outs = _kernel_case(kv_quant)
    _attn, kc2, _vc2, ks2, _vs2 = outs
    kc0 = caches[0]
    np.testing.assert_array_equal(np.asarray(kc2[0]), np.asarray(kc0[0]))
    for (slot, start, ln), off in zip(entries, offs):
        _, kn_r = _oracle_row(kv_quant, layer, slot, start, ln, off,
                              hists, news, None, None)
        qfn = _quant_kv4 if kv_quant == "int4" else _quant_kv
        nq_k, ns_k = qfn(kn_r)
        vals = np.asarray(kc2)[layer, slot]
        hist0 = np.asarray(kc0)[layer, slot]
        if kv_quant == "int4":
            vals = np.asarray(unpack_int4(jnp.asarray(vals), axis=0))
            hist0 = np.asarray(unpack_int4(jnp.asarray(hist0), axis=0))
        np.testing.assert_array_equal(vals[start:start + ln],
                                      np.asarray(nq_k))
        np.testing.assert_array_equal(vals[:start], hist0[:start])
        np.testing.assert_allclose(
            np.asarray(ks2)[layer, slot, start:start + ln],
            np.asarray(ns_k), rtol=1e-6,
        )


# ---------------------------------------------------------------------------
# transformer-level parity vs chunk_prefill_into_cache (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("kv_quant", [False, "int8", "int4"])
def test_ragged_prefill_matches_chunk_prefill(kv_quant):
    """Full-model parity: identical history (written by the chunk path),
    then the SAME ragged tails through both programs — last-token logits
    agree (argmax identical), quantized cache bytes agree to at most an
    ulp-flip of round() (the two whole-layer programs fuse differently),
    and history regions stay untouched."""
    cfg = replace(get_config("tiny", vocab_size=64), flash_interpret=True)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    s = 128
    cache0 = init_kv_cache(cfg, 5, s, jnp.float32, quant=kv_quant)
    rng = np.random.default_rng(0)
    jit_chunk = jax.jit(
        chunk_prefill_into_cache,
        static_argnames=("cfg", "kv_view", "return_all_logits"),
    )
    # Shared history via the chunk path.
    hist = {0: 32, 2: 16}
    tk = np.zeros((2, 32), np.int32)
    ln = np.zeros((2,), np.int32)
    sl = np.zeros((2,), np.int32)
    for i, (slot, n) in enumerate(hist.items()):
        tk[i, :n] = rng.integers(1, 60, size=n)
        ln[i] = n
        sl[i] = slot
    _, cache = jit_chunk(
        cfg=cfg, params=params, tokens=jnp.asarray(tk),
        lengths=jnp.asarray(ln), starts=jnp.zeros((2,), jnp.int32),
        kv_cache=cache0, slots=jnp.asarray(sl), kv_view=s,
    )
    tails = {slot: rng.integers(1, 60, size=n).tolist()
             for (slot, _st, n) in ENTRIES}
    # Chunked reference: one padded-bucket call.
    tb = 48
    tk = np.zeros((4, tb), np.int32)
    ln = np.zeros((4,), np.int32)
    st = np.zeros((4,), np.int32)
    sl = np.zeros((4,), np.int32)
    for i, (slot, start, n) in enumerate(ENTRIES):
        tk[i, :n] = tails[slot]
        ln[i] = n
        st[i] = start
        sl[i] = slot
    last_c, cache_c = jit_chunk(
        cfg=cfg, params=params, tokens=jnp.asarray(tk),
        lengths=jnp.asarray(ln), starts=jnp.asarray(st),
        kv_cache=jax.tree.map(jnp.copy, cache), slots=jnp.asarray(sl),
        kv_view=s,
    )
    # Ragged path: same rows, flat-packed.
    bq, tot = 16, 112
    slot_of, start_of, qoff_of, qlen_of, base_of, offs = plan_ragged_group(
        ENTRIES, bq, tot, scratch_slot=4
    )
    flat = np.zeros((tot,), np.int32)
    samp_idx = np.zeros((4,), np.int32)
    for i, ((slot, start, n), off) in enumerate(zip(ENTRIES, offs)):
        flat[off:off + n] = tails[slot]
        samp_idx[i] = off + n - 1
    jit_ragged = jax.jit(
        ragged_prefill_into_cache,
        static_argnames=("cfg", "block_q", "return_all_logits",
                         "interpret"),
    )
    last_r, cache_r = jit_ragged(
        cfg=cfg, params=params, tokens=jnp.asarray(flat),
        slot_of=jnp.asarray(slot_of), start_of=jnp.asarray(start_of),
        qoff_of=jnp.asarray(qoff_of),
        base_of=jnp.asarray(base_of), sample_idx=jnp.asarray(samp_idx),
        kv_cache=jax.tree.map(jnp.copy, cache), block_q=bq,
    )
    np.testing.assert_allclose(np.asarray(last_r), np.asarray(last_c),
                               rtol=2e-4, atol=2e-4)
    assert (np.asarray(last_r).argmax(-1)
            == np.asarray(last_c).argmax(-1)).all()
    for slot, start, n in ENTRIES:
        for key in cache_r:
            a = np.asarray(cache_r[key])[:, slot]
            b = np.asarray(cache_c[key])[:, slot]
            h0 = np.asarray(cache[key])[:, slot]
            if key in ("k", "v") and kv_quant == "int4":
                a = np.asarray(unpack_int4(jnp.asarray(a), axis=1))
                b = np.asarray(unpack_int4(jnp.asarray(b), axis=1))
                h0 = np.asarray(unpack_int4(jnp.asarray(h0), axis=1))
            if key in ("k", "v") and kv_quant in ("int8", "int4"):
                reg_a = a[:, start:start + n].astype(np.int32)
                reg_b = b[:, start:start + n].astype(np.int32)
                # ulp-flip budget: the two programs' rope fuses
                # differently, so round() may flip on exact halves —
                # never by more than one step, never often.
                assert np.abs(reg_a - reg_b).max() <= 1
                assert np.mean(reg_a != reg_b) < 0.01
            else:
                np.testing.assert_allclose(
                    a[:, start:start + n], b[:, start:start + n],
                    rtol=1e-5, atol=1e-5,
                )
            np.testing.assert_array_equal(a[:, :start], h0[:, :start])


# ---------------------------------------------------------------------------
# engine-level token-stream identity (ISSUE 15 acceptance; slow)
# ---------------------------------------------------------------------------

async def _engine_stream(kv_quant, ragged, prompts):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    eng = InferenceEngine(
        engine_cfg=EngineConfig(
            model="tiny", num_slots=4, max_seq=256, dtype="float32",
            decode_steps=4, kv_quant=kv_quant, mux=True,
            prefix_cache=True, ragged_prefill=ragged, seed=7,
        ),
        tokenizer=tok,
    )
    assert eng.ecfg.ragged_prefill == ragged, eng.config_fences

    async def collect(p):
        out = []
        async for ev in eng.generate(p, max_new_tokens=8, stop_ids=()):
            out.append(ev.token_id)
        return out

    await eng.start()
    outs = await asyncio.gather(*(collect(p) for p in prompts))
    # A prefix-hit tail after the pool is warm: the cached-wave route.
    outs.append(await collect(prompts[0][:40] + [99, 98, 97]))
    await eng.stop()
    return outs


@pytest.mark.slow
@pytest.mark.parametrize("kv_quant", ["none", "int8", "int4"])
def test_engine_stream_byte_identical_ragged_on_vs_off(kv_quant):
    """ISSUE 15 acceptance: under mux + prefix-grouped admission, the
    ragged path's token streams are identical to the chunked path's at
    every kv_quant — shared-prefix herd, multi-segment prompt, short
    prompt, and a warm prefix-hit tail all covered (seed 7 keeps greedy
    argmax tie-free)."""
    shared = list(range(1, 81))
    prompts = [shared + [100 + i] for i in range(3)]
    prompts.append(list(range(1, 150)))  # multi-segment (149 > chunk 128)
    prompts.append([5, 4, 3])
    a = asyncio.run(_engine_stream(kv_quant, False, prompts))
    b = asyncio.run(_engine_stream(kv_quant, True, prompts))
    assert all(len(x) == 8 for x in a)
    assert a == b, f"ragged stream diverged under kv_quant={kv_quant}"


def test_engine_fences_ragged_on_misaligned_geometry():
    """A prefill_chunk that shares no power-of-2 block >= 8 with the page
    size cannot align the grouped cache-append blocks — the engine fences
    the knob OFF and records why, instead of corrupting at serve time."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    eng = InferenceEngine(
        engine_cfg=EngineConfig(
            model="tiny", num_slots=2, max_seq=128, dtype="float32",
            prefill_chunk=100, ragged_prefill=True,
        ),
        tokenizer=ByteTokenizer(),
    )
    assert eng.ecfg.ragged_prefill is False
    assert any(f["knob"] == "ragged_prefill" for f in eng.config_fences)


# ---------------------------------------------------------------------------
# warmup-plan collapse (ISSUE 15 acceptance)
# ---------------------------------------------------------------------------

def _plan(ragged):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer

    eng = InferenceEngine(
        engine_cfg=EngineConfig(
            model="tiny", num_slots=8, max_seq=512, dtype="float32",
            mux=True, prefix_cache=True, ragged_prefill=ragged,
        ),
        tokenizer=ByteTokenizer(),
    )
    return eng.warmup_plan()


def test_warmup_plan_collapses_2x_on_mux_hero_shape():
    """ISSUE 15 acceptance: on the mux hero shape (prefix-grouped
    admission, defaulted segment width, max_seq 512) the ragged config's
    warmup program count is >= 2x smaller — the whole chunk[t, view]
    family becomes one ragged[tot] program, and the decode view set stays
    identical (raggedness must not bill decode)."""
    off = _plan(False)
    on = _plan(True)
    assert [p for p in off if p[0] == "decode"] == [
        p for p in on if p[0] == "decode"
    ]
    assert sum(1 for p in off if p[0] == "chunk") >= 8
    assert [p for p in on if p[0] not in ("decode",)] == [("ragged", (1024,))]
    assert len(off) >= 2 * len(on), (off, on)


# ---------------------------------------------------------------------------
# float64 golden-logits anchor through the ragged kernel (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ragged_prefill_matches_golden_logits():
    """Teacher-forced prefill of the committed float64 anchor through the
    ragged kernel (one ragged row, full-position logits): the grouped
    rope / append / prefix+tail attention math is pinned to an
    implementation that shares no code with it."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from make_synth_hf_ckpt import fake_llama_state

    from p2p_llm_tunnel_tpu.models.checkpoint import convert_hf

    fx = np.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "golden",
        "synth_llama_logits.npz",
    ))
    vocab, dim, layers, heads, kv_heads, head_dim, ffn, seed = fx["meta"]
    cfg = ModelConfig(
        name="synth-golden", vocab_size=int(vocab), dim=int(dim),
        n_layers=int(layers), n_heads=int(heads), n_kv_heads=int(kv_heads),
        head_dim=int(head_dim), ffn_dim=int(ffn),
        rope_theta=10000.0, norm_eps=1e-5, flash_interpret=True,
    )
    shape = types.SimpleNamespace(
        vocab_size=int(vocab), dim=int(dim), n_layers=int(layers),
        n_heads=int(heads), n_kv_heads=int(kv_heads),
        head_dim=int(head_dim), ffn_dim=int(ffn),
    )
    params = convert_hf(
        "llama", fake_llama_state(shape, int(seed)), cfg, jnp.float32
    )
    tokens = fx["tokens"]
    want = fx["logits"]
    n = len(tokens)
    bq = 16
    tot = -(-n // bq) * bq
    cache = init_kv_cache(cfg, 2, max(tot, 64), jnp.float32)
    slot_of, start_of, qoff_of, qlen_of, base_of, offs = plan_ragged_group(
        [(0, 0, n)], bq, tot, scratch_slot=1
    )
    flat = np.zeros((tot,), np.int32)
    flat[:n] = tokens
    logits, _cache = jax.jit(
        ragged_prefill_into_cache,
        static_argnames=("cfg", "block_q", "return_all_logits",
                         "interpret"),
    )(
        cfg=cfg, params=params, tokens=jnp.asarray(flat),
        slot_of=jnp.asarray(slot_of), start_of=jnp.asarray(start_of),
        qoff_of=jnp.asarray(qoff_of),
        base_of=jnp.asarray(base_of),
        sample_idx=jnp.zeros((1,), jnp.int32),
        kv_cache=cache, block_q=bq, return_all_logits=True,
    )
    got = np.asarray(logits, np.float32)[:n]
    # fp32 anchor family (test_golden_logits: 1e-5/1e-4) with headroom
    # for the online-softmax accumulation order.
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)
    assert (got.argmax(-1) == want.argmax(-1)).all()


# ---------------------------------------------------------------------------
# off-chip grouped-launch evidence (utils/hlo.py; slow)
# ---------------------------------------------------------------------------

#: TPU-tileable config: head_dim 128 so the REAL (non-interpret) kernel
#: cross-lowers for the TPU platform from this CPU host.
TILE_CFG = ModelConfig(
    name="tiny128", vocab_size=256, dim=128, n_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=128, ffn_dim=256,
)


@pytest.mark.slow
def test_ragged_group_cross_lowers_to_one_pallas_call_per_layer():
    """ISSUE 15 acceptance: the TPU-lowered ragged program's layer body
    carries exactly ONE tpu_custom_call for the whole GROUP — where the
    bucketed path launches one chunk program per (tail, view) pair, the
    grouped kernel is a single launch per layer regardless of how many
    rows ride it (the PR 4 launch-arithmetic technique on prefill)."""
    from p2p_llm_tunnel_tpu.utils.hlo import decode_launch_report

    params = init_params(TILE_CFG, jax.random.PRNGKey(0), jnp.float32)
    cache = init_kv_cache(TILE_CFG, 5, 256, jnp.float32)
    bq, tot = 16, 160
    entries = [(0, 32, 20), (1, 0, 33), (2, 16, 16), (3, 0, 40)]
    slot_of, start_of, qoff_of, qlen_of, base_of, _ = plan_ragged_group(
        entries, bq, tot, scratch_slot=4
    )
    jitted = jax.jit(
        ragged_prefill_into_cache,
        static_argnames=("cfg", "block_q", "return_all_logits",
                         "interpret"),
    )
    report = decode_launch_report(
        jitted,
        cfg=TILE_CFG, params=params, tokens=jnp.zeros((tot,), jnp.int32),
        slot_of=jnp.asarray(slot_of), start_of=jnp.asarray(start_of),
        qoff_of=jnp.asarray(qoff_of),
        base_of=jnp.asarray(base_of),
        sample_idx=jnp.zeros((4,), jnp.int32),
        kv_cache=cache, block_q=bq, interpret=False,
    )
    assert report is not None, "TPU cross-lowering failed"
    assert report["layer_body_pallas"] == 1, (
        "the grouped prefill layer is not ONE pallas call"
    )
