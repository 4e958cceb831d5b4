"""Packed-int4 weight quantization: round-trip invariants, matmul
equivalence vs the explicit-dequant reference, kernel composition, and
decode token-identity (ISSUE 2 tentpole).

Deliberately NOT marked slow: tiny shapes only, so the int4 invariants run
in every `make test-fast` iteration (the engine-level e2e lives with the
other compile-heavy quant tests in test_quant.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models.config import ModelConfig, get_config
from p2p_llm_tunnel_tpu.models.quant import (
    INT4_PACK_TOKENS,
    QTensor4,
    _dequant4,
    _quantize4,
    embed_lookup,
    head_matmul,
    mm,
    pack_byte,
    pack_int4,
    page_alignment_violations,
    quantize_params_int4,
    unpack_int4,
    unpack_seq,
)
from p2p_llm_tunnel_tpu.models.transformer import init_params, prefill


def test_pack_unpack_bit_exact():
    """Every nibble value in [-8, 7] survives pack→unpack on every axis."""
    rng = np.random.default_rng(0)
    v = rng.integers(-8, 8, (6, 10, 4)).astype(np.int8)
    for axis in (0, 1, 2, -1, -2, -3):
        if v.shape[axis] % 2:
            continue
        packed = pack_int4(jnp.asarray(v), axis=axis)
        assert packed.dtype == jnp.int8
        assert packed.shape[axis] == v.shape[axis] // 2
        out = np.asarray(unpack_int4(packed, axis=axis))
        np.testing.assert_array_equal(out, v)
    # The full nibble range, explicitly.
    edge = np.arange(-8, 8, dtype=np.int8)
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(pack_int4(jnp.asarray(edge)))), edge
    )


@pytest.mark.parametrize("k", [33, 64, 128, 130, 256])
def test_quantize4_roundtrip_error_bounded(k):
    """Dequant error per group is bounded by scale/2 = absmax/14, across
    odd contracted dims (33), sub-group dims (64), exact fits (128/256),
    and group-boundary crossings (130)."""
    rng = np.random.default_rng(k)
    w = rng.standard_normal((k, 16)).astype(np.float32)
    qt = _quantize4(jnp.asarray(w), axis=-2, group_size=128)
    assert isinstance(qt, QTensor4) and qt.q.dtype == jnp.int8
    deq = np.asarray(_dequant4(qt, jnp.float32))
    assert deq.shape == (k, 16)  # logical shape restored, pad sliced off
    assert np.abs(deq - w).max() <= np.abs(w).max() / 7 + 1e-6


@pytest.mark.parametrize("k,group", [(33, 128), (64, 128), (130, 128),
                                     (256, 128), (96, 32)])
def test_mm_matches_explicit_dequant(k, group):
    """The fused mm path must equal x @ dequant(w) exactly — the fusion
    may never change the math, only where the bytes are read."""
    rng = np.random.default_rng(k + group)
    w = rng.standard_normal((k, 24)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((4, k)).astype(np.float32))
    qt = _quantize4(jnp.asarray(w), axis=-2, group_size=group)
    got = np.asarray(jax.jit(mm)(x, qt))
    want = np.asarray(x) @ np.asarray(_dequant4(qt, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_embed_lookup_and_head_matmul_match_dequant():
    rng = np.random.default_rng(7)
    emb = rng.standard_normal((50, 130)).astype(np.float32)
    qe = _quantize4(jnp.asarray(emb), axis=-1, group_size=64)
    deq = np.asarray(_dequant4(qe, jnp.float32))
    toks = jnp.asarray(rng.integers(0, 50, (2, 7)))
    rows = np.asarray(embed_lookup(qe, toks, jnp.float32))
    np.testing.assert_allclose(rows, deq[np.asarray(toks)], rtol=1e-5,
                               atol=1e-6)
    x = jnp.asarray(rng.standard_normal((3, 130)).astype(np.float32))
    logits = np.asarray(head_matmul(x, qe))
    np.testing.assert_allclose(logits, np.asarray(x) @ deq.T, rtol=1e-4,
                               atol=1e-5)


def _dequant_tree(qparams):
    """QTensor4 tree -> plain bf16 tree: the unfused reference weights.

    bf16, not f32: the quantized serving path runs bf16 activations (the
    embed gather casts int->bfloat16, same as int8), and mm dequantizes
    into x.dtype — so the bit-identical reference is the bf16 dequant."""
    return jax.tree.map(
        lambda leaf: _dequant4(leaf, jnp.bfloat16)
        if isinstance(leaf, QTensor4) else leaf,
        qparams,
        is_leaf=lambda leaf: isinstance(leaf, QTensor4),
    )


def test_int4_prefill_tracks_fp32():
    """Full tiny forward through scanned QTensor4 blocks (the negative-axis
    aux must survive lax.scan's layer slicing) stays close to fp32."""
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params_int4(params, group_size=32)
    tokens = jnp.arange(24)[None, :] % cfg.vocab_size
    valid = jnp.ones_like(tokens, bool)
    ref, _, _ = jax.jit(lambda p: prefill(cfg, p, tokens, valid))(params)
    got, _, _ = jax.jit(lambda p: prefill(cfg, p, tokens, valid))(qparams)
    ref, got = np.asarray(ref), np.asarray(got)
    # Random tiny weights are int4's WORST case (no structure for the
    # group scales to exploit; bf16 activations compound): measured ~33%
    # mean drift.  The numerics anchor is tests/test_golden_logits.py;
    # here we bound gross divergence and require the distributions to
    # stay strongly aligned — a conventions bug (wrong axis, wrong scale
    # grouping) decorrelates them entirely.
    denom = np.abs(ref).mean() + 1e-6
    assert np.abs(ref - got).mean() / denom < 0.6
    r = ref.reshape(-1, ref.shape[-1])
    g = got.reshape(-1, got.shape[-1])
    cos = (r * g).sum(-1) / (
        np.linalg.norm(r, axis=-1) * np.linalg.norm(g, axis=-1) + 1e-9
    )
    assert cos.min() > 0.75, cos.min()
    assert cos.mean() > 0.9, cos.mean()


def test_int4_decode_token_identical_to_dequant_reference():
    """ISSUE 2 acceptance: greedy decode with int4 weights must emit
    EXACTLY the tokens of the same int4 weights run through the unfused
    reference path (explicit dequant to plain fp32 arrays) — the packing
    is a storage format, not a numerics change."""
    from p2p_llm_tunnel_tpu.models.transformer import (
        decode_step, init_kv_cache, prefill_into_cache,
    )

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    qparams = quantize_params_int4(params, group_size=32)
    ref_params = _dequant_tree(qparams)
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]])
    plen = prompt.shape[1]

    def run(p):
        cache = init_kv_cache(cfg, 2, 64, jnp.float32)
        last, cache = prefill_into_cache(
            cfg, p, prompt, jnp.array([plen]), cache, jnp.array([0])
        )
        toks = [int(np.asarray(last).argmax(-1)[0])]
        for i in range(12):
            step_tok = jnp.array([toks[-1], 0], jnp.int32)
            step_pos = jnp.array([plen + i, 0], jnp.int32)
            logits, cache = decode_step(cfg, p, cache, step_tok, step_pos)
            toks.append(int(np.asarray(logits).argmax(-1)[0]))
        return toks

    assert run(qparams) == run(ref_params)


@pytest.mark.parametrize("kv,page,chunk,named", [
    (None, 15, 31, []),
    ("int8", 15, 31, []),
    ("int4", 16, 32, []),
    ("int4", 15, 32, ["pool page size 15"]),
    ("int4", 16, 31, ["chunk segment width 31"]),
    ("int4", 15, 31, ["pool page size 15", "chunk segment width 31"]),
    ("int4", 16, 0, []),      # no chunked prefill: nothing to align
])
def test_only_whole_bytes_of_a_packed_cache_are_written_in_bulk(
        kv, page, chunk, named):
    """The ISSUE 14 rule the engine's start-up fences are made from: under
    the packed int4 cache, and there alone, a pool page and a chunk segment
    hold whole bytes (``INT4_PACK_TOKENS`` tokens each)."""
    got = page_alignment_violations(kv, page, chunk)
    assert len(got) == len(named)
    for why, what in zip(got, named):
        assert what in why and f"{INT4_PACK_TOKENS} tokens/byte" in why


@pytest.mark.parametrize("rows", [16, 2, 6])
def test_the_kernels_nibble_forms_are_the_formats_own(rows):
    """``unpack_seq`` / ``pack_byte`` (int32 shifts, for Mosaic) against
    ``unpack_int4`` / ``pack_int4`` (the int8 shifts that define the
    format) on a random plane, every byte value possible: a token at an
    even position is the low nibble, the next one the high nibble."""
    rng = np.random.default_rng(rows)
    plane = jnp.asarray(
        rng.integers(-128, 128, (rows // 2, 3, 8)).astype(np.int8))
    vals = unpack_seq(plane)
    assert vals.dtype == jnp.int32 and vals.shape == (rows, 3, 8)
    np.testing.assert_array_equal(
        np.asarray(vals), np.asarray(unpack_int4(plane, axis=0)))
    even, odd = vals[0::2], vals[1::2]
    back = pack_byte(even, odd)
    assert back.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(back), np.asarray(plane))
    np.testing.assert_array_equal(
        np.asarray(back), np.asarray(pack_int4(vals, axis=0)))


def test_int4_params_shard_over_tp_mesh(cpu_devices):
    """QTensor4 leaves get rank-congruent specs (scale takes the weight
    spec verbatim): int4 params place onto a tp mesh and the sharded
    forward matches the single-device one."""
    from p2p_llm_tunnel_tpu.parallel import make_mesh
    from p2p_llm_tunnel_tpu.parallel.sharding import shard_params

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    qparams = quantize_params_int4(params, group_size=32)
    tokens = jnp.arange(16)[None, :] % cfg.vocab_size
    valid = jnp.ones_like(tokens, bool)
    want, _, _ = jax.jit(lambda p: prefill(cfg, p, tokens, valid))(qparams)
    mesh = make_mesh(tp=2, dp=1)
    sharded = shard_params(qparams, cfg, mesh)
    got, _, _ = jax.jit(
        lambda p: prefill(cfg, p, tokens, valid, mesh=mesh)
    )(sharded)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    # bf16 activations + GSPMD's different reduction order: bound the
    # absolute drift (rtol is meaningless on near-zero logits).
    assert np.abs(got - want).max() < 0.06
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.9


def test_engine_config_rejects_odd_group_size():
    with pytest.raises(ValueError):
        _quantize4(jnp.ones((8, 8)), axis=-2, group_size=3)


def test_engine_adopts_injected_tree_group_size(cpu_devices):
    """An injected pre-quantized tree wins over the configured group size:
    otherwise _prefix_snapshot_meta would pin a group_size the served
    weights were never dequantized with, and a snapshot saved here would
    be accepted by a genuinely different engine."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quantize_params_int4(params, group_size=32)
    eng = InferenceEngine(
        model_cfg=cfg,
        engine_cfg=EngineConfig(model="tiny", num_slots=2, max_seq=64,
                                dtype="float32", quant="int4",
                                quant_group_size=64),
        params=qparams,
    )
    assert eng.params["blocks"]["wq"].group_size == 32
    # _prefix_snapshot_meta reads ecfg.quant_group_size; the adopted value
    # is what any snapshot pin will now record.
    assert eng.ecfg.quant_group_size == 32


def test_qtensor4_logical_shape():
    qt = _quantize4(jnp.ones((33, 5)), axis=-2, group_size=16)
    assert qt.shape == (33, 5)
    assert qt.in_dim == 33
    assert qt.q.shape == (24, 5)  # padded to 48, two per byte
    assert qt.scale.shape == (3, 5)


def test_convert_hf_int4_quantizes_with_group_scales():
    """checkpoint.convert_hf(quant='int4') returns QTensor4 leaves whose
    dequant matches quantizing the converted bf16 tree after the fact."""
    import sys
    import os
    import types

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ))
    from make_synth_hf_ckpt import fake_llama_state

    from p2p_llm_tunnel_tpu.models.checkpoint import convert_hf

    cfg = ModelConfig(name="synth", vocab_size=64, dim=32, n_layers=2,
                      n_heads=2, n_kv_heads=1, head_dim=16, ffn_dim=48)
    shape = types.SimpleNamespace(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, n_kv_heads=1,
        head_dim=16, ffn_dim=48,
    )
    state = fake_llama_state(shape, 1)
    got = convert_hf("llama", state, cfg, jnp.float32, quant="int4",
                     group_size=16)
    assert isinstance(got["blocks"]["wq"], QTensor4)
    assert got["blocks"]["wq"].group_size == 16
    want = quantize_params_int4(
        convert_hf("llama", state, cfg, jnp.float32), group_size=16
    )
    np.testing.assert_array_equal(
        np.asarray(got["blocks"]["wq"].q), np.asarray(want["blocks"]["wq"].q)
    )
    np.testing.assert_allclose(
        np.asarray(got["embed"].scale), np.asarray(want["embed"].scale)
    )
