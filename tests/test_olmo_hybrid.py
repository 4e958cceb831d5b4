"""The pattern family's gated delta-rule layer (a matrix state a head) beside
full attention, a dense gated MLP after every mixer and every branch normed
AFTER it (``tiny-delta-mlp``: Olmo-Hybrid-7B at a size the CPU runs) against
the benchmark's plain reference, benchmarks/olmo_hybrid_reference.py, on the
program's own seeded random weights: whole-prompt prefill, chunk prefill in
segments that split a chunk then decode through state and cache, each piece
of the layer left out, the chunked form against the token-by-token one,
padding and parked rows, a snapshot restored, a Mamba-2 model's programs
unchanged, the engine end to end, /healthz, the published preset's count of
parameters, the configuration file, and the tiny cell in one process.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import olmo_hybrid_reference as bench
from p2p_llm_tunnel_tpu.models import delta, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.ops.pallas_delta_step import DELTA_STEP_KERNEL
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import ELEMENTWISE
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_attention_branch,
    decode_step,
    init_kv_cache,
    init_params,
    prefill,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
import tinycell_olmo as tiny  # noqa: E402

ROWS, MAX_SEQ = 4, 128
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a chunked solve against the recurrence, the output read
# from the old state) differ in the last places of a float32; sixteen
# branches' norms carry them on.  A bfloat16 state reads 40 x this
# (test_a_narrower_state_fails_the_tolerance).
ATOL = 3e-4
SHAPES = bench.shapes_of(tiny.CONFIG)


def _as_reference(params):
    """The program's parameter tree in the reference's layout: its norms are
    ones and not stored there, and the six projections of a delta layer are
    matrices of their own."""
    assert all(float(jnp.abs(params[g]["norm"] - 1).max()) == 0
               for g in ("delta", "attn", "mlp"))
    h, dk, dv = SHAPES["d_heads"], SHAPES["dk"], SHAPES["dv"]
    d = params["delta"]
    cuts = np.cumsum([h * dk, h * dk, h * dv])
    wq, wk, wv, wz = jnp.split(d["w_in"], cuts, axis=-1)
    wa, wb = jnp.split(d["w_ab"], 2, axis=-1)
    return {
        "embed": params["embed"], "lm_head": params["lm_head"],
        "mlp": {k: params["mlp"][k] for k in ("w_in", "w_out")},
        "attn": {k: params["attn"][k] for k in ("wq", "wk", "wv", "wo")},
        "delta": {"wq": wq, "wk": wk, "wv": wv, "wz": wz, "wa": wa,
                  "wb": wb, "conv_w": d["conv_w"], "wo": d["w_out"],
                  "dt_bias": d["dt_bias"], "a_log": d["a_log"]},
    }


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-delta-mlp")
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    return cfg, params


def _want(params, tokens):
    return np.asarray(bench.forward_logprobs(
        SHAPES, _as_reference(params), tokens))


def _prompt(seed, n):
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


def _logprobs(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


_prefill = jax.jit(prefill, static_argnums=(0,))
_chunk_prefill = jax.jit(chunk_prefill_into_cache, static_argnums=(0,),
                         static_argnames=("kv_view", "return_all_logits"))
_decode_step = jax.jit(decode_step, static_argnums=(0,),
                       static_argnames=("kv_view",))


def _whole(cfg, params, prompt, fn=_prefill):
    n = len(prompt)
    tok = jnp.array([prompt + [0] * (48 - n)])
    logits, rows, _ = fn(cfg, params, tok, jnp.arange(48)[None, :] < n)
    return _logprobs(logits[0, :n]), rows


# ---- the programs against the benchmark's reference ----------------------------

def test_the_preset_is_the_layer_the_issue_writes_down(model):
    cfg, params = model
    assert cfg.mixer_kinds == "LLL*LLL*" and cfg.mixer_mlp and cfg.norm_after
    assert not cfg.tie_embeddings and "lm_head" in params
    # heads no power of two, the two widths apart, neither a lane tile
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim) \
        == (3, 16, 24)
    assert {k: v.shape for k, v in params["delta"].items()} == {
        "norm": (6, 48), "w_in": (6, 48, 3 * (16 + 16 + 24 + 24)),
        "w_ab": (6, 48, 6), "conv_w": (6, 4, 3 * 56),
        "w_out": (6, 72, 48), "dt_bias": (6, 3), "a_log": (6, 3),
        "gate_norm": (6, 24)}
    assert params["attn"]["q_norm"].shape == (2, 48)  # the whole width
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    # a head's [16, 24] lies 16 rows side by side: 384 lanes, three tiles
    assert delta.pack(16, 24) == 16 and delta.pack(96, 192) == 2
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, ROWS, MAX_SEQ, 48), "v": (2, ROWS, MAX_SEQ, 48),
        "delta": (6, ROWS, 3, 1, 384), "dconv": (6, ROWS, 3 * 168)}
    assert ssm_moe.state_keys(cfg) == ("delta", "dconv")
    # one row a head: the chip's compiler is not asked, the interpreter is
    assert ssm_moe.state_update_branch(
        replace(cfg, flash_force=True), None) == ELEMENTWISE
    assert ssm_moe.state_update_branch(
        _decoding(cfg, "kernel"), None) == DELTA_STEP_KERNEL
    assert SHAPES["kinds"] == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)


def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params = model
    prompt = _prompt(3, 43)
    got, rows = _whole(cfg, params, prompt)
    np.testing.assert_allclose(got, _want(params, prompt), atol=ATOL)
    assert rows["full"][0].shape == (2, 1, 48, 48)
    assert rows["state"][0].shape == (6, 1, 3, 16, 24)


def _scan_without_correction(q, k, v, g, beta, state, chunk):
    s, outs = state, []
    for t in range(q.shape[1]):
        s = jnp.exp(g[:, t])[..., None, None] * s
        s = s + k[:, t][..., None] * (beta[:, t][..., None]
                                      * v[:, t].astype(jnp.float32)
                                      )[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, t]))
    return jnp.stack(outs, 1), s


def _bf16_state_scan(q, k, v, g, beta, state, chunk):
    """The recurrence with the state rounded to bfloat16 after every
    token."""
    s, outs = state, []
    for t in range(q.shape[1]):
        o, s = delta.delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], s.astype(jnp.bfloat16))
        outs.append(o)
    return jnp.stack(outs, 1), s.astype(jnp.float32)


LEFT_OUT = {
    "the-norm-after-a-branch": dict(norm_after=False),
    "the-whole-width-qk-norm": dict(qk_norm=False),
    "linear_allow_neg_eigval": dict(delta_neg_eigval=False),
    "the-beta-correction": _scan_without_correction,
    "a-float32-state": _bf16_state_scan,
}


@pytest.mark.parametrize("piece", sorted(LEFT_OUT))
def test_each_piece_left_out_fails_the_tolerance(model, piece, monkeypatch):
    """The program with a branch normed before it, no QK norm, a write
    strength in [0, 1], the rule without its correction ``- S^T k``, or a
    bfloat16 state is another model: whole-prompt log-probabilities leave
    the reference's by far more than ``ATOL``."""
    cfg, params = model
    least = 50 * ATOL
    if callable(LEFT_OUT[piece]):
        monkeypatch.setattr(ssm_moe, "delta_scan", LEFT_OUT[piece])
        if piece == "a-float32-state":
            least = 10 * ATOL
    else:
        cfg = replace(cfg, **LEFT_OUT[piece])
    prompt = _prompt(3, 43)
    got, _ = _whole(cfg, params, prompt, jax.jit(
        lambda c, *rest: prefill(c, *rest), static_argnums=(0,)))
    apart = np.abs(got - _want(params, prompt)).max()
    assert apart > least, apart


#: Decode's state update as ``delta.delta_step`` in XLA, and as the kernel
#: over the live rows (interpreted): ISSUE 52.
UPDATES = {"elementwise": {}, "kernel": {"flash_interpret": True}}


def _decoding(cfg, update):
    cfg = replace(cfg, **UPDATES[update])
    assert ssm_moe.state_update_branch(cfg, None) == (
        DELTA_STEP_KERNEL if update == "kernel" else ELEMENTWISE)
    return cfg


def _chunk(cfg, params, cache, prompt, start, end, slot, width=32):
    """One segment beside a padding row on the scratch slot."""
    tok = jnp.zeros((2, width), jnp.int32).at[0, :end - start].set(
        jnp.array(prompt[start:end]))
    return _chunk_prefill(
        cfg, params, tok, jnp.array([end - start, 1]), jnp.array([start, 0]),
        cache, jnp.array([slot, ROWS - 1]), kv_view=MAX_SEQ,
        return_all_logits=True)[:2]


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_chunk_prefill_in_segments_then_decode_through_the_cache(model,
                                                                 update):
    """The prompt as chunk-prefill segments of uneven lengths that split
    the rule's chunks of 8 (0-21, 21-27, 27-43), then 40 decode steps (the
    state by ``delta_step`` over the leaf as it lies, or by the kernel over
    the live rows of the leaf), against ONE full forward of the reference:
    logits, at a tolerance a bfloat16 state fails."""
    cfg, params = model
    cfg = _decoding(cfg, update)
    full = _prompt(3, 43) + _prompt(4, 40)
    want = _want(params, full)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    for a, b in [(0, 21), (21, 27), (27, 43)]:
        logits, cache = _chunk(cfg, params, cache, full, a, b, 1)
        np.testing.assert_allclose(_logprobs(logits[0, :b - a]), want[a:b],
                                   atol=ATOL)
    for p in range(43, 83):
        tokens = jnp.zeros((ROWS,), jnp.int32).at[1].set(full[p])
        positions = jnp.full((ROWS,), MAX_SEQ).at[1].set(p)
        logits, cache = _decode_step(cfg, params, cache, tokens, positions,
                                     kv_view=MAX_SEQ)
        np.testing.assert_allclose(_logprobs(logits[1]), want[p], atol=ATOL)


def test_a_narrower_state_fails_the_decode_tolerance(model, monkeypatch):
    """The same decode with the state leaf held in bfloat16: the logits
    leave the reference's by more than ``ATOL`` within 40 steps."""
    cfg, params = model
    monkeypatch.setattr(ssm_moe, "STATE_DTYPE", jnp.bfloat16)
    full = _prompt(3, 43) + _prompt(4, 40)
    want = _want(params, full)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    assert cache["delta"].dtype == jnp.bfloat16
    step = jax.jit(lambda c, *rest, **kw: decode_step(c, *rest, **kw),
                   static_argnums=(0,), static_argnames=("kv_view",))
    chunk = jax.jit(
        lambda c, *rest, **kw: chunk_prefill_into_cache(c, *rest, **kw),
        static_argnums=(0,), static_argnames=("kv_view",
                                               "return_all_logits"))
    tok = jnp.zeros((2, 48), jnp.int32).at[0, :43].set(jnp.array(full[:43]))
    _, cache = chunk(cfg, params, tok, jnp.array([43, 1]), jnp.array([0, 0]),
                     cache, jnp.array([1, ROWS - 1]), kv_view=MAX_SEQ)[:2]
    apart = 0.0
    for p in range(43, 83):
        tokens = jnp.zeros((ROWS,), jnp.int32).at[1].set(full[p])
        positions = jnp.full((ROWS,), MAX_SEQ).at[1].set(p)
        logits, cache = step(cfg, params, cache, tokens, positions,
                             kv_view=MAX_SEQ)
        apart = max(apart, np.abs(_logprobs(logits[1]) - want[p]).max())
    assert apart > 5 * ATOL, apart


# ---- the rule itself -----------------------------------------------------------

def _operands(seed, b=2, t=37, h=3, dk=16, dv=24):
    rng = np.random.RandomState(seed)
    q, k = delta.unit(jnp.asarray(rng.randn(b, t, h, dk), jnp.float32),
                      jnp.asarray(rng.randn(b, t, h, dk), jnp.float32))
    v = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 1.6, (b, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (b, t, h)), jnp.float32)
    state = jnp.asarray(rng.randn(b, h, dk, dv), jnp.float32)
    return q, k, v, g, beta, state


def _token_by_token(q, k, v, g, beta, state):
    """The issue's three lines, a position at a time, on ``[Dk, Dv]``."""
    outs = []
    for t in range(q.shape[1]):
        state = jnp.exp(g[:, t])[..., None, None] * state
        d = beta[:, t][..., None] * (
            v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., None] * d[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("chunk", [1, 5, 8, 64])
def test_the_chunked_form_is_the_recurrence_at_any_chunking(chunk):
    q, k, v, g, beta, state = _operands(1)
    want_o, want_s = _token_by_token(q, k, v, g, beta, state)
    o, s = delta.delta_scan(q, k, v, g, beta, state, chunk)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


@pytest.mark.parametrize("dk,dv", [(16, 24), (8, 192), (8, 128), (6, 40)])
def test_the_step_over_the_packed_leaf_is_the_recurrence(dk, dv):
    """``delta_step`` over ``[Dk / f, f * Dv]`` (16, 2, 1 rows side by side,
    and a shape that does not pack) against the three lines on ``[Dk,
    Dv]``; the layout is the same bytes in the same order."""
    q, k, v, g, beta, state = _operands(2, t=5, dk=dk, dv=dv)
    f = delta.pack(dk, dv)
    assert f == {(16, 24): 16, (8, 192): 2, (8, 128): 1, (6, 40): 1}[dk, dv]
    held = state.reshape(2, 3, dk // f, f * dv)
    for t in range(5):
        o, held = delta.delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], held)
    want_o, want_s = _token_by_token(q, k, v, g, beta, state)
    np.testing.assert_allclose(o, want_o[:, -1], atol=2e-5)
    np.testing.assert_allclose(held.reshape(state.shape), want_s, atol=2e-5)


def test_padding_leaves_the_state_to_the_bit():
    """Positions whose ``g`` and ``beta`` are 0 (padding after a segment's
    real tokens, a parked row) leave the state as it is: the scan's end
    state is that of the real tokens alone, and a step changes no bit."""
    q, k, v, g, beta, state = _operands(3, t=24)
    real = jnp.arange(24)[None, :, None] < jnp.array([13, 24])[:, None, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    _, s = delta.delta_scan(q, k, v, g, beta, state, 8)
    _, short = delta.delta_scan(q[:1, :13], k[:1, :13], v[:1, :13],
                                g[:1, :13], beta[:1, :13], state[:1], 8)
    np.testing.assert_allclose(s[0], short[0], atol=1e-6)
    held = state.reshape(2, 3, 1, 384)
    zeros = jnp.zeros((2, 3))
    _, after = delta.delta_step(q[:, 0], k[:, 0], v[:, 0], zeros, zeros, held)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(held))


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_padded_and_parked_rows_leave_state_and_tail_unchanged(model,
                                                               update):
    cfg, params = model
    cfg = _decoding(cfg, update)
    prompt = _prompt(5, 30)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    _, cache = _chunk(cfg, params, cache, prompt, 0, 30, 2)
    keys = ssm_moe.state_keys(cfg)
    before = {k: np.asarray(cache[k][:, 2]) for k in keys}
    assert all(np.abs(v).max() > 0 for v in before.values())
    # a decode step in which row 2 is parked, and a chunk on other slots
    tokens = jnp.zeros((ROWS,), jnp.int32).at[1].set(7)
    positions = jnp.full((ROWS,), MAX_SEQ).at[1].set(0)
    _, cache = _decode_step(cfg, params, cache, tokens, positions,
                            kv_view=MAX_SEQ)
    _, cache = _chunk(cfg, params, cache, prompt, 0, 9, 0)
    for k in keys:
        np.testing.assert_array_equal(np.asarray(cache[k][:, 2]), before[k])
    # a segment's padding (22 of 32 positions) leaves what its 10 real
    # tokens left: the same state as the same tokens in a segment of 10
    # (sums in another order: the rule's chunks are cut elsewhere)
    tok = jnp.zeros((2, 10), jnp.int32).at[0].set(jnp.array(prompt[:10]))
    _, tight = _chunk_prefill(
        cfg, params, tok, jnp.array([10, 1]), jnp.array([0, 0]),
        init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32),
        jnp.array([0, ROWS - 1]), kv_view=MAX_SEQ, return_all_logits=True)[:2]
    _, loose = _chunk(cfg, params, init_kv_cache(cfg, ROWS, MAX_SEQ,
                                                 jnp.float32),
                      prompt, 0, 10, 0)
    for k in keys:
        np.testing.assert_allclose(np.asarray(loose[k][:, 0]),
                                   np.asarray(tight[k][:, 0]), atol=1e-4)


def test_a_snapshot_restored_continues_as_the_uninterrupted_run(model):
    """The pool's copy programs over this state's leaves: save slot 1's
    state at a block boundary (32), run on, restore it into slot 2 with the
    pages' rows, and continue there: the logits are those of the run that
    was never interrupted."""
    from p2p_llm_tunnel_tpu.engine.prefix_cache import make_state_copy_ops

    cfg, params = model
    full = _prompt(6, 50)
    keys = ssm_moe.state_keys(cfg)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    _, cache = _chunk(cfg, params, cache, full, 0, 32, 1)
    restore, save = make_state_copy_ops(keys, 2)
    snaps = {k: jnp.zeros((cache[k].shape[0], 3) + cache[k].shape[2:],
                          cache[k].dtype) for k in keys}
    snaps = save(snaps, cache, jnp.array([1, ROWS - 1]), jnp.array([1, 0]))
    straight, cache = _chunk(cfg, params, cache, full, 32, 50, 1)
    # slot 2: the pages' rows copied, the state restored, then the tail
    for name in ("k", "v"):
        cache[name] = cache[name].at[:, 2, :32].set(cache[name][:, 1, :32])
    cache = restore(cache, snaps, jnp.array([2, ROWS - 1]),
                    jnp.array([1, 0]))
    resumed, cache = _chunk(cfg, params, cache, full, 32, 50, 2)
    np.testing.assert_allclose(np.asarray(resumed[0, :18]),
                               np.asarray(straight[0, :18]), atol=1e-5)
    for k in keys:
        np.testing.assert_allclose(np.asarray(cache[k][:, 2]),
                                   np.asarray(cache[k][:, 1]), atol=1e-5)


# ---- what this family's programs are, and what the others' stay ------------------

#: sha256 of the lowered text of ``tiny-ssm-moe``'s and ``tiny-ssm-mlp``'s
#: decode and chunk-prefill programs at the parent of the PR that added the
#: delta rule (936b177): a Mamba-2 model's programs are what they were.
PARENT_PROGRAMS = {
    ("tiny-ssm-moe", "decode"): "cea3e51108dcb9bde8e815d4b3f59db66e19a627449f225c421257e0a893a73c",
    ("tiny-ssm-moe", "chunk"): "9c26f568f0061fcd6a0027b1fc15338f6f9b46b7fbae8136a7aa3f6b20ac03c1",
    ("tiny-ssm-mlp", "decode"): "2b991596036f8ebb898586ddeb1440d2faf5b3f4e68373459419bf00fdd75b8a",
    ("tiny-ssm-mlp", "chunk"): "a86020bd6a5c780d61679b7cdff560c83d670a451aef0145decda28cc107f362",
}


def lowered_text(preset: str, program: str) -> str:
    cfg = get_config(preset)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 5, 128, jnp.bfloat16))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    if program == "decode":
        fn = lambda p, c, tok, pos: decode_step(  # noqa: E731
            cfg, p, c, tok, pos, kv_view=128)
        args = (params, cache, i32(5), i32(5))
    else:
        fn = lambda p, c, tok, ln, st, sl: chunk_prefill_into_cache(  # noqa: E731
            cfg, p, tok, ln, st, c, sl, kv_view=128)
        args = (params, cache, i32(2, 16), i32(2), i32(2), i32(2))
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).as_text()


@pytest.mark.parametrize("preset,program", sorted(PARENT_PROGRAMS))
def test_a_mamba2_presets_programs_lower_to_the_parents_text(preset, program):
    text = lowered_text(preset, program)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_PROGRAMS[preset, program]


def test_a_mamba2_models_leaves_are_what_they_were():
    cfg = get_config("tiny-ssm-mlp")
    assert ssm_moe.state_kind(cfg) == "M"
    assert ssm_moe.state_keys(cfg) == ssm_moe.STATE_KEYS == ("ssm", "conv")
    assert list(init_kv_cache(cfg, 3, 32)) == ["k", "v", "ssm", "conv"]
    with pytest.raises(ValueError, match="two kinds"):
        ssm_moe.state_kind(replace(cfg, mixer_pattern="ML*M"))


def test_thirty_kv_heads_of_128_are_whole_lane_tiles():
    """A row of 30 KV heads of 128 is 30 lane tiles: the rule that asks the
    ROW's width lets the published shape take the rows kernel where the
    backend is the TPU; a CPU backend keeps the einsum."""
    cfg = get_config("olmo-hybrid-7b")
    assert decode_attention_branch(cfg, None, 1024) == "einsum"
    forced = replace(cfg, flash_force=True)
    assert decode_attention_branch(forced, None, 1024) == "pallas-rows"
    assert ssm_moe.state_update_branch(forced, None) == DELTA_STEP_KERNEL


# ---- the published preset -------------------------------------------------------

def test_the_published_preset_counts_4101_m_parameters():
    cfg = get_config("olmo-hybrid-7b")
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # 12 x (88.75 M a delta mixer + 126.81 M an MLP) + 4 x (58.98 M + 126.81
    # M) + 770.7 M of embedding and head (the issue's 4,097.5 M counts a
    # delta mixer at 88.5 M)
    assert abs(count - 4100.8e6) < 1e6, count
    assert cfg.mixer_kinds == "LLL*" * 4 and cfg.published_layers == 32
    # a slot's state: 12 x (30 x 96 x 192 float32 + 3 x 11,520 bfloat16)
    assert ssm_moe.state_bytes_per_slot(cfg) == 12 * (2211840 + 69120) \
        == 27_371_520
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 65, 1024))
    # whole (8, 128) tiles a row: 48 sublanes x 384 lanes a head
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (4, 65, 1024, 3840), "v": (4, 65, 1024, 3840),
        "delta": (12, 65, 30, 48, 384), "dconv": (12, 65, 3 * 11520)}


def test_the_configuration_file_keeps_every_published_key():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row for row in map(json.loads, f)
                         if row["name"] == "Olmo-Hybrid-7B")
    assert body["source"] == published["source_url"]
    assert body["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in published["config"].items():
        if key not in body["reduced"]:
            assert body[key] == value, key
    # four whole periods of the published list
    assert body["num_hidden_layers"] == 16
    assert body["layer_types"] == published["config"]["layer_types"][:16]
    shapes = bench.shapes_of(body)
    cfg = get_config(body["serve"]["model"])
    assert "".join("L" if k == "linear_attention" else "*"
                   for k in shapes["kinds"]) == cfg.mixer_kinds
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
            cfg.vocab_size, cfg.norm_eps) == (
        shapes["dim"], shapes["heads"], shapes["kv"], shapes["hd"],
        shapes["ffn"], shapes["vocab"], shapes["eps"])
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.delta_conv, 2.0 if cfg.delta_neg_eigval else 1.0) == (
        shapes["d_heads"], shapes["dk"], shapes["dv"], shapes["conv"],
        shapes["beta_x"])
    assert (cfg.ssm_dt_min, cfg.ssm_dt_max) == (bench.DT_MIN, bench.DT_MAX)
    assert delta.UNIT_EPS == bench.UNIT_EPS
    assert not cfg.tie_embeddings and cfg.mixer_mlp and cfg.norm_after
    assert jnp.dtype(ssm_moe.STATE_DTYPE).name == body["state_type"]
    # 4 attention layers x 2 x 30 KV heads of 128 in bfloat16
    assert bench.cache_bytes_per_token(body) == 61440
    # the cell's clients are the file's slots
    args = body["serve"]["args"]
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "chatturns-closed.json")) as f:
        assert json.load(f)["clients"] == int(
            args[args.index("--slots") + 1])


def test_the_benchmarks_reference_draws_the_programs_weights():
    cfg = get_config("tiny-delta-mlp")
    weights = bench.make_weights(SHAPES, 5)
    mine = _as_reference(init_params(cfg, jax.random.PRNGKey(5),
                                     jnp.bfloat16))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), weights, mine)
    # the embedding's rows are drawn at a unit RMS (0.88: the truncation)
    assert float(jnp.std(weights["embed"].astype(jnp.float32))) \
        == pytest.approx(0.88, rel=0.05)
    assert bench.cache_bytes_per_token(tiny.CONFIG) == tiny.CACHE_BYTES


# ---- through the engine ------------------------------------------------------------

def _engine(model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model="tiny-delta-mlp", num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def _generate(eng, prompts, new=8):
    async def main():
        await eng.start()
        try:
            out = []
            for prompt in prompts:
                events = [ev async for ev in eng.generate(
                    prompt, max_new_tokens=new, logprobs=1, stop_ids=())]
                out.append(([ev.token_id for ev in events],
                            [ev.logprob for ev in events]))
            return out
        finally:
            await eng.stop()

    return asyncio.run(asyncio.wait_for(main(), 300))


def test_a_prefix_hit_restores_a_snapshot_and_decodes_as_the_unshared_run():
    """Two prompts that share their first 48 tokens, one after the other
    (chunk prefill in segments of 16, the pool, decode bursts): the second
    restores the snapshot of state at 48 and its generated tokens and their
    log-probabilities are those of an engine with no pool, and the
    reference's; the state's counters count for this state as for
    Mamba-2's."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    base = _prompt(9, 60)
    prompts = [base, base[:48] + _prompt(10, 11)]
    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                  prefill_chunk=16)
    assert not eng.config_fences
    hit = global_metrics.counter("engine_prefix_hit_tokens_total")
    restores = global_metrics.counter("engine_state_restores_total")
    saves = global_metrics.counter("engine_state_snapshots_total")
    moved = global_metrics.counter("engine_state_bytes_total")
    shared = _generate(eng, prompts)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit == 48
    assert global_metrics.counter("engine_state_restores_total") - restores \
        == 1
    assert global_metrics.counter("engine_state_snapshots_total") > saves
    assert global_metrics.counter("engine_state_bytes_total") > moved
    alone = _generate(_engine(mux=True, prefix_cache=False,
                              prefill_chunk=16), prompts[1:])
    assert shared[1][0] == alone[0][0]
    np.testing.assert_allclose(shared[1][1], alone[0][1], atol=ATOL)
    tokens, values = shared[1]
    want = _want(eng.params, prompts[1] + tokens)
    n = len(prompts[1])
    np.testing.assert_allclose(
        values, [want[n - 1 + j, t] for j, t in enumerate(tokens)], atol=ATOL)
    # /healthz names the layer, the head, the delta state a slot (heads, key
    # and value widths, type, bytes) and the snapshots' room in bytes
    said = eng._model_section()
    assert said["layer"] == {"mixers": {"L": 6, "*": 2}, "mlp_width": 96}
    assert said["head"] == "its own"
    state = said["cache"]["kinds"]["state"]
    per_slot = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    assert per_slot == 6 * (3 * 16 * 24 * 4 + 3 * 168 * 4)
    assert {k: state[k] for k in (
        "rule", "layers", "heads", "key_width", "value_width", "type",
        "held_as", "bytes_per_slot", "update")} == {
        "rule": "gated delta", "layers": 6, "heads": 3, "key_width": 16,
        "value_width": 24, "type": "float32", "held_as": [1, 384],
        "bytes_per_slot": per_slot, "update": "elementwise"}
    assert state["snapshots"] == {
        "room": 16, "held": len(eng._snapshots), "bytes_each": per_slot,
        "bytes": 16 * per_slot}
    assert said["cache"]["kinds"]["attention"]["kv_heads"] == 3
    assert eng._snap_pool["delta"].shape[:2] == (6, 17)


@pytest.mark.parametrize("update", sorted(UPDATES))
def test_the_dispatch_records_carry_state_rows(update):
    """``engine.decode_burst`` and ``engine.prefill_segment`` records name
    the rows whose state the dispatch read and wrote and their bytes, as a
    Mamba-2 model's do; a burst's ``state_update`` names the branch's answer
    (as /healthz does) and ``engine_decode_state_kernel_steps_total`` counts
    the steps that took the kernel."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics
    from tests.moe_records import tracing

    name = "engine_decode_state_kernel_steps_total"
    with tracing() as tracer:
        eng = _engine(_decoding(get_config("tiny-delta-mlp"), update),
                      mux=True, prefill_chunk=16)
        before = global_metrics.counter(name)
        steps = global_metrics.counter("engine_decode_steps_total")
        _generate(eng, [_prompt(12, 20)], new=4)
        grew = global_metrics.counter(name) - before
        steps = global_metrics.counter("engine_decode_steps_total") - steps
        records = tracer.records()
    row = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    segs = [r for r in records if r.name == "engine.prefill_segment"]
    bursts = [r for r in records if r.name == "engine.decode_burst"]
    assert [r.attrs["tokens"] for r in segs] == [16, 4] and bursts
    for r in segs:
        assert (r.attrs["state_rows"], r.attrs["state_bytes"]) == (1, 2 * row)
    for r in bursts:
        a = r.attrs
        assert a["state_rows"] == a["live_rows"] * a["steps"]
        assert a["state_bytes"] == 2 * row * a["state_rows"]
    want = DELTA_STEP_KERNEL if update == "kernel" else ELEMENTWISE
    assert {r.attrs["state_update"] for r in bursts} == {want}
    assert eng._model_section()["cache"]["kinds"]["state"]["update"] == want
    assert steps > 0 and grew == (steps if update == "kernel" else 0)


@pytest.mark.parametrize("case", [
    dict(quant="int8"), dict(kv_quant="int4"), dict(spec_ngram=2),
    dict(ragged_prefill=True), dict(tp=2)], ids=lambda c: next(iter(c)))
def test_what_the_family_lacks_is_refused_for_this_model_too(case):
    with pytest.raises(ValueError, match="a dense MLP a layer"):
        _engine(**case)


# ---- the tiny cell, in one process ------------------------------------------------

@pytest.mark.parametrize("mode", ["stated", "weights"])
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_olmo.py's cell (``tiny-delta-mlp`` in
    bfloat16 against benchmarks/olmo_hybrid_reference.py) through the
    engine in this process: what ``correct`` compares, as stated and with
    the weights' precision lowered.  The ladder's prefixes reach the chunk
    program through the pool and the snapshots.  (Through signal + serve +
    proxy: tests/benchmarks/test_bm_olmo_rehearsal.py, ``slow``.)"""
    from test_mla_moe import _ask_in_process

    from benchmarks import correctness, traffic
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine
    from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

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
    weights = bench.make_weights(SHAPES, seed)
    stated = bench.cache_bytes_per_token(config)
    if mode == "stated":
        class Words(ByteTokenizer):
            vocab_size = vocab

        restores = global_metrics.counter("engine_state_restores_total")
        eng = InferenceEngine(
            engine_cfg=EngineConfig(
                model=config["serve"]["model"], num_slots=4, max_seq=256,
                seed=seed, mux=True, prefix_cache=True, prefill_chunk=16),
            tokenizer=Words())
        _ask_in_process(eng, seqs)
        counted = eng._prefix_block_bytes / eng._prefix_block
        assert global_metrics.counter("engine_state_restores_total") \
            > restores
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
                SHAPES, weights, seq["tokens"], weight_bits=8))
            seq["system"] = [float(lp[p, t]) for p, t in seq["probes"]]
    reference = []
    for seq in seqs:
        lp = np.asarray(bench.forward_logprobs(SHAPES, weights, seq["tokens"]))
        reference.append([float(lp[p, t]) for p, t in seq["probes"]])
    numbers = correctness.compare(seqs, reference)
    said = []
    held = correctness.judge(numbers, limits, counted, stated, said.append)
    print("\n".join(said))
    assert held is (mode == "stated"), "\n".join(said)
    assert stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
