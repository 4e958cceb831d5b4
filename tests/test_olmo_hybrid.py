"""The pattern family's gated delta-rule layer (a matrix state a head) beside
full attention, a dense gated MLP after every mixer and every branch normed
AFTER it (``tiny-delta-mlp``: Olmo-Hybrid-7B at a size the CPU runs) against
the benchmark's plain reference, benchmarks/olmo_hybrid_reference.py, on the
program's own seeded random weights: whole-prompt prefill, chunk prefill in
segments that split a chunk then decode through state and cache, each piece
of the layer left out, the chunked form against the token-by-token one,
padding and parked rows, a snapshot restored, and a Mamba-2 model's programs
unchanged.  The family through the engine is
tests/test_olmo_hybrid_engine.py; the published preset, the configuration
file and the tiny cell are tests/test_olmo_hybrid_cell.py.
"""

from __future__ import annotations

import hashlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
from tests.olmo_hybrid_tiny import (
    ATOL,
    SHAPES,
    UPDATES,
    _decoding,
    _prompt,
    _want,
)


ROWS, MAX_SEQ = 4, 128


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-delta-mlp")
    params = init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    return cfg, params


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


def _by_token(q, k, v, g, beta):
    """The operands of a token-by-token ``lax.scan``, time first (a Python
    loop over 48 positions inside one jit is one huge program: 155 s to
    compile where this is 2)."""
    return tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))


def _scan_without_correction(q, k, v, g, beta, state, chunk):
    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[..., None, None] * s
        s = s + k[..., None] * (beta[..., None] * v.astype(jnp.float32)
                                )[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q)

    s, outs = jax.lax.scan(token, state, _by_token(q, k, v, g, beta))
    return jnp.moveaxis(outs, 0, 1), s


def _bf16_state_scan(q, k, v, g, beta, state, chunk):
    """The recurrence with the state rounded to bfloat16 after every
    token."""
    def token(s, x):
        o, s = delta.delta_step(*x, s)
        return s, o

    s, outs = jax.lax.scan(token, state.astype(jnp.bfloat16),
                           _by_token(q, k, v, g, beta))
    return jnp.moveaxis(outs, 0, 1), s.astype(jnp.float32)


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
