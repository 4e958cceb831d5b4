"""Decode's one-token update of the delta rule's matrix state as a kernel over
the step's live rows (ops/pallas_delta_step.py, ISSUE 52), in interpret mode
on the CPU: against ``delta.delta_step`` on a stacked leaf as ``delta.pack``
lays it (live, parked and scratch rows, first and last layer, the published
head of ``[96, 192]`` at a few counts of heads, one part and sixteen), the
rows it never names to the bit, how the small operands are laid, the shapes
it declines, and what the decode program lowers to for a TPU.  (The branch's
table is tests/test_ssm_step_kernel.py's, one rule for both kinds.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import delta, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.ops import pallas_delta_step as kernel
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import live_rows_worklist

SEQ = 128
#: rows 0-3 are slots, row 4 the scratch row (parked at every decode step).
LIVE = {
    "all-rows-live": [True, True, True, True, True],
    "one-row-live": [False, False, True, False, False],
    "some-parked-and-the-scratch-row": [True, False, True, False, False],
    "none-live": [False] * 5,
}
#: (Ld, H, Dk, Dv): the published head, two rows side by side (``[48,
#: 384]``), at two heads (one butterfly), ten (two butterflies of five a
#: block) and thirty (two blocks of three); a head that packs no rows (``f`` 1) and
#: the tiny preset's, sixteen rows side by side in one row of 384 lanes.
SHAPES = {
    "two-heads": (3, 2, 96, 192),
    "two-butterflies-a-block": (2, 10, 96, 192),
    "two-blocks-of-heads": (2, 30, 96, 192),
    "one-part": (3, 4, 16, 128),
    "sixteen-parts-in-one-row": (2, 3, 16, 24),
}
#: float32 sums taken in another order: the last places.
TOL = dict(atol=2e-6, rtol=2e-6)

_step_rows = jax.jit(
    lambda *a: kernel.delta_step_rows(*a, interpret=True))


def _operands(shape, seed=0):
    ld, h, dk, dv = shape
    rows = len(LIVE["none-live"])
    f = delta.pack(dk, dv)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    leaf = jax.random.normal(ks[0], (ld, rows, h, dk // f, f * dv),
                             jnp.float32)
    q, k = delta.unit(jax.random.normal(ks[1], (rows, h, dk)),
                      jax.random.normal(ks[2], (rows, h, dk)))
    v = jax.random.normal(ks[3], (rows, h, dv))
    g = -jax.random.uniform(ks[4], (rows, h), minval=0.001, maxval=1.6)
    beta = jax.random.uniform(ks[5], (rows, h), maxval=2.0)
    return leaf, [q, k, v, g, beta]


def _work(live):
    return live_rows_worklist(jnp.where(jnp.array(live), 7, SEQ), SEQ)


def _held_to_the_step(shape, live, layer, seed=0):
    leaf, operands = _operands(shape, seed)
    live = np.array(live)
    o, new = _step_rows(leaf, layer, _work(live), *operands)
    want_o, want_s = delta.delta_step(*operands, leaf[layer])
    o, new, leaf = np.asarray(o), np.asarray(new), np.asarray(leaf)
    np.testing.assert_allclose(o[live], np.asarray(want_o)[live], **TOL)
    np.testing.assert_allclose(new[layer][live], np.asarray(want_s)[live],
                               **TOL)
    # never named: to the bit, and a defined o
    np.testing.assert_array_equal(new[layer][~live], leaf[layer][~live])
    others = [i for i in range(leaf.shape[0]) if i != layer]
    np.testing.assert_array_equal(new[others], leaf[others])
    np.testing.assert_array_equal(o[~live], 0.0)


@pytest.mark.parametrize("shape", ["two-heads", "one-part"])
@pytest.mark.parametrize("layer", [0, -1], ids=["layer-0", "last-layer"])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_kernel_is_the_step_on_the_live_rows_and_touches_no_other(
        live, layer, shape):
    shape = SHAPES[shape]
    _held_to_the_step(shape, LIVE[live], layer % shape[0])


@pytest.mark.parametrize("shape", ["two-butterflies-a-block",
                                   "sixteen-parts-in-one-row"])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_blocks_of_several_butterflies_and_rows_of_many_parts(live, shape):
    """Ten heads are two butterflies of five in one block; the tiny
    preset's head lies in ONE row of sixteen parts (no group of 8 sublanes:
    the interpreter's shape, tests/test_olmo_hybrid.py decodes through
    it)."""
    _held_to_the_step(SHAPES[shape], LIVE[live], 1, seed=3)


def test_the_published_heads_are_two_blocks_of_fifteen():
    assert kernel.heads_blocks(30, 48, 384, 192) == (5, 15)
    _held_to_the_step(SHAPES["two-blocks-of-heads"],
                      LIVE["some-parked-and-the-scratch-row"], 0, seed=5)


def test_a_live_row_whose_gate_and_strength_are_zero_keeps_its_state():
    """``g = beta = 0`` (what a padded position carries) on a row the list
    names: ``1 * S + k (x) 0``, the state to the bit; its ``o`` is ``S^T
    q``."""
    shape = SHAPES["two-heads"]
    leaf, (q, k, v, g, beta) = _operands(shape, seed=7)
    g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)
    live = LIVE["some-parked-and-the-scratch-row"]
    o, new = _step_rows(leaf, 1, _work(live), q, k, v, g, beta)
    np.testing.assert_array_equal(np.asarray(new[1, 2]),
                                  np.asarray(leaf[1, 2]))
    assert np.abs(np.asarray(new[1, 0] - leaf[1, 0])).max() > 0.1
    state = leaf[1, 2].reshape(2, 48, 2, 192).reshape(2, 96, 192)
    np.testing.assert_allclose(
        o[2], jnp.einsum("hkv,hk->hv", state, q[2], precision="highest"),
        **TOL)


@pytest.mark.parametrize("h,r,w,dv,want", [
    (30, 48, 384, 192, (5, 15)),     # the cell's: 24 columns a head
    (2, 48, 384, 192, (2, 2)),
    (3, 1, 384, 24, (3, 3)),         # the tiny preset's: 32 columns a head
    (64, 8, 128, 128, (64, 64)),     # two columns a head, 32 KB a head
    (7, 48, 384, 192, (1, 7)),       # a prime count: one head a butterfly
    (60, 48, 384, 192, (5, 20)),     # 2 MiB a block: 28 heads' room
], ids=["cell", "two-heads", "tiny", "one-part", "seven-heads",
        "sixty-heads"])
def test_heads_a_butterfly_and_a_block_divide_the_heads(h, r, w, dv, want):
    assert kernel.shapes_decline(r, w, dv) is None
    per, hb = kernel.heads_blocks(h, r, w, dv)
    assert (per, hb) == want
    assert h % hb == 0 and hb % per == 0
    assert per * 2 * (w // dv) * (r // kernel._sublanes(r)) <= kernel.LANES


@pytest.mark.parametrize("h,r,w,dv,why", [
    (30, 96, 192, 192, "no whole lane tiles"),     # a head that packs none
    (30, 32, 768, 256, "no power of two"),         # three parts
    (30, 512, 384, 192, "do not lie in one register"),
], ids=["192-lanes", "three-parts", "256-columns-a-head"])
def test_the_shapes_the_kernel_declines(h, r, w, dv, why):
    assert why in kernel.shapes_decline(r, w, dv)
    leaf = jnp.zeros((1, 2, h, r, w), jnp.float32)
    small = jnp.zeros((2, h))
    with pytest.raises(ValueError, match="cannot take this state"):
        kernel.delta_step_rows(
            leaf, 0, _work([True, False]), jnp.zeros((2, h, 8)),
            jnp.zeros((2, h, 8)), jnp.zeros((2, h, dv)), small, small,
            interpret=True)


def test_a_narrower_leaf_is_refused():
    leaf = jnp.zeros((1, 2, 2, 48, 384), jnp.bfloat16)
    small = jnp.zeros((2, 2))
    with pytest.raises(ValueError, match="bfloat16"):
        kernel.delta_step_rows(
            leaf, 0, _work([True, False]), jnp.zeros((2, 2, 96)),
            jnp.zeros((2, 2, 96)), jnp.zeros((2, 2, 192)), small, small,
            interpret=True)


def test_k_and_q_are_laid_as_the_butterflies_hold_them():
    """A register of ``[8, 128]``: sublane ``s``, lane ``((hh * 2 + which) *
    f + j) * groups + g`` <- ``(k, q)[which]`` of head ``hh`` of the five
    at ``Dk`` index ``(g * 8 + s) * f + j``; 120 of the 128 lanes hold
    columns, the rest zeros."""
    k = jnp.arange(2 * 10 * 96, dtype=jnp.float32).reshape(2, 10, 96)
    q = -k
    laid = kernel._columns(q, k, 2, 5)
    assert laid.shape == (2, 2, 8, 128)
    lane = ((3 * 2 + 0) * 2 + 1) * 6 + 4   # head 3 of 5, k, part 1, group 4
    assert float(laid[1, 1, 5, lane]) == float(k[1, 5 + 3, (4 * 8 + 5) * 2 + 1])
    lane = ((0 * 2 + 1) * 2 + 0) * 6 + 2   # head 0, q, part 0, group 2
    assert float(laid[0, 0, 7, lane]) == float(q[0, 0, (2 * 8 + 7) * 2])
    np.testing.assert_array_equal(np.asarray(laid[..., 120:]), 0.0)
    # what delta_step meets the state with is what the lanes spread to
    want = delta._rows_on_lanes(k, 2, 192)           # [2, 10, 48, 384]
    cols = np.asarray(laid[..., :120]).reshape(2, 2, 8, 5, 2, 2, 6)
    for part, at in ((0, 0), (1, 383)):
        np.testing.assert_array_equal(
            cols[1, 0, :, 2, 0, part, 3], np.asarray(want[1, 2, 24:32, at]))


def test_the_decode_program_lowered_for_a_tpu_holds_the_leaf_once():
    """``decode_step`` of ``olmo-hybrid-7b``, lowered for the TPU platform
    from here (``flash_force``): the kernel is lowered ONCE, in a function
    of its own that the twelve delta layers call with their index, and the
    state leaf is no operand or result of anything else: no slice of a
    layer out of it, no update of a layer into it, no copy.  (The compiled
    program is held to the same in tests/test_tpu_compile.py.)"""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.utils.hlo import lower_for_tpu

    cfg = get_config("olmo-hybrid-7b", flash_force=True, vocab_size=1024)
    assert ssm_moe.state_update_branch(cfg, None) == kernel.DELTA_STEP_KERNEL
    rows, seq = 9, 256
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: T.init_kv_cache(cfg, rows, seq))
    assert cache["delta"].shape == (12, rows, 30, 48, 384)
    row = jax.ShapeDtypeStruct((rows,), jnp.int32)
    text = lower_for_tpu(
        jax.jit(lambda p, c, tok, pos: T.decode_step(
            cfg, p, c, tok, pos, kv_view=seq), donate_argnums=(1,)),
        params, cache, row, row).as_text()
    leaf = "x".join(str(d) for d in cache["delta"].shape) + "xf32"
    named = [ln.strip() for ln in text.splitlines()
             if f"tensor<{leaf}>" in ln]
    kernels = [ln for ln in named if "tpu_custom_call" in ln]
    assert len(kernels) == 1 and kernel.DELTA_STEP_KERNEL in kernels[0]
    layers = [ln for ln in named if " call @" in ln]
    assert len(layers) == ssm_moe.kind_counts(cfg)["L"] == 12
    assert len({ln.split("call @")[1].split("(")[0] for ln in layers}) == 1
    # besides: the two functions' signatures and results, nothing else
    assert sorted(ln.split()[0] for ln in named
                  if ln not in kernels + layers) == [
        "func.func", "func.func", "return", "return"]
