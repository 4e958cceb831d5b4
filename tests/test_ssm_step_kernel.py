"""Decode's one-token state update as a kernel over the step's live rows
(ops/pallas_ssm_step.py, ISSUE 45), in interpret mode on the CPU: against
``ssm.ssm_step`` on a stacked leaf (live, parked and scratch rows, first and
last layer, one group and eight), the rows it never names to the bit, the
list of live rows, the branch by what the code observes (both kinds of
state: the delta rule's kernel is tests/test_delta_step_kernel.py's), and
what the decode program lowers to for a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import ssm, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.ops import pallas_ssm_step as kernel
from p2p_llm_tunnel_tpu.ops.pallas_delta_step import DELTA_STEP_KERNEL

SEQ = 128
#: rows 0-3 are slots, row 4 the scratch row (parked at every decode step).
LIVE = {
    "all-rows-live": [True, True, True, True, True],
    "some-parked-and-the-scratch-row": [True, False, True, False, False],
    "none-live": [False] * 5,
}
#: (Lm, H, P, N): the tiny presets' kind of state (no whole tile anywhere)
#: and a state of whole float32 tiles, two butterflies of 16 heads a block.
SHAPES = {"no-whole-tile": (3, 8, 8, 16), "whole-tiles": (2, 32, 64, 128)}

_step_rows = jax.jit(
    lambda *a: kernel.ssm_step_rows(*a, interpret=True))


def _operands(shape, groups, seed=0):
    lm, h, p, n = shape
    rows = len(LIVE["none-live"])
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    leaf = jax.random.normal(ks[0], (lm, rows, h, p, n), jnp.float32)
    x = jax.random.normal(ks[1], (rows, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (rows, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[3], (h,)))
    bm = jax.random.normal(ks[4], (rows, groups, n))
    cm = jax.random.normal(ks[5], (rows, groups, n))
    return leaf, (x, dt, a, bm, cm)


def _held_to_the_step(shape, groups, live, layer):
    leaf, (x, dt, a, bm, cm) = _operands(shape, groups)
    live = np.array(live)
    positions = jnp.where(jnp.array(live), 7, SEQ)
    work = kernel.live_rows_worklist(positions, SEQ)
    y, new = _step_rows(leaf, layer, work, x, dt, a, bm, cm)
    want_y, want_s = ssm.ssm_step(x, dt, a, bm, cm, leaf[layer])
    y, new, leaf = np.asarray(y), np.asarray(new), np.asarray(leaf)
    # the tolerance the scan is held to the recurrence by (test_ssm_moe.py)
    np.testing.assert_allclose(y[live], np.asarray(want_y)[live],
                               atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(new[layer][live], np.asarray(want_s)[live],
                               atol=2e-4, rtol=1e-4)
    # never named: to the bit, and a defined y
    np.testing.assert_array_equal(new[layer][~live], leaf[layer][~live])
    others = [i for i in range(leaf.shape[0]) if i != layer]
    np.testing.assert_array_equal(new[others], leaf[others])
    np.testing.assert_array_equal(y[~live], 0.0)


@pytest.mark.parametrize("groups", [1, 8], ids=["G1", "G8"])
@pytest.mark.parametrize("layer", [0, -1], ids=["layer-0", "last-layer"])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_kernel_is_the_step_on_the_live_rows_and_touches_no_other(
        live, layer, groups):
    shape = SHAPES["no-whole-tile"]
    _held_to_the_step(shape, groups, LIVE[live], layer % shape[0])


@pytest.mark.parametrize("live", sorted(LIVE))
def test_a_state_of_whole_tiles_goes_through_both_butterflies(live):
    """Nemotron's kind of block: 32 heads of ``[64, 128]``, two butterflies
    of 128 registers in a loop, four heads a group."""
    _held_to_the_step(SHAPES["whole-tiles"], 8, LIVE[live], 1)


def test_a_state_of_two_lane_tiles_is_one_butterfly_of_256_lanes():
    """``N`` 256: a register pair a row of the butterfly, 32 heads in it."""
    _held_to_the_step((2, 32, 64, 256), 4,
                      LIVE["some-parked-and-the-scratch-row"], 0)


def test_the_small_operands_are_laid_as_the_butterflies_hold_them():
    v = jnp.arange(2 * 32 * 64, dtype=jnp.float32).reshape(2, 32, 64)
    laid = kernel._scrambled(v, 128)
    assert laid.shape == (2, 2, 8, 128)
    # sublane s, lane hh * 8 + pb <- head hh of the 16, p = pb * 8 + s
    assert float(laid[1, 1, 3, 5 * 8 + 2]) == float(v[1, 16 + 5, 2 * 8 + 3])
    np.testing.assert_array_equal(kernel._unscrambled(laid, 32, 64), v)
    few = jnp.ones((1, 4, 8))  # fewer heads than a register's lanes
    assert kernel._scrambled(few, 16).shape == (1, 1, 8, 16)
    np.testing.assert_array_equal(
        kernel._unscrambled(kernel._scrambled(few, 16), 4, 8), few)


@pytest.mark.parametrize("positions,want", [
    ([3, SEQ, 9, SEQ + 5, 0], [3, 0, 2, 4, 4, 4]),
    ([SEQ] * 5, [0, 4, 4, 4, 4, 4]),
    ([1, 2, 3, 4, 5], [5, 0, 1, 2, 3, 4]),
    ([SEQ, SEQ, 7, SEQ, SEQ], [1, 2, 2, 2, 2, 2]),
], ids=["some", "none", "all", "one"])
def test_the_list_is_the_count_the_live_rows_and_the_last_again(
        positions, want):
    got = kernel.live_rows_worklist(jnp.array(positions), SEQ)
    assert got.dtype == jnp.int32
    assert list(np.asarray(got)) == want


def _two_chips(cpu_devices):
    from jax.sharding import Mesh

    return Mesh(np.asarray(cpu_devices[:2]).reshape(1, 2), ("dp", "tp"))


CELL = "nemotron-3-nano-30b-a3b-ep2s"
#: The delta rule's cell (a head's ``[96, 192]`` held as ``[48, 384]``) and
#: the tiny preset of its kind (``[16, 24]`` held as one row of 384 lanes).
DELTA_CELL, DELTA_TINY = "olmo-hybrid-7b", "tiny-delta-mlp"
#: (what the code can observe) -> the state update's branch: the backend
#: (None: this one, a CPU), the model and its fields, a mesh of two chips?
BRANCHES = {
    "interpreting-any-shape":
        (None, "tiny-ssm-moe", dict(flash_interpret=True), False,
         kernel.SSM_STEP_KERNEL),
    "the-cells-state-on-a-tpu-backend":
        ("tpu", CELL, {}, False, kernel.SSM_STEP_KERNEL),
    "the-cells-state-lowered-for-a-tpu":
        (None, CELL, dict(flash_force=True), False, kernel.SSM_STEP_KERNEL),
    "a-cpu-backend":
        (None, CELL, {}, False, kernel.ELEMENTWISE),
    "the-reference":
        ("tpu", CELL, dict(flash=False), False, kernel.ELEMENTWISE),
    "a-mesh-of-two-chips":
        ("tpu", CELL, {}, True, kernel.ELEMENTWISE),
    "a-mesh-of-two-chips-interpreting":
        (None, "tiny-ssm-moe", dict(flash_interpret=True), True,
         kernel.ELEMENTWISE),
    "the-tiny-presets-state-on-a-tpu-backend":  # [8, 16]: no whole tile
        ("tpu", "tiny-ssm-moe", {}, False, kernel.ELEMENTWISE),
    "a-state-of-64-lanes":
        ("tpu", CELL, dict(ssm_state=64), False, kernel.ELEMENTWISE),
    "a-head-of-12":
        ("tpu", CELL, dict(ssm_head_dim=12), False, kernel.ELEMENTWISE),
    "a-head-of-24-interpreting":  # three registers: no butterfly
        (None, "tiny-ssm-moe", dict(flash_interpret=True, ssm_head_dim=24),
         False, kernel.ELEMENTWISE),
    "two-lane-tiles-of-state":
        ("tpu", CELL, dict(ssm_state=256), False, kernel.SSM_STEP_KERNEL),
    # the delta rule's matrix state: the same rule, its own kernel (ISSUE 52)
    "delta-interpreting-one-row-a-head":
        (None, DELTA_TINY, dict(flash_interpret=True), False,
         DELTA_STEP_KERNEL),
    "delta-the-cells-state-on-a-tpu-backend":
        ("tpu", DELTA_CELL, {}, False, DELTA_STEP_KERNEL),
    "delta-the-cells-state-lowered-for-a-tpu":
        (None, DELTA_CELL, dict(flash_force=True), False, DELTA_STEP_KERNEL),
    "delta-a-cpu-backend":
        (None, DELTA_CELL, {}, False, kernel.ELEMENTWISE),
    "delta-the-reference":
        ("tpu", DELTA_CELL, dict(flash=False), False, kernel.ELEMENTWISE),
    "delta-a-mesh-of-two-chips":
        ("tpu", DELTA_CELL, {}, True, kernel.ELEMENTWISE),
    "delta-a-mesh-of-two-chips-interpreting":
        (None, DELTA_TINY, dict(flash_interpret=True), True,
         kernel.ELEMENTWISE),
    "delta-one-row-a-head-on-a-tpu-backend":  # no group of 8 sublanes
        ("tpu", DELTA_TINY, {}, False, kernel.ELEMENTWISE),
    "delta-a-key-width-of-100":  # [50, 384]: six groups and two sublanes
        ("tpu", DELTA_CELL, dict(delta_key_dim=100), False,
         kernel.ELEMENTWISE),
    "delta-a-key-width-of-97":  # packs no rows: [97, 192], half a lane tile
        ("tpu", DELTA_CELL, dict(delta_key_dim=97), False,
         kernel.ELEMENTWISE),
    "delta-a-key-width-of-97-interpreting":
        (None, DELTA_CELL, dict(delta_key_dim=97, flash_interpret=True),
         False, kernel.ELEMENTWISE),
    "delta-a-key-width-of-1024":  # 256 columns of k and q a head
        ("tpu", DELTA_CELL, dict(delta_key_dim=1024), False,
         kernel.ELEMENTWISE),
    "delta-a-value-width-of-128":  # packs no rows: [96, 128]
        ("tpu", DELTA_CELL, dict(delta_value_dim=128), False,
         DELTA_STEP_KERNEL),
}


@pytest.mark.parametrize("case", sorted(BRANCHES))
def test_the_branch_is_decided_by_what_the_code_observes(
        case, cpu_devices, monkeypatch):
    """No option, no environment variable, no model's name: the backend,
    the mesh, ``flash`` and the state's shape."""
    backend, name, fields, two, want = BRANCHES[case]
    if backend is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = get_config(name, **fields)
    mesh = _two_chips(cpu_devices) if two else None
    assert ssm_moe.state_update_branch(cfg, mesh) == want


def test_the_decode_program_lowered_for_a_tpu_holds_the_leaf_once():
    """``decode_step`` of the cell's share, lowered for the TPU platform
    from here (``flash_force``): the kernel is lowered ONCE, in a function
    of its own that the six Mamba-2 layers call with their index, and the
    state leaf is no operand or result of anything else, so there is
    nothing for the compiler to copy it around: no slice of a layer out of
    it, no update of a layer into it, no copy.  (The compiled program is
    held to the same in tests/test_tpu_compile.py.)"""
    from p2p_llm_tunnel_tpu.models import transformer as T
    from p2p_llm_tunnel_tpu.utils.hlo import lower_for_tpu

    cfg = get_config(CELL, flash_force=True, vocab_size=1024)
    rows, seq = 9, 256
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: T.init_kv_cache(cfg, rows, seq))
    row = jax.ShapeDtypeStruct((rows,), jnp.int32)
    text = lower_for_tpu(
        jax.jit(lambda p, c, tok, pos: T.decode_step(
            cfg, p, c, tok, pos, kv_view=seq), donate_argnums=(1,)),
        params, cache, row, row).as_text()
    leaf = "x".join(str(d) for d in cache["ssm"].shape) + "xf32"
    named = [ln.strip() for ln in text.splitlines()
             if f"tensor<{leaf}>" in ln]
    kernels = [ln for ln in named if "tpu_custom_call" in ln]
    assert len(kernels) == 1 and kernel.SSM_STEP_KERNEL in kernels[0]
    layers = [ln for ln in named if " call @" in ln]
    assert len(layers) == ssm_moe.kind_counts(cfg)["M"] == 6
    assert len({ln.split("call @")[1].split("(")[0] for ln in layers}) == 1
    # besides: the two functions' signatures and results, nothing else
    assert sorted(ln.split()[0] for ln in named
                  if ln not in kernels + layers) == [
        "func.func", "func.func", "return", "return"]
