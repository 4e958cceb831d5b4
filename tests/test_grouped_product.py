"""The grouped expert product that streams each touched expert once
(ISSUE 39): ``ops/pallas_grouped_matmul.py``'s kernel against
``jax.lax.ragged_dot`` on the same operands (interpreted on the CPU), and
``models/moe.py``'s rule for which of the two a program takes."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.ops import pallas_grouped_matmul as pg

# (rows, K, N, layers in the stack, held experts a layer, the layer, sizes):
# the three cells' decode shapes at a sixteenth of their widths
SDAR = (200, 128, 48, 3, 8)      # every row held, 25 rows an expert
SARVAM = (72, 256, 128, 2, 8)    # a quarter of the rows held
MIMO = (88, 256, 128, 3, 4)      # a sixteenth held
CASES = {
    "empty groups between full ones":
        (SDAR, 1, [0, 30, 0, 0, 5, 140, 1, 24]),
    "one group takes every row": (SDAR, 2, [0, 0, 0, 200, 0, 0, 0, 0]),
    "the first group takes every row": (SDAR, 0, [200, 0, 0, 0, 0, 0, 0, 0]),
    "rows past the held groups": (SARVAM, 0, [3, 0, 9, 1, 0, 0, 4, 1]),
    "a layer in the middle of the stack": (MIMO, 1, [2, 0, 3, 1]),
    "the last layer of the stack": (MIMO, 2, [0, 0, 0, 6]),
    "no row held": (SARVAM, 1, [0] * 8),
    "groups under, at and over a window":
        ((424, 128, 128, 2, 8), 1, [16, 17, 33, 65, 129, 1, 130, 31]),
    "a row count that is no multiple of the row tile":
        ((77, 128, 128, 1, 4), 0, [20, 7, 0, 50]),
    "fewer rows than a window": ((8, 128, 128, 2, 4), 1, [1, 0, 5, 2]),
}


@pytest.fixture
def constants(monkeypatch):
    """Set the kernel's module constants for one test: the jitted calls it
    keeps by shape were built under the old ones."""
    def set_(**values):
        for name, value in values.items():
            monkeypatch.setattr(pg, name, value)
        pg._grouped_call.cache_clear()
    yield set_
    pg._grouped_call.cache_clear()


def _operands(shape, dtype):
    m, k, n, layers, held = shape
    keys = jax.random.split(jax.random.PRNGKey(m + k), 2)
    rows = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    experts = (jax.random.normal(keys[1], (layers * held, k, n), jnp.float32)
               * k ** -0.5).astype(dtype)
    return rows, experts


@pytest.mark.parametrize("dtype,out", [
    (jnp.bfloat16, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
    (jnp.float32, jnp.float32)], ids=["bf16-f32", "bf16-bf16", "f32-f32"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_ragged_dot_on_the_same_operands(case, dtype, out):
    shape, layer, sizes = CASES[case]
    m, k, n, layers, held = shape
    rows, experts = _operands(shape, dtype)
    sizes = np.asarray(sizes, np.int32)
    stacked = np.zeros(layers * held, np.int32)
    stacked[layer * held:(layer + 1) * held] = sizes
    want = jax.lax.ragged_dot(rows, experts, jnp.asarray(stacked),
                              preferred_element_type=out)
    got = pg.grouped_matmul(
        rows, experts, pg.visit_list(jnp.asarray(sizes), layer * held),
        out_dtype=out, interpret=True)
    assert got.shape == (m, n) and got.dtype == out
    live = int(sizes.sum())
    # float32 accumulation over all of K in both, in another order; one
    # rounding to the result's type
    tol = 2e-2 if out == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got[:live], np.float32), np.asarray(want[:live], np.float32),
        rtol=tol, atol=tol)
    # rows past the held groups: weightless in moe_mlp, zeros here
    assert not np.asarray(got[live:], np.float32).any()


def test_an_experts_matrix_in_column_tiles_is_the_same_product(constants):
    """A matrix over ``TILE_BYTES`` goes through the ring in column tiles
    (sarvam's and mimo's ``[4096, 2048]``: four): the tiles of one visit and
    of the next touched group follow each other through ``DEPTH`` slots."""
    shape, layer, sizes = (120, 128, 512, 2, 8), 1, [0, 30, 0, 0, 5, 60, 1, 24]
    constants(TILE_BYTES=128 * 128 * 2)
    assert pg.column_tile(128, 512, 2) == 128
    rows, experts = _operands(shape, jnp.bfloat16)
    stacked = np.zeros(16, np.int32)
    stacked[8:] = sizes
    want = jax.lax.ragged_dot(rows, experts, jnp.asarray(stacked),
                              preferred_element_type=jnp.float32)
    got = pg.grouped_matmul(
        rows, experts, pg.visit_list(jnp.asarray(sizes, jnp.int32), 8),
        out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_the_visit_list_names_the_touched_groups_in_order():
    meta, nonempty, starts, sizes = pg.visit_list(
        jnp.asarray([0, 3, 0, 0, 2, 0], jnp.int32), 12)
    assert list(np.asarray(meta)) == [12, 5]  # the first expert, rows held
    assert list(np.asarray(nonempty)) == [1, 1, 4, 4, 4, 6, 6]
    assert list(np.asarray(starts)) == [0, 0, 3, 3, 3, 5, 2**31 - 1]
    assert list(np.asarray(sizes)) == [0, 3, 0, 0, 2, 0]


# the cells' presets, interpreting so that a CPU backend may answer the kernel
SDAR_CFG = get_config("sdar-30b-a3b-pp7s", flash_interpret=True)
SARVAM_CFG = get_config("sarvam-105b-ep4s", flash_interpret=True)
MIMO_CFG = get_config("mimo-v2-flash-ep16s", flash_interpret=True)


@pytest.mark.parametrize("cfg,tokens,want", [
    (SDAR_CFG, 49 * 4, pg.GROUPED_KERNEL),     # decode: [1568, 2048]
    (SDAR_CFG, 512, pg.GROUPED_KERNEL),        # chunk, one row: [4096, ..]
    (SDAR_CFG, 1024, pg.GROUPED_KERNEL),       # chunk, two rows: [8192, ..]
    (SARVAM_CFG, 33, pg.GROUPED_KERNEL),       # decode: [264, 4096]
    (SARVAM_CFG, 1024, pg.GROUPED_KERNEL),     # chunk: [8192, 4096]
    (MIMO_CFG, 49, pg.GROUPED_KERNEL),         # decode: [392, 4096]
    (MIMO_CFG, 1024, pg.GROUPED_KERNEL),       # chunk: [8192, 4096]
    # more rows an expert than the contest measured: 256 and 128
    (SDAR_CFG, 8 * 512, moe.RAGGED),
    (MIMO_CFG, 8 * 512, moe.RAGGED),
], ids=["sdar-decode", "sdar-chunk-1", "sdar-chunk-2", "sarvam-decode",
        "sarvam-chunk", "mimo-decode", "mimo-chunk", "sdar-8-rows",
        "mimo-8-rows"])
def test_the_rule_answers_the_cells_six_shapes(cfg, tokens, want):
    assert moe.grouped_product_branch(cfg, None, tokens) == want


def test_the_rule_keeps_ragged_dot_off_the_tpu_under_a_mesh_and_for_slices(
        cpu_devices):
    from jax.sharding import Mesh

    tokens = 49 * 4
    assert moe.grouped_product_branch(SDAR_CFG, None, tokens) == \
        pg.GROUPED_KERNEL
    # a CPU backend that does not interpret
    plain = replace(SDAR_CFG, flash_interpret=False)
    assert jax.default_backend() == "cpu"
    assert moe.grouped_product_branch(plain, None, tokens) == moe.RAGGED
    # the reference, as in attention
    assert moe.grouped_product_branch(
        replace(SDAR_CFG, flash=False), None, tokens) == moe.RAGGED
    # GSPMD partitions ragged_dot, not a kernel
    for axes in (("dp", "ep"), ("dp", "tp")):
        mesh = Mesh(np.asarray(cpu_devices[:2]).reshape(1, 2), axes)
        assert moe.grouped_product_branch(
            SDAR_CFG, mesh, tokens) == moe.RAGGED
    mesh = Mesh(np.asarray(cpu_devices[:1]).reshape(1, 1), ("dp", "ep"))
    assert moe.grouped_product_branch(
        SDAR_CFG, mesh, tokens) == pg.GROUPED_KERNEL
    # a layer's slice of the stack would be copied for the kernel
    assert moe.grouped_product_branch(
        SDAR_CFG, None, tokens, stacked=False) == moe.RAGGED


def test_the_rule_holds_the_kernel_to_what_vmem_holds():
    """The kernel keeps a block of rows, its result and a ring of an
    expert's tiles in VMEM: experts too wide for that keep ``ragged_dot``
    however few rows they get."""
    wide = replace(SDAR_CFG, dim=32768, moe_ffn_dim=16384)
    assert pg.vmem_bytes(1568, 32768, 16384, jnp.bfloat16,
                         jnp.float32) > moe.KERNEL_VMEM
    assert moe.grouped_product_branch(wide, None, 49 * 4) == moe.RAGGED
    # the cells' largest: sarvam's chunk prefill, 60 MiB
    assert pg.vmem_bytes(8192, 4096, 2048, jnp.bfloat16,
                         jnp.bfloat16) == 60 * 2**20


def test_rows_go_through_in_blocks_past_row_block(constants):
    """More sorted rows than ``ROW_BLOCK`` (chunk prefill's 4,096 and
    8,192) go through a block at a time: a group across an edge is
    multiplied on both sides of it, a block past the held rows is zeros."""
    constants(ROW_BLOCK=64)
    assert pg.row_blocks(300) == (64, 5) and pg.row_blocks(60) == (64, 1)
    for sizes in ([0, 30, 0, 0, 5, 140, 1, 80], [64, 64, 0, 64, 1, 62, 1, 0],
                  [0, 0, 0, 3, 0, 0, 0, 0], [0] * 8):
        shape = (300, 128, 128, 2, 8)
        rows, experts = _operands(shape, jnp.bfloat16)
        stacked = np.zeros(16, np.int32)
        stacked[8:] = sizes
        want = jax.lax.ragged_dot(rows, experts, jnp.asarray(stacked),
                                  preferred_element_type=jnp.float32)
        got = pg.grouped_matmul(
            rows, experts, pg.visit_list(jnp.asarray(sizes, jnp.int32), 8),
            out_dtype=jnp.float32, interpret=True)
        live = sum(sizes)
        np.testing.assert_allclose(np.asarray(got[:live]),
                                   np.asarray(want[:live]),
                                   rtol=1e-5, atol=1e-5)
        assert got.shape == (300, 128) and not np.asarray(got[live:]).any()


@pytest.mark.parametrize("name", ["tiny-sdar-moe", "tiny-mla-moe",
                                  "tiny-swa-moe"])
def test_moe_mlp_on_the_kernel_agrees_with_ragged_dot(name):
    """The routed layer whole, the experts read where they lie in the stack
    of all layers, a layer in the middle: the kernel's branch against the
    parent's, same weights, same tokens, float32 and bfloat16."""
    cfg = get_config(name)
    layers, held = 3, cfg.experts_held[1]
    dm, f, e = cfg.dim, cfg.expert_dim, cfg.n_experts
    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)):
        keys = jax.random.split(jax.random.PRNGKey(7), 6)
        stacked = {
            "moe_gate": jax.random.normal(keys[0], (layers * held, dm, f)),
            "moe_up": jax.random.normal(keys[1], (layers * held, dm, f)),
            "moe_down": jax.random.normal(keys[2], (layers * held, f, dm)),
        }
        stacked = {k: (v * 0.1).astype(dtype) for k, v in stacked.items()}
        blk = {"router": jax.random.normal(keys[3], (dm, e))}
        if cfg.router_bias:
            blk["router_bias"] = jnp.zeros((e,))
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            blk.update(
                shared_gate=(jax.random.normal(keys[4], (dm, fs)) * 0.1
                             ).astype(dtype),
                shared_up=(jax.random.normal(keys[4], (dm, fs)) * 0.1
                           ).astype(dtype),
                shared_down=(jax.random.normal(keys[5], (fs, dm)) * 0.1
                             ).astype(dtype))
        h = jax.random.normal(keys[5], (3, 5, dm)).astype(dtype)
        outs = [
            moe.moe_mlp(c, blk, h, jax.nn.silu, stacked=stacked, layer=1)
            for c in (cfg, replace(cfg, flash_interpret=True))]
        (want, want_stats), (got, got_stats) = outs
        np.testing.assert_array_equal(np.asarray(want_stats),
                                      np.asarray(got_stats))
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)
