"""The window layers' decode on the rows kernel (ISSUE 56), through both tiny
window-and-full presets: ``ring_kernel_decline``'s table and /healthz's
wording, ``decode_step`` with every layer on the kernel (interpreted) against
the einsum path over steps that cross the ring's wrap, and an engine run on
each path: tokens, ``kv_rows_window_read`` on every record, the counter.

A file of its own beside tests/test_swa_moe.py and tests/test_laguna_moe.py
(the presets' other tests): each of those is already among the longest files
of a tier-1 run, and a file is one worker's.  The kernel alone against the
einsum: tests/test_decode_rows.py; compiled for a described v5e:
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.models import swa
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_step,
    init_kv_cache,
    init_params,
)
from tests import ring_rows


def _prompt(seed, n):
    """Token ids under 250: the engine's default tokenizer has 259."""
    return list(np.random.RandomState(seed).randint(1, 250, size=n))


#: (what the code can observe of a ring) -> how a window layer's decode
#: reads it where the full layers take the rows kernel (ISSUE 56).
RING_READS = {
    "the-tiny-presets-ring-of-16": ("tiny-swa-moe", dict(
        flash_interpret=True), 16, "does not tile"),
    "a-ring-in-whole-blocks-interpreting": ("tiny-swa-moe", dict(
        flash_interpret=True), 256, None),
    "a-ring-short-of-a-block": ("tiny-swa-moe", dict(
        flash_interpret=True), 192, "does not tile"),
    "mimos-rings-on-a-tpu-backend": ("mimo-v2-flash-ep16s", dict(
        flash_force=True), 640, None),
    "lagunas-rings-on-a-tpu-backend": ("laguna-s-2.1-ep8s", dict(
        flash_force=True), 1024, None),
    "a-window-key-row-that-is-no-whole-lane-tile": (  # 1 x 192
        "mimo-v2-flash-ep16s", dict(flash_force=True, window_kv_heads=1),
        640, "key row of 192"),
    "a-window-value-row-that-is-no-whole-lane-tile": (  # 8 x 72 = 576
        "mimo-v2-flash-ep16s", dict(flash_force=True, v_head_dim=72,
                                    n_kv_heads=16), 640, "value row of 576"),
}


@pytest.mark.parametrize("case", sorted(RING_READS))
def test_a_ring_follows_the_full_layers_where_it_tiles(case):
    """No flag and no model name: a ring's slots in whole blocks of 128 and
    a window layer's rows in whole lane tiles decide, /healthz's wording
    follows, and so does what ``decode_step`` traces: the kernel in every
    run of layers, or in the full layers' runs only."""
    from p2p_llm_tunnel_tpu.models.transformer import decode_branch_coverage
    from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import ROWS_KERNEL

    name, fields, ring, why = RING_READS[case]
    cfg = get_config(name, ring_positions=ring, **fields)
    decline = swa.ring_kernel_decline(cfg, ring)
    assert (decline is None) if why is None else (why in decline)
    assert swa.ring_read(cfg, ring) == (
        "rows of the ring" if why is None else "einsum over the ring")
    assert decode_branch_coverage(cfg, "pallas-rows", ring) == (
        f"pallas-rows (full layers; window layers: {swa.ring_read(cfg, ring)})")
    assert decode_branch_coverage(cfg, "einsum", ring) == "einsum"
    if not name.startswith("tiny"):
        return  # the shares at their sizes are tests/test_tpu_compile.py's
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cache = init_kv_cache(cfg, 2, 512, jnp.float32)
    row = jnp.zeros((2,), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, c: decode_step(
        cfg, p, c, row, row, kv_view=512))(params, cache))
    runs = swa.layer_runs(cfg)
    assert text.count(ROWS_KERNEL) == (
        len(runs) if why is None
        else sum(run.attn == "full" for run in runs))


@pytest.mark.parametrize("name,ring", [("tiny-swa-moe", 640),
                                       ("tiny-laguna", 384)])
def test_decode_on_the_ring_kernel_agrees_with_the_einsum_across_the_wrap(
        name, ring):
    """Rings in blocks of 128 (640 slots of two KV heads side by side and a
    sink; 384 of three, 9 query heads on them and the gate), a window of
    200: every layer on the kernel."""
    ring_rows.check_decode_steps_across_the_wrap(name, ring, 200)


SWA_RUN = dict(name="tiny-swa-moe", ring=640, window=200,
                prompts=[_prompt(21, 636), _prompt(22, 70)], new=9)


#: Rings of 384 (three KV heads side by side), 9 query heads on 3 KV heads in
#: a window layer and 6 in a full one, the gate after either.
LAGUNA_RUN = dict(name="tiny-laguna", ring=384, window=200,
                  prompts=[_prompt(21, 380), _prompt(22, 70)], new=9)
RUNS = {"tiny-swa-moe": (SWA_RUN, 768), "tiny-laguna": (LAGUNA_RUN, 512)}


@pytest.fixture(scope="module", params=sorted(RUNS))
def ring_run(request):
    run, max_seq = RUNS[request.param]
    return run, ring_rows.run_engine_both_paths(max_seq=max_seq, **run)


def test_the_ring_kernel_path_emits_the_einsum_paths_tokens_and_counts_its_read(
        ring_run):
    """A prompt that ends four slots short of the ring's wrap and one that
    leaves the ring part full, through the engine on both paths: tokens,
    ``kv_rows_window_read`` on every record, the counter held to them,
    /healthz's wording."""
    run, out = ring_run
    ring_rows.check_engine_records(out, **run)


