"""``tiny-laguna`` through the engine (the programs alone are
tests/test_laguna_moe.py): a prefix-pool hit that restores a ring, held to
the benchmark's plain reference, /healthz, the branches and the refusals.
"""

from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest

from benchmarks import laguna_moe_reference as bench
from p2p_llm_tunnel_tpu.models import moe
from p2p_llm_tunnel_tpu.models.config import get_config
from tests.laguna_moe_tiny import (
    ATOL,
    RING,
    WINDOW,
    _config,
    _prompt,
    as_reference,
    tiny,
)


# ---- the engine -----------------------------------------------------------------

def _engine(model_name="tiny-laguna-ep2s", model_cfg=None, **kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(model_cfg=model_cfg, engine_cfg=EngineConfig(
        model=model_name, num_slots=2, max_seq=128, dtype="float32",
        decode_steps=2, **kw))


def test_a_prefix_hit_through_the_engine_reads_like_the_reference():
    """Prompts that share their first blocks, one after another through the
    engine (chunk prefill in segments of 16, the pool, decode bursts): the
    later ones hit the pool, are restored into rings, and every generated
    token's log-probability is the reference's."""
    from tests.swa_moe_tiny import _generate

    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=32,
                  prefill_chunk=16)
    assert eng._ring == RING and not eng.config_fences
    shapes = bench.shapes_of(dict(_config(True), vocab_size=259))
    base = _prompt(9, 70)
    prompts = [base, base[:55], base[:64] + _prompt(10, 13)]
    hit0 = global_metrics.counter("engine_prefix_hit_tokens_total")
    outs = _generate(eng, prompts)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit0 \
        == 48 + 64
    weights = as_reference(eng.params)
    for prompt, (tokens, values) in zip(prompts, outs):
        ref = np.asarray(bench.forward_logprobs(shapes, weights,
                                                prompt + tokens))
        n = len(prompt)
        np.testing.assert_allclose(
            values, [ref[n - 1 + j, t] for j, t in enumerate(tokens)],
            atol=ATOL)


def test_healthz_states_the_heads_by_kind_the_gate_the_ring_and_the_share():
    eng = _engine(prefix_cache=True, prefix_pool_blocks=8, mux=True,
                  prefill_chunk=16)
    section = eng._model_section()
    assert section["attention"] == {
        "query_heads": {"full": 6, "window": 9},
        "rotary_columns": {"full": 8, "window": 16},
        "gate": "per-head", "qk_norm": True}
    cache = section["cache"]
    assert cache["form"] == "window_rings+full"
    assert cache["ring_positions"] == RING and cache["window"] == WINDOW
    assert cache["kinds"]["window"]["layers"] == 6
    assert cache["bytes_per_token"] == tiny.CACHE_BYTES * 2  # float32 here
    assert section["experts"] == {"held": 8, "first": 0, "of": 16}
    assert section["layers"] == {"held": 8, "of": 8}
    assert section["expert_products"]["decode"] == moe.RAGGED
    # a model with one answer for both kinds says so in the same place
    mimo = _engine("tiny-swa-moe")._model_section()["attention"]
    assert mimo == {"query_heads": {"full": 4, "window": 4},
                    "rotary_columns": {"full": 8, "window": 8},
                    "gate": None, "qk_norm": False}


def test_the_branches_answer_from_the_shares_shapes():
    """What ``decode_attention_branch`` and ``grouped_product_branch`` answer
    for the cell's share on a TPU backend: a KV row of 8 x 128 = 1,024
    values is whole lane tiles, so the full layers take the rows kernel; 650
    sorted rows of a decode step (65 x 10) and 10,240 of a chunk dispatch
    (2 x 512 x 10) are under 64 a published expert, widths 3072 and 1024
    are whole tiles and the blocks fit VMEM, so both take the grouped
    kernel.  On the CPU both are the references."""
    from p2p_llm_tunnel_tpu.models.transformer import (
        decode_attention_branch,
        decode_kernel_decline,
    )
    from p2p_llm_tunnel_tpu.ops.pallas_grouped_matmul import GROUPED_KERNEL

    share = get_config("laguna-s-2.1-ep8s")
    chip = replace(share, flash_force=True)
    assert decode_kernel_decline(chip, None, 6144) is None
    assert decode_attention_branch(chip, None, 6144, None, 6144) \
        == "pallas-rows"
    assert decode_attention_branch(chip, None, 6144, "int8", 6144) == "einsum"
    assert decode_attention_branch(share, None, 6144, None, 6144) == "einsum"
    assert moe.grouped_product_branch(chip, None, 65) == GROUPED_KERNEL
    assert moe.grouped_product_branch(chip, None, 2 * 512) == GROUPED_KERNEL
    assert moe.grouped_product_branch(share, None, 65) == moe.RAGGED


def test_what_the_family_lacks_is_refused_at_start_up():
    with pytest.raises(ValueError, match=r"window rings beside full planes"
                                         r".* cannot be served with --"):
        _engine("tiny-laguna", quant="int8")
