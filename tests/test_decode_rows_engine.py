"""The rows decode kernel (tests/test_decode_rows.py holds it to the einsum)
where the engine decides on it: the branch, by what the code can observe;
the plan that follows it (where the kernel runs, decode's view ladder is one
entry); and two engine runs, the dense model's and the window family's,
held to the einsum path's tokens, records and counter.
"""

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_tunnel_tpu.engine.engine import (
    EngineConfig,
    InferenceEngine,
    _program_key,
)
from p2p_llm_tunnel_tpu.engine.tokenizer import ByteTokenizer
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_attention_branch,
    decode_step,
    init_kv_cache,
    init_params,
)
from p2p_llm_tunnel_tpu.utils.metrics import global_metrics


# ---------------------------------------------------------------------------
# the branch: the default where it can run, by what the code can observe
# ---------------------------------------------------------------------------

TINY = get_config("tiny", vocab_size=ByteTokenizer().vocab_size)
INTERP = replace(TINY, flash_interpret=True)
INTERP_F32 = replace(get_config("tiny"), flash_interpret=True)


@pytest.mark.parametrize("cfg,view,kv,want", [
    (INTERP, 256, None, "pallas-rows"),
    (INTERP, 256, "int8", "einsum"),       # a quantised cache keeps the einsum
    (INTERP, 256, "int4", "einsum"),
    (INTERP, 192, None, "einsum"),         # does not tile by 128
    (TINY, 256, None, "einsum"),           # a CPU backend, not interpreting
    (replace(INTERP, flash=False), 256, None, "einsum"),  # the reference
    (replace(get_config("tiny-mla-moe"), flash_interpret=True), 256, None,
     "einsum"),                            # the latent family
])
def test_the_branch_is_decided_by_what_the_code_observes(cfg, view, kv, want):
    assert decode_attention_branch(cfg, None, view, kv) == want


#: (serve preset, the cell's --max-seq) of the benchmark's seven
#: configurations, and the decode read its cells were measured on over the
#: plain cache (their ``engine.decode_burst`` records' ``attn``).
CELL_PRESETS = {
    "mistral-7b": (1024, "pallas-rows"),
    "qwen2-7b": (1024, "pallas-rows"),
    "sarvam-105b-ep4s": (4096, "einsum"),          # latent planes
    "mimo-v2-flash-ep16s": (8192, "pallas-rows"),  # its full layers
    "sdar-30b-a3b-pp7s": (2048, "einsum"),         # passes over blocks
    "nemotron-3-nano-30b-a3b-ep2s": (4096, "pallas-rows"),  # attention layers
    "granite-4.0-h-micro": (2560, "pallas-rows"),  # 8 heads of 64 a row
}


@pytest.mark.parametrize("kv", [None, "int8"])
@pytest.mark.parametrize("preset", sorted(CELL_PRESETS))
def test_every_cells_preset_takes_the_branch_its_cell_was_measured_on(
        preset, kv):
    """The whole rule as data, on the TPU's gate (``flash_force``): the
    kernel over the plain cache where the family's planes are KV heads
    whose rows tile, the einsum elsewhere and over every int8 cache
    (``--kv-quant int8`` is the control a configuration's limits are read
    with).  The view changes nothing: the kernel reads the cache."""
    max_seq, plain = CELL_PRESETS[preset]
    cfg = replace(get_config(preset), flash_force=True)
    want = plain if kv is None else "einsum"
    assert decode_attention_branch(cfg, None, max_seq, kv, max_seq) == want
    assert decode_attention_branch(cfg, None, 128, kv, max_seq) == want


@pytest.mark.parametrize("view,max_seq,want", [
    (128, 512, "pallas-rows"),  # the kernel reads the cache, not the view
    (128, 320, "einsum"),       # a rung tiles, the cache does not
    (256, 320, "einsum"),
    (320, 320, "einsum"),
])
def test_the_rows_branch_is_decided_on_the_cache_not_the_view(
        view, max_seq, want):
    assert decode_attention_branch(INTERP, None, view, None, max_seq) == want


def test_a_cache_that_does_not_tile_keeps_the_einsum_at_every_rung():
    """An engine whose ``max_seq`` is no multiple of 128 still dispatches
    the rungs 128 and 256 of its view ladder: ``decode_step`` there is the
    einsum's, to the bit, whatever the backend would let a kernel do."""
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jnp.asarray([3, 5, 7, 11], jnp.int32)
    pos = jnp.asarray([0, 100, 126, 320], jnp.int32)
    got, want = (
        decode_step(c, params, init_kv_cache(cfg, 4, 320, jnp.float32),
                    toks, pos, kv_view=128)[0]
        for c in (INTERP_F32, cfg))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_tp_mesh_declines_the_rows_kernel(cpu_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(cpu_devices[:2]).reshape(1, 2), ("dp", "tp"))
    assert decode_attention_branch(INTERP, mesh, 256) == "einsum"
    mesh = Mesh(np.asarray(cpu_devices[:2]).reshape(2, 1), ("dp", "tp"))
    assert decode_attention_branch(INTERP, mesh, 256) == "pallas-rows"


def test_decode_step_on_the_rows_kernel_agrees_with_the_einsum():
    """Whole ``decode_step``, float32, over steps that cross a block edge;
    gemma's alternating windows and soft cap ride the same kernel."""
    for name in ("tiny", "tiny-gemma"):
        cfg = get_config(name)
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        toks = jnp.asarray([3, 5, 7, 11], jnp.int32)
        pos = jnp.asarray([0, 126, 255, 256], jnp.int32)  # the last: parked
        outs = {}
        for label, c in (("einsum", cfg),
                         ("rows", replace(cfg, flash_interpret=True))):
            cache = init_kv_cache(cfg, 4, 256, jnp.float32)
            t, p, seq = toks, pos, []
            for _ in range(3):
                logits, cache = decode_step(c, params, cache, t, p,
                                            kv_view=256)
                seq.append(np.asarray(logits))
                t = jnp.argmax(logits, -1).astype(jnp.int32)
                p = p + 1
            outs[label] = (np.stack(seq), np.asarray(cache["k"]))
        np.testing.assert_allclose(outs["rows"][0][:, :2],
                                   outs["einsum"][0][:, :2],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(outs["rows"][1][:, :2],
                                   outs["einsum"][1][:, :2],
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the plan: one decode program a step count where the kernel runs
# ---------------------------------------------------------------------------

ECFG = dict(
    model="tiny", num_slots=4, max_seq=512, dtype="float32", seed=0,
    decode_steps=4, decode_steps_eager=2, prefill_rows=2, prefill_chunk=16,
    prefix_cache=True, mux=True,
)


def _engine(mcfg=None, **over):
    return InferenceEngine(model_cfg=mcfg,
                           engine_cfg=EngineConfig(**{**ECFG, **over}),
                           tokenizer=ByteTokenizer())


def _decode_entries(eng):
    return [shape for kind, shape in eng.warmup_plan() if kind == "decode"]


def test_the_plan_holds_one_decode_entry_a_step_count_on_the_kernel_path():
    eng = _engine(INTERP)
    assert eng._decode_reads_rows()
    assert _decode_entries(eng) == [(512, 2), (512, 4)]
    # chunk programs read by einsum and keep their views
    views = {shape[2] for kind, shape in eng.warmup_plan() if kind == "chunk"}
    assert views == {128, 256, 512}


def test_the_einsum_paths_plan_is_unchanged():
    want = [(v, k) for v in (128, 256, 512) for k in (2, 4)]
    assert _decode_entries(_engine()) == want  # a CPU backend
    assert _decode_entries(_engine(INTERP, kv_quant="int8")) == want
    eng = _engine(INTERP, max_seq=320)  # 320 does not tile by 128
    assert not eng._decode_reads_rows()
    assert _decode_entries(eng) == [(v, k) for v in (128, 256, 320)
                                    for k in (2, 4)]
    rest = [e for e in _engine(INTERP).warmup_plan() if e[0] != "decode"]
    assert rest == [e for e in _engine().warmup_plan() if e[0] != "decode"]


def test_spec_programs_keep_their_views_beside_the_rows_kernel():
    eng = _engine(INTERP, spec_ngram=3, spec_k=2)
    assert _decode_entries(eng) == [(512, 2), (512, 4)]
    assert {s[0] for kind, s in eng.warmup_plan() if kind == "spec"} == \
        {128, 256, 512}


def _run_both(kernel_cfg, **over):
    """A run on the kernel path whose rows cross the old bucket edges at 128
    and 256: what was dispatched, the records, the counters' growth, and the
    same prompts' tokens on the einsum path."""
    from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

    names = ("engine_cold_compiles_total", "engine_decode_steps_total",
             "engine_decode_kernel_steps_total")
    prompts = [list(range(1, 101)), list(range(5, 125)),
               [7 + i % 50 for i in range(230)], [9, 8, 7]]

    async def collect(eng, ids):
        return [ev.token_id async for ev in eng.generate(
            ids, max_new_tokens=40, stop_ids=())]

    async def run(mcfg, trace):
        eng = _engine(mcfg, **over)
        await eng.start()
        await eng.warmup()
        planned = {_program_key(kind, shape)
                   for kind, shape in eng.warmup_plan()}
        global_tracer.clear()
        global_tracer.configure(enabled=trace, sample=1.0, capacity=65536)
        before = {n: global_metrics.counter(n) for n in names}
        try:
            toks = await asyncio.gather(*(collect(eng, p) for p in prompts))
            await asyncio.sleep(0.3)
            grown = {n: global_metrics.counter(n) - before[n] for n in names}
            records = [r for r in global_tracer.records()
                       if r.name == "engine.decode_burst"]
        finally:
            global_tracer.configure(enabled=False)
            global_tracer.clear()
            ready = set(eng._programs_ready)
            branches = dict(eng.attention_branches)
            await eng.stop()
        return toks, grown, records, ready, planned, branches

    kernel = asyncio.run(run(kernel_cfg, True))
    einsum = asyncio.run(run(None, True))
    return kernel, einsum


@pytest.fixture(scope="module")
def kernel_run():
    return _run_both(INTERP)


def test_no_program_outside_the_plan_runs_across_the_old_bucket_edges(
        kernel_run):
    (toks, grown, records, ready, planned, branches), _ = kernel_run
    assert all(len(t) == 40 for t in toks)
    assert grown["engine_cold_compiles_total"] == 0
    assert {k for k in ready if k.startswith("decode")} <= planned
    assert len({k for k in ready if k.startswith("decode")}) == 2
    assert branches["decode"] == ["pallas-rows"]
    # rows passed 128 and 256 while decoding: the old ladder's edges
    assert {r.attrs["view"] for r in records} == {512}


def test_the_kernel_path_emits_the_einsum_paths_tokens(kernel_run):
    (toks, *_), (want, *_) = kernel_run
    assert toks == want


def test_records_carry_the_branch_and_the_counter_is_held_to_them(kernel_run):
    for (_t, grown, records, *_), branch in zip(
            kernel_run, ("pallas-rows", "einsum")):
        assert records and {r.attrs["attn"] for r in records} == {branch}
        steps = sum(r.attrs["steps"] for r in records)
        assert steps == grown["engine_decode_steps_total"] > 0
        assert grown["engine_decode_kernel_steps_total"] == sum(
            r.attrs["steps"] for r in records if r.attrs["attn"] != "einsum")
    assert kernel_run[0][1]["engine_decode_kernel_steps_total"] == \
        kernel_run[0][1]["engine_decode_steps_total"]
    assert kernel_run[1][1]["engine_decode_kernel_steps_total"] == 0


# ---------------------------------------------------------------------------
# the family with window rings beside full planes (ISSUE 36): its full
# layers on the kernel, its plan of one decode entry a step count
# ---------------------------------------------------------------------------

SWA_INTERP = get_config("tiny-swa-moe", flash_interpret=True,
                        vocab_size=ByteTokenizer().vocab_size)


@pytest.fixture(scope="module")
def swa_run():
    return _run_both(SWA_INTERP, model="tiny-swa-moe")


def test_the_window_and_full_familys_plan_is_one_decode_entry_a_step_count():
    eng = _engine(SWA_INTERP, model="tiny-swa-moe")
    assert eng._decode_reads_rows()
    assert _decode_entries(eng) == [(512, 2), (512, 4)]
    views = {shape[2] for kind, shape in eng.warmup_plan() if kind == "chunk"}
    assert views == {128, 256, 512}  # chunk prefill reads by einsum
    rest = [e for e in eng.warmup_plan() if e[0] != "decode"]
    einsum = _engine(model="tiny-swa-moe")
    assert rest == [e for e in einsum.warmup_plan() if e[0] != "decode"]
    assert _decode_entries(einsum) == [
        (v, k) for v in (128, 256, 512) for k in (2, 4)]


def test_the_familys_kernel_path_emits_the_einsum_paths_tokens(swa_run):
    (toks, *_), (want, *_) = swa_run
    assert all(len(t) == 40 for t in toks)
    assert toks == want


def test_the_family_runs_no_program_outside_its_plan(swa_run):
    (_, grown, records, ready, planned, branches), (
        _, _, einsum_records, _, _, einsum_branches) = swa_run
    assert grown["engine_cold_compiles_total"] == 0
    assert {k for k in ready if k.startswith("decode")} <= planned
    assert len({k for k in ready if k.startswith("decode")}) == 2
    # rows passed 128 and 256 while decoding: the old ladder's edges
    assert {r.attrs["view"] for r in records} == {512}
    assert len({r.attrs["view"] for r in einsum_records}) > 1  # the ladder
    # /healthz says which layers the kernel covers
    assert branches["decode"] == [
        "pallas-rows (full layers; window layers: einsum over the ring)"]
    assert einsum_branches["decode"] == ["einsum"]


def test_the_familys_records_and_counter_say_the_kernel_engaged(swa_run):
    for (_t, grown, records, *_), branch in zip(
            swa_run, ("pallas-rows", "einsum")):
        assert records and {r.attrs["attn"] for r in records} == {branch}
        assert sum(r.attrs["steps"] for r in records) == \
            grown["engine_decode_steps_total"] > 0
    kernel, einsum = (run[1] for run in swa_run)
    assert kernel["engine_decode_kernel_steps_total"] == \
        kernel["engine_decode_steps_total"]
    assert einsum["engine_decode_kernel_steps_total"] == 0


def test_swa_decode_step_on_the_kernel_agrees_with_the_einsum():
    """Whole ``swa.decode_step``, float32, over steps that cross a block
    edge, a row at a block's last position and a parked one: logits, both
    kinds of plane and the routed layers' counts."""
    cfg = replace(SWA_INTERP, flash_interpret=False)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jnp.asarray([3, 5, 7, 11], jnp.int32)
    pos = jnp.asarray([0, 126, 255, 256], jnp.int32)  # the last: parked
    outs = {}
    for label, c in (("einsum", cfg), ("rows", SWA_INTERP)):
        cache = init_kv_cache(cfg, 4, 256, jnp.float32)
        t, p, seq = toks, pos, []
        for _ in range(3):
            logits, cache, stats = decode_step(c, params, cache, t, p,
                                               kv_view=256, with_stats=True)
            seq.append((np.asarray(logits), np.asarray(stats)))
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            p = p + 1
        outs[label] = seq, {k: np.asarray(v) for k, v in cache.items()}
    for step, ((got, got_stats), (want, want_stats)) in enumerate(zip(
            outs["rows"][0], outs["einsum"][0])):
        rows = 3 if step == 0 else 2  # the third row parks itself at 256
        np.testing.assert_allclose(got[:rows], want[:rows],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(got_stats, want_stats)
    for name, plane in outs["rows"][1].items():
        np.testing.assert_allclose(plane[:, :2], outs["einsum"][1][name][:, :2],
                                   rtol=2e-4, atol=2e-5)


def test_healthz_device_section_survives_a_dispatch_in_flight():
    """/healthz is answered from the serve loop's thread.  While the
    engine's thread is inside a dispatch ``kv_cache`` still names the arrays
    that dispatch has just donated; reading the devices off them raised
    ``Array has been deleted`` out of ``run_serve`` and the proxy lost the
    peer with every stream in flight (my chip runs, PR 33: 3 runs of 40
    polled beside their load)."""
    from p2p_llm_tunnel_tpu.engine.engine import device_section

    eng = _engine()
    want = eng.resident_devices()
    for leaf in eng.kv_cache.values():
        leaf.delete()  # what a donation leaves behind until the call returns
    assert eng.resident_devices() == want
    assert device_section([eng])["engines"] == [want]
