"""What the two window-and-full presets' tests ask of the ring form of the
rows kernel (ISSUE 56): ``decode_step`` with every layer on the kernel
(interpreted) against the einsum path over steps that cross the ring's
wrap, and an engine run whose records and counter say what the window
layers' read fetched."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    decode_step,
    init_kv_cache,
    init_params,
)
from p2p_llm_tunnel_tpu.ops.pallas_decode_attention import (
    ROWS_KERNEL,
    rows_block,
)
from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG, global_metrics
from tests.moe_records import dispatches_closed, tracing

COUNTERS = ("engine_kv_rows_window_total", "engine_kv_rows_window_read_total")


def configs(name, ring, window, **fields):
    """(the einsum path's config, the kernel path's): the tiny preset with
    rings of ``ring`` positions in whole blocks of 128 and a window that
    spans several of them."""
    cfg = get_config(name, ring_positions=ring, sliding_window=window,
                     **fields)
    return cfg, get_config(name, ring_positions=ring, sliding_window=window,
                           flash_interpret=True, **fields)


def check_decode_steps_across_the_wrap(name, ring, window, steps=5):
    """Rows two steps short of the wrap, part full, at position 0, wrapped
    many times and parked, over planes that hold values everywhere (a ring
    that had wrapped): logits, every plane and the routed counts of the two
    paths, and the kernel once a run of layers in the kernel path's trace."""
    einsum, kernel = configs(name, ring, window)
    block = rows_block(ring, kernel.kv_heads_of("window"))
    assert ring // block > 1 and window > block  # a run of several blocks
    seq = 8 * ring
    params = init_params(einsum, jax.random.PRNGKey(0), jnp.float32)
    toks = jnp.asarray([3, 5, 7, 11, 13, 17], jnp.int32)
    pos = jnp.asarray([ring - 2, window - 3, 0, 5 * ring + 77, seq,
                       2 * ring - 2], jnp.int32)
    live = [0, 1, 2, 3, 5]
    held = {k: 0.3 * jax.random.normal(jax.random.PRNGKey(i), v.shape, v.dtype)
            for i, (k, v) in enumerate(sorted(
                init_kv_cache(einsum, 6, seq, jnp.float32).items()))}
    text = str(jax.make_jaxpr(lambda c: decode_step(
        kernel, params, c, toks, pos, kv_view=seq))(held))
    from p2p_llm_tunnel_tpu.models.swa import layer_runs

    assert text.count(ROWS_KERNEL) == len(layer_runs(kernel))
    outs = {}
    for label, cfg in (("einsum", einsum), ("kernel", kernel)):
        step = jax.jit(lambda c, t, p, cfg=cfg: decode_step(
            cfg, params, c, t, p, kv_view=seq, with_stats=True))
        cache, t, p, seen = held, toks, pos, []
        for _ in range(steps):
            logits, cache, stats = step(cache, t, p)
            seen.append((np.asarray(logits), np.asarray(stats)))
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            p = p + 1
        outs[label] = seen, {k: np.asarray(v) for k, v in cache.items()}
    for (got, got_stats), (want, want_stats) in zip(
            outs["kernel"][0], outs["einsum"][0]):
        np.testing.assert_allclose(got[live], want[live],
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_array_equal(got_stats, want_stats)
    for leaf, plane in outs["kernel"][1].items():
        np.testing.assert_allclose(plane[:, live],
                                   outs["einsum"][1][leaf][:, live],
                                   rtol=3e-4, atol=3e-5)


def blocks_by_hand(p, ring, window, block):
    """The ring blocks that hold a position of ``(p - window, p]``, oldest
    first, one position at a time."""
    order = []
    for held in range(max(0, p - window + 1), p + 1):
        if held % ring // block not in order:
            order.append(held % ring // block)
    return order


def _fetched_by_hand(first, steps, ring, window, block):
    """Positions one window layer's kernel read fetches for a row that
    decodes ``steps`` tokens from position ``first``: whole blocks, each
    block that holds a position of the window once a step."""
    return block * sum(len(blocks_by_hand(p, ring, window, block))
                       for p in range(first, first + steps))


def run_engine_both_paths(name, ring, window, prompts, new, max_seq):
    """The same prompts through an engine on each path (segments of 64 so
    that the longer prompt wraps the ring in chunk prefill and the shorter
    leaves it part full) -> {path: (tokens, counters' growth, records,
    /healthz's decode coverage)}."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    assert all(n in METRICS_CATALOG for n in COUNTERS)

    async def run(cfg):
        eng = InferenceEngine(model_cfg=cfg, engine_cfg=EngineConfig(
            model=name, num_slots=2, max_seq=max_seq, dtype="float32",
            decode_steps=4, decode_steps_eager=2, mux=True, prefix_cache=True,
            prefix_pool_blocks=16, prefill_chunk=64, prefill_rows=2))
        assert eng._ring == ring
        await eng.start()
        try:
            before = [global_metrics.counter(n) for n in COUNTERS]

            async def one(prompt):
                return [ev.token_id async for ev in eng.generate(
                    prompt, max_new_tokens=new, stop_ids=())]

            toks = await asyncio.gather(*(one(p) for p in prompts))
            await dispatches_closed(eng)
            grew = [global_metrics.counter(n) - b
                    for n, b in zip(COUNTERS, before)]
            from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

            records = [r for r in global_tracer.records()
                       if r.name in ("engine.decode_burst",
                                     "engine.prefill_segment")]
            return toks, grew, records, dict(eng.attention_branches)
        finally:
            await eng.stop()

    out = {}
    for path, cfg in zip(("einsum", "kernel"), configs(
            name, ring, window, vocab_size=259)):
        with tracing():
            out[path] = asyncio.run(asyncio.wait_for(run(cfg), 600))
    return out


def check_engine_records(out, name, ring, window, prompts, new):
    """The kernel path emits the einsum path's tokens; every record carries
    ``kv_rows_window_read``: a prefill segment's is its need, a decode
    burst's ``ring`` a live row, step and layer on the einsum and the work
    list's blocks on the kernel (recounted here one position at a time, over
    the whole run: bursts overlap requests, their sum does not); the counter
    grows by the records' sum; /healthz names the ring's read."""
    cfg = get_config(name)
    lw = cfg.attn_kinds.count("window")
    block = rows_block(ring, cfg.kv_heads_of("window"))
    assert out["kernel"][0] == out["einsum"][0]
    assert all(len(t) == new for t in out["kernel"][0])
    for path, (_toks, grew, records, branches) in out.items():
        segs = [r.attrs for r in records if r.name == "engine.prefill_segment"]
        bursts = [r.attrs for r in records if r.name == "engine.decode_burst"]
        assert segs and bursts
        assert all(a["kv_rows_window_read"] == a["kv_rows_window"] > 0
                   for a in segs)
        assert [sum(a[k] for a in segs + bursts) for k in (
            "kv_rows_window", "kv_rows_window_read")] == grew
        # a request's first token is prefill's: new - 1 decode steps a row,
        # and what a burst ran past a row's end is no live row-step
        row_steps = sum(a["live_rows"] * a["steps"] for a in bursts)
        read = sum(a["kv_rows_window_read"] for a in bursts)
        if path == "einsum":
            assert read == lw * ring * row_steps
            assert branches["decode"] == ["einsum"]
        else:
            assert read < lw * ring * row_steps
            assert read >= lw * sum(
                _fetched_by_hand(len(p), new - 1, ring, window, block)
                for p in prompts)
            assert read % (lw * block) == 0
            assert branches["decode"] == [
                "pallas-rows (full layers; window layers: rows of the ring)"]
        need = sum(a["kv_rows_window"] for a in bursts)
        assert 0 < need <= read
