"""The pattern family's layer that is a mixer AND a dense gated MLP, with four
published multipliers and a head tied to the embedding (``tiny-ssm-mlp``:
granite-4.0-h-micro at a size the CPU runs) against the benchmark's plain
reference, benchmarks/granite_hybrid_reference.py, on the program's own
seeded random weights: whole-prompt prefill, chunk prefill in segments then
decode through state and cache, each published piece left out, a prefix hit
that restores a snapshot, the state kernel at one group, the engine end to
end through engine/api.py, /healthz, the published preset's count of
parameters, the configuration file, and the tiny cell in one process.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import granite_hybrid_reference as bench
from p2p_llm_tunnel_tpu.models import ssm, ssm_moe
from p2p_llm_tunnel_tpu.models.config import get_config
from p2p_llm_tunnel_tpu.models.transformer import (
    chunk_prefill_into_cache,
    decode_attention_branch,
    decode_step,
    init_kv_cache,
    init_params,
    prefill,
)
from p2p_llm_tunnel_tpu.ops.pallas_ssm_step import (
    SSM_STEP_KERNEL,
    live_rows_worklist,
    shapes_decline,
    ssm_step_rows,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
import tinycell_granite as tiny  # noqa: E402

ROWS, MAX_SEQ = 4, 128
# float32 program against the float32 reference at `highest`: sums taken in
# another order (a chunked scan against the recurrence, 6 x the embedding
# into a stream of that size) differ in the last places of a float32.
ATOL = 2e-4
SHAPES = bench.shapes_of(tiny.CONFIG)


def _as_reference(params):
    """The program's parameter tree in the reference's layout (its norms
    and ``D`` are ones and not stored there)."""
    assert all(float(jnp.abs(params[g]["norm"] - 1).max()) == 0
               for g in ("mamba", "attn", "mlp"))
    assert float(jnp.abs(params["mamba"]["d_skip"] - 1).max()) == 0
    return {
        "embed": params["embed"],
        "mlp": {k: params["mlp"][k] for k in ("w_in", "w_out")},
        "attn": {k: params["attn"][k] for k in ("wq", "wk", "wv", "wo")},
        "mamba": {k: params["mamba"][k] for k in (
            "w_in", "conv_w", "conv_b", "w_out", "dt_bias", "a_log")},
    }


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny-ssm-mlp")
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
    assert cfg.mixer_kinds == "MM*M" and cfg.mixer_mlp and cfg.ssm_groups == 1
    assert cfg.tie_embeddings and "lm_head" not in params
    assert 1.0 not in (cfg.embed_multiplier, cfg.residual_multiplier,
                       cfg.logits_divisor)
    assert cfg.query_scale != cfg.head_dim ** -0.5
    assert {k: v.shape for k, v in params["mlp"].items()} == {
        "norm": (4, 64), "w_in": (4, 64, 2 * 96), "w_out": (4, 96, 64)}
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, ROWS, MAX_SEQ, 32), "v": (1, ROWS, MAX_SEQ, 32),
        "ssm": (3, ROWS, 4, 8, 16), "conv": (3, ROWS, 3 * (32 + 2 * 16))}
    assert SHAPES["kinds"] == ("mamba", "mamba", "attention", "mamba")


def test_whole_prompt_prefill_matches_the_reference(model):
    cfg, params = model
    prompt = _prompt(3, 43)
    got, rows = _whole(cfg, params, prompt)
    np.testing.assert_allclose(got, _want(params, prompt), atol=ATOL)
    assert rows["full"][0].shape == (1, 1, 48, 32)
    assert rows["state"][0].shape == (3, 1, 4, 8, 16)


def _mlp_without_its_norm(cfg, blk, x, dtype):
    from p2p_llm_tunnel_tpu.models.transformer import _act

    ab = ssm_moe._mm32(x.astype(dtype), blk["w_in"], cfg.act_quant)
    gated = _act(cfg, ab[..., :cfg.ffn_dim]) * ab[..., cfg.ffn_dim:]
    return ssm_moe._mm32(gated.astype(dtype), blk["w_out"], cfg.act_quant)


LEFT_OUT = {
    "embedding_multiplier": dict(embed_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "attention_multiplier": dict(query_scale=None),
    "logits_scaling": dict(logits_divisor=1.0),
    "tie_word_embeddings": dict(tie_embeddings=False),
    "the-mlps-own-norm": {},
}


@pytest.mark.parametrize("piece", sorted(LEFT_OUT))
def test_each_published_piece_left_out_fails_the_tolerance(model, piece,
                                                           monkeypatch):
    """The program with one multiplier at the identity, an untied head, or
    the MLP fed the stream without its own norm is another model: whole-
    prompt log-probabilities leave the reference's by far more than
    ``ATOL``."""
    cfg, params = model
    cfg = replace(cfg, **LEFT_OUT[piece])
    if piece == "tie_word_embeddings":
        params = dict(params, lm_head=init_params(
            cfg, jax.random.PRNGKey(11), jnp.float32)["lm_head"])
    if piece == "the-mlps-own-norm":
        monkeypatch.setattr(ssm_moe, "_mlp", _mlp_without_its_norm)
    prompt = _prompt(3, 43)
    # (a function of its own: the patched one is read when it is traced,
    # and a jit of ``prefill`` itself would find the stated model's trace)
    got, _ = _whole(cfg, params, prompt, jax.jit(
        lambda c, *rest: prefill(c, *rest), static_argnums=(0,)))
    apart = np.abs(got - _want(params, prompt)).max()
    assert apart > 50 * ATOL, apart


def _chunk(cfg, params, cache, prompt, start, end, slot, width=16):
    """One segment beside a padding row on the scratch slot."""
    tok = jnp.zeros((2, width), jnp.int32).at[0, :end - start].set(
        jnp.array(prompt[start:end]))
    return _chunk_prefill(
        cfg, params, tok, jnp.array([end - start, 1]), jnp.array([start, 0]),
        cache, jnp.array([slot, ROWS - 1]), kv_view=MAX_SEQ,
        return_all_logits=True)[:2]


@pytest.mark.parametrize("update", ["elementwise", "kernel"])
def test_chunk_prefill_in_segments_then_decode_through_the_cache(model,
                                                                 update):
    """The prompt as chunk-prefill segments of uneven lengths, then 40
    decode steps (the state by ``ssm.ssm_step`` or by the kernel over the
    live rows, interpreted; the attention then by the rows kernel over 2
    heads of 16 at the stated score scale), against ONE full forward of the
    reference."""
    cfg, params = model
    full = _prompt(3, 43) + _prompt(4, 40)
    want = _want(params, full)
    cache = init_kv_cache(cfg, ROWS, MAX_SEQ, jnp.float32)
    for a, b in [(0, 16), (16, 27), (27, 43)]:
        logits, cache = _chunk(cfg, params, cache, full, a, b, 1)
        np.testing.assert_allclose(_logprobs(logits[0, :b - a]), want[a:b],
                                   atol=ATOL)
    if update == "kernel":
        cfg = replace(cfg, flash_interpret=True)
        assert ssm_moe.state_update_branch(cfg, None) == SSM_STEP_KERNEL
        assert decode_attention_branch(cfg, None, MAX_SEQ) == "pallas-rows"
    for p in range(43, 83):
        tokens = jnp.zeros((ROWS,), jnp.int32).at[1].set(full[p])
        positions = jnp.full((ROWS,), MAX_SEQ).at[1].set(p)
        logits, cache = _decode_step(cfg, params, cache, tokens, positions,
                                     kv_view=MAX_SEQ)
        np.testing.assert_allclose(_logprobs(logits[1]), want[p], atol=ATOL)


# ---- the kernels that exist, at these shapes -----------------------------------

def test_the_state_kernel_at_one_group_is_the_step(rows=5, layers=2):
    """``ssm_step_rows`` at ``G`` 1 (one ``B`` and ``C`` for every head) over
    the live rows of a stacked leaf against ``ssm.ssm_step``; a parked row's
    state stays to the bit.  The published shape passes the kernel's rule."""
    assert shapes_decline(64, 64, 128, 1) is None
    h, p, n = 8, 8, 128
    rng = np.random.RandomState(7)
    leaf = jnp.asarray(rng.randn(layers, rows, h, p, n), jnp.float32)
    x = jnp.asarray(rng.randn(rows, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (rows, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    bm, cm = (jnp.asarray(rng.randn(rows, 1, n), jnp.float32)
              for _ in range(2))
    positions = jnp.array([3, 64, 0, 64, 9])  # rows 1 and 3 are parked
    work = live_rows_worklist(positions, 64)
    y, out = ssm_step_rows(leaf, 1, work, x, dt, a, bm, cm, interpret=True)
    want_y, want = ssm.ssm_step(x, dt, a, bm, cm, leaf[1])
    live = np.asarray(positions) < 64
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[1])[live],
                               np.asarray(want)[live], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[1])[~live],
                                  np.asarray(leaf[1])[~live])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))


def test_heads_of_64_decode_through_the_rows_kernel():
    """A row of 8 KV heads of 64 is four lane tiles: the rule that asks the
    ROW's width lets the published shape take the rows kernel (compiled for
    a described v5e: tests/test_tpu_compile.py), a CPU backend keeps the
    einsum, and the score scale is the stated one, not ``64 ** -0.5``."""
    cfg = get_config("granite-4.0-h-micro")
    assert cfg.query_scale == 0.015625 != cfg.head_dim ** -0.5
    assert decode_attention_branch(cfg, None, 2560) == "einsum"
    forced = replace(cfg, flash_force=True)
    assert decode_attention_branch(forced, None, 2560) == "pallas-rows"
    assert ssm_moe.state_update_branch(forced, None) == SSM_STEP_KERNEL
    # a row that is no whole lane tiles keeps the einsum on the chip too
    narrow = replace(forced, n_kv_heads=1)
    assert decode_attention_branch(narrow, None, 2560) == "einsum"


# ---- the published preset -------------------------------------------------------

def test_the_published_preset_counts_3191_m_parameters():
    cfg = get_config("granite-4.0-h-micro")
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert abs(count - 3191e6) < 1e6, count
    assert cfg.mixer_kinds.count("M") == 36
    assert [i for i, k in enumerate(cfg.mixer_kinds) if k == "*"] \
        == [5, 15, 25, 35]
    # a slot's state: 36 x (64 x 64 x 128 float32 + 3 x 4352 bfloat16)
    assert ssm_moe.state_bytes_per_slot(cfg) == 36 * (2097152 + 26112) \
        == 76_437_504


def test_the_configuration_file_keeps_every_published_key():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        body = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(row for row in map(json.loads, f)
                         if row["name"] == "granite-4.0-h-micro")
    assert body["reduced"] == [] and body["source"] == published["source_url"]
    for key, value in published["config"].items():
        assert body[key] == value, key
    shapes = bench.shapes_of(body)
    cfg = get_config(body["serve"]["model"])
    assert "".join("M" if k == "mamba" else "*" for k in shapes["kinds"]) \
        == cfg.mixer_kinds
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
            cfg.vocab_size) == (
        shapes["dim"], shapes["heads"], shapes["kv"], shapes["hd"],
        shapes["ffn"], shapes["vocab"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        shapes["ssm_heads"], shapes["ssm_p"], shapes["ssm_groups"],
        shapes["ssm_n"], shapes["conv"], body["mamba_chunk_size"])
    assert (cfg.embed_multiplier, cfg.residual_multiplier, cfg.query_scale,
            cfg.logits_divisor) == (
        shapes["embed_x"], shapes["residual_x"], shapes["score_x"],
        shapes["logits_over"])
    assert (cfg.ssm_dt_min, cfg.ssm_dt_max, cfg.ssm_dt_floor) == (
        bench.DT_MIN, bench.DT_MAX, bench.DT_FLOOR)
    assert cfg.tie_embeddings and cfg.mixer_mlp
    assert jnp.dtype(ssm_moe.STATE_DTYPE).name == body["state_type"]
    # 4 attention layers x 2 x 8 KV heads of 64 in bfloat16
    assert bench.cache_bytes_per_token(body) == 8192
    # the cell's clients are the file's slots
    args = body["serve"]["args"]
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "assistants-closed.json")) as f:
        assert json.load(f)["clients"] == int(
            args[args.index("--slots") + 1])


@pytest.mark.usefixtures("full_optimiser")  # weights held to the bit
def test_the_benchmarks_reference_draws_the_programs_weights():
    cfg = get_config("tiny-ssm-mlp")
    weights = bench.make_weights(SHAPES, 5)
    mine = _as_reference(init_params(cfg, jax.random.PRNGKey(5),
                                     jnp.bfloat16))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), weights, mine)
    # the table that is the head too is drawn logits_scaling times as wide
    assert float(jnp.std(weights["embed"].astype(jnp.float32))) \
        == pytest.approx(2 * 0.88 / 8, rel=0.05)
    assert bench.cache_bytes_per_token(tiny.CONFIG) == tiny.CACHE_BYTES


# ---- through the engine ------------------------------------------------------------

def _engine(**kw):
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    return InferenceEngine(engine_cfg=EngineConfig(
        model="tiny-ssm-mlp", num_slots=2, max_seq=128, dtype="float32",
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
    reference's."""
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    base = _prompt(9, 60)
    prompts = [base, base[:48] + _prompt(10, 11)]
    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                  prefill_chunk=16)
    assert not eng.config_fences
    hit = global_metrics.counter("engine_prefix_hit_tokens_total")
    restores = global_metrics.counter("engine_state_restores_total")
    shared = _generate(eng, prompts)
    assert global_metrics.counter("engine_prefix_hit_tokens_total") - hit == 48
    assert global_metrics.counter("engine_state_restores_total") - restores \
        == 1
    alone = _generate(_engine(mux=True, prefix_cache=False,
                              prefill_chunk=16), prompts[1:])
    assert shared[1][0] == alone[0][0]
    np.testing.assert_allclose(shared[1][1], alone[0][1], atol=ATOL)
    tokens, values = shared[1]
    want = _want(eng.params, prompts[1] + tokens)
    n = len(prompts[1])
    np.testing.assert_allclose(
        values, [want[n - 1 + j, t] for j, t in enumerate(tokens)], atol=ATOL)
    # /healthz names the layer, the multipliers, the head, the state a slot
    # and the snapshots' room in bytes
    said = eng._model_section()
    assert said["layer"] == {"mixers": {"M": 3, "*": 1}, "mlp_width": 96}
    assert said["multipliers"] == {
        "embedding": 6.0, "residual": 0.4, "attention_scores": 0.125,
        "logits_divisor": 2.0}
    assert said["head"] == "the embedding"
    state = said["cache"]["kinds"]["state"]
    per_slot = ssm_moe.state_bytes_per_slot(eng.mcfg, jnp.float32)
    assert state["bytes_per_slot"] == per_slot
    assert state["snapshots"] == {
        "room": 16, "held": len(eng._snapshots), "bytes_each": per_slot,
        "bytes": 16 * per_slot}
    assert said["experts"]["of"] == 0 and "expert_products" not in said


def test_the_snapshots_room_is_capped_in_bytes(monkeypatch):
    """The snapshots' count follows the pool's tokens up to
    ``STATE_SNAPSHOT_BYTES``: the accepted cell of a 12.8 MB state keeps its
    96, this cell's 76 MB state gets 17 beside a pool of 2,048 blocks (64 by
    the tokens alone: 4.9 GB), and an engine under a small cap holds as
    many as the cap says."""
    from p2p_llm_tunnel_tpu.engine import engine as engine_mod

    cap = engine_mod.STATE_SNAPSHOT_BYTES
    for preset, blocks, want in (("nemotron-3-nano-30b-a3b-ep2s", 3072, 96),
                                 ("granite-4.0-h-micro", 2048, 17)):
        a_slot = ssm_moe.state_bytes_per_slot(get_config(preset))
        assert min(blocks * 16 // 512, cap // a_slot) == want
    a_slot = ssm_moe.state_bytes_per_slot(get_config("tiny-ssm-mlp"),
                                          jnp.float32)
    monkeypatch.setattr(engine_mod, "STATE_SNAPSHOT_BYTES", 5 * a_slot + 7)
    eng = _engine(mux=True, prefix_cache=True, prefix_pool_blocks=16,
                  prefill_chunk=16)
    assert eng._model_section()["cache"]["kinds"]["state"]["snapshots"] == {
        "room": 5, "held": 0, "bytes_each": a_slot, "bytes": 5 * a_slot}
    assert eng._snap_pool["ssm"].shape[:2] == (3, 6)  # + the scratch one


def test_the_api_serves_the_preset_end_to_end():
    """``engine/api.py``'s handler over the tiny preset: a completion with
    log-probabilities whose values are the reference's for the tokens it
    chose, streamed and not; the state's counters count for this model."""
    from p2p_llm_tunnel_tpu.engine.api import EngineAPI
    from p2p_llm_tunnel_tpu.protocol.frames import RequestHeaders
    from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

    eng = _engine(mux=True, prefill_chunk=16)
    api = EngineAPI(eng, "tiny-ssm-mlp")
    text = "a short turn for a small hybrid model"
    ids = eng.tokenizer.encode(text)
    moved = global_metrics.counter("engine_state_bytes_total")

    async def post(body):
        req = RequestHeaders(1, "POST", "/v1/completions", {})
        status, _, chunks = await api.handle(req, json.dumps(body).encode())
        return status, b"".join([c async for c in chunks]).decode()

    async def main():
        await eng.start()
        try:
            body = {"prompt": text, "max_tokens": 6, "ignore_eos": True,
                    "logprobs": 0, "temperature": 0}
            return await post(body), await post(dict(body, stream=True))
        finally:
            await eng.stop()

    (status, raw), (streamed, events) = asyncio.run(
        asyncio.wait_for(main(), 300))
    assert status == streamed == 200 and events.rstrip().endswith("[DONE]")
    said = json.loads(raw)
    assert said["usage"]["completion_tokens"] == 6
    values = said["choices"][0]["logprobs"]["token_logprobs"]
    tokens = eng.tokenizer.encode(said["choices"][0]["text"])
    assert len(values) == 6
    if len(tokens) == 6:  # (bytes that are no text of their own decode apart)
        want = _want(eng.params, ids + tokens)
        np.testing.assert_allclose(
            values, [want[len(ids) - 1 + j, t] for j, t in enumerate(tokens)],
            atol=ATOL)
    assert global_metrics.counter("engine_state_bytes_total") > moved


@pytest.mark.parametrize("case", [
    dict(quant="int8"), dict(kv_quant="int4"), dict(spec_ngram=2),
    dict(ragged_prefill=True), dict(tp=2)], ids=lambda c: next(iter(c)))
def test_what_the_family_lacks_is_refused_for_this_model_too(case):
    with pytest.raises(ValueError, match="a dense MLP a layer"):
        _engine(**case)


# ---- the tiny cell, in one process ------------------------------------------------

@pytest.mark.parametrize("mode", ["stated", "weights"])
def test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control(mode):
    """tests/benchmarks/tinycell_granite.py's cell (``tiny-ssm-mlp`` in
    bfloat16 against benchmarks/granite_hybrid_reference.py) through the
    engine in this process: what ``correct`` compares, as stated and with
    the weights' precision lowered.  The ladder's prefixes reach the chunk
    program through the pool and the snapshots.  (Through signal + serve +
    proxy: tests/benchmarks/test_bm_granite_rehearsal.py, ``slow``.)"""
    from tests.tiny_cell import _ask_in_process

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
    assert held is (mode == "stated"), "\n".join(said)
    assert stated == tiny.CACHE_BYTES
    if mode != "stated":
        assert numbers["echo_prompt"]["mean_abs"] > limits["echo_prompt"], said
