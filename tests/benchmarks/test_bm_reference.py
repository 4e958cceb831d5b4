"""The plain reference against the program on the CPU at tiny sizes, and
the controls that must fail: int4 weights, an int8 cache."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference

#: What bf16 activations read against float32 at tiny size is 0.0031-0.0037
#: (mean |difference of log-probabilities|); int8 activations read 0.0061
#: and more, int4 weights 0.07 and more.  The limit sits between, as the
#: chip's limits do at 7B (benchmarks/configs/*.json "correct").
TOLERANCE = 0.005


def published(cfg):
    """A program preset as the configuration-file keys the reference reads."""
    return {"hidden_size": cfg.dim, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.ffn_dim, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "sliding_window": cfg.sliding_window,
            "attention_bias": cfg.attn_bias}


def program_logprobs(cfg, seed, tokens):
    from p2p_llm_tunnel_tpu.models.quant import init_params_quantized
    from p2p_llm_tunnel_tpu.models.transformer import prefill

    params = init_params_quantized(cfg, jax.random.PRNGKey(seed))
    t = len(tokens)
    logits, _, _ = prefill(cfg, params, jnp.asarray(tokens)[None],
                           jnp.ones((1, t), bool))
    return np.asarray(jax.nn.log_softmax(logits[0].astype(jnp.float32), -1))


CASES = [("tiny", {}), ("tiny-qwen", {}),
         ("tiny", {"sliding_window": 6, "window_pattern": "all"})]


@pytest.mark.parametrize("name,over", CASES,
                         ids=["tiny", "tiny-qwen-bias", "tiny-window"])
@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_reference_agrees_with_the_programs_prefill(name, over, seed):
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config(name, **over)
    shapes = reference.shapes_of(published(cfg))
    weights = reference.make_weights(shapes, seed)
    tokens = list(np.random.RandomState(seed % 997).randint(
        3, cfg.vocab_size, size=24))
    ref = np.asarray(reference.forward_logprobs(shapes, weights, tokens))
    got = program_logprobs(cfg, seed, tokens)
    assert np.abs(got - ref).mean() < TOLERANCE


def test_the_window_changes_the_answer():
    """The window is exercised: without it the same weights give another
    distribution at positions past the window."""
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config("tiny", sliding_window=6, window_pattern="all")
    shapes = reference.shapes_of(published(cfg))
    weights = reference.make_weights(shapes, 1)
    tokens = list(range(3, 27))
    windowed = np.asarray(reference.forward_logprobs(shapes, weights, tokens))
    full = np.asarray(reference.forward_logprobs(
        dict(shapes, window=None), weights, tokens))
    assert np.abs(windowed[:6] - full[:6]).max() < 1e-5
    assert np.abs(windowed[12:] - full[12:]).mean() > 0.01


def test_the_seed_makes_the_weights_the_program_serves():
    from p2p_llm_tunnel_tpu.models.config import get_config
    from p2p_llm_tunnel_tpu.models.quant import init_params_quantized

    cfg = get_config("tiny-qwen")
    params = init_params_quantized(cfg, jax.random.PRNGKey(5))
    weights = reference.make_weights(reference.shapes_of(published(cfg)), 5)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert (np.asarray(params["blocks"][name].q)
                == np.asarray(weights[name]["q"])).all(), name
        assert np.allclose(np.asarray(params["blocks"][name].scale),
                           np.asarray(weights[name]["scale"]))
    assert (np.asarray(params["lm_head"].q)
            == np.asarray(weights["lm_head"]["q"])).all()
    assert np.allclose(np.asarray(params["blocks"]["bq"], np.float32),
                       np.asarray(weights["bq"]))


def test_qwen2_file_keys_are_read_as_qwen2_means_them():
    shapes = reference.shapes_of({
        "model_type": "qwen2", "hidden_size": 3584, "num_hidden_layers": 28,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "intermediate_size": 18944, "vocab_size": 152064,
        "rope_theta": 1e6, "rms_norm_eps": 1e-6, "sliding_window": 131072,
        "use_sliding_window": False})
    assert shapes["qkv_bias"] and shapes["window"] is None
    assert shapes["head_dim"] == 128


def engine_logprobs(quant, kv_quant, seed, prompt, new):
    """Token ids and log-probabilities, prompt and generated, from the
    engine itself: whole-prompt prefill, then decoding through its cache."""
    from p2p_llm_tunnel_tpu.engine.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(engine_cfg=EngineConfig(
        model="tiny", num_slots=2, max_seq=128, quant=quant,
        kv_quant=kv_quant, seed=seed))

    async def run():
        await eng.start()
        tokens, values = list(prompt), [None]
        async for ev in eng.generate(prompt, max_new_tokens=new,
                                     temperature=0.0, logprobs=1,
                                     echo_logprobs=True, stop_ids=()):
            if ev.prompt_logprobs is not None:
                values += [float(x) for x in ev.prompt_logprobs[1:]]
            tokens.append(ev.token_id)
            values.append(ev.logprob)
        await eng.stop()
        return tokens, values

    tokens, values = asyncio.run(run())
    return published(eng.mcfg), tokens, values


@pytest.fixture(scope="module")
def tiny_run():
    seed = 3
    prompt = [int(t) for t in np.random.RandomState(1).randint(3, 200, size=40)]
    return seed, {name: engine_logprobs(quant, kv, seed, prompt, 16)
                  for name, quant, kv in (("stated", "int8", "none"),
                                          ("w8a8", "w8a8", "none"),
                                          ("kv_int8", "int8", "int8"))}


def _mean_abs(seed, run):
    config, tokens, values = run
    assert len(tokens) == len(values) == 56
    shapes = reference.shapes_of(config)
    weights = reference.make_weights(shapes, seed)
    ref = np.asarray(reference.forward_logprobs(shapes, weights, tokens))
    return float(np.mean([abs(values[t] - ref[t - 1, tokens[t]])
                          for t in range(1, len(tokens))]))


def test_the_engine_agrees_through_prefill_and_cache(tiny_run):
    seed, runs = tiny_run
    assert _mean_abs(seed, runs["stated"]) < TOLERANCE


def test_int8_activations_fail_the_tolerance(tiny_run):
    """The program's own lower-precision path (--quant w8a8) as the
    control: int8 activations where the configuration states bfloat16."""
    seed, runs = tiny_run
    assert _mean_abs(seed, runs["w8a8"]) > TOLERANCE


def test_an_int8_cache_is_not_seen(tiny_run):
    """What the comparison cannot see, kept here so that nobody assumes it
    can: an int8 cache (--kv-quant int8) reads like the bfloat16 one, here
    and at 7B on the chip (PERF.md section 2).  Its error sits under the
    floor that bfloat16 activations set."""
    seed, runs = tiny_run
    assert _mean_abs(seed, runs["kv_int8"]) < TOLERANCE


@pytest.mark.parametrize("name,over", CASES[:2], ids=["tiny", "tiny-qwen"])
def test_int4_weights_fail_the_tolerance(name, over):
    """The control: the reference itself with its weights rounded to int4,
    in the program's place."""
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config(name, **over)
    shapes = reference.shapes_of(published(cfg))
    weights = reference.make_weights(shapes, 11)
    tokens = list(np.random.RandomState(2).randint(3, cfg.vocab_size, size=32))
    ref = np.asarray(reference.forward_logprobs(shapes, weights, tokens))
    ctl = np.asarray(reference.forward_logprobs(shapes, weights, tokens,
                                                weight_bits=4))
    picked = [abs(ctl[t, tokens[t + 1]] - ref[t, tokens[t + 1]])
              for t in range(len(tokens) - 1)]
    assert np.mean(picked) > 10 * TOLERANCE


@pytest.mark.parametrize("name,over", CASES[:2], ids=["tiny", "tiny-qwen"])
def test_as_stated_is_the_int8_the_dense_family_draws(name, over):
    """``weight_bits=None`` (the weights as the configuration states them)
    and ``8`` are the same arithmetic for the dense module, bit for bit."""
    from p2p_llm_tunnel_tpu.models.config import get_config

    cfg = get_config(name, **over)
    shapes = reference.shapes_of(published(cfg))
    weights = reference.make_weights(shapes, 2**31 + 3)
    tokens = list(np.random.RandomState(4).randint(3, cfg.vocab_size, size=32))
    stated = np.asarray(reference.forward_logprobs(shapes, weights, tokens))
    eight = np.asarray(reference.forward_logprobs(shapes, weights, tokens,
                                                  weight_bits=8))
    assert stated.tobytes() == eight.tobytes()
    assert reference._requant(weights["wq"], None) is weights["wq"]
