"""What ``correct`` compares, without a stack: which sequences are asked,
where each answer's log-probabilities land beside the reference's, and
what fails the judgement."""

import json
import os

import pytest

from benchmarks import correctness, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(REPO, "benchmarks")


def load(*parts):
    with open(os.path.join(REPO, "benchmarks", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("name,want", [
    # layers x (K, V) x KV heads x head width x 2 bytes of bfloat16
    ("mistral-7b", 32 * 2 * 8 * 128 * 2),   # 128 KiB
    ("qwen2-7b", 28 * 2 * 4 * 128 * 2),     # 56 KiB
])
def test_cache_bytes_of_a_token_as_the_configuration_states_them(name, want):
    config = load("configs", name + ".json")
    assert config["precision"]["kv_cache"] == "bfloat16"
    assert correctness.cache_bytes_stated(config, DATA) == want
    # the pool the file sizes is those bytes, blocks and tokens
    blocks = int(config["serve"]["args"][
        config["serve"]["args"].index("--prefix-pool-blocks") + 1])
    gib = blocks * config["serve"]["kv_block_tokens"] * want / 2**30
    assert gib == {"mistral-7b": 2.0, "qwen2-7b": 0.875}[name]
    half = dict(config, precision=dict(config["precision"], kv_cache="int8"))
    assert correctness.cache_bytes_stated(half, DATA) == want // 2


@pytest.mark.parametrize("kv_cache,want", [("bfloat16", 6 * 576 * 2),
                                           ("int8", 6 * 576)])
def test_the_cache_statement_is_the_familys(tmp_path, kv_cache, want):
    """A configuration that names its reference is held to what that module
    says a token caches: here a latent of 512 and one shared roped key of
    64 a layer, and no KV head in it."""
    from test_bm_contract import MLA_PUBLISHED, MLA_STUB

    (tmp_path / "latent.py").write_text(MLA_STUB)
    config = dict(MLA_PUBLISHED, reference="latent", num_hidden_layers=6,
                  precision={"kv_cache": kv_cache})
    assert config["num_key_value_heads"] is None
    assert correctness.cache_bytes_stated(config, str(tmp_path)) == want
    # without a module of its own it is the dense family's, which needs
    # KV heads: refused by the key's name
    del config["reference"]
    with pytest.raises(correctness.BenchFailure,
                       match="num_key_value_heads is None"):
        correctness.cache_bytes_stated(config, DATA)


def test_a_family_module_has_to_give_all_five_names(tmp_path):
    (tmp_path / "half.py").write_text("REQUIRED_KEYS = ()\n")
    with pytest.raises(correctness.BenchFailure, match="gives no shapes_of"):
        correctness.family({"reference": "half"}, str(tmp_path))


@pytest.fixture(scope="module")
def asked():
    plan = traffic.make_plan(load("traffic", "chat-open.json"), 2**31 + 7, 48,
                             32000)
    return plan, correctness.sequences(plan, 2**31 + 7, 32000, 1024)


def test_the_sequences_of_a_seed(asked):
    plan, seqs = asked
    again = correctness.sequences(plan, 2**31 + 7, 32000, 1024)
    assert [s["prompt"] for s in seqs] == [s["prompt"] for s in again]
    groups = [s["group"] for s in seqs]
    assert groups == ["echo"] * 8 + ["traffic"] * 8 + ["ladder"] * 2
    assert all(65 <= len(s["prompt"]) <= 128 for s in seqs[:8])
    prompts = {tuple(correctness._ids(r.prompt))
               for r in plan.all_requests()}
    for s in seqs[8:16]:   # the plan's own requests, with room to decode
        assert tuple(s["prompt"]) in prompts
        assert len(s["prompt"]) + correctness.NEW_TOKENS <= 1024
    for s in seqs[16:]:
        assert len(s["prompt"]) == 512
        assert correctness._rungs(s) == list(range(32, 513, 8))
    other = correctness.sequences(plan, 2**31 + 8, 32000, 1024)
    assert other[0]["prompt"] != seqs[0]["prompt"]


def test_a_short_context_shortens_the_ladder_and_refuses_crowded_plans(asked):
    plan, _ = asked
    with pytest.raises(correctness.BenchFailure, match="too few requests"):
        correctness.sequences(plan, 1, 32000, 96)
    seqs = correctness.sequences(plan, 1, 32000, 900)
    assert len(seqs[-1]["prompt"]) == 450
    assert correctness._rungs(seqs[-1])[-1] == 448


def fake_system(monkeypatch, tell):
    """A system whose log-probability of token ``k`` after ``n`` tokens is
    ``tell(n, k)`` and whose greedy token after ``n`` tokens is ``n``."""
    def complete(port, prompt, max_tokens, echo):
        n = len(prompt)
        new = list(range(n, n + max_tokens))
        seq = list(prompt) + new
        values = [tell(t, seq[t]) for t in range(n, n + max_tokens)]
        if echo:
            return {"tokens": seq, "completed": max_tokens,
                    "values": [None] + [tell(t, seq[t])
                                        for t in range(1, n)] + values}
        return {"tokens": new, "values": values, "completed": max_tokens}

    monkeypatch.setattr(correctness, "_complete", complete)


def test_every_answer_lands_beside_its_probe(asked, monkeypatch):
    _, seqs = asked
    seqs = [dict(s) for s in seqs]
    fake_system(monkeypatch, lambda n, k: -(n + k / 1e6))
    assert correctness.ask_engine(0, seqs) == []
    for s in seqs:
        assert len(s["probes"]) == len(s["system"]) == len(s["parts"])
        for (pos, tok), got in zip(s["probes"], s["system"]):
            assert got == -(pos + 1 + tok / 1e6)   # P(tok | tokens[..pos])
            assert pos < len(s["tokens"])
    n = len(seqs[0]["prompt"])
    assert seqs[0]["parts"] == (["echo_prompt"] * (n - 1)
                                + ["echo_decode"] * 64)
    assert seqs[8]["parts"] == ["traffic_decode"] * 64
    assert len(seqs[8]["tokens"]) == len(seqs[8]["prompt"]) + 64
    assert seqs[16]["parts"] == ["traffic_prefill"] * 61
    assert [p for p, _ in seqs[16]["probes"]] == list(range(31, 512, 8))
    assert seqs[16]["tokens"] == seqs[16]["prompt"]
    # a reference that agrees but for 0.01 everywhere
    ref = [[g + 0.01 for g in s["system"]] for s in seqs]
    numbers = correctness.compare(seqs, ref)
    assert set(numbers) == set(correctness.NUMBERS)
    assert numbers["echo_decode"]["n"] == numbers["traffic_decode"]["n"] == 512
    assert numbers["traffic_prefill"]["n"] == 122
    assert all(v["mean_abs"] == pytest.approx(0.01) for v in numbers.values())


def test_a_short_answer_is_a_fault(asked, monkeypatch):
    _, seqs = asked
    seqs = [dict(s) for s in seqs]
    fake_system(monkeypatch, lambda n, k: -1.0)
    real = correctness._complete

    def short(port, prompt, max_tokens, echo):
        got = real(port, prompt, max_tokens, echo)
        if max_tokens > 1 and not echo:
            got.update(tokens=got["tokens"][:-1], values=got["values"][:-1],
                       completed=max_tokens - 1)
        return got

    monkeypatch.setattr(correctness, "_complete", short)
    faults = correctness.ask_engine(0, seqs)
    assert len(faults) == 8 and "63 tokens, asked 64" in faults[0]


NUMBERS = {n: {"mean_abs": 0.005, "max_abs": 0.02, "n": 512}
           for n in correctness.NUMBERS}
LIMITS = dict.fromkeys(correctness.NUMBERS, 0.008)


@pytest.mark.parametrize("numbers,counted,holds", [
    (NUMBERS, 131072.0, True),
    (dict(NUMBERS, traffic_prefill={"mean_abs": 0.0081, "max_abs": 0.1,
                                    "n": 122}), 131072.0, False),
    ({k: v for k, v in NUMBERS.items() if k != "echo_decode"}, 131072.0,
     False),
    (NUMBERS, 67584.0, False),     # an int8 cache and its scales
    (NUMBERS, None, False),        # nothing to count
], ids=["sound", "over-a-limit", "a-number-missing", "int8-cache",
        "no-pool"])
def test_the_judgement(numbers, counted, holds):
    lines = []
    assert correctness.judge(numbers, LIMITS, counted, 131072,
                             lines.append) is holds
    assert all(line.startswith("correct: ") for line in lines)
    assert len(lines) == 5 or counted is None
    if holds:
        assert all(line.endswith("holds") for line in lines)
