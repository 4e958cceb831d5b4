"""The byte and FLOP functions against both configurations' sizes worked
out by hand, and never over 100 % of the peak for a measured step."""

import json
import os

import pytest

from benchmarks import roofline

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


# mistral-7b: a layer is q 4096x4096 + k, v 4096x1024 + o 4096x4096 +
# three 4096x14336 = 218,103,808 weights; 32 layers + a 4096x32000 head.
MISTRAL_LAYER = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
MISTRAL_MATMUL = 32 * MISTRAL_LAYER + 4096 * 32000
# qwen2-7b: q, o 3584x3584; k, v 3584x512; three 3584x18944; 28 layers and
# a 3584x152064 head.
QWEN_LAYER = 3584 * 3584 * 2 + 3584 * 512 * 2 + 3 * 3584 * 18944
QWEN_MATMUL = 28 * QWEN_LAYER + 3584 * 152064


@pytest.mark.parametrize("name,layer,total", [
    ("mistral-7b", MISTRAL_LAYER, MISTRAL_MATMUL),
    ("qwen2-7b", QWEN_LAYER, QWEN_MATMUL)])
def test_matmul_weights(name, layer, total):
    p = roofline.matmul_params(config(name))
    assert p["layer"] == layer
    assert p["layer"] * p["layers"] + p["head"] == total


def test_hand_computed_totals():
    assert MISTRAL_LAYER == 218_103_808
    assert MISTRAL_MATMUL == 7_110_393_856
    assert QWEN_MATMUL == 7_070_285_824


@pytest.mark.parametrize("name,matmul", [("mistral-7b", MISTRAL_MATMUL),
                                         ("qwen2-7b", QWEN_MATMUL)])
def test_weight_bytes_are_the_stored_int8_plus_small_change(name, matmul):
    b = roofline.weight_bytes(config(name))
    assert matmul < b < matmul * 1.002  # scales, norms, biases: under 0.2 %


@pytest.mark.parametrize("name,per_token", [("mistral-7b", 128 * 1024),
                                            ("qwen2-7b", 56 * 1024)])
def test_kv_bytes_of_a_token(name, per_token):
    assert roofline.kv_bytes_per_token(config(name)) == per_token


def test_kv_bytes_follow_live_tokens_not_capacity():
    c = config("mistral-7b")
    few = roofline.decode_step_bytes(c, 1000)
    many = roofline.decode_step_bytes(c, 33 * 1024)
    assert many - few == (33 * 1024 - 1000) * 128 * 1024


@pytest.mark.parametrize("name,matmul,heads,layers", [
    ("mistral-7b", MISTRAL_MATMUL, 32, 32), ("qwen2-7b", QWEN_MATMUL, 28, 28)])
def test_flops(name, matmul, heads, layers):
    f = roofline.decode_step_flops(config(name), rows=32, live_tokens=10000)
    assert f == 2 * matmul * 32 + 4 * layers * heads * 128 * 10000


@pytest.mark.parametrize("name", ["mistral-7b", "qwen2-7b"])
def test_a_decode_step_is_bound_by_memory(name):
    least = roofline.least_step_seconds(config(name), peaks(), 32, 32 * 300)
    assert least["bound"] == "memory"
    assert least["seconds"] == least["by_bytes_s"] > least["by_flops_s"]
    assert 0.008 < least["seconds"] < 0.012  # ~7.1 GB + KV over 819 GB/s


@pytest.mark.parametrize("live_rows,context", [(1, 64), (16, 300), (32, 512),
                                               (32, 1024), (33, 1024)])
def test_never_over_the_peak_for_the_measured_step(live_rows, context):
    """PR 23 measured a 32-slot mistral decode step at 34 ms on the chip
    (ledger, PR 23: 1.7498 ms a token at 32 rows, decode_roofline 44 %).
    Whatever the batch held, the least time is under the measured one."""
    least = roofline.least_step_seconds(config("mistral-7b"), peaks(),
                                        live_rows, live_rows * context)
    share = 100.0 * least["seconds"] / 0.034
    assert 25.0 < share < 100.0


def test_an_unknown_device_kind_has_no_peaks():
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        table = json.load(f)
    assert "source" in table and "Google Cloud" in table["source"]
    assert set(table["devices"]) == {"TPU v5 lite"}
    assert table["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
