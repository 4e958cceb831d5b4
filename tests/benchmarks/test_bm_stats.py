"""Percentile arithmetic and what counts as failed."""

import numpy as np
import pytest

from benchmarks import stats
from benchmarks.stats import Outcome


@pytest.mark.parametrize("p", [0, 10, 50, 75, 90, 95, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100, 231])
def test_percentile_is_linear_interpolation(p, n):
    xs = list(np.random.RandomState(n).lognormal(size=n))
    assert stats.percentile(xs, p) == pytest.approx(np.percentile(xs, p))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,p,ok", [(100, 90, True), (99, 90, False),
                                    (200, 95, True), (150, 95, False),
                                    (5, 50, True), (231, 90, True)])
def test_a_percentile_needs_ten_samples_beyond_it(n, p, ok):
    assert stats.supported(n, p) is ok


def sound(index, due, first, last, asked, arrivals=None):
    o = Outcome(index, due, asked)
    o.sent, o.first_token, o.last_token = due + 0.001, first, last
    o.status, o.done, o.finish = 200, True, "length"
    o.usage_prompt, o.usage_completion, o.tokens_seen = 10, asked, asked
    o.token_times = arrivals or [(first, 1)] + [(last, asked - 1)]
    o.in_window = True
    return o


def test_a_sound_request_has_not_failed():
    assert sound(0, 0.0, 0.5, 1.5, 11).failed() is None


@pytest.mark.parametrize("spoil,why", [
    (lambda o: setattr(o, "status", 429), "status 429"),
    (lambda o: setattr(o, "done", False), "without [DONE]"),
    (lambda o: setattr(o, "usage_completion", 9), "asked"),
    (lambda o: setattr(o, "tokens_seen", 10), "counted"),
    (lambda o: setattr(o, "error", "no end after 60s"), "no end"),
    (lambda o: setattr(o, "usage_completion", None), "no usage"),
    (lambda o: setattr(o, "first_token", None), "no content"),
])
def test_what_counts_as_failed(spoil, why):
    o = sound(0, 0.0, 0.5, 1.5, 11)
    spoil(o)
    assert why in o.failed()


def test_failed_requests_enter_percentiles_at_the_timeout():
    sample = [sound(i, 0.0, 0.1 + i * 0.001, 1.0, 11) for i in range(99)]
    bad = sound(99, 0.0, 0.1, 1.0, 11)
    bad.done = False
    vals = stats.ttft_values(sample + [bad], timeout_s=60.0)
    assert len(vals) == 100 and max(vals) == 60000.0
    assert stats.tpot_values([bad], 60.0) == [60000.0]


def test_tpot_is_the_mean_gap_and_skips_short_requests():
    o = sound(0, 0.0, 1.0, 2.0, 11)
    assert o.tpot_ms() == pytest.approx(100.0)
    short = sound(1, 0.0, 1.0, 2.0, 4)
    assert stats.tpot_values([o, short], 60.0) == [pytest.approx(100.0)]


def test_tokens_are_counted_by_arrival_inside_the_window():
    a = sound(0, 0.0, 9.5, 10.5, 6, [(9.5, 1), (9.9, 2), (10.0, 1), (10.5, 2)])
    b = sound(1, 0.0, 11.0, 30.0, 5, [(11.0, 2), (30.0, 3)])
    assert stats.tokens_in_window([a, b], 10.0, 20.0) == 1 + 2 + 2


def test_end_to_end_prints_every_sample_count():
    said = []
    sample = [sound(i, 0.0, 0.1 + i * 0.01, 2.0, 11) for i in range(120)]
    out = stats.end_to_end(
        {"ttft_p90_ms": {"kind": "ttft_percentile", "percentile": 90},
         "tpot_p90_ms": {"kind": "tpot_percentile", "percentile": 90},
         "out_tok_per_s": {"kind": "output_tokens_per_s"}},
        sample, sample, 0.0, 10.0, 60.0, said.append)
    assert set(out) == {"ttft_p90_ms", "tpot_p90_ms", "out_tok_per_s"}
    assert out["out_tok_per_s"] == pytest.approx(120 * 11 / 10.0)
    assert sum("over 120 requests" in line for line in said) == 2
    assert any("12.0 beyond it" in line for line in said)


def test_end_to_end_refuses_a_tail_the_sample_cannot_carry():
    sample = [sound(i, 0.0, 0.1, 2.0, 11) for i in range(50)]
    with pytest.raises(ValueError, match="beyond p90"):
        stats.end_to_end({"x": {"kind": "ttft_percentile", "percentile": 90}},
                         sample, sample, 0.0, 10.0, 60.0, lambda s: None)


def test_quartile_spread_is_the_contracts():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx((q[2] - q[0]) / 102.5)


def test_the_client_ttft_reader_is_the_end_to_end_definition():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                        "benchmarks", "layer_metrics", "client_ttft.py")
    spec = importlib.util.spec_from_file_location("client_ttft", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    sample = [sound(i, 0.0, 0.1 + i * 0.01, 2.0, 11) for i in range(120)]

    class Load:
        def sample(self):
            return sample

    class Ctx:
        load = Load()

        class plan:
            timeout_s = 60.0

    want = stats.percentile(stats.ttft_values(sample, 60.0), 90)
    assert reader.read(Ctx, 90) == pytest.approx(want)
    sample[:] = sample[:50]     # too few for a p90: nothing to read
    assert reader.read(Ctx, 90) is None
