"""ARQ core: semantic unit tests + the native/Python equivalence oracle.

PyArq (transport/arq.py) is the reference semantics; NativeArq must make
IDENTICAL decisions on any schedule of sends/acks/timeouts — the oracle
drives both with randomized schedules and fails on any divergence."""

import random

import pytest

from p2p_llm_tunnel_tpu.transport.arq import (
    CWND_INIT,
    CWND_MIN,
    NativeArq,
    PyArq,
    RTO_MAX,
    RTO_MIN,
)

# tests/conftest.py builds the native core before collection; where it could
# not, ``native_libs`` fails these tests with the build's output.
IMPLS = [PyArq, NativeArq]


@pytest.fixture(params=IMPLS, ids=lambda c: c.__name__)
def arq(request):
    if request.param is NativeArq:
        request.getfixturevalue("native_libs")
    return request.param(cwnd_cap=512.0)


# ---------------------------------------------------------------------------
# semantics (run against BOTH implementations)
# ---------------------------------------------------------------------------

def test_slow_start_growth(arq):
    for seq in range(8):
        arq.on_send(seq, 0.0)
    acked = arq.on_ack(8, 0.05)
    assert acked == list(range(8))
    assert arq.cwnd == CWND_INIT + 8  # slow start: +1 per acked packet
    assert arq.in_flight == 0


def test_rtt_estimator_sets_rto(arq):
    arq.on_send(0, 0.0)
    arq.on_ack(1, 0.2)
    assert arq.srtt == pytest.approx(0.2)
    # rto = srtt + 4*rttvar = 0.2 + 4*0.1 = 0.6
    assert arq.rto == pytest.approx(0.6)
    assert RTO_MIN <= arq.rto <= RTO_MAX


def test_karn_rule_skips_retransmitted_samples(arq):
    arq.on_send(0, 0.0)
    # expire it (default rto = RTO_MAX/2 = 1.0)
    assert arq.due(1.5) == [0]
    arq.on_ack(1, 5.0)  # huge apparent RTT — must NOT poison the estimator
    assert arq.srtt is None


def test_timeout_halves_cwnd_once_per_rtt(arq):
    for seq in range(16):
        arq.on_send(seq, 0.0)
    arq.on_ack(8, 0.1)  # srtt ~= 0.1, cwnd = 32+8 = 40
    cwnd0 = arq.cwnd
    due = arq.due(2.0)  # remaining 8 all expired
    assert due == list(range(8, 16))
    # ONE multiplicative decrease despite 8 expirees in the tick.
    assert arq.cwnd == pytest.approx(cwnd0 / 2)
    assert arq.retransmits == 8


def test_backoff_exponential_per_retry(arq):
    arq.on_send(0, 0.0)
    assert arq.due(1.5) == [0]  # first expiry at base rto 1.0
    # second retry needs 2*rto ... but rto is clamped at RTO_MAX
    assert arq.due(2.0) == []
    assert arq.due(1.5 + RTO_MAX + 0.01) == [0]


def test_window_gates_can_send(arq):
    cap = int(min(512.0, arq.cwnd))
    for seq in range(cap):
        assert arq.can_send()
        arq.on_send(seq, 0.0)
    assert not arq.can_send()
    arq.on_ack(1, 0.05)
    assert arq.can_send()


def test_cwnd_floor_after_repeated_loss(arq):
    for seq in range(4):
        arq.on_send(seq, 0.0)
    t = 2.0
    for _ in range(12):  # repeated loss events, spaced > rtt apart
        arq.due(t)
        t += RTO_MAX + 0.5
    assert arq.cwnd >= CWND_MIN


# ---------------------------------------------------------------------------
# the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_native_matches_python_on_random_schedules(seed, native_libs):
    rng = random.Random(seed)
    py, nat = PyArq(512.0), NativeArq(512.0)
    if rng.random() < 0.5:
        cap = float(rng.randint(CWND_MIN, 512))
        py.set_cwnd_cap(cap)
        nat.set_cwnd_cap(cap)
    now = 0.0
    next_seq = rng.randrange(0, 2**32)  # exercise u32 wraparound too
    lowest_unacked = next_seq
    for _ in range(600):
        now += rng.random() * rng.choice([0.01, 0.3, 1.5])
        op = rng.random()
        if op < 0.45 and py.can_send():
            assert nat.can_send()
            py.on_send(next_seq, now)
            nat.on_send(next_seq, now)
            next_seq = (next_seq + 1) & 0xFFFFFFFF
        elif op < 0.8:
            # ACK a random amount of the outstanding range (may be zero).
            span = (next_seq - lowest_unacked) & 0xFFFFFFFF
            cum = (lowest_unacked + rng.randint(0, span)) & 0xFFFFFFFF
            a, b = py.on_ack(cum, now), nat.on_ack(cum, now)
            assert a == b, f"ack divergence at seed {seed}"
            lowest_unacked = cum if a else lowest_unacked
        else:
            a, b = py.due(now), nat.due(now)
            assert a == b, f"due divergence at seed {seed}"
        assert py.in_flight == nat.in_flight
        assert py.can_send() == nat.can_send()
        assert py.retransmits == nat.retransmits
        assert py.cwnd == pytest.approx(nat.cwnd, rel=1e-12)
        assert py.rto == pytest.approx(nat.rto, rel=1e-12)
        if py.srtt is None:
            assert nat.srtt is None
        else:
            assert py.srtt == pytest.approx(nat.srtt, rel=1e-12)
