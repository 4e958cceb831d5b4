"""Loss injection on the reliable UDP channel: the ARQ must deliver
everything, estimate RTT, and back its congestion window off under loss
instead of retransmit-storming.

The reference gets congestion control wholesale from SCTP inside the webrtc
crate (rtc.rs via Cargo.toml:14); these tests pin the behavior of the native
equivalent (transport/udp.py): Jacobson RTO, AIMD window, graceful
degradation at 1-5% loss (VERDICT r3 item 5).

Loss is injected by wrapping the asyncio datagram transport's ``sendto``
with a deterministic dropper — real sockets, real loopback, reproducible
loss pattern.
"""

import asyncio
import random
import time

import pytest

pytest.importorskip("cryptography")  # optional dep: skip (not fail) where absent

from p2p_llm_tunnel_tpu.transport.crypto import HandshakeKeys
from p2p_llm_tunnel_tpu.transport.arq import CWND_INIT
from p2p_llm_tunnel_tpu.transport.udp import WINDOW, UdpChannel


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


class _LossyTransport:
    """Wraps an asyncio DatagramTransport; drops data-plane packets with
    probability ``p`` (deterministic seed).  Tiny packets (punch/ack sized)
    always pass so establishment and teardown stay reliable — loss on the
    bulk path is what the test targets."""

    def __init__(self, inner, p: float, seed: int = 7):
        self._inner = inner
        self._p = p
        self._rng = random.Random(seed)
        self.dropped = 0
        self.sent = 0

    def sendto(self, data, addr=None):
        self.sent += 1
        if len(data) > 200 and self._rng.random() < self._p:
            self.dropped += 1
            return
        self._inner.sendto(data, addr)

    def __getattr__(self, name):
        return getattr(self._inner, name)


async def _lossy_pair(p: float):
    a_keys, b_keys = HandshakeKeys(), HandshakeKeys()
    a = await UdpChannel.bind("127.0.0.1")
    b = await UdpChannel.bind("127.0.0.1")
    a.set_session(a_keys.derive(b_keys.public_bytes, True, "lossy"))
    b.set_session(b_keys.derive(a_keys.public_bytes, False, "lossy"))
    await asyncio.gather(
        a.punch([("127.0.0.1", b.local_port)]),
        b.punch([("127.0.0.1", a.local_port)]),
    )
    lossy = _LossyTransport(a._transport, p)
    a._transport = lossy
    return a, b, lossy


async def _pump(a: UdpChannel, b: UdpChannel, n_msgs: int, size: int) -> float:
    payloads = [bytes([i % 256]) * size for i in range(n_msgs)]
    t0 = time.monotonic()

    async def send_all():
        for m in payloads:
            await a.send(m)

    async def recv_all():
        for m in payloads:
            got = await asyncio.wait_for(b.recv(), 60)
            assert got == m, "payload corrupted or reordered"

    await asyncio.gather(send_all(), recv_all())
    return time.monotonic() - t0


@pytest.mark.parametrize("loss", [0.01, 0.05])
def test_lossy_delivery_complete_and_in_order(loss):
    async def main():
        a, b, lossy = await _lossy_pair(loss)
        try:
            await _pump(a, b, n_msgs=40, size=4000)  # 40 × 4 fragments
            stats = a.congestion_stats
            assert lossy.dropped > 0, "loss injection never fired"
            assert stats["retransmits"] > 0, "drops must trigger retransmits"
            assert stats["srtt"] is not None, "ACKs must produce RTT samples"
            assert stats["in_flight"] == 0, "everything must drain"
        finally:
            a.close()
            b.close()

    run(main())


def test_loss_triggers_multiplicative_backoff():
    async def main():
        a, b, lossy = await _lossy_pair(0.3)  # heavy loss forces timeouts
        try:
            await _pump(a, b, n_msgs=12, size=4000)
            stats = a.congestion_stats
            assert stats["retransmits"] > 0
            # ssthresh must have come down from the initial WINDOW cap:
            # proof that _on_timeout_loss ran (AIMD decrease happened).
            assert stats["ssthresh"] < WINDOW
        finally:
            a.close()
            b.close()

    run(main())


def test_clean_path_grows_window_and_tracks_rtt():
    async def main():
        a, b, lossy = await _lossy_pair(0.0)
        try:
            await _pump(a, b, n_msgs=60, size=4000)
            stats = a.congestion_stats
            assert stats["retransmits"] == 0, "no loss → no retransmits"
            assert stats["cwnd"] > CWND_INIT, "slow start must grow cwnd"
            # loopback RTT is sub-millisecond; the estimator must keep the
            # RTO clamped near its floor, not the old fixed 2 s ceiling.
            assert stats["srtt"] < 0.05
            assert stats["rto"] <= 0.2
        finally:
            a.close()
            b.close()

    run(main())


def test_throughput_degrades_sublinearly():
    """5% packet loss must not cost anywhere near a 2x slowdown once the
    estimator is warm (the r3 fixed-RTO design stalled a full 150 ms floor
    per loss).  Generous bound: < 5x, asserting shape not raw speed, so CI
    jitter can't flake it."""

    async def timed(loss):
        a, b, _ = await _lossy_pair(loss)
        try:
            # Warm the RTT estimator first so RTO reflects loopback.
            await _pump(a, b, n_msgs=20, size=1000)
            return await _pump(a, b, n_msgs=40, size=4000)
        finally:
            a.close()
            b.close()

    t_clean = run(timed(0.0))
    t_lossy = run(timed(0.05))
    assert t_lossy < max(5 * t_clean, t_clean + 2.0), (
        f"5% loss degraded throughput {t_lossy / t_clean:.1f}x "
        f"({t_clean:.2f}s → {t_lossy:.2f}s)"
    )


def test_concurrent_senders_never_splice_a_message_into_another():
    """Many tasks send on one channel, as a serve peer's 32 streams do.  A
    message of several fragments that waits for the window mid-message must
    not be passed by another task's send: the receiver joins fragments up to
    the next fin, so a passing frame would be spliced into the waiting one
    (PERF.md section 7's SSE fault: a control character inside a delta's
    text, then ``unknown message type``).  The small senders are woken from
    a thread, as token events are: their steps can be queued ahead of the
    waiting sender's wake-up."""
    import threading

    big, small, per, size = 4, 16, 40, 100_000

    async def main():
        a, b, _ = await _lossy_pair(0.0)
        loop = asyncio.get_running_loop()
        queues = [asyncio.Queue() for _ in range(small)]

        def feed():
            for j in range(per):
                for q in queues:
                    loop.call_soon_threadsafe(q.put_nowait, j)
                time.sleep(0.003)

        async def big_sender(i):
            for _ in range(per):
                await a.send(bytes([i]) * size)  # 84 fragments

        async def small_sender(i):
            for _ in range(per):
                await queues[i].get()
                await a.send(bytes([100 + i]) * 300)

        async def recv_all():
            return [await b.recv() for _ in range((big + small) * per)]

        feeder = threading.Thread(target=feed, daemon=True)
        feeder.start()
        try:
            got, *_ = await asyncio.gather(
                recv_all(),
                *(big_sender(i) for i in range(big)),
                *(small_sender(i) for i in range(small)))
        finally:
            a.close()
            b.close()
        feeder.join()
        spliced = [m for m in got
                   if len(set(m)) != 1 or len(m) not in (300, size)]
        assert not spliced, f"{len(spliced)} of {len(got)} messages spliced"
        for i in range(big):  # each sender's own messages all arrived
            assert sum(m[0] == i for m in got) == per

    run(main())
