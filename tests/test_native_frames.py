"""Native C++ codec vs Python codec: byte-identical behavior.

tests/conftest.py builds the library before collection (g++ is in the image);
where it could not, ``native_libs`` fails these tests with the build's output.
The Python codec in protocol/frames.py is the oracle.
"""

import pytest

from p2p_llm_tunnel_tpu.protocol import frames
from p2p_llm_tunnel_tpu.protocol import native

pytestmark = pytest.mark.usefixtures("native_libs")


@pytest.mark.parametrize("mtype,stream_id,payload", [
    (frames.MessageType.PING, 0, b""),
    (frames.MessageType.REQ_BODY, 1, b"hello"),
    (frames.MessageType.RES_BODY, 0xFFFFFFFF, b"x" * 1000),
    (frames.MessageType.ERROR, 42, "boom ü".encode()),
])
def test_encode_matches_python(mtype, stream_id, payload):
    py = frames.TunnelMessage(mtype, stream_id, payload).encode()
    nat = native.encode_frame(int(mtype), stream_id, payload)
    assert nat == py


def test_decode_matches_python():
    msg = frames.TunnelMessage(frames.MessageType.RES_HEADERS, 7, b'{"a":1}')
    wire = msg.encode()
    mt, sid, payload = native.decode_frame(wire)
    assert (mt, sid, payload) == (20, 7, b'{"a":1}')
    py = frames.TunnelMessage.decode(wire)
    assert (int(py.msg_type), py.stream_id, py.payload) == (mt, sid, payload)


def test_decode_rejects_bad_input():
    with pytest.raises(ValueError):
        native.decode_frame(b"\x01\x00")  # truncated
    with pytest.raises(ValueError):
        native.decode_frame(b"\x05" + b"\x00" * 4)  # type 5 unknown
    with pytest.raises(ValueError):
        native.decode_frame(b"\x01" + b"\x00" * (frames.MAX_FRAME_SIZE + 10))


def test_flow_frame_byte_parity():
    """FLOW (type 30) roundtrips through BOTH codecs identically — the one
    frame type we added over the reference wire format (ADVICE r2 low #3)."""
    py = frames.TunnelMessage.flow(11, 65536)
    wire = py.encode()
    assert native.encode_frame(int(frames.MessageType.FLOW), 11, py.payload) == wire
    mt, sid, payload = native.decode_frame(wire)
    assert (mt, sid, payload) == (30, 11, py.payload)
    assert frames.TunnelMessage.decode(wire).flow_credit() == 65536


def test_decode_error_frame_is_valid():
    mt, sid, payload = native.decode_frame(b"\x63" + b"\x00\x00\x00\x01" + b"oops")
    assert mt == 99 and sid == 1 and payload == b"oops"


def test_chunk_body_matches_python_path():
    body = bytes(range(256)) * 700  # ~175 KB → 3 chunks
    nat = native.chunk_body(
        int(frames.MessageType.RES_BODY), 9, body, frames.MAX_BODY_CHUNK
    )
    py = [
        frames.TunnelMessage.res_body(9, c).encode()
        for c in frames.iter_body_chunks(body, frames.MAX_BODY_CHUNK)
    ]
    assert nat == py
    # reassembles exactly
    assert b"".join(f[5:] for f in nat) == body


def test_chunk_body_empty():
    assert native.chunk_body(21, 1, b"", 100) == []
