"""Frame-codec tests for the network front door (repro.runtime.wire).

Round-trips over every message kind and payload dtype, plus the failure
taxonomy: truncated, garbage, and oversized frames must raise a typed
``ProtocolError`` *promptly* — the reader never buffers past
``max_frame_bytes`` and never spins on a stream it cannot resynchronize.
"""
import asyncio
import json
import struct

import numpy as np
import pytest

from repro.runtime import wire
from repro.runtime.api import DeliveryRequest, DeliveryResult
from repro.runtime.wire import ProtocolError

from _hypothesis_compat import given, settings, st


def _feed(data: bytes, eof: bool = True) -> asyncio.StreamReader:
    # Must run inside a loop: StreamReader binds the current event loop.
    r = asyncio.StreamReader()
    r.feed_data(data)
    if eof:
        r.feed_eof()
    return r


def _read(data: bytes, eof: bool = True, **kw):
    async def go():
        return await wire.read_frame(_feed(data, eof), **kw)

    return asyncio.run(go())


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

def test_request_roundtrip_rows():
    payload = np.arange(24, dtype=np.float32).reshape(2, 3, 2, 2)
    req = DeliveryRequest("tenant-1", payload, priority=2, deadline_ms=40.0,
                          metadata={"k": "v", "n": 3})
    rid, age, out = wire.decode_request(
        *_read(wire.encode_request(req, "r-7", age_ms=12.5))[1:]
    )
    assert rid == "r-7" and age == 12.5
    assert out.tenant_id == "tenant-1" and out.lane == "rows"
    assert out.priority == 2 and out.deadline_ms == 40.0
    assert out.metadata == {"k": "v", "n": 3}
    np.testing.assert_array_equal(out.payload, payload)


def test_request_roundtrip_tokens_lane():
    tokens = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    req = DeliveryRequest("lm-0", tokens, lane="tokens", deliver="embed")
    _, _, out = wire.decode_request(
        *_read(wire.encode_request(req, "t-1"))[1:]
    )
    assert out.lane == "tokens" and out.deliver == "embed"
    assert out.payload.dtype == np.int32
    np.testing.assert_array_equal(out.payload, tokens)


def test_result_roundtrip():
    res = DeliveryResult(
        request_id=42, tenant_id="tenant-3", lane="rows", deliver="tokens",
        priority=1, payload=np.ones((4, 7), np.float32),
        submitted_at=10.0, completed_at=10.004, queue_depth_at_submit=9,
        metadata={"trace": True},
    )
    out = wire.decode_result(*_read(wire.encode_result("r-9", res))[1:])
    assert out.rid == "r-9" and out.engine_rid == 42
    assert out.tenant_id == "tenant-3" and out.lane == "rows"
    assert out.latency_ms == pytest.approx(4.0)
    assert out.metadata == {"trace": True}
    np.testing.assert_array_equal(out.payload, res.payload)


def _result(payload, request_id=7, lane="rows"):
    return DeliveryResult(
        request_id=request_id, tenant_id="tenant-0", lane=lane,
        deliver="tokens", priority=0, payload=payload,
        submitted_at=1.0, completed_at=1.0125, queue_depth_at_submit=2,
        metadata={"n": 1},
    )


def _copied_frame(rid, res):
    """A result frame as a payload copy: head, JSON header, ``tobytes()``."""
    arr = np.ascontiguousarray(res.payload)
    header = {
        "rid": rid, "engine_rid": res.request_id, "tenant": res.tenant_id,
        "lane": res.lane, "latency_ms": float(res.latency_ms),
        "metadata": dict(res.metadata),
        "dtype": arr.dtype.name, "shape": list(arr.shape),
    }
    return wire.encode_frame(wire.KIND_RES, header, arr.tobytes())


@pytest.mark.parametrize("payload,lane", [
    (np.arange(2 * 16 * 8 * 8, dtype=np.float32).reshape(2, 16, 8, 8) / 7,
     "rows"),
    (np.arange(3 * 5, dtype=np.int32).reshape(3, 5) - 4, "tokens"),
    (np.zeros((0, 16, 8, 8), np.float32), "rows"),
], ids=["float32-vision", "int32-tokens", "empty"])
def test_result_parts_join_to_the_copied_frame(payload, lane):
    """Head plus view, joined, are the bytes of the frame built by copying
    the payload, and read back as the same result."""
    res = _result(payload, lane=lane)
    parts = wire.encode_result_parts("r-1", res)
    frame = b"".join(parts)
    assert frame == _copied_frame("r-1", res)
    assert wire.encode_result("r-1", res) == frame
    out = wire.decode_result(*_read(frame)[1:])
    assert out.rid == "r-1" and out.engine_rid == 7 and out.lane == lane
    assert out.payload.dtype == payload.dtype
    np.testing.assert_array_equal(out.payload, payload)


def test_result_parts_payload_is_a_readonly_view_of_the_result():
    payload = np.random.default_rng(0).standard_normal(
        (4, 16, 8, 8)).astype(np.float32)
    head, body = wire.encode_result_parts("r-2", _result(payload))
    assert isinstance(head, bytes) and isinstance(body, memoryview)
    assert body.readonly and body.nbytes == payload.nbytes
    assert np.shares_memory(np.asarray(body), payload)
    with pytest.raises(TypeError):
        body[0] = 1


def test_result_parts_refuse_a_non_wire_dtype():
    with pytest.raises(ProtocolError, match="not wire-transportable"):
        wire.encode_result_parts("r-3", _result(np.ones(3, np.complex64)))
    with pytest.raises(ProtocolError, match="not wire-transportable"):
        wire.encode_result_parts("r-3", _result(np.array([object()])))


def test_result_parts_of_a_non_contiguous_payload():
    """A strided result is the one case that copies: its bytes are the
    C-order bytes of the array, not of the buffer it views."""
    base = np.arange(4 * 6 * 5, dtype=np.float32).reshape(4, 6, 5)
    payload = base[:, ::2].transpose(0, 2, 1)
    assert not payload.flags.c_contiguous
    res = _result(payload)
    head, body = wire.encode_result_parts("r-4", res)
    assert not np.shares_memory(np.asarray(body), base)
    assert head + bytes(body) == _copied_frame("r-4", res)
    out = wire.decode_result(*_read(head + bytes(body))[1:])
    assert out.payload.shape == payload.shape
    np.testing.assert_array_equal(out.payload, payload)


def test_reject_roundtrip_all_codes():
    for code in wire.REJECT_CODES:
        kind, header, payload = _read(wire.encode_reject("x-1", code, "why"))
        assert kind == wire.KIND_REJ and payload == b""
        rej = wire.decode_reject(header)
        assert rej.rid == "x-1" and rej.code == code and rej.message == "why"


def test_bye_and_multiframe_stream():
    buf = (
        wire.encode_reject("a", "OVERLOADED")
        + wire.encode_bye("drain")
    )
    async def drain():
        reader = _feed(buf)
        frames = []
        while (f := await wire.read_frame(reader)) is not None:
            frames.append(f)
        return frames

    frames = asyncio.run(drain())
    assert [k for k, _, _ in frames] == [wire.KIND_REJ, wire.KIND_BYE]
    assert frames[1][1]["reason"] == "drain"


@pytest.mark.parametrize("dtype", ["float16", "float32", "float64", "int8",
                                   "int32", "int64", "uint8", "bool"])
def test_array_roundtrip_dtypes(dtype, rng):
    arr = (rng.standard_normal((3, 5)) * 10).astype(dtype)
    hdr, body = wire._encode_array(arr)
    out = wire._decode_array(hdr, body)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)


@settings(max_examples=50, deadline=None)
@given(
    shape=st.lists(st.integers(0, 5), min_size=1, max_size=4),
    dtype=st.sampled_from(["float32", "int32", "uint8", "float16", "bool"]),
    rid=st.text(min_size=1, max_size=32),
    metadata=st.dictionaries(
        st.text(max_size=8),
        st.one_of(st.integers(-10, 10), st.text(max_size=8), st.booleans()),
        max_size=4,
    ),
    age=st.floats(0, 1e6, allow_nan=False),
)
def test_request_roundtrip_property(shape, dtype, rid, metadata, age):
    """Property sweep: any wire dtype/shape/metadata/rid round-trips
    bit-exactly through encode_request -> decode_frame -> decode_request."""
    payload = np.zeros(shape, dtype=dtype)
    req = DeliveryRequest("t", payload, metadata=metadata)
    out_rid, out_age, out = wire.decode_request(
        *wire.decode_frame(wire.encode_request(req, rid, age_ms=age))[1:]
    )
    assert out_rid == rid
    assert out_age == pytest.approx(age)
    assert out.metadata == metadata
    assert out.payload.dtype == payload.dtype
    np.testing.assert_array_equal(out.payload, payload)


# ---------------------------------------------------------------------------
# failure taxonomy: every malformed stream is a *typed*, *prompt* error
# ---------------------------------------------------------------------------

def test_clean_eof_returns_none():
    assert _read(b"") is None


def test_truncated_head():
    with pytest.raises(ProtocolError, match="truncated frame head"):
        _read(b"ML\x01")


def test_truncated_body():
    frame = wire.encode_reject("r", "FAILED", "boom")
    with pytest.raises(ProtocolError, match="truncated frame body"):
        _read(frame[:-3])


def test_garbage_magic():
    with pytest.raises(ProtocolError, match="bad magic"):
        _read(b"XX" + b"\x01" + struct.pack(">II", 2, 0) + b"{}")


def test_unknown_kind():
    with pytest.raises(ProtocolError, match="unknown frame kind"):
        _read(b"ML" + b"\x77" + struct.pack(">II", 2, 0) + b"{}")


def test_non_json_header():
    head = struct.pack(">2sBII", b"ML", wire.KIND_BYE, 4, 0)
    with pytest.raises(ProtocolError, match="not JSON"):
        _read(head + b"\xff\xfe\x00\x01")


def test_non_object_header():
    hdr = json.dumps([1, 2]).encode()
    head = struct.pack(">2sBII", b"ML", wire.KIND_BYE, len(hdr), 0)
    with pytest.raises(ProtocolError, match="JSON object"):
        _read(head + hdr)


def test_oversized_frame_rejected_before_body_is_read():
    # The declared body never arrives (no EOF fed) — the reader must still
    # fail promptly from the length prefix alone, without buffering.
    head = struct.pack(">2sBII", b"ML", wire.KIND_REQ, 16, 1 << 30)

    async def attempt():
        reader = _feed(head, eof=False)
        return await asyncio.wait_for(
            wire.read_frame(reader, max_frame_bytes=1 << 20), timeout=5.0
        )

    with pytest.raises(ProtocolError, match="oversized frame"):
        asyncio.run(attempt())


def test_oversized_encode_side_cap():
    frame = wire.encode_request(
        DeliveryRequest("t", np.zeros((4, 9), np.float32)), "r"
    )
    with pytest.raises(ProtocolError, match="oversized frame"):
        _read(frame, max_frame_bytes=64)


def test_payload_size_mismatch():
    with pytest.raises(ProtocolError, match="payload size mismatch"):
        wire._decode_array({"dtype": "float32", "shape": [2, 2]}, b"\x00" * 15)


def test_payload_dtype_not_whitelisted():
    with pytest.raises(ProtocolError, match="not wire-transportable"):
        wire._decode_array({"dtype": "object", "shape": [1]}, b"\x00" * 8)
    with pytest.raises(ProtocolError, match="not wire-transportable"):
        wire._encode_array(np.array([object()]))


def test_request_missing_rid_and_tenant():
    with pytest.raises(ProtocolError, match="without a rid"):
        wire.decode_request({"tenant": "t", "dtype": "float32",
                             "shape": [1, 1]}, b"\x00" * 4)
    with pytest.raises(ProtocolError, match="without a tenant"):
        wire.decode_request({"rid": "r", "dtype": "float32",
                             "shape": [1, 1]}, b"\x00" * 4)


def test_request_semantic_error_is_valueerror_not_protocolerror():
    # Bad lane combinations are the descriptor's own ValueError: the server
    # maps those to a typed INVALID rejection instead of closing the stream.
    frame = wire.encode_request(
        DeliveryRequest("t", np.zeros((1, 4), np.float32)), "r"
    )
    _, header, payload = wire.decode_frame(frame)
    header["deliver"] = "embed"          # deliver=embed needs lane=tokens
    with pytest.raises(ValueError, match="deliver"):
        wire.decode_request(header, payload)


def test_bad_age_ms():
    frame = wire.encode_request(
        DeliveryRequest("t", np.zeros((1, 4), np.float32)), "r"
    )
    _, header, payload = wire.decode_frame(frame)
    header["age_ms"] = -5.0
    with pytest.raises(ProtocolError, match="bad age_ms"):
        wire.decode_request(header, payload)


def test_encode_frame_rejects_bad_producer_input():
    with pytest.raises(ProtocolError, match="unknown frame kind"):
        wire.encode_frame(99, {})
    with pytest.raises(ProtocolError, match="not JSON-able"):
        wire.encode_frame(wire.KIND_BYE, {"x": object()})


def test_protocol_errors_never_echo_frame_bytes():
    """Decode-side ProtocolError text must describe violations by
    type/length only — a crafted garbage frame's bytes and header strings
    are attacker-controlled and must never be reflected (they reach other
    parties via reject frames and logs)."""
    marker = "SECRETPAYLOADBYTES"
    bmarker = marker.encode()

    # 1. bad magic: the two garbage prefix bytes stay out of the message
    with pytest.raises(ProtocolError) as ei:
        wire.decode_frame(b"XY" + bytes(9))
    assert "XY" not in str(ei.value)

    # 2. non-JSON header carrying the marker bytes
    garbage = struct.pack(">2sBII", b"ML", wire.KIND_REQ, len(bmarker), 0)
    with pytest.raises(ProtocolError) as ei:
        wire.decode_frame(garbage + bmarker)
    assert marker not in str(ei.value)

    # 3. undecodable (non-UTF8) header: no byte values in the message
    bad = b"\xff\xfe" + bmarker
    frame = struct.pack(">2sBII", b"ML", wire.KIND_REQ, len(bad), 0) + bad
    with pytest.raises(ProtocolError) as ei:
        wire.decode_frame(frame)
    assert marker not in str(ei.value) and "0xff" not in str(ei.value)

    # 4. attacker-chosen dtype / shape / rid / tenant strings
    hdr = {"rid": marker, "tenant": "t", "age_ms": 0,
           "dtype": marker, "shape": [1]}
    with pytest.raises(ProtocolError) as ei:
        wire.decode_request(hdr, b"\x00")
    assert marker not in str(ei.value)
    for broken in (
        {"rid": None, "tenant": marker},
        {"rid": "r", "tenant": None, "age_ms": marker},
    ):
        with pytest.raises(ProtocolError) as ei:
            wire.decode_request({"dtype": "float32", "shape": [1], **broken},
                                b"\x00" * 4)
        assert marker not in str(ei.value)

    # 5. reject-frame code echo
    with pytest.raises(ProtocolError) as ei:
        wire.decode_reject({"rid": "r", "code": marker})
    assert marker not in str(ei.value)

    # 6. result-frame engine_rid echo
    with pytest.raises(ProtocolError) as ei:
        wire.decode_result({"rid": "r", "engine_rid": marker}, b"")
    assert marker not in str(ei.value)
