"""Wire codec + transport tests (in-proc faults, TCP pipelining)."""

import threading

import pytest

from ripplemq_tpu.wire import (
    InProcNetwork,
    RpcError,
    RpcTimeout,
    TcpClient,
    TcpServer,
    decode,
    encode,
)


@pytest.mark.parametrize(
    "value",
    [
        None,
        True,
        False,
        0,
        -1,
        2**62,
        -(2**62),
        3.75,
        "",
        "héllo wörld",
        b"",
        b"\x00\xff" * 100,
        [],
        [1, "two", b"three", None, [4.5]],
        {},
        {"type": "append", "msgs": [b"a", b"b"], "n": 2, "nested": {"x": None}},
    ],
)
def test_codec_roundtrip(value):
    assert decode(encode(value)) == value


def test_codec_rejects_trailing_and_bad_tags():
    with pytest.raises(ValueError):
        decode(encode(1) + b"x")
    with pytest.raises(ValueError):
        decode(b"\xfe")
    with pytest.raises(TypeError):
        encode(object())
    with pytest.raises(TypeError):
        encode({1: "non-string key"})


def test_codec_rejects_hostile_lengths():
    """Malformed/hostile frames with negative or oversized length
    prefixes must fail as clean decode errors, not empty slices or
    backwards position moves."""
    from ripplemq_tpu.wire.codec import _write_varint

    def varint(n):
        out = bytearray()
        _write_varint(out, n)
        return bytes(out)

    for tag in (b"s", b"b", b"l", b"m", b"v"):
        with pytest.raises(ValueError):
            decode(tag + varint(-1))          # negative length/count
        with pytest.raises(ValueError):
            decode(tag + varint(1 << 40))     # exceeds remaining buffer
    # negative dict-key length inside an otherwise valid dict
    with pytest.raises(ValueError):
        decode(b"m" + varint(1) + varint(-3) + b"n")
    # vector whose length table overruns the frame, and one whose blob
    # does (table valid, payload bytes missing)
    import struct as _struct

    with pytest.raises(ValueError):
        decode(b"v" + varint(3) + _struct.pack("<I", 1))
    with pytest.raises(ValueError):
        decode(b"v" + varint(2) + _struct.pack("<II", 3, 3) + b"abc")


def test_inproc_basic_and_handler_error():
    net = InProcNetwork()
    net.register("b1", lambda req: {"ok": True, "echo": req["x"]})
    net.register("boom", lambda req: 1 / 0)
    c = net.client("c1")
    assert c.call("b1", {"type": "t", "x": b"payload"})["echo"] == b"payload"
    resp = c.call("boom", {"type": "t"})
    assert resp["ok"] is False and "ZeroDivisionError" in resp["error"]


def test_inproc_faults():
    net = InProcNetwork()
    net.register("b1", lambda req: {"ok": True})
    c = net.client("c1")
    assert c.call("b1", {"type": "t"})["ok"]

    net.set_down("b1")
    with pytest.raises(RpcError):
        c.call("b1", {"type": "t"})
    net.set_up("b1")

    net.block("c1", "b1")
    with pytest.raises(RpcTimeout):
        c.call("b1", {"type": "t"})
    net.unblock("c1", "b1")

    net.drop_next("c1", "b1", 2)
    for _ in range(2):
        with pytest.raises(RpcTimeout):
            c.call("b1", {"type": "t"})
    assert c.call("b1", {"type": "t"})["ok"]

    with pytest.raises(RpcError):
        c.call("nonexistent", {"type": "t"})


def test_tcp_roundtrip_pipelined():
    seen = []

    def handler(req):
        seen.append(req["i"])
        return {"ok": True, "i": req["i"], "data": req["data"]}

    server = TcpServer("127.0.0.1", 0, handler)
    server.start()
    client = TcpClient()
    try:
        addr = f"127.0.0.1:{server.port}"
        futs = [
            client.call_async(addr, {"type": "echo", "i": i, "data": b"x" * i})
            for i in range(32)
        ]
        for i, fut in enumerate(futs):
            resp = fut.result(timeout=5)
            assert resp["i"] == i and resp["data"] == b"x" * i
    finally:
        client.close()
        server.stop()


def test_tcp_handler_exception_becomes_error_response():
    server = TcpServer("127.0.0.1", 0, lambda req: {}[req["missing"]])
    server.start()
    client = TcpClient()
    try:
        resp = client.call(f"127.0.0.1:{server.port}", {"type": "t", "missing": "k"})
        assert resp["ok"] is False and "internal" in resp["error"]
    finally:
        client.close()
        server.stop()


def test_tcp_concurrent_callers_share_connection():
    server = TcpServer("127.0.0.1", 0, lambda req: {"ok": True, "i": req["i"]})
    server.start()
    client = TcpClient()
    errors = []

    def worker(i):
        try:
            resp = client.call(f"127.0.0.1:{server.port}", {"type": "t", "i": i})
            assert resp["i"] == i
        except Exception as e:  # pragma: no cover
            errors.append(e)

    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(20)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not errors
    finally:
        client.close()
        server.stop()


def test_tcp_server_stop_fails_inflight_cleanly():
    server = TcpServer("127.0.0.1", 0, lambda req: {"ok": True})
    server.start()
    client = TcpClient()
    addr = f"127.0.0.1:{server.port}"
    assert client.call(addr, {"type": "t"})["ok"]
    server.stop()
    with pytest.raises(RpcError):
        client.call(addr, {"type": "t"}, timeout=2)
    client.close()


def test_bulk_vector_roundtrip_fuzz():
    """Property check for the packed-vector fast path: random bytes
    lists (varied lengths, empty elements, nesting) round-trip exactly,
    through BOTH encoders, and the two wire forms decode to the same
    value (bulk encoder ↔ generic decoder interop is the same codec —
    the vector is just another tag — so equality across forms is the
    interop contract)."""
    import random

    rng = random.Random(0xC0DEC)
    for _ in range(200):
        n = rng.randrange(0, 40)
        vec = [
            bytes(rng.getrandbits(8) for _ in range(rng.randrange(0, 64)))
            for _ in range(n)
        ]
        value = rng.choice([
            vec,
            {"messages": vec, "n": n},
            {"nested": [vec, {"again": vec}], "tag": "x"},
        ])
        bulk = encode(value)
        generic = encode(value, bulk=False)
        assert decode(bulk) == value
        assert decode(generic) == value
        assert decode(bulk) == decode(generic)


def test_bulk_vector_edge_cases():
    from ripplemq_tpu.wire.codec import _VEC

    # Empty-bytes elements and bytearray/memoryview inputs normalize to
    # bytes on decode, same as the generic path.
    v = [b"", bytearray(b"xy"), memoryview(b"z"), b"\x00" * 5]
    assert decode(encode(v)) == [b"", b"xy", b"z", b"\x00" * 5]
    # Mixed lists must stay on the generic form (no vector tag).
    mixed = [b"a", 1, b"c"]
    assert encode(mixed)[0:1] != _VEC
    assert decode(encode(mixed)) == mixed
    # Empty list stays generic too (nothing to pack).
    assert encode([])[0:1] != _VEC
    # The produce-body shape takes the vector form and is
    # self-consistent.
    body = {"type": "produce", "messages": [b"m" * 100] * 64}
    assert _VEC in encode(body)
    assert decode(encode(body)) == body


def test_tcp_pipelining_out_of_order_responses_concurrent():
    """Frame pipelining under concurrent callers with responses
    completing OUT OF ORDER: early requests are held by the handler
    while later ones answer first; every future must still resolve to
    its own request's payload (request-id matching, not FIFO)."""
    import time as _time

    def handler(req):
        if req["i"] % 4 == 0:
            _time.sleep(0.05)  # stall every 4th: later ids overtake it
        return {"ok": True, "i": req["i"], "data": req["data"]}

    server = TcpServer("127.0.0.1", 0, handler, workers=8)
    server.start()
    client = TcpClient()
    errors = []

    def caller(base):
        try:
            addr = f"127.0.0.1:{server.port}"
            futs = [
                (i, client.call_async(
                    addr, {"type": "echo", "i": i, "data": b"%d" % i}))
                for i in range(base, base + 16)
            ]
            for i, fut in futs:
                resp = fut.result(timeout=10)
                assert resp["i"] == i and resp["data"] == b"%d" % i
        except Exception as e:  # pragma: no cover - failure detail
            errors.append(repr(e))

    try:
        threads = [threading.Thread(target=caller, args=(k * 100,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
    finally:
        client.close()
        server.stop()


def test_codec_rejects_out_of_range_ints():
    with pytest.raises(OverflowError):
        encode(2**63)
    with pytest.raises(OverflowError):
        encode(-(2**63) - 1)
    assert decode(encode(2**63 - 1)) == 2**63 - 1
    assert decode(encode(-(2**63))) == -(2**63)


@pytest.mark.parametrize("workers,waits", [(8, False), (2, True)])
def test_rpc_queue_wait_grows_when_pool_is_smaller_than_concurrency(
        workers, waits):
    """ISSUE 24: `rpc.queue_wait_us` is the time from a frame's read to
    its handler's start — the wait for a pool worker. Eight concurrent
    50 ms requests find a worker at once in a pool of eight and queue
    three deep behind a pool of two."""
    import time as _time

    from ripplemq_tpu.obs.metrics import Metrics

    def handler(req):
        _time.sleep(0.05)
        return {"ok": True}

    m = Metrics()
    server = TcpServer("127.0.0.1", 0, handler, workers=workers, metrics=m)
    server.start()
    client = TcpClient()
    try:
        addr = f"127.0.0.1:{server.port}"
        futs = [client.call_async(addr, {"type": "x"}) for _ in range(8)]
        for fut in futs:
            assert fut.result(timeout=10)["ok"]
    finally:
        client.close()
        server.stop()
    h = m.histogram("rpc.queue_wait_us")
    assert h.count == 8  # one observation per RPC
    if waits:
        # Waves of two: the last pair waited three handler times.
        assert h.max >= 120_000 and h.total >= 6 * 45_000, (h.max, h.total)
    else:
        assert h.max < 40_000, h.max


def test_tcp_server_without_a_registry_observes_nothing():
    """A bare TcpServer (tests, engine workers) takes no registry: the
    queue-wait observation is a no-op and nothing else changes."""
    server = TcpServer("127.0.0.1", 0, lambda req: {"ok": True})
    server.start()
    client = TcpClient()
    try:
        assert client.call(f"127.0.0.1:{server.port}", {"type": "x"})["ok"]
    finally:
        client.close()
        server.stop()
    assert server._clock() == 0.0  # the disabled registry's constant clock
