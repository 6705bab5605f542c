"""RPC-plane robustness: truncated transfers, over-long lines, chunked
streaming.

Covers the failure modes the reference's net/http handles for free
(IncompleteRead on early close, 414/431 on over-long lines) that a
hand-rolled HTTP plane must reproduce explicitly."""

import socket
import threading

import pytest

from seaweedfs_tpu.cluster import rpc


def _raw_server(script):
    """One-shot raw-socket server: accepts one connection, runs
    script(conn), closes.  Returns (port, thread)."""
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def run():
        conn, _ = srv.accept()
        try:
            script(conn)
        finally:
            conn.close()
            srv.close()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return port, th


def _drain_request(conn):
    buf = b""
    while b"\r\n\r\n" not in buf:
        data = conn.recv(65536)
        if not data:
            return buf
        buf += data
    return buf


def test_early_close_with_content_length_raises():
    """A peer that dies mid-body must surface an error, not a short
    'successful' read (ADVICE r2 medium)."""
    def script(conn):
        _drain_request(conn)
        conn.sendall(b"HTTP/1.1 200 OK\r\n"
                     b"Content-Length: 100\r\n\r\n"
                     b"only-ten-b")  # 10 of 100 bytes, then close

    port, _ = _raw_server(script)
    with pytest.raises(ConnectionError):
        rpc.call(f"http://127.0.0.1:{port}/x", timeout=5.0)


def test_early_close_to_file_raises(tmp_path):
    def script(conn):
        _drain_request(conn)
        conn.sendall(b"HTTP/1.1 200 OK\r\n"
                     b"Content-Length: 1048576\r\n\r\n" + b"x" * 1000)

    port, _ = _raw_server(script)
    dest = tmp_path / "out.bin"
    with pytest.raises(ConnectionError):
        rpc.call_to_file(f"http://127.0.0.1:{port}/x", str(dest),
                         timeout=5.0)


def test_chunked_body_streams_incrementally(tmp_path):
    """call_to_file must stream a chunked upstream in bounded reads, and
    reassemble the exact payload."""
    payload = bytes(range(256)) * 512  # 128KB
    def script(conn):
        _drain_request(conn)
        head = (b"HTTP/1.1 200 OK\r\n"
                b"Transfer-Encoding: chunked\r\n"
                b"Connection: close\r\n\r\n")
        conn.sendall(head)
        for i in range(0, len(payload), 7001):  # awkward chunk sizes
            chunk = payload[i:i + 7001]
            conn.sendall(hex(len(chunk))[2:].encode() + b"\r\n" +
                         chunk + b"\r\n")
        conn.sendall(b"0\r\n\r\n")

    port, _ = _raw_server(script)
    dest = tmp_path / "out.bin"
    n = rpc.call_to_file(f"http://127.0.0.1:{port}/x", str(dest),
                         timeout=5.0)
    assert n == len(payload)
    assert dest.read_bytes() == payload


def test_chunked_read_honors_requested_size():
    def script(conn):
        _drain_request(conn)
        conn.sendall(b"HTTP/1.1 200 OK\r\n"
                     b"Transfer-Encoding: chunked\r\n"
                     b"Connection: close\r\n\r\n"
                     b"10\r\n" + b"a" * 16 + b"\r\n"
                     b"10\r\n" + b"b" * 16 + b"\r\n"
                     b"0\r\n\r\n")

    port, _ = _raw_server(script)
    resp, conn = rpc._request(f"http://127.0.0.1:{port}/x", "GET", None,
                              5.0)
    try:
        assert resp.read(4) == b"aaaa"
        assert resp.read(20) == b"a" * 12 + b"b" * 8
        assert resp.read() == b"b" * 8
        assert resp.read() == b""
    finally:
        conn.close()


def test_chunked_early_close_raises():
    def script(conn):
        _drain_request(conn)
        conn.sendall(b"HTTP/1.1 200 OK\r\n"
                     b"Transfer-Encoding: chunked\r\n\r\n"
                     b"100\r\n" + b"x" * 16)  # promises 256, sends 16

    port, _ = _raw_server(script)
    with pytest.raises(ConnectionError):
        rpc.call(f"http://127.0.0.1:{port}/x", timeout=5.0)


def test_server_rejects_overlong_request_line():
    server = rpc.JsonHttpServer()
    server.route("GET", "/ok", lambda q, b: {"ok": True})
    server.start()
    try:
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5.0) as s:
            s.sendall(b"GET /" + b"a" * 70000 + b" HTTP/1.1\r\n\r\n")
            data = s.recv(65536)
        assert b"414" in data.split(b"\r\n", 1)[0]
    finally:
        server.stop()


def test_server_rejects_overlong_header():
    server = rpc.JsonHttpServer()
    server.route("GET", "/ok", lambda q, b: {"ok": True})
    server.start()
    try:
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5.0) as s:
            s.sendall(b"GET /ok HTTP/1.1\r\nX-Big: " + b"a" * 70000 +
                      b"\r\n\r\n")
            data = s.recv(65536)
        assert b"431" in data.split(b"\r\n", 1)[0]
    finally:
        server.stop()


def test_server_ignores_truncated_request():
    """EOF mid-headers must not route a half-request."""
    hits = []
    server = rpc.JsonHttpServer()
    server.route("POST", "/mutate", lambda q, b: hits.append(1) or {})
    server.start()
    try:
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=5.0) as s:
            s.sendall(b"POST /mutate HTTP/1.1\r\nContent-Le")
        # connection closed mid-headers; give the server a beat
        import time
        time.sleep(0.1)
        assert hits == []
    finally:
        server.stop()


def test_whole_pool_reaped_by_server_idle_timeout_still_delivers_a_post():
    """A client that sat out the server's idle timeout holds a pool of
    dead keep-alives (found on the chip: minutes inside one XLA compile
    between the seal's scatter and the rebuild's).  The one-shot stale
    retry must dial fresh — handing it the next pooled corpse failed
    the POST with BrokenPipe."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    server = rpc.JsonHttpServer(idle_timeout=0.3)
    server.route("POST", "/echo", lambda q, b: {"n": len(b)})
    server.start()
    url = f"http://127.0.0.1:{server.port}/echo"
    gate = threading.Barrier(4)

    def one(_):
        gate.wait(timeout=10)       # four conns open at once
        return rpc.call(url, "POST", b"x", timeout=5.0)
    try:
        with ThreadPoolExecutor(4) as ex:
            assert list(ex.map(one, range(4))) == [{"n": 1}] * 4
        key = ("http", "127.0.0.1", server.port)
        assert len(rpc._pool._idle.get(key, [])) >= 2
        time.sleep(1.0)             # the server reaps them all
        body = b"y" * (4 << 20)
        assert rpc.call(url, "POST", body, timeout=10.0) == \
            {"n": len(body)}
        assert rpc.call(url, "POST", b"z", timeout=5.0) == {"n": 1}
    finally:
        server.stop()
