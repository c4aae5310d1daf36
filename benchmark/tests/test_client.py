"""The client's one kept connection (harness.Client),
against a stub HTTP/1.1 server that counts what it accepts."""

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import harness


class Stub(ThreadingHTTPServer):
    daemon_threads = True

    def __init__(self):
        self.accepts = 0
        self.requests = 0
        self.hang_up_after = set()      # request numbers answered, then
        #                                 the connection dropped unannounced
        self.announce_close = set()     # ... or closed with Connection: close
        super().__init__(("127.0.0.1", 0), Handler)

    def get_request(self):
        got = super().get_request()
        self.accepts += 1
        return got


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def answer(self):
        srv = self.server
        srv.requests += 1
        n = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(n) if n else b""
        if self.path.startswith("/refuse"):
            out, status = b'{"status":"error"}', 422
        else:
            out, status = f"{self.command} {self.path} {len(body)}".encode(), 200
        self.send_response(status)
        self.send_header("Content-Length", str(len(out)))
        if srv.requests in srv.announce_close:
            self.send_header("Connection", "close")
            self.close_connection = True
        self.end_headers()
        self.wfile.write(out)
        if srv.requests in srv.hang_up_after:
            self.close_connection = True

    do_GET = do_POST = answer


@pytest.fixture
def stub():
    srv = Stub()
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    client = harness.Client(*srv.server_address)
    try:
        yield srv, client
    finally:
        client.hang_up()
        srv.shutdown()
        srv.server_close()
        thread.join(5)


def test_fifty_calls_open_one_connection(stub):
    srv, client = stub
    for i in range(25):
        assert client.get("/a", n=i) == f"GET /a?n={i} 0".encode()
        client.post("/b", b"x" * (1000 * i))
    assert srv.requests == 50
    assert srv.accepts == 1 and client.connects == 1


def test_a_connection_the_server_closed_is_reopened_once(stub):
    srv, client = stub
    srv.hang_up_after = {2}
    assert client.get("/a") == b"GET /a 0"
    assert client.get("/a") == b"GET /a 0"      # then the server hangs up
    assert client.get("/c") == b"GET /c 0"      # met closed, asked again
    client.post("/d", b"12345")
    assert srv.accepts == 2 and client.connects == 2


def test_an_announced_close_is_taken_at_its_word(stub):
    srv, client = stub
    srv.announce_close = {1}
    assert client.get("/a") == b"GET /a 0"
    assert client.conn is None
    assert client.get("/a") == b"GET /a 0"
    assert srv.accepts == 2 and client.connects == 2


def test_a_refusal_is_an_empty_answer_and_keeps_the_connection(stub):
    srv, client = stub
    with pytest.raises(harness.HTTPStatus) as e:
        client.get("/refuse")
    assert e.value.status == 422
    client.get = lambda path, **params: harness.Client.get(
        client, "/refuse", **params)
    assert client.query_range("up", 0, 60_000, 60_000, True) == b""
    assert srv.accepts == 1


def test_a_server_that_is_gone_raises(stub):
    srv, client = stub
    client.get("/a")
    srv.shutdown()
    srv.server_close()
    # the kept connection's handler thread still answers; a NEW connection
    # is refused
    client.hang_up()
    with pytest.raises(OSError):
        client.get("/a")
