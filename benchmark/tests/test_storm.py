"""The generator `storm`: several clients, open loop or closed, against
a stub HTTP/1.1 server that counts what it accepts and can be slow."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import harness

BENCH = harness.HERE
NOW = 1_794_000_000_000
FLOOD = harness.load_json(BENCH, "traffic", "flood.json")
# the open loop, which no mix file asks for yet (PERF.md, Open questions)
MIX = dict(FLOOD, arrivals="poisson", rate_qps=100.0)
CFG = dict(harness.load_json(BENCH, "configs", "dash8k.json"),
           series=64, instances=8, jobs=4, range_h=1)


def storm():
    return harness.load_module("traffic", "storm")


class Stub(ThreadingHTTPServer):
    daemon_threads = True

    def __init__(self, delay=0.0):
        self.accepts = 0
        self.asked = []
        self.delay = delay
        self.lock = threading.Lock()
        super().__init__(("127.0.0.1", 0), Handler)

    def get_request(self):
        got = super().get_request()
        self.accepts += 1
        return got


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_GET(self):
        srv = self.server
        with srv.lock:
            srv.asked.append(self.path)
            n = len(srv.asked)
        time.sleep(srv.delay)
        out = json.dumps({"status": "success", "isPartial": False,
                          "data": {"result": []}, "n": n,
                          "path": self.path}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)


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


def generator(client, seed=7, **over):
    data = harness.Dataset(CFG, seed, NOW)
    return storm().Generator(client, data, CFG, dict(MIX, **over), seed)


def test_the_mix_is_explores_texts_without_nocache_for_eight_clients():
    explore = harness.load_json(BENCH, "traffic", "explore.json")
    assert FLOOD["queries"] == explore["queries"]
    assert FLOOD["generator"] == "storm" and FLOOD["clients"] == 8
    assert FLOOD["arrivals"] == "closed" and FLOOD["check_sample"] == 16
    assert FLOOD["nocache"] is False and FLOOD["ingest"] is False
    assert "rate_qps" not in FLOOD      # a closed loop offers no rate
    with pytest.raises(ValueError, match="ingest"):
        generator(None, ingest=True)


def test_the_schedule_is_a_function_of_the_seed_alone():
    s = storm()

    def drawn(seed, seconds=5.0, **over):
        gen = generator(None, seed, **over)
        due, n = s.schedule(gen.mix, gen.rng, seconds)
        return due, [gen._next_query() for _ in range(n)]
    due, asked = drawn(7, rate_qps=200)
    again, asked_again = drawn(7, rate_qps=200)
    np.testing.assert_array_equal(due, again)
    assert asked == asked_again
    other, asked_other = drawn(8, rate_qps=200)
    # every seed meets the same number of arrivals and the same gaps, in
    # another order; the last falls due as the seconds end
    assert len(due) == len(other) == 1000 and (due != other).any()
    assert asked != asked_other
    gaps = lambda d: np.sort(np.diff(d, prepend=0.0))   # noqa: E731
    np.testing.assert_allclose(gaps(due), gaps(other), rtol=1e-9)
    assert due[-1] == pytest.approx(5.0) and (np.diff(due) >= 0).all()
    # exponential gaps: the standard deviation is the mean
    g = np.diff(due, prepend=0.0)
    assert 0.85 < g.std() / g.mean() < 1.15
    # balanced rounds: any four in a row are the four templates
    for r in range(0, 40, 4):
        assert sorted(t for t, _ in asked[r:r + 4]) == [0, 1, 2, 3]
    assert s.schedule(dict(MIX, arrivals="closed"), None, 2.0) == \
        (None, 2 * s.CLOSED_CAP_QPS)
    with pytest.raises(ValueError, match="arrivals"):
        s.schedule(dict(MIX, arrivals="bursty"), None, 2.0)


def test_eight_connections_are_opened_once_each_and_kept(stub):
    srv, client = stub
    gen = generator(client, rate_qps=150)
    try:
        warmed = gen.warm_up()
        texts = 4 * CFG["jobs"]
        # every text once on the harness's connection, once a client
        assert warmed == texts + 8 * texts == len(srv.asked)
        assert srv.accepts == 1 + 8 and gen.connects == 8
        win = gen.window(2.0)
    finally:
        gen.close()
    assert gen.proc is None
    assert srv.accepts == 1 + 8                 # none reopened
    n = len(win["latencies"])
    assert n == 300 == len(win["asked"]) == len(win["client_wait_s"])
    assert len(srv.asked) == warmed + n and win["failed"] == 0
    assert win["producer_wait_s"] == 0.0
    assert 2.0 <= win["window_s"] < 2.5
    # the texts asked are the drawn ones, each with the bulk's range
    d = gen.data
    for rec in win["asked"][:8]:
        assert rec["start"] == d.start and rec["end"] == d.end
        assert rec["n_tails"] == 0
    assert "nocache" not in "".join(srv.asked)
    assert sum(len(v) for v in gen.by_template(win).values()) == n
    assert set(gen.by_template(win)) == set(MIX["queries"]["templates"])


def test_the_reservoir_is_drawn_from_the_seed_and_holds_the_last(stub):
    srv, client = stub

    def kept(seed):
        gen = generator(client, seed, rate_qps=150, check_sample=5)
        try:
            gen.warm_up()
            win = gen.window(1.0)
        finally:
            gen.close()
        assert len(win["latencies"]) == 150
        return win
    win = kept(7)
    # five by the reservoir and the last arrival's, in arrival order
    assert len(win["kept"]) == 6
    assert win["kept"][-1]["query"] == win["asked"][-1]["query"]
    bodies = [json.loads(r["body"]) for r in win["kept"]]
    assert all(b["status"] == "success" for b in bodies)
    # a kept record's body is the answer to its own text
    from urllib.parse import parse_qs, urlparse
    for r, b in zip(win["kept"], bodies):
        assert parse_qs(urlparse(b["path"]).query)["query"] == [r["query"]]
    # the same seed keeps the same arrivals
    again = kept(7)
    assert [r["query"] for r in again["kept"]] == \
        [r["query"] for r in win["kept"]]


def test_a_latency_counts_from_the_scheduled_arrival(stub):
    """Two clients, a server that takes 50 ms, 100 queries/s offered: a
    queue grows at the clients, and the latencies hold it."""
    srv, client = stub
    gen = generator(client, rate_qps=100, clients=2)
    try:
        gen.warm_up()
        srv.delay = 0.05
        win = gen.window(1.0)
    finally:
        gen.close()
    lat, waits = win["latencies"], win["client_wait_s"]
    assert len(lat) == 100 and win["failed"] == 0
    # two clients complete 40 a second: the last waits some 1.5 s
    assert 2.2 < win["window_s"] < 3.2
    assert max(lat) > 1.0 and max(waits) > 1.0
    # latency = wait + service, query by query
    for l, w in zip(lat, waits):
        assert 0.045 < l - w < 0.2


def test_a_closed_loop_asks_again_at_once(stub):
    srv, client = stub
    gen = storm().Generator(client, harness.Dataset(CFG, 7, NOW), CFG,
                            dict(FLOOD, clients=4), 7)
    try:
        gen.warm_up()
        srv.delay = 0.02
        win = gen.window(1.0)
    finally:
        gen.close()
    n = len(win["latencies"])
    # four clients, 20 ms an answer: some 200 a second, none waiting
    assert 120 < n < 210 and len(win["asked"]) == n
    assert max(win["client_wait_s"]) < 0.01
    assert 1.0 <= win["window_s"] < 1.2
    assert len(win["kept"]) in (16, 17)


def test_a_helper_that_fails_says_so_in_the_parent(stub):
    srv, client = stub
    gen = generator(client)
    gen.server = harness.Client("127.0.0.1", 1)     # nothing listens there
    gen.ask = lambda q: (0.0, b"")
    try:
        with pytest.raises(RuntimeError, match="helper"):
            gen.warm_up()
    finally:
        gen.close()
    assert gen.proc is None
