#!/usr/bin/env python3
"""Traffic generator `storm`: several clients at once, each on its own
kept connection, open loop or closed.  A mix (benchmark/traffic/<name>.json)
names it and gives it

  queries        as `ticker`'s: the mix's own templates and parameters, or
                 the name of one of the configuration's lists
  nocache        send nocache=1
  ingest         has to be false: a storm posts nothing, the range stays
                 where the bulk ends
  clients        how many kept connections, each a thread of ONE helper
                 process (this file run as a program): a client is a
                 `harness.Client`, what `ticker` asks through, with its
                 `quick_ack`; no byte of an answer is read under the
                 server's GIL
  arrivals       "closed": each client asks again at once until the
                 seconds have passed: the rate completed IS the rate the
                 server sustains with that many answers in flight, and
                 the latencies hold no queue at the clients.
                 "poisson": open loop.  round(rate_qps x seconds) queries
                 fall due at times drawn BEFORE the window: exponential
                 gaps drawn from ARRIVAL_SEED and the count, so every
                 seed meets the same gaps, in an order drawn from the
                 seed, stretched so that the last falls due as the
                 window's seconds end.  An arrival is taken by the next
                 free client, which sleeps until it is due; where all are
                 busy it waits, and that wait counts.
  rate_qps       with "poisson": the offered rate
  check_sample   how many answers are kept for the comparison, by a
                 reservoir over the arrivals whose slots are drawn from
                 the seed before the window; the last arrival's always

The texts come in `ticker`'s balanced rounds, drawn from the seed before
the window too: arrival i asks the same text whatever the server does.

A query's latency is its completion less its SCHEDULED arrival (closed:
less its send), so a queue at the clients counts: no coordinated
omission.  The window runs from its opening until the last answer is
read, and every arrival is in it: `queries_per_s` is all of them over
that.  `client_wait_s` holds, per query, its send less its scheduled
arrival (nil in a closed loop): in an open loop most of it is the queue
the mix asks for, arrivals that found every client busy (no per-layer
metric reads it yet: a reader over `win` is the open-loop cell's to
bring); the run says so LOUDLY
only where arrivals a free client slept for were sent late: then the
helper was starved.  `producer_wait_s` is 0.0: there is no producer.

Warm-up: every distinct text once on the harness's own connection (the
result cache, the compiled shapes), then every client connects and asks
every text once, all clients at once (the serving threads, the writer's
helpers and its kept buffers under overlapping answers).

The helper takes two frames on its stdin (8 bytes of length, a pickle):
the plan (address, clients, texts, range) and, once it has answered
"ready", the window (arrivals, text of each, reservoir slots, seconds).
It answers the second with every query's times and the kept bodies, and
ends when its stdin closes or its parent goes.
"""

from __future__ import annotations

import http.client
import os
import pickle
import struct
import subprocess
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:       # run as a program: the helper
    sys.path.insert(0, BENCH)

import harness  # noqa: E402
import stats  # noqa: E402

ticker = harness.load_module("traffic", "ticker")
expand, answered = ticker.expand, ticker.answered   # the texts, a whole answer

HEADER = struct.Struct("<Q")
# closed loop: texts and slots are drawn for this many queries a second,
# far over what eight clients complete
CLOSED_CAP_QPS = 5000
# open loop: what the gaps every seed meets are drawn from
ARRIVAL_SEED = 38
LOUD_WAIT_MS = 2.0


def send(pipe, obj) -> None:
    frame = pickle.dumps(obj, protocol=5)
    pipe.write(HEADER.pack(len(frame)))
    pipe.write(frame)
    pipe.flush()


def recv(pipe):
    """The next frame, or None at the pipe's end.  Only bytes that this
    benchmark's own processes wrote."""
    head = pipe.read(HEADER.size)
    if len(head) < HEADER.size:
        return None
    (n,) = HEADER.unpack(head)
    frame = pipe.read(n)
    if len(frame) < n:
        return None
    return pickle.loads(frame)


def schedule(mix: dict, seed_rng, seconds: float):
    """-> (due times in seconds from the window's opening, or None for a
    closed loop; how many queries texts and slots are drawn for)."""
    if mix["arrivals"] == "closed":
        return None, int(CLOSED_CAP_QPS * seconds)
    if mix["arrivals"] != "poisson":
        raise ValueError(f"storm knows no arrivals {mix['arrivals']!r}")
    n = max(1, round(mix["rate_qps"] * seconds))
    gaps = np.random.default_rng([ARRIVAL_SEED, n]).exponential(1.0, n)
    due = np.cumsum(seed_rng.permutation(gaps))
    return due * (seconds / due[-1]), n


class Generator(ticker.Generator):
    """`ticker`'s texts, rounds and warm-up on the harness's own
    connection; the window is the helper's."""

    def __init__(self, server, data, cfg: dict, mix: dict, seed: int,
                 annotate=None):
        if mix["ingest"]:
            raise ValueError("a storm mix does not ingest")
        super().__init__(server, data, cfg, mix, seed, annotate)
        self.texts = [q for _, texts in self.queries for q in texts]
        self.index = {q: i for i, q in enumerate(self.texts)}
        self.proc = None
        self.connects = 0

    def warm_up(self) -> int:
        # the helper imports while the harness's own connection asks
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        n = super().warm_up()
        d = self.data
        send(self.proc.stdin, dict(
            addr=self.server.srv_addr, clients=self.mix["clients"],
            texts=self.texts, start=d.start, end=d.end, step=d.step,
            nocache=self.mix["nocache"]))
        ready = self._answer()
        self.connects = ready["connects"]
        return n + ready["asked"]

    def _answer(self) -> dict:
        out = recv(self.proc.stdout)
        if out is None:
            raise RuntimeError("the storm's helper ended (exit code "
                               f"{self.proc.wait()}): its stderr says why")
        if isinstance(out, BaseException):
            raise out
        return out

    def window(self, seconds: float) -> dict:
        due, n = schedule(self.mix, self.rng, seconds)
        drawn = [self._next_query() for _ in range(n)]
        text_of = np.fromiter((self.index[q] for _, q in drawn), np.int64, n)
        # the reservoir's slots, as `ticker` draws them: arrival i goes
        # to slot i while slots are free, then to slots[i] where that is
        # one of them
        slots = self.rng.integers(0, np.arange(1, n + 1))
        with self.annotate("bench:storm"):
            t0 = time.perf_counter()
            send(self.proc.stdin, dict(
                due=due, text_of=text_of, slots=slots, seconds=seconds,
                keep=self.mix["check_sample"]))
            got = self._answer()
            wall = time.perf_counter() - t0
        done = got["done"]
        n = int((done >= 0).sum())      # a closed loop stops short of n
        d = self.data
        record = [dict(query=q, template=t, start=d.start, end=d.end,
                       n_tails=0) for t, q in drawn[:n]]
        lat = (done[:n] - got["due"][:n]).tolist()
        waits = (got["sent"][:n] - got["due"][:n]).tolist()
        window_s = float(done[:n].max()) if n else wall
        win = dict(latencies=lat, asked=record, kept=[
            dict(record[i], body=body) for i, body in got["kept"]],
            failed=int((~got["ok"][:n]).sum()), window_s=window_s,
            producer_wait_s=0.0, client_wait_s=waits)
        self._say(win, got, wall)
        return win

    def _say(self, win: dict, got: dict, wall: float) -> None:
        """The window's own line, and loudly where the HELPER ran late:
        an arrival a free client slept for, sent late, is the harness's
        doing; one that found every client busy is the queue the mix
        asks for, and its wait is in the latencies."""
        n, waits = len(win["latencies"]), np.array(win["client_wait_s"])
        early = got["early"][:n]
        p90 = 1e3 * stats.percentile(waits.tolist(), 90) if n else 0.0
        late = 1e3 * stats.percentile(waits[early].tolist(), 90) \
            if early.any() else 0.0
        open_loop = self.mix["arrivals"] == "poisson"
        harness.log(
            f"storm: {self.mix['clients']} clients, {self.mix['arrivals']}"
            + (f" at {self.mix['rate_qps']} queries/s offered"
               if open_loop else "")
            + f"; {n} queries in {win['window_s']:.3f} s = "
            f"{n / win['window_s']:.2f} queries/s completed (the harness's "
            f"own clock round the helper: {wall:.3f} s); "
            + (f"client wait p90 {p90:.3f} ms, longest "
               f"{1e3 * waits.max(initial=0.0):.3f} ms; "
               f"{n - int(early.sum())} arrivals found every client busy; "
               f"the others were sent {late:.3f} ms (p90) after they fell "
               "due; " if open_loop else "")
            + f"connections opened {self.connects} before the window, "
            f"{got['connects']} by its end")
        if late > LOUD_WAIT_MS:
            print(f"storm.py: THE HELPER SENT {late:.2f} ms (p90) AFTER "
                  "QUERIES FELL DUE THOUGH A CLIENT WAS FREE: it was "
                  "starved; the latencies hold that wait, and they read "
                  "the harness, not the server", file=sys.stderr)

    def close(self) -> None:
        """Stops the helper and waits until it has ended."""
        super().close()
        if self.proc is None:
            return
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:     # its reader went first
                pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.proc = None


# ---- the helper process

class Clients:
    """The helper's side: `clients` connections, one thread each."""

    def __init__(self, plan: dict):
        self.plan = plan
        self.conns = [harness.Client(*plan["addr"])
                      for _ in range(plan["clients"])]

    def ask(self, conn, text: int) -> bytes:
        """The raw answer; b"" where the server refused or the
        connection failed (a failed query, not a failed run)."""
        p = self.plan
        try:
            return conn.query_range(p["texts"][text], p["start"], p["end"],
                                    p["step"], p["nocache"])
        except (OSError, http.client.HTTPException):
            conn.hang_up()
            return b""

    def connects(self) -> int:
        return sum(c.connects for c in self.conns)

    def each(self, work) -> None:
        """`work(conn)` on every connection at once; the first failure
        is raised here."""
        failed = []

        def run(conn):
            try:
                work(conn)
            except BaseException as e:  # noqa: BLE001 - raised below
                failed.append(e)
        threads = [threading.Thread(target=run, args=(c,), daemon=True)
                   for c in self.conns]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if failed:
            raise failed[0]

    def warm_up(self) -> int:
        """Every text once on every connection, all at once; an answer
        that is not whole fails the run here, before it measures."""
        texts = self.plan["texts"]

        def warm(conn):
            for t, text in enumerate(texts):
                if not answered(self.ask(conn, t)):
                    raise RuntimeError(f"no whole answer to {text!r}")
        self.each(warm)
        return len(texts) * len(self.conns)

    def storm(self, w: dict) -> dict:
        """The window: every arrival's due, send and completion time
        from the opening (completion -1 where a closed loop never came
        to it), whether it was answered whole, and the kept bodies."""
        n, due_at, closed = len(w["text_of"]), w["due"], w["due"] is None
        due, sent = np.zeros(n), np.zeros(n)
        done, ok = np.full(n, -1.0), np.zeros(n, dtype=bool)
        early = np.zeros(n, dtype=bool)     # taken before it fell due
        lock = threading.Lock()
        taken = [0]
        kept = {}       # slot -> (arrival, body), the newest arrival's
        latest = {}     # connection -> (arrival, body)
        t_open = time.perf_counter()

        def client(conn):
            while True:
                with lock:
                    i = taken[0]
                    if i >= n or (closed and time.perf_counter() - t_open
                                  >= w["seconds"]):
                        return
                    taken[0] += 1
                if closed:
                    due[i] = time.perf_counter() - t_open
                else:
                    due[i] = due_at[i]
                    wait = t_open + due[i] - time.perf_counter()
                    if wait > 0:
                        early[i] = True
                        time.sleep(wait)
                sent[i] = time.perf_counter() - t_open
                body = self.ask(conn, int(w["text_of"][i]))
                done[i] = time.perf_counter() - t_open
                ok[i] = answered(body)
                latest[id(conn)] = (i, body)
                slot = i if i < w["keep"] else int(w["slots"][i])
                if slot < w["keep"]:
                    with lock:
                        if kept.get(slot, (-1,))[0] < i:
                            kept[slot] = (i, body)

        self.each(client)
        bodies = dict(kept.values())            # arrival -> body
        bodies.update([max(latest.values(), key=lambda ib: ib[0])]
                      if latest else [])        # ... and the last arrival's
        return dict(due=due, sent=sent, done=done, ok=ok, early=early,
                    kept=sorted(bodies.items()), connects=self.connects())


def serve() -> int:
    """The helper: plan, "ready", window, result; then it waits for its
    stdin to close."""
    out = os.fdopen(os.dup(sys.stdout.fileno()), "wb")
    # nothing but frames may reach the pipe: a stray print goes to stderr
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    stdin = sys.stdin.buffer
    try:
        plan = recv(stdin)
        if plan is None:        # closed before it was started
            return 0
        try:
            clients = Clients(plan)
            send(out, dict(asked=clients.warm_up(),
                           connects=clients.connects()))
            while True:
                w = recv(stdin)
                if w is None:
                    return 0
                send(out, clients.storm(w))
        except BrokenPipeError:
            raise
        except BaseException as e:  # noqa: BLE001 - handed to the parent
            send(out, RuntimeError(
                f"the storm's helper failed: {type(e).__name__}: {e}"))
            return 1
    except BrokenPipeError:     # the parent closed, or went
        return 0


if __name__ == "__main__":
    os._exit(serve())
