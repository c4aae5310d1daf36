#!/usr/bin/env python3
"""The producer of a refresh mix's ticks: ONE helper process that makes
each tick's scrapes `(ts, vals)` and their exposition text off the
window's clock, a bounded number of ticks ahead of the client.

The helper is a fresh interpreter (what multiprocessing's `spawn` starts,
without its resource tracker, a third process that would outlive a run):
this file run as a program.  It imports NumPy, `harness` (the text's
format, the deployment generators' loader) and `deployments/<name>.py`,
never jax or the program.  It reads from its stdin what
`Dataset.hand_over()` gave - the rng's state, the deployment generator's
own state, `end`, the keys - and goes on drawing exactly the stream
`Dataset.advance()` would have drawn in the client's process, tick for
tick and byte for byte (tests/test_producer.py holds the two to each
other).  Each tick goes to its stdout as one frame: 8 bytes of length,
then the pickled `(ts, vals, text)`; a failure goes as a pickled
exception, which `Producer.take` raises in the client.

The helper ends with its parent: the frame it is writing when the parent
goes fails with a broken pipe, and it exits.
"""

from __future__ import annotations

import fcntl
import os
import pickle
import queue
import select
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

# ticks made ahead of the client: AHEAD - 1 wait in the helper's queue
# and one in the pipe (some 14 MB a tick at 32,768 series)
AHEAD = 4
PIPE_BYTES = 1 << 20    # the most an unprivileged process may ask for
HEADER = struct.Struct("<Q")


class Producer:
    """The client's end.  The helper starts (and imports) at once;
    `start(state)` hands it the stream, `take()` -> (ts, vals, text) of
    the next tick; `wait_s` sums the time the client waited for a tick
    that was not ready (the read of a tick that was is not in it)."""

    def __init__(self):
        self.wait_s = 0.0
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, bufsize=0)
        self.fd = self.proc.stdout.fileno()
        widen(self.fd)

    def start(self, state: dict) -> None:
        try:
            self.proc.stdin.write(pickle.dumps(state, protocol=5))
            self.proc.stdin.close()
        except BrokenPipeError:
            self.close()
            raise RuntimeError("the producer ended before it was started")

    def _read(self, n: int) -> bytearray:
        buf = bytearray(n)
        view, got = memoryview(buf), 0
        while got < n:
            k = self.proc.stdout.readinto(view[got:])
            if not k:
                raise RuntimeError(
                    "the producer ended (exit code "
                    f"{self.proc.wait()}): its stderr says why")
            got += k
        return buf

    def take(self):
        if not select.select([self.fd], [], [], 0)[0]:
            t0 = time.perf_counter()
            select.select([self.fd], [], [])
            self.wait_s += time.perf_counter() - t0
        (n,) = HEADER.unpack(self._read(HEADER.size))
        # only bytes that this benchmark's own helper wrote
        tick = pickle.loads(self._read(n))
        if isinstance(tick, BaseException):
            raise tick
        return tick

    def close(self) -> None:
        """Stops the helper and waits until it has ended."""
        for pipe in (self.proc.stdin, self.proc.stdout):
            if pipe is not None and not pipe.closed:
                pipe.close()
        try:
            self.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def widen(fd: int) -> None:
    """A pipe of 1 MiB where the kernel grants it: a tick crosses in
    some tens of reads, not hundreds."""
    try:
        fcntl.fcntl(fd, fcntl.F_SETPIPE_SZ, PIPE_BYTES)
    except OSError:
        pass


def ticks(state: dict):
    """The stream itself: what Dataset.advance() + harness.exposition()
    would have made from here on, one query step a tick, without end."""
    cfg = state["cfg"]
    gen = harness.load_module("deployments", cfg["deployment"]).Deployment(cfg)
    vars(gen).update(state["gen"])
    rng = np.random.default_rng()
    rng.bit_generator.state = state["rng"]
    end, step = state["end"], state["step"]
    while True:
        ts, vals = gen.scrapes(rng, end, step // state["scrape"])
        end += step
        yield ts, vals, harness.exposition(state["keys"], ts, vals)


def serve() -> int:
    """The helper: a thread makes ticks into a bounded queue, this one
    writes them out; a broken pipe (the parent closed, or went) ends it."""
    out = os.dup(sys.stdout.fileno())
    # nothing but frames may reach the pipe: a stray print goes to stderr
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    widen(out)
    handed = sys.stdin.buffer.read()
    if not handed:      # closed before it was started
        return 0
    state = pickle.loads(handed)
    made = queue.Queue(maxsize=AHEAD - 1)

    def make():
        try:
            for tick in ticks(state):
                made.put(pickle.dumps(tick, protocol=5))
        except BaseException as e:  # noqa: BLE001 - handed to the client
            made.put(pickle.dumps(RuntimeError(
                f"the producer failed: {type(e).__name__}: {e}")))
            made.put(None)

    threading.Thread(target=make, daemon=True).start()
    try:
        with os.fdopen(out, "wb", buffering=0) as pipe:
            while True:
                frame = made.get()
                if frame is None:
                    return 1
                pipe.write(HEADER.pack(len(frame)))
                view = memoryview(frame)
                while view:
                    view = view[pipe.write(view):]
    except BrokenPipeError:
        return 0


if __name__ == "__main__":
    # _exit: the making thread may hold a tick half made; nothing to flush
    os._exit(serve())
