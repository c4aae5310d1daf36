"""The producer's stream of ticks is the one Dataset.advance() +
harness.exposition() would have drawn in the client's own process: arrays and
bytes, tick for tick; and the helper ends with its parent."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness

BENCH = harness.HERE
NOW = 1_794_000_000_000
PREROLL = 110
TICKS = 12


def small(config: str) -> dict:
    cfg = harness.load_json(BENCH, "configs", config + ".json")
    if cfg["deployment"] == "histogram":
        cfg.update(series=96, instances=8, jobs=4, range_h=1)
    else:
        cfg.update(series=64, instances=8, jobs=4, range_h=1)
    return cfg


def producer():
    return harness.load_module("traffic", "producer")


@pytest.mark.parametrize("seed", [5, 3_500_000_029])
@pytest.mark.parametrize("config", ["dash8k", "histo8k", "dash8k-ha2"])
def test_the_helpers_ticks_equal_the_in_process_twins(config, seed):
    cfg = small(config)
    assert cfg["deployment"] == {"dash8k": "counters",
                                 "histo8k": "histogram",
                                 "dash8k-ha2": "counters_ha"}[config]
    twin = harness.Dataset(cfg, seed, NOW)
    data = harness.Dataset(cfg, seed, NOW)
    twin.advance(PREROLL)
    data.advance(PREROLL)
    helper = producer().Producer()
    try:
        helper.start(data.hand_over())
        with pytest.raises(RuntimeError, match="producer"):
            data.advance()
        for _ in range(TICKS):
            ts, vals, text = helper.take()
            data.take((ts, vals))
            want_ts, want_vals = twin.advance()
            np.testing.assert_array_equal(ts, want_ts)
            np.testing.assert_array_equal(vals, want_vals)
            assert ts.dtype == want_ts.dtype and vals.dtype == want_vals.dtype
            assert text == harness.exposition(twin.keys, want_ts, want_vals)
            assert data.end == twin.end
        assert helper.proc.poll() is None      # still ahead, not ended
    finally:
        helper.close()
    assert helper.proc.poll() is not None
    assert len(data.tails) == len(twin.tails) == 1 + TICKS
    for got, want in zip(data.tails, twin.tails):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    for n in (0, 1, 1 + TICKS):
        for got, want in zip(data.snapshot(n), twin.snapshot(n)):
            np.testing.assert_array_equal(got, want)
    # a line a sample, the newest sample of the last series last
    last = text.decode().splitlines()[-1]
    assert last == f"{data.keys[-1].decode()} {int(vals[-1, -1])} {ts[-1, -1]}"


def test_a_helper_that_fails_says_so_in_the_client():
    data = harness.Dataset(small("dash8k"), 5, NOW)
    state = data.hand_over()
    state["cfg"] = dict(state["cfg"], deployment="no_such_generator")
    helper = producer().Producer()
    try:
        helper.start(state)
        with pytest.raises(RuntimeError, match="the producer failed"):
            helper.take()
    finally:
        helper.close()
    assert helper.proc.poll() is not None


def test_a_helper_never_started_ends_on_close():
    helper = producer().Producer()
    helper.close()
    assert helper.proc.poll() == 0


def gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


def test_the_helper_ends_with_its_parent():
    """A parent that is killed while its helper is four ticks ahead and
    blocked on the pipe: the helper's write fails, and it exits."""
    script = f"""
import os, sys, time
sys.path.insert(0, {BENCH!r})
import harness
cfg = harness.load_json({BENCH!r}, "configs", "dash8k.json")
cfg.update(series=64, instances=8, jobs=4, range_h=1)
data = harness.Dataset(cfg, 5, {NOW})
helper = harness.load_module("traffic", "producer").Producer()
helper.start(data.hand_over())
helper.take()
print(helper.proc.pid, flush=True)
time.sleep(0.5)
os.kill(os.getpid(), 9)
"""
    parent = subprocess.Popen([sys.executable, "-c", script],
                              stdout=subprocess.PIPE)
    pid = int(parent.stdout.readline())
    assert parent.wait(timeout=60) == -9
    deadline = time.time() + 20
    while not gone(pid) and time.time() < deadline:
        time.sleep(0.05)
    assert gone(pid)
