#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the chip it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip.  Set-up (server, bulk load from the seed, flush,
warm-up of every shape the mix uses), then the timed window, then the
server is stopped and the sampled answers are held to the plain
reference.  The last line of stdout is the result; the numbers compared
stand beside their limits on the last lines of stderr and under "checks"
in the result.  It knows no cell, deployment, mix or layer metric by
name: it finds their files by the names BENCHMARK.json gives.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402
import stats  # noqa: E402
import xtrace  # noqa: E402
from harness import HERE, ROOT, log  # noqa: E402

# what a user of the system sees; the harness takes these itself
END_TO_END = {
    "query_p50_ms": lambda w, setup: 1e3 * stats.percentile(w["latencies"], 50),
    "query_p90_ms": lambda w, setup: 1e3 * stats.percentile(w["latencies"], 90),
    "queries_per_s": lambda w, setup: stats.rate(len(w["latencies"]),
                                                 w["window_s"]),
    "setup_s": lambda w, setup: setup,
}


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: BENCHMARK.json has no {what} {name!r}")


def devices_or_exit(chips: int):
    """The chips this cell asks for, or a non-zero exit: never a fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    return devs


def set_up(cfg: dict, mix: dict, seed: int, data_dir: str, annotate=None):
    """Server, bulk load from the seed, flush, warm-up.  -> (server, data,
    the mix's traffic generator, {phase: seconds})."""
    from victoriametrics_tpu import native
    if not native.available():
        raise SystemExit("run.py: the native library is unavailable; the "
                         "served fetch path would be another program")
    split = {}
    t = time.perf_counter()
    server = harness.Server(data_dir, cfg.get("server_flags", []))
    split["server"] = time.perf_counter() - t
    t = time.perf_counter()
    data = harness.Dataset(cfg, seed, int(time.time() * 1000))
    n = harness.load_columnar(server, data, data.ts, data.vals)
    # flushed AND merged: the window then meets the same parts in every
    # run, not whatever the background merger had got to (six hours of a
    # deployment's data have long been merged)
    server.get("/internal/force_flush")
    server.get("/internal/force_merge")
    split["load"] = time.perf_counter() - t
    log(f"load: {len(data.keys)} series, {n} samples through "
        "Storage.add_rows_columnar (not HTTP text), then "
        "/internal/force_flush and /internal/force_merge")
    t = time.perf_counter()
    ticker = harness.load_module("traffic", mix["generator"]).Generator(
        server, data, cfg, mix, seed, annotate)
    try:
        warmed = ticker.warm_up()
    except BaseException:
        ticker.close()
        raise
    split["warm_up"] = time.perf_counter() - t
    log(f"warm-up: {warmed} queries")
    return server, data, ticker, split


SLIDES = "vm_device_window_compactions_total"


def window_line(win: dict, ticks: int, room: int, m0: dict, m1: dict) -> str:
    """What a reader of a run's log needs of its window beside the
    metrics: how near the ticks came to the anchor's ceiling, whether the
    client ever waited for its producer, the slowest queries by index
    (the resident window's slide among them, or not), the slides."""
    lat = win["latencies"]
    slowest = sorted(range(len(lat)), key=lambda i: -lat[i])[:3]
    slides = f"{m1[SLIDES] - m0.get(SLIDES, 0):.0f}" if SLIDES in m1 \
        else "not exported"
    return (f"{ticks} ticks of ingest of the {room} the anchor allowed; "
            f"producer_wait_s {win['producer_wait_s']:.4f}; slowest "
            + ", ".join(f"{1e3 * lat[i]:.1f} ms at query {i}"
                        for i in slowest)
            + f"; slides of the resident window {slides}")


def layer_metrics(bench: dict, cell: str, ctx: dict) -> dict:
    """Every per-layer metric this cell reports, by its own reader; a
    reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in bench["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        spec = harness.load_json(HERE, "layers", m["name"] + ".json")
        value = harness.load_module("readers", spec["reader"]).read(
            spec["args"], ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(numbers: dict, failed: int, limits: dict) -> list:
    """[(name, number, limit text, ok)]: each number beside its limit."""
    zero = [(name, numbers[name]) for name in
            ("series_mismatch", "nan_mismatch", "unreadable")] + \
        [("failed", failed)]
    return [("rel_err", numbers["rel_err"], f"<= {limits['rel_err']}",
             numbers["rel_err"] <= limits["rel_err"])] + \
        [(name, v, "== 0", v == 0) for name, v in zero] + \
        [("values", numbers["values"], ">= 1", numbers["values"] >= 1)]


def measure(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
            seconds: float, trace_on: bool, devs: list, peaks: dict,
            control=None):
    """Everything after the look for a chip: set-up, window, checks.
    -> (the result line's object, the verdicts).  `control` (control.py's,
    never a run's) is a rounding put under the reference, whose numbers
    then stand under "control" in the result."""
    import jax
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    annotate = jax.profiler.TraceAnnotation if trace_on else None
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        server = ticker = None
        try:
            server, data, ticker, split = set_up(
                cfg, mix, seed, os.path.join(tmp, "data"), annotate)
            if trace_on:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(tmp, "trace"),
                                         profiler_options=opts)
            m0 = server.metrics()
            setup_s = time.perf_counter() - T_PROCESS
            log(f"setup_s {setup_s:.2f} = server {split['server']:.2f} + "
                f"load {split['load']:.2f} + warm-up {split['warm_up']:.2f} "
                "+ imports and device init")

            room = data.room()
            win = ticker.window(seconds)

            m1 = server.metrics()
            trace = None
            if trace_on:
                jax.profiler.stop_trace()
                trace = xtrace.read(os.path.join(tmp, "trace"))
            device["memory_peak_bytes"] = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)
        finally:
            # the program's state is freed before the reference runs
            if ticker is not None:
                ticker.close()
            if server is not None:
                server.stop()

        n = len(win["latencies"])
        log(f"window: {win['window_s']:.2f} s, {n} queries (n of both "
            f"percentiles), {win['failed']} failed; "
            + window_line(win, room - data.room(), room, m0, m1))
        if win["producer_wait_s"] > 0.01 * win["window_s"]:
            print(f"run.py: THE CLIENT WAITED {win['producer_wait_s']:.2f} s "
                  f"of a window of {win['window_s']:.2f} s FOR TICKS ITS "
                  "PRODUCER HAD NOT READY: queries_per_s reads the "
                  "harness, not the server", file=sys.stderr)
        by_template = ticker.by_template(win)
        for tmpl, lats in by_template.items():
            log(f"  {len(lats)} x {tmpl}: p50 "
                f"{1e3 * stats.percentile(lats, 50):.1f} ms, p90 "
                f"{1e3 * stats.percentile(lats, 90):.1f} ms")
        breakdown = None
        if trace_on:
            ctx = dict(m0=m0, m1=m1, queries=n, window_s=win["window_s"],
                       trace=trace, peaks=peaks, by_template=by_template,
                       work=lambda: [harness.query_work(data, a)
                                     for a in win["asked"]])
            metrics = layer_metrics(bench, cell["name"], ctx)
            device["busy_s"] = xtrace.busy_s(trace)
            device["window_s"] = win["window_s"]
            breakdown = {"device_ops": xtrace.top_ops(trace),
                         "idle_gaps": xtrace.idle_gaps(trace)}
        else:
            metrics = {m["name"]: {"value": END_TO_END[m["name"]](win, setup_s),
                                   "unit": m["unit"]}
                       for m in bench["end_to_end"]
                       if cell["name"] in m.get("workloads", [cell["name"]])}
        t = time.perf_counter()
        numbers = harness.check_answers(data, win["kept"])
        log(f"checked {numbers['answers']} answers, {numbers['values']} "
            f"values, against the plain reference in "
            f"{time.perf_counter() - t:.2f} s")
        controlled = None if control is None else \
            harness.check_answers(data, win["kept"], round_rollup=control)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    verdicts = judge(numbers, win["failed"], cfg["limits"])
    result = {"correct": all(ok for *_, ok in verdicts), "attempted": n,
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if controlled is not None:
        result["control"] = dict(controlled, correct=all(
            ok for *_, ok in judge(controlled, 0, cfg["limits"])))
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, v, limit, _ in verdicts}
    return result, verdicts


def on_the_chip(workload: str, seed: int, seconds: float, trace_on: bool,
                control=None):
    """The cell's files by the names BENCHMARK.json gives, the look for
    the chip, then measure()."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], workload, "workload")
    cfg = harness.load_json(
        ROOT, find(bench["configs"], cell["config"], "config")["file"])
    mix = harness.load_json(HERE, "traffic", cell["traffic"] + ".json")
    devs = devices_or_exit(cell["chips"])
    peaks = harness.load_json(HERE, "peaks.json")
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"run.py: no peaks for {devs[0].device_kind!r}")
    log(f"device: {devs[0].platform} / {devs[0].device_kind} / "
        f"{len(devs)} chip(s); cell {workload}, seed {seed}")
    return measure(bench, cell, cfg, mix, seed, seconds, trace_on, devs,
                   peaks[devs[0].device_kind], control)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()

    result, verdicts = on_the_chip(args.workload, args.seed, args.seconds,
                                   bool(args.trace))
    sys.stdout.flush()
    for name, v, limit, ok in verdicts:
        print(f"check {name}: {v} (limit {limit}) "
              f"{'ok' if ok else 'NOT CORRECT'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
