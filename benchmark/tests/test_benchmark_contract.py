"""What every cell of BENCHMARK.json rests on, by cell and by
configuration, on the CPU in seconds: the guard ISSUE 37 asked for in
tier-1 (`tests/`), which a PR of kind `benchmark` may not add there; it
imports nothing of the program, so a later PR can copy it to `tests/` as
it is (PERF.md, Open questions).

* the anchor's arithmetic on three fixed wall clocks, a month's end
  among them, for every configuration;
* every query of every cell's mix parses in the plain reference;
* every cell's config, mix, generator, deployment and layer files exist;
* the cell's generator at 64 series draws the same queries twice for one
  seed.
"""

import calendar
import os
import re
import time

import pytest

import harness
import reference

BENCH = harness.HERE
BENCHMARK = harness.load_json(harness.ROOT, "BENCHMARK.json")
CONFIGS = {c["name"]: c for c in BENCHMARK["configs"]}
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
H = 3_600_000


def at(*ymdhm) -> int:
    return calendar.timegm(ymdhm + (0,)) * 1000


NOWS = [at(2026, 11, 15, 9, 0), at(2026, 11, 1, 0, 5), at(2028, 2, 29, 23, 59)]


def small(config: str) -> dict:
    """The configuration at 64 series or so (8 hosts of ten gauges: a
    cpu-max-all-8 query draws eight)."""
    cfg = harness.load_json(harness.ROOT, CONFIGS[config]["file"])
    sizes = {"counters": dict(series=64, instances=8, jobs=4),
             "counters_ha": dict(series=64, instances=8, jobs=4),
             "histogram": dict(series=96, instances=8, jobs=4),
             "tsbs_cpu": dict(hosts=8)}
    cfg.update(sizes[cfg["deployment"]])
    return cfg


def month(ms: int) -> tuple:
    return time.gmtime(ms // 1000)[:2]


@pytest.mark.parametrize("now", NOWS)
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_anchor_keeps_a_run_in_one_past_month(config, now):
    cfg = small(config)
    data = harness.Dataset(cfg, 3_700_000_051, now)
    jitter = int(cfg["jitter_s"] * 1000)
    assert data.latest <= now - harness.WALL_MARGIN_MS
    assert data.ts.max() <= data.latest + jitter
    assert month(int(data.ts.min())) == month(data.latest + jitter)
    if cfg.get("ingests", True):
        # two days of ticks under the ceiling at a 60 s step
        assert data.room() >= harness.WINDOW_TICKS + 110 + 3
        assert data.latest - data.end >= (harness.WINDOW_TICKS + 113) * data.step
    else:
        assert data.room() == 0
        assert now - data.ts.max() < 45 * 24 * H


def queries_of(cell: dict, n: int = 12, seed: int = 3_700_000_053) -> list:
    """The first n queries the cell's generator draws, as records."""
    cfg = small(cell["config"])
    mix = harness.load_json(BENCH, "traffic", cell["traffic"] + ".json")
    data = harness.Dataset(cfg, seed, NOWS[0])
    gen = harness.load_module("traffic", mix["generator"]).Generator(
        None, data, cfg, mix, seed)
    out = []
    for _ in range(n):
        q = gen._next_query()
        out.append(q if isinstance(q, dict) else dict(
            template=q[0], query=q[1], start=data.start, end=data.end))
    return out


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_has_its_files_and_asks_what_the_reference_reads(cell):
    w = CELLS[cell]
    cfg = harness.load_json(harness.ROOT, CONFIGS[w["config"]]["file"])
    mix = harness.load_json(BENCH, "traffic", w["traffic"] + ".json")
    for kind, name in (("deployments", cfg["deployment"]),
                       ("traffic", mix["generator"])):
        assert os.path.exists(os.path.join(BENCH, kind, name + ".py"))
    if mix["ingest"]:
        assert cfg.get("ingests", True), "the anchor keeps this config no room"
    for m in BENCHMARK["per_layer"]:
        if cell in m.get("workloads", [cell]):
            spec = harness.load_json(BENCH, "layers", m["name"] + ".json")
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    asked = queries_of(w)
    labels = harness.Dataset(small(w["config"]), 1, NOWS[0]).labels
    for q in asked:
        # no placeholder left unfilled, and the text selects something
        assert not re.search(r"\{[A-Za-z_]+(:[0-9]+)?\}", q["query"])
        assert reference.row_labels(reference.parse(q["query"]), labels)
    # the same seed draws the same queries, another seed another order
    assert asked == queries_of(w)
    if len({q["query"] for q in asked}) > 1:
        assert asked != queries_of(w, seed=3_700_000_059)
