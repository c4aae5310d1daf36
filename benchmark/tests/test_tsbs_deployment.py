"""The TSBS devops deployment (`tsbs-devops-512`): what its generator
promises, the `devops` mix's two templates, a rehearsal of the cell
through run.py's measure() on the CPU's device at 16 hosts x 14 h - a
sound run reads correct, the control (the reference in bfloat16) reads
above the configuration's limit, and an answer made with a regex that is
not anchored reads series_mismatch."""

import json
import os
import re

import numpy as np
import pytest

import harness
import reference
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOW = 1_790_000_000_000
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]
TAGS = {"hostname", "region", "datacenter", "rack", "os", "arch", "team",
        "service", "service_version", "service_environment"}


def config(**over) -> dict:
    cfg = harness.load_json(BENCH, "configs", "tsbs-devops-512.json")
    cfg.update(over)
    return cfg


def small() -> dict:
    return config(hosts=16, range_h=14)


def mix() -> dict:
    return harness.load_json(BENCH, "traffic", "devops.json")


def test_a_seed_gives_the_same_fleet_and_values_another_other_values():
    a = harness.Dataset(small(), 3_700_000_011, NOW)
    b = harness.Dataset(small(), 3_700_000_011, NOW)
    c = harness.Dataset(small(), 3_700_000_012, NOW)
    assert a.labels == b.labels == c.labels     # the fleet is tag_seed's
    assert (a.ts == b.ts).all() and (a.vals == b.vals).all()
    assert (a.ts == c.ts).all() and (a.vals != c.vals).mean() > 0.9
    other = harness.Dataset(dict(small(), tag_seed=1), 3_700_000_011, NOW)
    assert other.labels != a.labels


def test_the_fleet_has_tsbs_shapes():
    cfg = small()
    data = harness.Dataset(cfg, 3_700_000_013, NOW)
    labels = data.labels
    assert len(labels) == 160 == len(set(data.keys))
    # ten gauges a host, side by side, each carrying the host's ten tags
    for h in range(16):
        host = labels[10 * h:10 * h + 10]
        assert [l["__name__"] for l in host] == ["cpu_" + f for f in FIELDS]
        assert all(set(l) == TAGS | {"__name__"} for l in host)
        tags = [{k: v for k, v in l.items() if k != "__name__"} for l in host]
        assert all(t == tags[0] for t in tags)
        assert tags[0]["hostname"] == f"host_{h}"
        assert tags[0]["datacenter"].startswith(tags[0]["region"])
    # one timestamp a scrape for the whole fleet, every 10 s on the grid
    assert (data.ts == data.ts[0]).all()
    assert (np.diff(data.ts[0]) == 10_000).all() and data.ts[0, 0] % 10_000 == 0
    assert data.ts.shape == (160, 14 * 360)
    assert data.ts[0, 0] == data.t_start and data.t_start % 3_600_000 == 0
    # gauges in [0, 100], kept to two decimals, walking by about N(0, 1)
    assert data.vals.min() >= 0.0 and data.vals.max() <= 100.0
    assert (np.round(data.vals, 2) == data.vals).all()
    steps = np.diff(data.vals, axis=1)
    inside = (data.vals[:, 1:] > 5) & (data.vals[:, :-1] > 5) & \
        (data.vals[:, 1:] < 95) & (data.vals[:, :-1] < 95)
    assert 0.9 < steps[inside].std() < 1.1 and abs(steps[inside].mean()) < 0.02
    # at the configuration's own size: 5120 series, 47.9 M samples
    full = config()
    assert full["hosts"] * 10 * full["range_h"] * 360 == 47_923_200
    with pytest.raises(ValueError, match="jitter"):
        harness.Dataset(dict(small(), jitter_s=1), 1, NOW)


def test_the_walk_goes_on_where_it_stopped():
    gen = harness.load_module("deployments", "tsbs_cpu").Deployment(small())
    rng = np.random.default_rng(5)
    ts, vals = gen.scrapes(rng, 1_000_000, 50)
    more_ts, more = gen.scrapes(rng, int(ts[0, -1]), 5)
    assert more_ts[0, 0] == ts[0, -1] + 10_000
    assert np.abs(more[:, 0] - vals[:, -1]).max() < 6.0     # a step, not a draw


def test_the_bulk_lies_behind_the_wall_clock_with_no_room_for_ticks():
    for now in (NOW, 1_793_491_200_000 + 3 * 3_600_000):   # 2026-11-01 03:00
        data = harness.Dataset(small(), 3, now)
        newest = int(data.ts.max())
        assert newest <= now - harness.WALL_MARGIN_MS
        assert harness.month_start_ms(int(data.ts.min())) == \
            harness.month_start_ms(newest)
        assert data.room() == 0
        with pytest.raises(RuntimeError, match="ceiling"):
            data.advance()
    # no month's end in the way: the newest sample is under an hour and
    # ten minutes behind the wall clock, not two days
    data = harness.Dataset(small(), 3, NOW)
    assert NOW - int(data.ts.max()) < harness.WALL_MARGIN_MS + 3_600_000 + 10_000


def test_the_two_templates_parse_and_answer_tsbs_shapes():
    data = harness.Dataset(small(), 3_700_000_014, NOW)
    gen = harness.load_module("traffic", "intervals").Generator(
        None, data, small(), mix(), 9)
    by_name = {}
    for _ in range(4):
        rec = gen._next_query()
        by_name[mix()["templates"][rec["template"]]["name"]] = rec
    dg, cm = by_name["double-groupby-1"], by_name["cpu-max-all-8"]
    assert reference.parse(dg["query"]) == (
        "avg", ("__name__", "hostname"),
        ("rollup", "avg_over_time", None,
         {"__name__": ("=~", "cpu_(usage_user)")}, 3_600_000))
    ast = reference.parse(cm["query"])
    assert ast[:2] == ("max", ("__name__",)) and ast[2][1] == "max_over_time"
    assert ast[2][3]["__name__"] == ("=~", "cpu_(%s)" % "|".join(FIELDS))
    hosts = ast[2][3]["hostname"][1].split("|")
    assert len(set(hosts)) == 8 and all(re.fullmatch(r"host_\d+", h)
                                        for h in hosts)
    # 16 groups of one row over 13 steps; 10 groups of 8 rows over 9
    assert harness.query_work(data, dg) == {
        "samples": 16 * 13 * 360, "out_values": 16 * 13}
    assert harness.query_work(data, cm) == {
        "samples": 80 * 9 * 360, "out_values": 10 * 9}
    assert len(data.work_memo) == 2
    harness.query_work(data, dict(cm))
    assert len(data.work_memo) == 2


def drive(seed=3_700_000_019):
    import jax
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cell = run.find(bench["workloads"], "tsbs-devops-512.devops", "workload")
    result, _ = run.measure(bench, cell, small(), mix(), seed, 2.0, False,
                            jax.devices()[:1], {})
    return result


def test_a_sound_rehearsal_is_correct():
    result = drive()
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"query_p90_ms", "queries_per_s",
                                      "setup_s"}
    # a double-groupby answer alone is 16 rows x 13 steps
    assert result["checks"]["values"]["value"] >= 16 * 13


def test_the_control_reads_above_the_limit():
    """The reference in bfloat16 against the reference on the mix's own
    queries: no server needed."""
    cfg = small()
    data = harness.Dataset(cfg, 3_700_000_023, NOW)
    gen = harness.load_module("traffic", "intervals").Generator(
        None, data, cfg, mix(), 3_700_000_023)
    records = [gen._next_query() for _ in range(6)]
    numbers = harness.check_answers(data, records,
                                    round_rollup=reference.to_bfloat16)
    assert numbers["answers"] == 6 and numbers["series_mismatch"] == 0
    assert numbers["rel_err"] > 3 * cfg["limits"]["rel_err"]
    assert not all(ok for *_, ok in run.judge(numbers, 0, cfg["limits"]))


def test_an_altered_answer_is_not_correct(monkeypatch):
    sound = harness.Server.query_range

    def altered(self, q, *a):
        doc = json.loads(sound(self, q, *a))
        row = doc["data"]["result"][0]["values"]
        row[len(row) // 2][1] = repr(float(row[len(row) // 2][1]) * 1.001)
        return json.dumps(doc, separators=(",", ":")).encode()
    monkeypatch.setattr(harness.Server, "query_range", altered)
    result = drive()
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > 5e-4


def test_an_unanchored_hostname_regex_is_a_series_mismatch(monkeypatch):
    """A server that searches `host_1|host_2` instead of matching the
    whole value answers for host_10..host_15 too: stood in for by asking
    the sound server the loosened pattern in the query's place."""
    sound = harness.Server.query_range

    def loose(self, q, *a):
        return sound(self, re.sub(r'hostname=~"([^"]*)"',
                                  r'hostname=~".*(\1).*"', q), *a)
    monkeypatch.setattr(harness.Server, "query_range", loose)
    result = drive()
    assert not result["correct"]
    # cpu-max-all-8 groups by name alone, so the extra hosts change the
    # maxima, not the rows; the per-host form shows them as rows
    assert result["checks"]["rel_err"]["value"] > 1e-3 or \
        result["checks"]["series_mismatch"]["value"] > 0


def test_an_unanchored_regex_in_a_stand_in_answer_reads_series_mismatch():
    """The planted fault where it shows as rows: `avg by (__name__,
    hostname)` over hosts matched unanchored, compared with the
    reference's anchored answer."""
    cfg = small()
    data = harness.Dataset(cfg, 3_700_000_029, NOW)
    q = ('avg(avg_over_time({__name__=~"cpu_(usage_user)",'
         'hostname=~"host_1|host_2"}[1h])) by (__name__, hostname)')
    start = data.t_start + 3_600_000
    rec = dict(query=q, start=start, end=start + 4 * 3_600_000,
               step=3_600_000, n_tails=0)
    grid = np.arange(rec["start"], rec["end"] + 1, rec["step"])
    idx = [i for i, l in enumerate(data.labels)
           if re.search("host_1|host_2", l["hostname"])
           and re.search("cpu_(usage_user)", l["__name__"])]
    assert len(idx) == 8        # host_1, host_2 and host_10..host_15
    vals = reference.rollup("avg_over_time", data.ts[idx], data.vals[idx],
                            grid, 3_600_000)
    body = json.dumps({"status": "success", "isPartial": False, "data": {
        "resultType": "matrix", "result": [
            {"metric": {"__name__": "cpu_usage_user",
                        "hostname": data.labels[i]["hostname"]},
             "values": [[t / 1000, repr(float(v))]
                        for t, v in zip(grid.tolist(), row)]}
            for i, row in zip(idx, vals)]}}).encode()
    numbers = harness.check_answers(data, [dict(rec, body=body)])
    assert numbers["series_mismatch"] == 6 and numbers["rel_err"] == 0.0
    assert not all(ok for *_, ok in run.judge(numbers, 0, cfg["limits"]))
