"""The histogram deployment (`histo8k`): what its generator promises, the
`services` mix's one query, and a rehearsal of both cells through
run.py's measure() on the CPU's device at 8 instances x 12 buckets - a
sound run reads correct, the control (the reference in bfloat16) reads
above the configuration's limit."""

import os

import numpy as np
import pytest

import harness
import reference
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["refresh", "services"]


def config(**over) -> dict:
    cfg = harness.load_json(BENCH, "configs", "histo8k.json")
    cfg.update(over)
    return cfg


def small() -> dict:
    return config(series=96, instances=8, jobs=4, range_h=1)


def bulk(cfg: dict, seed: int, k: int = 40):
    gen = harness.load_module("deployments", "histogram").Deployment(cfg)
    rng = np.random.default_rng(seed)
    ts, vals = gen.scrapes(rng, 1_000_000, k)
    more_ts, more = gen.scrapes(rng, 1_000_000 + k * gen.scrape_ms, 4)
    return gen, np.hstack([ts, more_ts]), np.hstack([vals, more])


def test_a_seed_gives_the_same_scrapes_and_a_scrape_is_one_histogram():
    cfg = small()
    gen, ts, vals = bulk(cfg, 2_400_000_011)
    _, ts2, vals2 = bulk(cfg, 2_400_000_011)
    assert (ts == ts2).all() and (vals == vals2).all()
    _, ts3, vals3 = bulk(cfg, 2_400_000_017)
    assert (vals != vals3).any()
    n_b = len(cfg["buckets"])
    assert ts.shape == vals.shape == (cfg["series"], 44)
    by_inst = ts.reshape(-1, n_b, 44)
    # the 12 buckets of an instance are one scrape: one timestamp
    assert (by_inst == by_inst[:, :1]).all()
    assert (np.diff(ts, axis=1) > 0).all()
    assert len({t.tobytes() for t in by_inst[:, 0]}) == cfg["instances"]
    counts = vals.reshape(-1, n_b, 44)
    assert (counts == np.floor(counts)).all()           # whole numbers
    assert (np.diff(counts, axis=1) >= 0).all()         # cumulative along le
    assert (np.diff(counts, axis=2) >= 0).all()         # never fall, tails too
    # +Inf is the total: every request of a scrape interval, 15 s at
    # 20 to 200 a second, lands in it; the tails go on from the bulk
    assert cfg["buckets"][-1] == "+Inf"
    per_scrape = np.diff(counts[:, -1], axis=1)
    assert (per_scrape > 0).all() and 300 * 0.8 < per_scrape.mean() < 3000
    assert (gen.last == counts[:, :, -1]).all()


def test_the_labels_are_distinct_and_bucket_major_within_an_instance():
    cfg = config()
    gen = harness.load_module("deployments", "histogram").Deployment(cfg)
    labels = gen.labels()
    assert len(labels) == cfg["series"] == 8160
    assert len({tuple(sorted(l.items())) for l in labels}) == 8160
    assert [l["le"] for l in labels[:12]] == cfg["buckets"]
    assert {l["instance"] for l in labels[:12]} == {"host-0"}
    jobs = {}
    for l in labels:
        jobs.setdefault(l["job"], set()).add(l["instance"])
    assert len(jobs) == 17 and {len(v) for v in jobs.values()} == {40}
    with pytest.raises(ValueError):
        harness.load_module("deployments", "histogram").Deployment(
            config(series=8161))


def test_every_p99_lies_in_a_finite_bucket_and_every_bucket_counts():
    """At the configuration's own size, over an hour of the bulk."""
    cfg = config()
    _, _, vals = bulk(cfg, 2_400_000_029, k=240)
    les = np.array([float(b) for b in cfg["buckets"]])
    total = vals.reshape(cfg["instances"], len(les), -1)[:, :, -1]
    fleet = total.sum(axis=0)
    assert (np.diff(fleet, prepend=0.0) > 0).all()
    job = np.arange(cfg["instances"]) % cfg["jobs"]
    for counts in [fleet] + [total[job == j].sum(axis=0)
                             for j in range(cfg["jobs"])]:
        at = int(np.searchsorted(counts, 0.99 * counts[-1], side="left"))
        assert np.isfinite(les[at]) and 0.1 < les[at] <= 1.0
        q = reference._histogram_quantile(0.99, les, counts[:, None])[0]
        assert 0.1 < q < 1.0


def test_the_services_mix_asks_its_one_query():
    mix = harness.load_json(BENCH, "traffic", "services.json")
    expand = harness.load_module("traffic", mix["generator"]).expand
    (_, texts), = expand(config(), mix["queries"])
    assert texts == ["histogram_quantile(0.99, sum by (le, job)"
                     "(rate(latency_bucket[5m])))"]
    assert reference.parse(texts[0]) == (
        "hq", 0.99, ("sum", ("le", "job"),
                     ("rollup", "rate", "latency_bucket", {}, 300_000)))
    refresh = harness.load_json(BENCH, "traffic", "refresh.json")
    assert {k: v for k, v in mix.items() if k not in ("what", "queries")} == \
        {k: v for k, v in refresh.items() if k not in ("what", "queries")}


@pytest.mark.parametrize("mix_name", CELLS)
def test_a_sound_rehearsal_is_correct(mix_name):
    import jax
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cell = run.find(bench["workloads"], "histo8k." + mix_name, "workload")
    mix = harness.load_json(BENCH, "traffic", mix_name + ".json")
    result, _ = run.measure(bench, cell, small(), mix, 3_000_000_019, 2.0,
                            False, jax.devices()[:1], {})
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    # the percentiles are end to end in `refresh` alone (PERF.md section 2)
    percentiles = {"query_p50_ms", "query_p90_ms"} \
        if mix_name == "refresh" else set()
    assert set(result["metrics"]) == \
        percentiles | {"queries_per_s", "setup_s"}
    # 1 row, or 4 jobs' rows, of the range's steps in each checked answer
    rows = 1 if mix_name == "refresh" else 4
    assert result["checks"]["values"]["value"] >= rows * 40


@pytest.mark.parametrize("mix_name", CELLS)
def test_the_control_reads_above_the_limit(mix_name):
    """The reference in bfloat16 against the reference, on the data and
    the query of the mix, at a size where a group sums as many rows as
    the cell's does (refresh 680, services 40)."""
    cfg = config(range_h=1) if mix_name == "refresh" else \
        config(series=480, instances=40, jobs=1, range_h=1)
    data = harness.Dataset(cfg, 3_000_000_023, 1_790_000_000_000)
    mix = harness.load_json(BENCH, "traffic", mix_name + ".json")
    expand = harness.load_module("traffic", mix["generator"]).expand
    records = [dict(query=q, start=data.start, end=data.end, n_tails=0)
               for _, texts in expand(cfg, mix["queries"]) for q in texts]
    numbers = harness.check_answers(data, records,
                                    round_rollup=reference.to_bfloat16)
    assert numbers["rel_err"] > 3 * cfg["limits"]["rel_err"]
    assert not all(ok for *_, ok in run.judge(numbers, 0, cfg["limits"]))
