"""`correct` has to come out false when it should.  These drive run.py's
measure() - everything after the look for a chip - on the CPU backend at a
size a test run can hold, against the real server:

* a sound run reads correct;
* the CONTROL (the reference in bfloat16, put in the program's place)
  reads rel_err above the limit of every configuration;
* an answer altered where it is produced reads not correct;
* an acknowledged import that the server then drops (its state left
  unchanged under a tick) reads not correct: the newest step is missing
  or stale;
* the HA pair's server started WITHOUT its dedup flag, or the reference's
  rule for equal timestamps flipped, reads not correct;
* an answer of the mix `flood` (generator `storm`, eight clients; no
  cell runs it yet) altered where the harness takes it from its helper
  reads not correct.
"""

import glob
import json
import os

import pytest

import harness
import reference
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(series=1024, instances=32, jobs=4, range_h=1)


def small(config: str) -> dict:
    cfg = harness.load_json(BENCH, "configs", config + ".json")
    cfg.update(SMALL)
    return cfg


def drive(config: str, mix_name: str, seed: int = 3_000_000_019):
    import jax
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cell = {"name": bench["workloads"][0]["name"]}
    mix = harness.load_json(BENCH, "traffic", mix_name + ".json")
    result, _ = run.measure(bench, cell, small(config), mix, seed, 2.0, False,
                            jax.devices()[:1], {})
    return result


CELLS = [("dash8k", "refresh"), ("dash8k", "explore"),
         ("dash32k", "refresh"), ("dash8k", "explore_live"),
         ("dash8k-ha2", "refresh"), ("dash8k", "flood")]


@pytest.mark.parametrize("config,mix", CELLS)
def test_a_sound_run_is_correct(config, mix):
    result = drive(config, mix)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("config,mix", CELLS)
def test_the_control_reads_above_the_limit(config, mix):
    """The reference in bfloat16 against the reference, on the data and
    the queries of the mix: no server needed."""
    cfg = small(config)
    data = harness.Dataset(cfg, 3_000_000_023, 1_790_000_000_000)
    mixd = harness.load_json(BENCH, "traffic", mix + ".json")
    expand = harness.load_module("traffic", mixd["generator"]).expand
    records = [dict(query=q, start=data.start, end=data.end, n_tails=0)
               for _, texts in expand(cfg, mixd["queries"]) for q in texts]
    numbers = harness.check_answers(data, records,
                                    round_rollup=reference.to_bfloat16)
    assert numbers["rel_err"] > 3 * cfg["limits"]["rel_err"]
    assert not all(ok for *_, ok in run.judge(numbers, 0, cfg["limits"]))


def test_an_altered_answer_is_not_correct(monkeypatch):
    sound = harness.Server.query_range

    def altered(self, q, *a):
        body = sound(self, q, *a)
        doc = json.loads(body)
        row = doc["data"]["result"][0]["values"]
        row[len(row) // 2][1] = repr(float(row[len(row) // 2][1]) * 1.001)
        return json.dumps(doc, separators=(",", ":")).encode()
    monkeypatch.setattr(harness.Server, "query_range", altered)
    result = drive("dash8k", "explore")
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > 5e-4


@pytest.mark.parametrize("config,mix", [
    ("dash8k", "refresh"), ("dash32k", "refresh"), ("dash8k", "explore_live"),
    ("dash8k-ha2", "refresh")])
def test_a_dropped_import_is_not_correct(monkeypatch, config, mix):
    """The server acknowledges the window's imports and stores none: its
    state stays as the warm-up left it."""
    sound = harness.Server.post
    posts = []

    def dropping(self, path, body):
        posts.append(path)
        if len(posts) > 3:          # the warm ticks' imports go through
            return None
        return sound(self, path, body)
    monkeypatch.setattr(harness.Server, "post", dropping)
    result = drive(config, mix)
    assert len(posts) > 4
    assert not result["correct"], result["checks"]


def test_the_ha_pair_without_its_dedup_flag_is_not_correct(monkeypatch):
    """The reference dedups, the server keeps both replicas' samples: the
    rate's first and last sample are others, and the window's newest
    step may hold a sample the reference dropped."""
    sound = harness.Server.__init__
    started = []

    def without(self, data_dir, flags=()):
        started.append(list(flags))
        sound(self, data_dir)
    monkeypatch.setattr(harness.Server, "__init__", without)
    result = drive("dash8k-ha2", "refresh")
    assert started == [["-dedup.minScrapeInterval=15s"]]
    assert not result["correct"], result["checks"]
    assert result["checks"]["rel_err"]["value"] > 10 * 5e-5


def test_the_tie_rule_flipped_is_not_correct(monkeypatch):
    """Of two samples at a window's newest timestamp the reference keeps
    the SMALLER value: the server, which keeps the larger as upstream
    does, then reads not correct, so the comparison holds the rule."""
    sound = reference.dedup

    def flipped(ts, vals, interval_ms):
        out_ts, out_vals = sound(ts, vals, interval_ms)
        lo_ts, lo_vals = sound(ts, -vals, interval_ms)
        assert (out_ts == lo_ts).all()
        return out_ts, -lo_vals
    monkeypatch.setattr(reference, "dedup", flipped)
    result = drive("dash8k-ha2", "refresh")
    assert not result["correct"], result["checks"]


def test_a_floods_altered_answer_is_not_correct(monkeypatch):
    storm = harness.load_module("traffic", "storm")
    sound = storm.recv

    def altered(pipe):
        got = sound(pipe)
        if isinstance(got, dict) and got.get("kept"):
            i, body = got["kept"][0]
            doc = json.loads(body)
            row = doc["data"]["result"][0]["values"]
            row[len(row) // 2][1] = repr(float(row[len(row) // 2][1]) * 1.001)
            got["kept"][0] = (i, json.dumps(doc, separators=(",", ":")).encode())
        return got
    monkeypatch.setattr(storm, "recv", altered)
    monkeypatch.setattr(harness, "load_module",
                        lambda kind, name, _load=harness.load_module:
                        storm if (kind, name) == ("traffic", "storm")
                        else _load(kind, name))
    result = drive("dash8k", "flood")
    assert not result["correct"]
    assert result["checks"]["rel_err"]["value"] > 5e-4


@pytest.mark.parametrize("cell", ["dash8k-ha2.refresh"])
def test_a_traced_run_reports_every_metric_the_cell_lists(cell):
    """A per-layer metric without a `workloads` key is due in every cell,
    and a reader with nothing to read leaves its metric out of the line,
    which the driver then refuses.  The device trace's metrics read
    nothing on the CPU; every other one the cell lists has to be in a
    traced run's line (`samples_scanned_per_s` among them: the survivors
    counted)."""
    import jax
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    mix = harness.load_json(BENCH, "traffic", entry["traffic"] + ".json")
    result, _ = run.measure(bench, entry, small(entry["config"]), mix,
                            3_800_000_029, 2.0, True, jax.devices()[:1], {})
    due = {m["name"] for m in bench["per_layer"]
           if cell in m.get("workloads", [cell])
           and m["source"] != "device_trace"}
    assert due - set(result["metrics"]) == set()
    assert result["correct"], result["checks"]


def test_run_refuses_a_machine_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.devices_or_exit(1)
    assert e.value.code == 2
    assert "TPU" in capsys.readouterr().err


def test_every_name_in_benchmark_json_has_its_files():
    bench = harness.load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.dirname(BENCH), c["file"])
        assert os.path.exists(os.path.join(
            BENCH, "deployments", cfg["deployment"] + ".py"))
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"]
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        spec = harness.load_json(BENCH, "layers", m["name"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    assert not glob.glob(os.path.join(BENCH, "layers", "* *"))
