"""A traffic file, a metric reader and a plain reference dropped in by
name are found with no edit to ``bench/run.py``."""
import json
import re

import pytest

from bench import harness, traffic

run = harness.load_module(harness.BENCH / "run.py", "bench_run_main")


def test_new_metric_and_traffic_files_are_found(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new.metric_ms.py").write_text(
        "def read(ctx):\n    return {'value': ctx['x'] * 2}\n")
    (tmp_path / "metrics" / "silent.py").write_text(
        "def read(ctx):\n    return None\n")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bursty.json").write_text(
        json.dumps({"driver": "serve", "rate_per_s": 9.0}))
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    monkeypatch.setattr(traffic, "HERE", tmp_path)
    spec = {"per_layer": [
        {"name": "new.metric_ms", "unit": "ms", "workloads": ["a.b"]},
        {"name": "silent", "unit": "%"},
        {"name": "elsewhere", "unit": "%", "workloads": ["c.d"]}]}
    got = run.per_layer(spec, "a.b", {"x": 21})
    assert got == {"new.metric_ms": {"value": 42, "unit": "ms"}}
    assert traffic.load_mix("bursty")["rate_per_s"] == 9.0


def test_every_named_file_exists():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for m in spec["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in spec["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        assert (harness.BENCH / "drivers" / f"{mix['driver']}.py").is_file()
        assert (harness.BENCH / "limits" / f"{w['name']}.json").is_file()


@pytest.mark.parametrize("path", sorted((harness.BENCH / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_every_configuration_names_an_existing_reference(path):
    config = harness.load_json(path)
    assert (harness.BENCH / "reference"
            / f"{config['reference']}.py").is_file()
    assert callable(harness.reference(config).forward)


def test_missing_reference_fails_before_the_chip(tmp_path, monkeypatch):
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = spec["workloads"][0]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = dict(harness.load_json(harness.ROOT / conf["file"]),
                  reference="no_such_reference")
    (tmp_path / conf["file"]).parent.mkdir(parents=True)
    (tmp_path / conf["file"]).write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "ROOT", tmp_path)

    def chip(_chips):
        raise AssertionError("the chip was looked for")

    monkeypatch.setattr(harness, "require_chips", chip)
    want = str(harness.BENCH / "reference" / "no_such_reference.py")
    with pytest.raises(SystemExit, match=re.escape(want)):
        run.main(["--workload", cell["name"], "--seed", "1",
                  "--seconds", "1"])
