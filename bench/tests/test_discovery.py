"""A traffic file and a metric reader dropped in by name are found with no
edit to ``bench/run.py``."""
import json

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
