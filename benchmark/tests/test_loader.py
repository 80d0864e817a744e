"""The loader accepts the committed benchmark and refuses what the
driver refuses."""

import importlib
import json
import os
import shutil

import pytest

from lib import spec


def test_the_committed_benchmark_loads_and_every_file_is_there():
    bench = spec.Benchmark()
    assert set(bench.cells) >= {"minload5k-backlog", "fifo1k-flood"}
    for cell in bench.cells:
        assert bench.config_file(cell)["nodes"] > 0
        assert bench.traffic_file(cell)["streams"]
        names = [m["name"] for m in bench.metrics_for(cell, "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        layer = bench.metrics_for(cell, "per_layer")
        assert layer
        for m in layer:
            doc = bench.metric_file(m["name"])
            reader = importlib.import_module("readers." + doc["reader"])
            assert callable(reader.read)
            # a per-layer metric moves an end-to-end metric this cell has
            assert m["moves"] in names


@pytest.fixture()
def copy(tmp_path):
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def edit(root, fn):
    path = root / "BENCHMARK.json"
    doc = json.loads(path.read_text())
    fn(doc)
    path.write_text(json.dumps(doc))


@pytest.mark.parametrize("bad", [
    "start p95", "start,p95", "start/p95", "", "-start", ".start",
    "x" * 65, "stärt", "a\tb"])
def test_a_name_the_driver_refuses_is_refused(copy, bad):
    edit(copy, lambda d: d["end_to_end"][0].update(name=bad))
    with pytest.raises(spec.SpecError):
        spec.Benchmark(str(copy))


@pytest.mark.parametrize("bad", [
    "tokens per second", "µs", "", "x" * 17, "a,b", "jobs s"])
def test_a_unit_the_driver_refuses_is_refused(copy, bad):
    edit(copy, lambda d: d["per_layer"][0].update(unit=bad))
    with pytest.raises(spec.SpecError):
        spec.Benchmark(str(copy))


def test_good_names_and_units_pass():
    for name in ("start_p95_ms", "prelude_ms.latency", "9lives", "_x-1"):
        assert spec.check_name(name, "name") == name
    for unit in ("ms", "jobs/s", "%", "bytes", "us"):
        assert spec.check_unit(unit, "unit") == unit


def test_other_refusals(copy):
    edit(copy, lambda d: d["per_layer"][0].update(moves="no_such_metric"))
    with pytest.raises(spec.SpecError):
        spec.Benchmark(str(copy))
    edit(copy, lambda d: (d["per_layer"][0].update(moves="setup_s"),
                          d["workloads"][0].update(config="nowhere")))
    with pytest.raises(spec.SpecError):
        spec.Benchmark(str(copy))


def test_an_unknown_cell_and_a_file_outside_paths(copy):
    bench = spec.Benchmark(str(copy))
    with pytest.raises(spec.SpecError):
        bench.cell("no-such-cell")
    edit(copy, lambda d: d["configs"][0].update(file="etc/config.yaml"))
    with pytest.raises(spec.SpecError):
        spec.Benchmark(str(copy)).config_file("minload5k-backlog")
