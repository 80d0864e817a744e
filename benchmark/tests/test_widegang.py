"""The `widegang10k-gangs64` cell's files (as test_northstar.py, less the
dry run, which tests/ covers at K = 64 by a cycle through the replay):
the loader takes the configuration and the traffic, the YAML the
deployment writes carries the bound, and the mixes deal widths 1-64,
the same multiset for every seed."""

import collections

from lib import deploy, spec
from lib.traffic import draw_jobs

CELL = "widegang10k-gangs64"


def test_the_loader_takes_the_configuration_and_the_traffic():
    bench = spec.Benchmark()
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "widegang-10k", "backlog-gangs64", 1)
    cfg = bench.config_file(CELL)
    north = bench.config_file("northstar10k-gangs")
    # northstar-10k's cluster, word for word, but for the bound
    same = ("nodes", "node_cpu", "node_mem_gib", "partitions",
            "drained_share", "priority", "wal", "snapshot_interval_s",
            "daemon_args", "base_seed", "guarantees", "reduced")
    assert {k: cfg[k] for k in same} == {k: north[k] for k in same}
    assert cfg["scheduler"] == dict(north["scheduler"], MaxNodesPerJob=64)
    assert cfg["reduced"] == [] == bench.configs["widegang-10k"]["reduced"]
    assert len(bench.configs["widegang-10k"]["source"]) <= 200
    traffic = bench.traffic_file(CELL)
    gangs = bench.traffic_file("northstar10k-gangs")
    assert {k: traffic[k] for k in ("drain_seconds", "trace")} == {
        k: gangs[k] for k in ("drain_seconds", "trace")}
    assert traffic["setup"]["preload"]["pending_target"] == 101_376
    assert traffic["setup"]["open_at_phase_s"] == 5
    rates = {s["name"]: (s["rate_per_s"], s["mix"])
             for s in traffic["streams"] if s["kind"] == "submit"}
    assert rates == {"timed": (20, "burst64"), "topup": (4, "gang64")}
    for mix in ("gang64", "burst64"):
        assert traffic["mixes"][mix]["node_num"] == [
            1, cfg["scheduler"]["MaxNodesPerJob"]]
    e2e = {m["name"] for m in bench.metrics_for(CELL, "end_to_end")}
    assert e2e == {"start_p95_ms", "submit_p95_ms", "query_p90_ms",
                   "setup_s"}
    assert ({m["name"] for m in bench.metrics_for(CELL, "per_layer")}
            == {m["name"] for m in bench.metrics_for(
                "northstar10k-gangs", "per_layer")})


def test_the_yaml_carries_the_bound(tmp_path):
    cfg = spec.Benchmark().config_file(CELL)
    cluster = deploy.make_cluster(cfg, seed=2_147_483_659)
    path = tmp_path / "ctld.yaml"
    deploy.write_config(str(path), cfg, cluster, str(tmp_path / "wal"))
    text = path.read_text()
    assert "  MaxNodesPerJob: 64\n" in text and "Solver" not in text
    # the same multiset of nodes as northstar-10k deals (one base_seed)
    north = deploy.make_cluster(
        spec.Benchmark().config_file("northstar10k-gangs"), seed=7)
    assert sorted(zip(cluster["cpu"], cluster["mem_gib"])) == sorted(
        zip(north["cpu"], north["mem_gib"]))


def test_the_mixes_deal_widths_1_to_64_the_same_for_every_seed():
    traffic = spec.Benchmark().traffic_file(CELL)
    for mix, parts in (("gang64", ["batch0", "batch1", "batch2"]),
                       ("burst64", ["inter"])):
        dealt = [draw_jobs(traffic["mixes"][mix], parts, 6_400,
                           traffic["base_seed"], seed, "timed")
                 for seed in (1, 2_147_483_659)]
        widths = [collections.Counter(j.node_num for j in jobs)
                  for jobs in dealt]
        assert widths[0] == widths[1] and set(widths[0]) == set(
            range(1, 65))
        assert [j.node_num for j in dealt[0]] != [
            j.node_num for j in dealt[1]]
        # uniform: no width a third rarer or commoner than 100 of 6,400
        assert all(60 <= n <= 145 for n in widths[0].values())
