"""The `deepqueue10k-backlog300k` cell's files (as test_widegang.py): the
loader takes the configuration, the traffic and the three metric files,
the YAML the deployment writes carries the job-size weight and NO
`ScheduledBatchSize` (upstream's default 100,000 is the point), the
cluster is northstar-10k's, and the preload deals the same multiset for
every seed.

No dry run here: `--dry-run N` divides the cluster and the preload, not
`ScheduledBatchSize`, so a rehearsal's queue (6,000 at N = 50) never
reaches the cut.  What covers the cut on the CPU is
tests/test_batch_cut.py, against the plain reference
(cranesched_tpu/testing/batch_cut_reference.py); at full size, on the
chip, tools/check_batch_cut.py."""

import collections

from lib import deploy, spec
from lib.traffic import draw_jobs

CELL = "deepqueue10k-backlog300k"
NORTH = "northstar10k-gangs"
BACKLOG_CELLS = ["minload5k-backlog", NORTH, "widegang10k-gangs64", CELL]
NEW_METRICS = {"prelude_ranked_jobs.latency": "ranked",
               "prelude_cut_jobs.latency": "cut",
               "prelude_cut_ms.latency": "cut_ms"}


def test_the_loader_takes_the_configuration_and_the_traffic():
    bench = spec.Benchmark()
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepqueue-10k", "backlog-deep300k", 1)
    assert len(cell["why"]) <= 200
    cfg = bench.config_file(CELL)
    north = bench.config_file(NORTH)
    # northstar-10k's cluster, word for word
    same = ("nodes", "node_cpu", "node_mem_gib", "partitions",
            "drained_share", "wal", "snapshot_interval_s", "daemon_args",
            "base_seed")
    assert {k: cfg[k] for k in same} == {k: north[k] for k in same}
    # its scheduler block less the key that got round the default
    block = dict(north["scheduler"])
    assert block.pop("ScheduledBatchSize") == 200000
    assert cfg["scheduler"] == block
    assert cfg["priority"] == {"WeightJobSize": 10000}
    # its guarantees, word for word, and the cut's
    for name, text in north["guarantees"].items():
        assert cfg["guarantees"][name] == text
    assert set(cfg["guarantees"]) - set(north["guarantees"]) == {
        "priority_cut"}
    entry = bench.configs["deepqueue-10k"]
    assert cfg["reduced"] == ["standing_backlog"] == entry["reduced"]
    assert (cfg["standing_backlog"], cfg["standing_backlog_published"]) == (
        300000, 900000)
    assert len(entry["source"]) <= 200 and entry["source"] == cfg["source"]
    assert not any(a.startswith("ScheduledBatchSize")
                   for a in cfg["assumed"])
    assert any("WeightJobSize 10000" in a for a in cfg["assumed"])

    traffic = bench.traffic_file(CELL)
    gangs = bench.traffic_file(NORTH)
    # backlog-gangs.json but for the seed and the depth of the queue
    for key in ("mixes", "streams", "drain_seconds", "trace"):
        assert traffic[key] == gangs[key], key
    assert traffic["base_seed"] == 44
    pre, north_pre = (t["setup"]["preload"] for t in (traffic, gangs))
    assert pre["pending_target"] == cfg["standing_backlog"]
    assert (pre["tolerance"], pre["fill_estimate"], pre["chunk"]) == (
        0.005, 3880, 2000)
    assert pre["max_jobs"] == 330000
    assert {k: pre[k] for k in ("partitions", "mix")} == {
        k: north_pre[k] for k in ("partitions", "mix")}
    assert (traffic["setup"]["open_at_phase_s"],
            traffic["setup"]["settle_cycles"]) == (5, 5)
    # three batches deep: the cut has something to cut
    assert pre["pending_target"] == 3 * 100000


def test_the_cell_reports_what_northstar_reports():
    bench = spec.Benchmark()
    e2e = {m["name"] for m in bench.metrics_for(CELL, "end_to_end")}
    assert e2e == {"start_p95_ms", "submit_p95_ms", "query_p90_ms",
                   "setup_s"}
    for m in bench.end_to_end:
        if m["name"] in ("start_p95_ms", "submit_p95_ms", "query_p90_ms"):
            assert m["workloads"][-1] == CELL
    assert ({m["name"] for m in bench.metrics_for(CELL, "per_layer")}
            == {m["name"] for m in bench.metrics_for(NORTH, "per_layer")})
    per_layer = {m["name"]: m for m in bench.per_layer}
    # appended: the last three entries, for all four backlog cells
    assert list(per_layer)[-3:] == list(NEW_METRICS)
    for name, field in NEW_METRICS.items():
        entry = per_layer[name]
        assert entry["workloads"] == BACKLOG_CELLS
        assert (entry["layer"], entry["moves"]) == ("prelude",
                                                    "start_p95_ms")
        doc = bench.metric_file(name)
        assert doc["reader"] == "cycle_trace"
        assert doc["args"] == {"field": field, "stat": "median"}


def test_the_new_metrics_read_nothing_from_a_program_without_the_fields():
    """The parent's rows have no `ranked`, `cut` or `cut_ms`: the reader
    returns nothing and does not raise, and the line leaves them out."""
    import importlib
    bench = spec.Benchmark()
    reader = importlib.import_module("readers.cycle_trace")
    old = [{"solver": "backfill", "candidates": 100000, "priority_ms": 26.7}]
    new = [dict(old[0], ranked=300400, cut=200400, cut_ms=2.5),
           dict(old[0], ranked=300404, cut=200404, cut_ms=3.5)]
    for name, field in NEW_METRICS.items():
        args = bench.metric_file(name)["args"]
        assert reader.read({"cycles": old, "window": (0, 1)}, args) is None
        assert reader.read({"cycles": new, "window": (0, 1)}, args) == (
            new[0][field] + new[1][field]) / 2


def test_the_yaml_keeps_upstreams_default_batch(tmp_path):
    cfg = spec.Benchmark().config_file(CELL)
    cluster = deploy.make_cluster(cfg, seed=2_147_483_659)
    path = tmp_path / "ctld.yaml"
    deploy.write_config(str(path), cfg, cluster, str(tmp_path / "wal"))
    text = path.read_text()
    assert "ScheduledBatchSize" not in text and "Solver" not in text
    assert "Priority:\n  WeightJobSize: 10000\n" in text
    assert "  MaxNodesPerJob: 8\n" in text and "  Backfill: true\n" in text
    # what the daemon reads back: no key, so its own default stands
    from cranesched_tpu.ctld import SchedulerConfig
    from cranesched_tpu.utils.config import load_config
    loaded = load_config(str(path))
    assert loaded.scheduler == {"Backfill": True, "MaxNodesPerJob": 8}
    assert loaded.priority == {"WeightJobSize": 10000}
    assert SchedulerConfig().schedule_batch_size == 100_000
    # the same multiset of nodes as northstar-10k deals (one base_seed)
    north = deploy.make_cluster(
        spec.Benchmark().config_file(NORTH), seed=7)
    assert sorted(zip(cluster["cpu"], cluster["mem_gib"])) == sorted(
        zip(north["cpu"], north["mem_gib"]))
    assert len(cluster["drained"]) == 200


def test_the_preload_deals_the_same_multiset_for_every_seed():
    traffic = spec.Benchmark().traffic_file(CELL)
    pre = traffic["setup"]["preload"]
    dealt = [draw_jobs(traffic["mixes"][pre["mix"]], pre["partitions"],
                       9_600, traffic["base_seed"], seed, "preload")
             for seed in (1, 2_147_483_659)]
    sizes = [collections.Counter((j.cpu, j.mem_gib, j.node_num)
                                 for j in jobs) for jobs in dealt]
    assert sizes[0] == sizes[1]
    assert [j.node_num for j in dealt[0]] != [j.node_num for j in dealt[1]]
    widths = collections.Counter(j.node_num for j in dealt[0])
    assert set(widths) == set(range(1, 9))
    # a third of the queue a partition
    parts = collections.Counter(j.partition for j in dealt[0])
    assert set(parts.values()) == {3_200}
