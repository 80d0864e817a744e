"""The handler side of the server lock (ISSUE 42): every per-layer metric
that reads the lock ledger names fields the PROGRAM really writes, here on
the CPU, into the rows of a served daemon whose handlers took the lock.
`test_cycle_metrics.py` drives a bare scheduler, which no handler touches,
so a class's fields (written only on rows of periods in which the class
took the lock) never show there: the metrics that read them go through
`readers/cycle_ratio.py`, and are held to the program here.  Counts and
names only: no number of this run is a measurement."""

import importlib
import time

import pytest

from lib import spec

BENCH = spec.Benchmark()
PER_LAYER = {m["name"]: m for m in BENCH.per_layer}
LATENCY = ["minload5k-backlog", "northstar10k-gangs", "widegang10k-gangs64"]
FLOOD = ["fifo1k-flood", "minload5k-flood"]
#: metric -> (the fields it reads, its cells, the end-to-end metric it moves)
HANDLER = {
    "lock_held_query_share.latency":
        (("rpc_query_held_ms", "period_ms"), LATENCY, "query_p90_ms"),
    "lock_held_submit_share.latency":
        (("rpc_submit_held_ms", "period_ms"), LATENCY, "submit_p95_ms"),
    "lock_held_submit_share.flood":
        (("rpc_submit_batch_held_ms", "period_ms"), FLOOD,
         "started_jobs_per_s"),
    "lock_held_stats_share.latency":
        (("rpc_stats_held_ms", "period_ms"), LATENCY, "query_p90_ms"),
    "lock_held_stats_share.flood":
        (("rpc_stats_held_ms", "period_ms"), FLOOD, "started_jobs_per_s"),
    "lock_unaccounted_share.latency":
        (("lock_unaccounted_ms",), LATENCY, "start_p95_ms"),
    "lock_unaccounted_share.flood":
        (("lock_unaccounted_ms",), FLOOD, "started_jobs_per_s"),
    "query_lock_wait_ms.latency":
        (("rpc_query_wait_ms", "rpc_query_n"), LATENCY, "query_p90_ms"),
    "query_held_ms.latency":
        (("rpc_query_held_ms", "rpc_query_n"), LATENCY, "query_p90_ms"),
    "query_snapshot_ms.latency":
        (("rpc_query_snapshot_ms", "rpc_query_n"), LATENCY, "query_p90_ms"),
    "query_lock_wait_max_ms.latency":
        (("rpc_query_wait_max_ms", "rpc_query_n"), LATENCY, "query_p90_ms"),
    "submit_lock_wait_ms.latency":
        (("rpc_submit_wait_ms", "rpc_submit_n"), LATENCY, "submit_p95_ms"),
    "submit_held_ms.latency":
        (("rpc_submit_held_ms", "rpc_submit_n"), LATENCY, "submit_p95_ms"),
    "submit_lock_wait_max_ms.latency":
        (("rpc_submit_wait_max_ms", "rpc_submit_n"), LATENCY,
         "submit_p95_ms"),
    "ingest_hold_wait_ms.flood":
        (("rpc_submit_batch_wait_ms", "rpc_submit_batch_n"), FLOOD,
         "started_jobs_per_s"),
    "ingest_hold_held_ms.flood":
        (("rpc_submit_batch_held_ms", "rpc_submit_batch_n"), FLOOD,
         "started_jobs_per_s"),
}


def _fields(doc):
    args = doc["args"]
    return tuple(args[k] for k in ("field", "num", "den") if k in args)


@pytest.fixture(scope="module")
def rows():
    """The closed cycle-trace rows of a served daemon over the sim plane
    whose handlers of every class took the lock: single submits, a batch,
    queries, stats reads."""
    from cranesched_tpu.craned import SimCluster
    from cranesched_tpu.ctld import (
        JobScheduler, MetaContainer, SchedulerConfig)
    from cranesched_tpu.rpc import crane_pb2 as pb
    from cranesched_tpu.rpc.client import CtldClient
    from cranesched_tpu.rpc.server import serve

    meta = MetaContainer()
    for i in range(4):
        meta.add_node(f"cn{i}", meta.layout.encode(
            cpu=16, mem_bytes=32 << 30, memsw_bytes=32 << 30,
            is_capacity=True), partitions=("default",))
        meta.craned_up(i)
    # a fifth job stays a candidate and the no-op fingerprint is off, so
    # every cycle rings a row (a cycle with no candidate rings none)
    sched = JobScheduler(meta, SchedulerConfig(
        backfill=False, incremental=False, cycle_idle_sleep=0.06))
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         cycle_interval=0.05)
    client = CtldClient(f"127.0.0.1:{port}")

    def job(cpu):
        return pb.JobSpec(res=pb.ResourceSpec(
            cpu=cpu, mem_bytes=1 << 30, memsw_bytes=1 << 30),
            time_limit=3600, partition="default", user="alice",
            sim_runtime=600.0)

    def closed():
        return [r for r in sched.cycle_trace.snapshot() if "period_ms" in r]

    def seen(name):
        return any(f"rpc_{name}_n" in r for r in closed())

    try:
        assert all(r.job_id for r in
                   client.submit_many([job(16.0)] * 5).replies)
        deadline = time.time() + 20.0
        while time.time() < deadline and not all(
                seen(name) for name in ("submit", "submit_batch", "query",
                                        "stats")):
            assert client.submit(job(0.1)).job_id > 0
            client.query_jobs(user="alice", limit=500)
            client.query_stats()
            client.submit_many([job(0.1)] * 2)
            time.sleep(0.05)
        out = [dict(r) for r in closed()]
    finally:
        server.stop()
    return out


def test_the_new_metrics_are_the_issues_sixteen():
    for name, (fields, cells, moves) in HANDLER.items():
        entry = PER_LAYER[name]
        assert entry["workloads"] == cells, name
        assert entry["moves"] == moves, name
        assert entry["layer"] == "ingest / cycle lock"
        assert entry["source"] == "program_counter"
        assert _fields(BENCH.metric_file(name)) == fields, name
    # appended: nothing that was there moved
    assert list(PER_LAYER)[-len(HANDLER):] == list(HANDLER)


@pytest.mark.parametrize("name", sorted(HANDLER))
def test_metric_reads_what_the_served_program_writes(name, rows):
    doc = BENCH.metric_file(name)
    reader = importlib.import_module("readers." + doc["reader"])
    for field in _fields(doc):
        assert any(field in r for r in rows), (
            f"{name}: no row of the served program has {field!r}")
    ctx = {"cycles": rows, "window": (0.0, 1.0)}
    value = reader.read(ctx, doc["args"])
    assert value is not None and value >= 0.0


def test_every_row_of_the_served_program_keeps_the_lock_identity(rows):
    assert len(rows) >= 3
    for r in rows:
        assert (r["lock_held_work_ms"] + r["lock_held_rpc_ms"]
                + r["lock_unaccounted_ms"]) == pytest.approx(
                    r["period_ms"], abs=0.01)


def test_the_held_shares_and_the_rest_sum_to_the_cycles_time(rows):
    """cycle_ratio over period_ms and cycle_trace's share of a window as
    long as the rows' periods agree: work + classes + rest = 100%."""
    ratio = importlib.import_module("readers.cycle_ratio")
    trace = importlib.import_module("readers.cycle_trace")
    seconds = sum(r["period_ms"] for r in rows) / 1e3
    ctx = {"cycles": rows, "window": (0.0, seconds)}
    share = sum(
        ratio.read(ctx, {"num": f"rpc_{c}_held_ms", "den": "period_ms",
                         "scale": 100.0}) or 0.0
        for c in ("submit", "submit_batch", "query", "stats", "snapshot"))
    share += trace.read(ctx, {"field": "lock_held_work_ms",
                              "stat": "share_of_window_pct"})
    share += trace.read(ctx, {"field": "lock_unaccounted_ms",
                              "stat": "share_of_window_pct"})
    assert share == pytest.approx(100.0, abs=0.05)


# ---------------------------------------------------------------------------
# readers/cycle_ratio.py
# ---------------------------------------------------------------------------

RATIO = importlib.import_module("readers.cycle_ratio")
CYCLES = [
    {"solver": "native", "period_ms": 100.0,
     "rpc_query_n": 2, "rpc_query_held_ms": 30.0, "rpc_query_wait_max_ms": 9.0},
    {"solver": "native", "period_ms": 300.0},              # no query
    {"solver": "native", "period_ms": 100.0,
     "rpc_query_n": 1, "rpc_query_held_ms": 20.0, "rpc_query_wait_max_ms": 3.0},
    {"solver": "skip", "period_ms": 5000.0,
     "rpc_query_n": 9, "rpc_query_held_ms": 900.0},        # left out
]


def test_ratio_is_sum_over_sum_and_a_row_without_the_field_counts_zero():
    ctx = {"cycles": CYCLES}
    assert RATIO.read(ctx, {"num": "rpc_query_held_ms",
                            "den": "rpc_query_n"}) == pytest.approx(50.0 / 3)
    # the period of the cycle that had no query is part of the window
    assert RATIO.read(ctx, {"num": "rpc_query_held_ms", "den": "period_ms",
                            "scale": 100.0}) == pytest.approx(10.0)
    assert RATIO.read(ctx, {"num": "rpc_query_wait_max_ms",
                            "den": "rpc_query_n",
                            "stat": "median_num"}) == pytest.approx(6.0)
    with pytest.raises(ValueError):
        RATIO.read(ctx, {"num": "rpc_query_held_ms", "den": "rpc_query_n",
                         "stat": "mean"})


@pytest.mark.parametrize("cycles", [
    [],                                                         # no cycle
    [{"solver": "native", "period_ms": 100.0}],                 # the parent
    [{"solver": "native", "rpc_query_held_ms": 5.0}],           # den missing
    [{"solver": "native", "rpc_query_held_ms": 0.0, "rpc_query_n": 0}],
    [{"solver": "skip", "rpc_query_held_ms": 5.0, "rpc_query_n": 1}],
], ids=["empty", "parent", "den_missing", "den_zero", "skip_only"])
@pytest.mark.parametrize("stat", ["ratio", "median_num"])
def test_ratio_reads_nothing_where_there_is_nothing(cycles, stat):
    """A missing or zero denominator is nothing to read: None, never 0."""
    args = {"num": "rpc_query_held_ms", "den": "rpc_query_n", "stat": stat}
    assert RATIO.read({"cycles": cycles}, args) is None


def test_a_program_without_the_lock_ledger_leaves_the_metrics_out(rows):
    """The parent commit's rows lack the ledger's fields: each reader
    returns nothing there, it does not raise or report 0."""
    fields = {f for spec_ in HANDLER.values() for f in spec_[0]} \
        - {"period_ms"}
    old = [{k: v for k, v in r.items()
            if k not in fields and not k.startswith("rpc_")} for r in rows]
    ctx = {"cycles": old, "window": (0.0, 1.0)}
    for name in HANDLER:
        doc = BENCH.metric_file(name)
        reader = importlib.import_module("readers." + doc["reader"])
        assert reader.read(ctx, doc["args"]) is None, name
