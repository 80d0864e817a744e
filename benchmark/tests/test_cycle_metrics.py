"""Every per-layer metric that reads the daemon's cycle trace or its
metric registry names something the PROGRAM really writes: the field is
a key of a cycle-trace row, and the family is in the registry, that the
program itself produced here on the CPU.  So a renamed field fails in
this test and not as a metric silently missing from a line on the chip.
Counts and names only: no number of this run is a measurement."""

import importlib
import threading

import pytest

from lib import spec

BENCH = spec.Benchmark()
PER_LAYER = {m["name"]: m for m in BENCH.per_layer}
READ = [name for name in PER_LAYER
        if BENCH.metric_file(name)["reader"] in ("cycle_trace", "prometheus")]


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    """One cycle-trace row and one registry snapshot of the real
    scheduler: a cycle that places jobs, then a snapshot."""
    from cranesched_tpu.rpc.interceptors import MetricsInterceptor
    from cranesched_tpu.craned import SimCluster
    from cranesched_tpu.ctld import (
        JobScheduler, JobSpec, MetaContainer, ResourceSpec, SchedulerConfig)
    from cranesched_tpu.ctld.wal import WriteAheadLog
    from cranesched_tpu.ha import Snapshotter
    from cranesched_tpu.obs import REGISTRY

    MetricsInterceptor()        # a served daemon's crane_rpc_* families
    path = str(tmp_path_factory.mktemp("wal") / "ctld.wal")
    wal = WriteAheadLog(path)
    meta = MetaContainer()
    for i in range(4):
        meta.add_node(f"cn{i}", meta.layout.encode(
            cpu=16, mem_bytes=32 << 30, memsw_bytes=32 << 30,
            is_capacity=True))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(), wal=wal)
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    for _ in range(3):
        sched.submit(JobSpec(res=ResourceSpec(
            cpu=2.0, mem_bytes=2 << 30, memsw_bytes=2 << 30),
            sim_runtime=30.0), now=0.0)
    assert len(sched.schedule_cycle(now=1.0)) == 3
    assert Snapshotter(sched, wal, threading.Lock(), path).snap_once() > 0
    rows = [r for r in sched.cycle_trace.snapshot()
            if r.get("solver") != "skip"]
    wal.close()
    return {"row": rows[-1], "metrics": REGISTRY.snapshot()}


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_metric_file_loads_and_lists_only_cells(name):
    doc = BENCH.metric_file(name)
    assert set(doc) == {"what", "reader", "args"} and doc["what"]
    reader = importlib.import_module("readers." + doc["reader"])
    assert callable(reader.read)
    entry = PER_LAYER[name]
    assert entry["workloads"] and set(entry["workloads"]) <= set(BENCH.cells)
    # it moves an end-to-end metric that each of its cells reports
    for cell in entry["workloads"]:
        assert entry["moves"] in [
            m["name"] for m in BENCH.metrics_for(cell, "end_to_end")]


@pytest.mark.parametrize("name", sorted(READ))
def test_metric_reads_what_the_program_writes(name, program):
    doc = BENCH.metric_file(name)
    args = doc["args"]
    reader = importlib.import_module("readers." + doc["reader"])
    stats = {"metrics": program["metrics"]}
    ctx = {"cycles": [program["row"]], "window": (0.0, 1.0),
           "stats_open": stats, "stats_close": stats,
           "harness": {}, "trace": {}}
    if doc["reader"] == "cycle_trace":
        assert args["field"] in program["row"], (
            f"{name}: the program's cycle trace has no {args['field']!r}")
        assert reader.read(ctx, args) is not None
    else:
        assert args["metric"] in program["metrics"], (
            f"{name}: the program registers no {args['metric']!r}")
        reader.read(ctx, args)      # raises on a stat it does not know


def test_a_program_without_the_ledger_leaves_the_metrics_out(program):
    """The parent commit's rows lack the ledger's fields: the reader
    returns nothing there, it does not raise or report 0."""
    reader = importlib.import_module("readers.cycle_trace")
    old = {k: v for k, v in program["row"].items()
           if k in ("now", "solver", "prelude_ms", "solve_ms", "commit_ms",
                    "dispatch_ms", "total_ms", "lock_held_ms")}
    ctx = {"cycles": [old], "window": (0.0, 1.0)}
    for name in READ:
        doc = BENCH.metric_file(name)
        if doc["reader"] == "cycle_trace" \
                and doc["args"]["field"] not in old:
            assert reader.read(ctx, doc["args"]) is None
