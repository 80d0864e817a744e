"""`solve_tail_pass_share.latency` reads a field the PROGRAM produces:
one cycle of the default block's split on the CPU, its Pallas tail in
the interpreter, counts the selection passes the kernel ran.  Counts
only: no number of this run is a measurement."""

import importlib

import pytest

from lib import spec

NAME = "solve_tail_pass_share.latency"


def _tail_cycle_row():
    from cranesched_tpu.craned import SimCluster
    from cranesched_tpu.ctld import (
        JobScheduler, JobSpec, MetaContainer, ResourceSpec, SchedulerConfig)

    meta = MetaContainer()
    for i in range(16):
        meta.add_node(f"cn{i}", meta.layout.encode(
            cpu=16, mem_bytes=32 << 30, memsw_bytes=32 << 30,
            is_capacity=True))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(
        solver="pallas", backfill_max_jobs=2))
    sched.pallas_interpret = True       # no TPU here
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    # the head takes the first two; the tail: whole-node gangs 1, 2, 4 and
    # 4 wide that fit, and one of 4 that finds 3 free nodes after them
    for width, cpu in ((1, 2.0), (1, 2.0), (1, 16.0), (2, 16.0), (4, 16.0),
                       (4, 16.0), (4, 16.0)):
        sched.submit(JobSpec(node_num=width, time_limit=3600, res=ResourceSpec(
            cpu=cpu, mem_bytes=2 << 30, memsw_bytes=2 << 30),
            sim_runtime=30.0), now=0.0)
    assert len(sched.schedule_cycle(now=1.0)) == 6
    return sched.cycle_trace.snapshot()[-1]


def test_the_tail_pass_share_reads_what_the_kernel_counts():
    bench = spec.Benchmark()
    doc = bench.metric_file(NAME)
    entry = {m["name"]: m for m in bench.per_layer}[NAME]
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "solve, kernels", "start_p95_ms", "lower")
    reader = importlib.import_module("readers." + doc["reader"])
    row = _tail_cycle_row()
    assert row["gang_bound"] == 4
    # by hand: pass 0 for each slot of the one block of 256; after it 0 +
    # 1 + 3 + 3 passes for the four that fit and 3 for the gang that
    # finds three nodes (the fourth reads the first infinite minimum)
    assert row[doc["args"]["field"]] == pytest.approx(
        100.0 * (256 + 0 + 1 + 3 + 3 + 3) / (256 * 4), abs=1e-3)
    ctx = {"cycles": [row], "window": (0.0, 1.0)}
    assert reader.read(ctx, doc["args"]) == row["tail_pass_pct"]
    # the parent's rows lack the field: nothing, not 0 and not a raise
    old = {k: v for k, v in row.items() if k != "tail_pass_pct"}
    assert reader.read({"cycles": [old], "window": (0.0, 1.0)},
                       doc["args"]) is None
