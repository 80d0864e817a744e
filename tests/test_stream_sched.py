"""Scheduler-level parity for the factored-mask / streamed-solve path.

The scheduler now builds a FactoredJobBatch (per-job class ids into a
device-resident [C, N] row table) instead of a dense [J, N] part_mask.
Everything downstream must be bit-identical to the dense reference:

- the gathered row equals the old per-job ``_mask_for`` row (including
  across a resv_epoch bump that rewrites the table),
- the native / pallas(serial) / pallas(streamed) backends agree with the
  solve_greedy oracle on both class-DISJOINT and class-OVERLAPPING
  cluster layouts,
- a full schedule_cycle with solver="pallas" reports the streamed kernel
  and its stream count in the cycle trace.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from cranesched_tpu.ctld import (  # noqa: E402
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.models.solver import (  # noqa: E402
    FactoredJobBatch,
    make_cluster_state,
    solve_greedy,
)

NUM_NODES = 24


def build(overlap: bool, solver: str = "auto"):
    """Cluster over 3 partitions; with ``overlap`` every node ALSO joins
    a shared 'all' partition, so eligibility rows cross."""
    meta = MetaContainer()
    for i in range(NUM_NODES):
        parts = (f"p{i % 3}", "all") if overlap else (f"p{i % 3}",)
        meta.add_node(f"n{i:02d}", meta.layout.encode(
            cpu=16.0, mem_bytes=64 << 30, is_capacity=True),
            partitions=parts)
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(
        backfill=False, solver=solver))
    # no TPU under pytest: the Pallas kernel runs in the interpreter
    sched.pallas_interpret = True
    return meta, sched


def submit_queue(sched, overlap: bool, n_jobs: int = 216):
    # 72 jobs a partition: a cycle's candidates pad to 256 rows
    # (JobScheduler._job_bucket), and the stream planner turns a batch
    # down whose padding class outweighs its real ones
    rng = np.random.default_rng(7)
    parts = ["p0", "p1", "p2"] + (["all"] if overlap else [])
    for i in range(n_jobs):
        sched.submit(JobSpec(
            res=ResourceSpec(cpu=float(rng.integers(1, 6)),
                             mem_bytes=int(rng.integers(1, 9)) << 30),
            node_num=int(rng.integers(1, 3)),
            time_limit=int(rng.integers(60, 7200)),
            partition=parts[i % len(parts)]), now=0.0)


def batch_for(sched, now=0.0):
    ordered = list(sched.pending.values())
    batch, max_nodes = sched._build_batch(ordered, NUM_NODES, now)
    return ordered, batch, max_nodes


def assert_placements_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.placed),
                                  np.asarray(b.placed))
    np.testing.assert_array_equal(np.asarray(a.nodes), np.asarray(b.nodes))
    np.testing.assert_array_equal(np.asarray(a.reason),
                                  np.asarray(b.reason))


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["disjoint", "overlapping"])
def test_backends_match_oracle(overlap):
    meta, sched = build(overlap)
    submit_queue(sched, overlap)
    ordered, batch, max_nodes = batch_for(sched)
    assert isinstance(batch, FactoredJobBatch)
    # the factored native fast path exists exactly when rows are disjoint
    assert (batch.node_class_np is None) == overlap

    avail, total, alive = meta.snapshot()
    cost0 = sched._ledger.cost0(0.0, total.shape[0])
    state = make_cluster_state(avail, total, alive, cost0)
    oracle, _ = solve_greedy(state, batch.dense, max_nodes=max_nodes)

    native = sched._solve_native(avail, total, alive, cost0, batch,
                                 max_nodes)
    assert native is not None
    assert_placements_equal(native, oracle)

    pallas, label = sched._solve_pallas(avail, total, alive, cost0,
                                        batch, max_nodes)
    assert label == ("pallas" if overlap else "pallas-stream")
    assert sched._cur_trace["num_streams"] == (1 if overlap else 4)
    assert_placements_equal(pallas, oracle)


def test_streamed_vs_serial_same_batch():
    from cranesched_tpu.models.pallas_solver import (
        plan_streams,
        solve_greedy_pallas,
        solve_greedy_pallas_auto,
    )

    meta, sched = build(overlap=False)
    submit_queue(sched, overlap=False)
    _, batch, max_nodes = batch_for(sched)
    avail, total, alive = meta.snapshot()
    state = make_cluster_state(avail, total, alive,
                               sched._ledger.cost0(0.0, total.shape[0]))
    serial, _ = solve_greedy_pallas(
        state, batch.req, batch.node_num, batch.time_limit, batch.valid,
        batch.job_class, batch.class_masks, max_nodes=max_nodes,
        interpret=True)
    plan = plan_streams(batch.job_class_np, batch.class_rows_np,
                        known_disjoint=True)
    assert plan is not None and plan[1] == 4  # 3 partitions + padding class
    streamed, _ = solve_greedy_pallas_auto(
        state, batch.req, batch.node_num, batch.time_limit, batch.valid,
        batch.job_class, batch.class_masks, max_nodes=max_nodes,
        interpret=True, plan=plan)
    assert_placements_equal(streamed, serial)


def test_factored_rows_match_dense_across_epoch_bump():
    meta, sched = build(overlap=False)
    submit_queue(sched, overlap=False)
    now = 10.0
    ordered, batch, _ = batch_for(sched, now)
    for i, job in enumerate(ordered):
        np.testing.assert_array_equal(
            batch.class_rows_np[batch.job_class_np[i]],
            sched._mask_for(job, now),
            err_msg=f"job {job.job_id} gathered row != dense row")
    refreshes0 = sched._mask_table.refreshes
    epoch0 = sched._mask_table.epoch

    # steady state: the next cycle's build must NOT rebuild the table
    ordered, batch, _ = batch_for(sched, now)
    assert sched._mask_table.refreshes == refreshes0

    # a reservation bumps resv_epoch: rows for overlapping windows
    # must change, and the gathered row must track the dense one
    assert meta.create_reservation(
        "maint", "p0", [f"n{i:02d}" for i in range(0, NUM_NODES, 3)],
        start_time=0.0, end_time=1e6) is not None
    assert meta.resv_epoch != epoch0
    ordered, batch, _ = batch_for(sched, now)
    assert sched._mask_table.refreshes == refreshes0 + 1
    touched = 0
    for i, job in enumerate(ordered):
        row = batch.class_rows_np[batch.job_class_np[i]]
        np.testing.assert_array_equal(row, sched._mask_for(job, now))
        if job.spec.partition == "p0":
            assert not row.any()   # whole partition is reserved
            touched += 1
    assert touched > 0

    # the device table gathers the same rows (the .dense property the
    # scan/backfill solvers consume)
    np.testing.assert_array_equal(
        np.asarray(batch.dense.part_mask),
        batch.class_rows_np[batch.job_class_np])


def test_from_batch_routes_through_auto():
    """solve_greedy_pallas_from_batch on a dense batch with disjoint
    rows must agree with the explicit auto path (it now routes through
    classes_from_part_mask + solve_greedy_pallas_auto)."""
    from cranesched_tpu.models.pallas_solver import (
        solve_greedy_pallas_from_batch,
    )

    meta, sched = build(overlap=False)
    submit_queue(sched, overlap=False)
    _, batch, max_nodes = batch_for(sched)
    avail, total, alive = meta.snapshot()
    state = make_cluster_state(avail, total, alive,
                               sched._ledger.cost0(0.0, total.shape[0]))
    oracle, _ = solve_greedy(state, batch.dense, max_nodes=max_nodes)
    out, _ = solve_greedy_pallas_from_batch(
        state, batch.dense, max_nodes=max_nodes, interpret=True)
    assert_placements_equal(out, oracle)


def test_cycle_trace_reports_streamed_kernel():
    meta, sched = build(overlap=False, solver="pallas")
    submit_queue(sched, overlap=False)
    sched.schedule_cycle(now=1.0)
    trace = sched.cycle_trace.snapshot()[-1]
    assert trace["solver"] == "pallas-stream"
    assert trace["num_streams"] == 4  # 3 partitions + padding class
    assert len(sched.running) > 0

    # an overlapping layout falls back to the serial kernel and says so
    meta2, sched2 = build(overlap=True, solver="pallas")
    submit_queue(sched2, overlap=True)
    sched2.schedule_cycle(now=1.0)
    trace2 = sched2.cycle_trace.snapshot()[-1]
    assert trace2["solver"] == "pallas"
    assert trace2["num_streams"] == 1
