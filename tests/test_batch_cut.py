"""The batch cut is by priority, after the sort, over the whole queue
(ISSUE 44).

A cycle ranks EVERY candidate (factor bounds over all of them, as
upstream's ``GetOrderedJobPtrVec(limit = ScheduledBatchSize)`` does),
hands the first ``schedule_batch_size`` of the order to ``_build_batch``
and leaves the rest waiting on "Priority" with their priority written.
The yardstick is ``cranesched_tpu/testing/batch_cut_reference.py``: a
plain numpy recomputation from what was SUBMITTED, which imports nothing
of the scheduler.  Counts and orders only, exact on the CPU:

(a) the ids and the order that reach ``_build_batch`` equal the
    reference's on seeded random jobs, on the rows route and on the
    legacy route (``incremental=False``); the bounds are over ALL
    candidates (the oldest job stands past row ``limit``);
(b) every job past the cut carries "Priority" and its priority; the
    batch is built at ``_job_bucket(limit)`` rows while the priority
    model ran at ``_job_bucket(len(candidates))``;
(c) starvation: 3 x limit old gangs that cannot run, one new small job
    on an empty partition: it starts in the first cycle after its submit;
(d) ``Priority: Type: basic``: the cut is the first ``limit`` ids;
(e) a queue that fits builds the arrays it built before the cut moved.
"""

import dataclasses

import numpy as np
import pytest

import cranesched_tpu.ctld.scheduler as scheduler_module
from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.ctld.defs import PendingReason
from cranesched_tpu.models.priority import PriorityWeights
from cranesched_tpu.testing.batch_cut_reference import (
    DEFAULT_WEIGHTS,
    cut_by_priority,
    priorities,
    ties_at_the_edge,
)
from cranesched_tpu.testing.priority_oracle import multifactor_priority_oracle

ROUTES = {"rows": True, "legacy": False}
PENDING, LIMIT = 3_000, 1_000
GIB = 1 << 30


def _scheduler(route, limit=LIMIT, job_size=10000.0, **config):
    """Two full nodes in ``full`` (what is queued there cannot run) and
    four empty ones in ``empty``."""
    meta = MetaContainer()
    for i in range(6):
        meta.add_node(f"n{i}", meta.layout.encode(
            cpu=8.0, mem_bytes=16 * GIB, memsw_bytes=16 * GIB,
            is_capacity=True), partitions=("full" if i < 2 else "empty",))
        meta.craned_up(i)
    config.setdefault("backfill", False)
    sched = JobScheduler(meta, SchedulerConfig(
        schedule_batch_size=limit, incremental=ROUTES[route],
        priority_weights=PriorityWeights(job_size=job_size), **config))
    sim = SimCluster(sched)
    sim.wire(sched)
    # what reached _build_batch last: the ids in order, and its arrays
    sched.built = None
    build = sched._build_batch

    def spy(ordered, num_nodes, now=0.0):
        batch, max_nodes = build(ordered, num_nodes, now)
        ids = (ordered.ids.tolist() if ordered.jobs is None
               else [job.job_id for job in ordered.jobs])
        sched.built = (ids, batch)
        return batch, max_nodes

    sched._build_batch = spy
    return sched, sim


def _job(cpu, mem_gib, node_num=1, partition="full", runtime=10_000.0):
    return JobSpec(res=ResourceSpec(cpu=float(cpu), mem_bytes=mem_gib * GIB,
                                    memsw_bytes=mem_gib * GIB),
                   node_num=node_num, partition=partition,
                   time_limit=86_400, sim_runtime=runtime)


def _fill(sched, sim):
    """Occupy both nodes of ``full``; returns the running jobs as the
    reference takes them."""
    for _ in range(2):
        assert sched.submit(_job(8, 16), now=0.0)
    sim.advance_to(1.0)
    assert len(sched.schedule_cycle(now=1.0)) == 2
    return [dict(cpu=8.0, mem=16 * 1024, node_num=1, run_time=0)
            for _ in range(2)]


def _queue(sched, rng, n, t_lo=10.0, t_hi=900.0):
    """n seeded jobs that cannot run, their submit times NOT in id order;
    returns them as the reference takes them, in queue order."""
    ref = []
    for _ in range(n):
        cpu = int(rng.integers(1, 9))
        mem = int(rng.integers(1, 17))
        nodes = int(rng.integers(1, 3))
        at = float(rng.integers(int(t_lo), int(t_hi)))
        jid = sched.submit(_job(cpu, mem, nodes), now=at)
        assert jid
        ref.append(dict(id=jid, cpu=float(cpu), mem=mem * 1024,
                        node_num=nodes, submit_time=at))
    return ref


def _weights(sched):
    return dataclasses.asdict(sched.config.priority_weights)


def _reference_ids(sched, ref, running, now, limit):
    inside, pri = cut_by_priority(ref, running, _weights(sched), now, limit)
    return [ref[i]["id"] for i in inside], pri


# ---------------------------------------------------------------------------
# (a) the cut equals the reference's, on both routes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", (1, 2, 3))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_the_cut_is_the_references(route, seed):
    sched, sim = _scheduler(route)
    running = _fill(sched, sim)
    ref = _queue(sched, np.random.default_rng(seed), PENDING)
    now = 1_000.0
    sim.advance_to(now)
    assert sched.schedule_cycle(now=now) == []
    want, pri = _reference_ids(sched, ref, running, now, LIMIT)
    got, _ = sched.built
    assert len(got) == LIMIT
    assert got == want
    # a second cycle, later: the ages moved, the bounds with them
    now = 1_500.0
    sim.advance_to(now)
    sched.submit(_job(1, 1), now=now)      # moves the table's epoch
    ref.append(dict(id=max(sched.pending), cpu=1.0, mem=1024, node_num=1,
                    submit_time=now))
    sched.schedule_cycle(now=now)
    want, _ = _reference_ids(sched, ref, running, now, LIMIT)
    assert sched.built[0] == want


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_the_bounds_are_over_the_whole_queue(route):
    """Priority by age alone (WeightJobSize 0), the oldest job by far at
    row 2,500: with bounds over the first ``limit`` rows only it would
    never be ranked; with bounds over all it is the first of the order
    and every other age factor is normalised against it."""
    sched, sim = _scheduler(route, job_size=0.0)
    running = _fill(sched, sim)
    rng = np.random.default_rng(7)
    ref = _queue(sched, rng, 2_500, t_lo=500.0)
    oldest = sched.submit(_job(2, 2), now=5.0)
    ref.append(dict(id=oldest, cpu=2.0, mem=2 * 1024, node_num=1,
                    submit_time=5.0))
    ref += _queue(sched, rng, 499, t_lo=500.0)
    now = 1_000.0
    sim.advance_to(now)
    sched.schedule_cycle(now=now)
    want, pri = _reference_ids(sched, ref, running, now, LIMIT)
    got, _ = sched.built
    assert got[0] == oldest == want[0]
    assert got == want
    # its age is the bound: 500 (WeightAge) x 1.0
    assert sched.job_priority(sched.pending[oldest]) == pytest.approx(500.0)


# ---------------------------------------------------------------------------
# (b) past the cut: the reason, the priority, the two buckets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", sorted(ROUTES))
def test_past_the_cut(route, monkeypatch):
    ranked_rows = []
    model = scheduler_module.multifactor_priority

    def spy(pending, *args, **kw):
        ranked_rows.append(int(pending.valid.shape[0]))
        return model(pending, *args, **kw)

    monkeypatch.setattr(scheduler_module, "multifactor_priority", spy)
    sched, sim = _scheduler(route)
    running = _fill(sched, sim)
    ranked_rows.clear()
    ref = _queue(sched, np.random.default_rng(11), PENDING)
    now = 1_000.0
    sim.advance_to(now)
    sched.schedule_cycle(now=now)
    want, pri = _reference_ids(sched, ref, running, now, LIMIT)
    inside = set(want)
    ids, batch = sched.built
    assert set(ids) == inside
    # the two buckets: the model ranked the queue's, the batch is the cut's
    assert ranked_rows == [JobScheduler._job_bucket(PENDING)] == [4_096]
    assert batch.valid.shape[0] == JobScheduler._job_bucket(LIMIT) == 1_024
    assert int(np.asarray(batch.valid).sum()) == LIMIT
    for k, job in enumerate(ref):
        live = sched.pending[job["id"]]
        # every RANKED job shows its priority, inside the cut or past it
        assert sched.job_priority(live) == pytest.approx(
            float(pri[k]), rel=2e-6, abs=1e-3)
        if job["id"] not in inside:
            assert live.pending_reason == PendingReason.PRIORITY
        else:
            assert live.pending_reason != PendingReason.PRIORITY
    lowest_in = min(pri[k] for k, j in enumerate(ref) if j["id"] in inside)
    highest_out = max(pri[k] for k, j in enumerate(ref)
                      if j["id"] not in inside)
    assert lowest_in >= highest_out
    row = sched.cycle_trace.snapshot()[-1]
    assert (row["ranked"], row["candidates"], row["cut"]) == (
        PENDING, LIMIT, PENDING - LIMIT)
    # a steady second cycle tells nobody anything again
    sched.submit(_job(8, 16), now=now + 1)     # the largest: ranks last
    sim.advance_to(now + 2)
    sched.schedule_cycle(now=now + 2)
    row = sched.cycle_trace.snapshot()[-1]
    assert (row["ranked"], row["cut"]) == (PENDING + 1, PENDING + 1 - LIMIT)
    if route == "rows":
        # the newcomer is looked up (its gate, the cut's stamp) and the
        # few rows its age moved across the edge; not the 2,001 cut rows
        assert row["prelude_jobs_touched"] < 50


# ---------------------------------------------------------------------------
# (c) starvation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backfill", (False, True), ids=("immediate",
                                                          "backfill"))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_new_small_job_is_not_starved_by_its_row(route, backfill):
    limit = 100
    sched, sim = _scheduler(route, limit=limit, backfill=backfill)
    _fill(sched, sim)
    for k in range(3 * limit):
        # old, wide, large: gangs that wait for the full partition
        assert sched.submit(_job(8, 16, node_num=2), now=10.0 + k)
    sim.advance_to(2_000.0)
    assert sched.schedule_cycle(now=2_000.0) == []
    # the youngest row of the table, on the empty partition
    small = sched.submit(_job(1, 1, partition="empty", runtime=30.0),
                         now=2_001.0)
    sim.advance_to(2_002.0)
    assert sched.schedule_cycle(now=2_002.0) == [small]
    assert small in sched.running
    assert sched.built[0][0] == small       # and it ranked FIRST


# ---------------------------------------------------------------------------
# (d) Priority: Type: basic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", sorted(ROUTES))
def test_basic_priority_cuts_in_id_order(route):
    limit = 100
    sched, sim = _scheduler(route, limit=limit, priority_type="basic")
    _fill(sched, sim)
    ref = _queue(sched, np.random.default_rng(5), 3 * limit)
    sim.advance_to(1_000.0)
    sched.schedule_cycle(now=1_000.0)
    ids = [job["id"] for job in ref]
    assert sched.built[0] == ids[:limit]
    assert sched.built[1].valid.shape[0] == JobScheduler._job_bucket(limit)
    for jid in ids[limit:]:
        assert sched.pending[jid].pending_reason == PendingReason.PRIORITY
    for jid in ids[:limit]:
        assert sched.pending[jid].pending_reason != PendingReason.PRIORITY
    row = sched.cycle_trace.snapshot()[-1]
    assert (row["ranked"], row["candidates"], row["cut"]) == (
        3 * limit, limit, 2 * limit)


# ---------------------------------------------------------------------------
# (e) a queue that fits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", (200, 300))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_queue_that_fits_builds_what_it_built(route, n):
    """With ``len(candidates) <= limit`` the slice is the whole order:
    the same ids, the same arrays at the same bucket, whether the limit
    is the default, far above the queue, or exactly the queue's length;
    nothing is cut and nobody is told "Priority"."""
    built = []
    for limit in (100_000, n):
        sched, sim = _scheduler(route, limit=limit)
        running = _fill(sched, sim)
        ref = _queue(sched, np.random.default_rng(13), n)
        sim.advance_to(1_000.0)
        sched.schedule_cycle(now=1_000.0)
        ids, batch = sched.built
        want, _ = _reference_ids(sched, ref, running, 1_000.0, n)
        assert ids == want                       # the whole order
        assert batch.valid.shape[0] == JobScheduler._job_bucket(n)
        row = sched.cycle_trace.snapshot()[-1]
        assert (row["ranked"], row["candidates"], row["cut"]) == (n, n, 0)
        assert not any(job.pending_reason == PendingReason.PRIORITY
                       for job in sched.pending.values())
        built.append((ids, batch))
    (ids_a, a), (ids_b, b) = built
    assert ids_a == ids_b
    for name in ("req", "node_num", "time_limit", "valid", "job_class"):
        x, y = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert x.shape == y.shape and (x == y).all(), name
    # and the contents are the table's rows in that order
    by_id = {job["id"]: job for job in ref}
    assert np.asarray(a.node_num)[:n].tolist() == [
        by_id[j]["node_num"] for j in ids_a]


# ---------------------------------------------------------------------------
# the reference itself: against the loop-for-loop transcription
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", (0, 1, 2))
def test_the_reference_agrees_with_the_transcription(seed):
    rng = np.random.default_rng(seed)
    accounts = ["a", "b", "c"]

    def jobs(n, running=False):
        out = []
        for _ in range(n):
            job = dict(cpu=float(rng.integers(1, 65)),
                       mem=int(rng.integers(1, 129)) * 1024,
                       node_num=int(rng.integers(1, 9)),
                       qos=int(rng.integers(0, 3)) * 100,
                       part=int(rng.integers(0, 2)) * 10,
                       account=accounts[int(rng.integers(0, 3))],
                       submit_time=float(rng.integers(0, 5_000)))
            if running:
                job["run_time"] = int(rng.integers(0, 3_600))
            out.append(job)
        return out

    pending, running = jobs(400), jobs(60, running=True)
    weights = dict(job_size=10000.0)
    now = 6_000.0
    got = priorities(pending, running, weights, now)

    def loop_form(job):
        return dict(job, age=int(now - job["submit_time"]),
                    cpus=job["cpu"] * job["node_num"],
                    mem=job["mem"] * job["node_num"])

    want = multifactor_priority_oracle(
        [loop_form(j) for j in pending], [loop_form(j) for j in running],
        dict(DEFAULT_WEIGHTS, **weights))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-3)
    inside, pri = cut_by_priority(pending, running, weights, now, 100)
    assert len(inside) == 100
    assert (np.diff(pri[inside]) <= 0).all()
    assert pri[inside].min() >= np.delete(pri, inside).max()
    taken, left = ties_at_the_edge(pri, inside, 100)
    assert taken >= 1 and left >= 0
