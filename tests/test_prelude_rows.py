"""The prelude hands the cycle table ROWS, not lists of ``Job``s (ISSUE 41).

Between the candidate scan and the commit a cycle carries PendingTable
row indices, and a ``Job`` is looked up only for a row Python really
visits.  That must change nothing a user, the WAL or a reply can see, so
the oracle is the route that still walks every job: the
``incremental=False`` rebuild.  Counts and parity only, exact on any
platform:

(a, e) one seeded script of submits, cancels, holds, releases, modifies,
    requeues, a ``begin_time`` edge, a compaction and a batch cut against
    both routes, compared cycle by cycle: the solve order, the started
    ids and their nodes, every pending job's reason, every job's priority
    as ``rpc/convert.py`` replies it, the WAL byte for byte;
(b) a job modified, held or cancelled while the solve runs with the lock
    released is void at the commit (the row's ``written`` epoch);
(c) ``prelude_jobs_touched``: 0 on a steady cycle of the default route,
    the candidates on a route that walks the jobs;
(d) the jobtrace "eligible" stamp lands once an incarnation.
"""

import time

import numpy as np
import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.ctld.defs import PendingReason
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.rpc.client import CtldClient
from cranesched_tpu.rpc.convert import job_to_pb
from cranesched_tpu.rpc.server import serve

NODES = 6
CPU = 8.0

# the blocks the cells serve, at a size a test can hold: the three
# backlog cells run multifactor priority through the split route
# (a timed head, the immediate solve for the tail), fifo-1k runs
# ``Priority: basic`` with Backfill off
BLOCKS = {
    "multifactor-split": dict(backfill=True, backfill_max_jobs=3),
    "multifactor-backfill": dict(backfill=True),
    "multifactor-immediate": dict(backfill=False),
    "basic-immediate": dict(priority_type="basic", backfill=False),
}


def _meta(nodes=NODES):
    meta = MetaContainer()
    for i in range(nodes):
        meta.add_node(f"n{i:02d}", meta.layout.encode(
            cpu=CPU, mem_bytes=16 << 30, memsw_bytes=16 << 30,
            is_capacity=True),
            partitions=("default", "other") if i % 2 else ("default",))
        meta.craned_up(i)
    return meta


def spec(cpu=2.0, runtime=3.0, **kw):
    kw.setdefault("time_limit", 600.0)
    return JobSpec(res=ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                                    memsw_bytes=1 << 30),
                   sim_runtime=runtime, **kw)


def _scheduler(wal_path=None, nodes=NODES, **config):
    sched = JobScheduler(
        _meta(nodes), SchedulerConfig(**config),
        wal=WriteAheadLog(wal_path, fsync=False) if wal_path else None)
    sim = SimCluster(sched)
    sim.wire(sched)
    # the solve order of the last cycle, as job ids
    sched.order = []
    sort = sched._priority_sort

    def spy(candidates, now):
        out = sort(candidates, now)
        sched.order = (out.ids.tolist() if out.jobs is None
                       else [job.job_id for job in out.jobs])
        return out

    sched._priority_sort = spy
    return sched, sim


def _cycle(sched, sim, now, during=None):
    """One cycle; ``during`` runs once the first solve closure has
    returned and before the cycle takes its results: where a handler
    runs with the server lock free."""
    sim.advance_to(now)
    gen = sched.cycle_phases(now)
    try:
        fn = next(gen)
        while True:
            out = fn()
            if during is not None:
                during(sched)
                during = None
            fn = gen.send(out)
    except StopIteration as stop:
        return stop.value or []


def _observed(sched, wal_path):
    """All that a user, a reply or a recovery can see of one side."""
    names = {i: n.name for i, n in sched.meta.nodes.items()}
    jobs = {**sched.pending, **sched.running}
    with open(wal_path, encoding="utf-8") as fh:
        wal = fh.read()
    return {
        "order": list(sched.order),
        "pending": {jid: (job.pending_reason, job.held)
                    for jid, job in sched.pending.items()},
        "placements": {jid: list(job.node_ids)
                       for jid, job in sched.running.items()},
        "priority": {jid: job_to_pb(job, names,
                                    sched.job_priority(job)).priority
                     for jid, job in jobs.items()},
        "history": sorted(sched.history),
        "wal": wal,
    }


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_rows_match_the_rebuild_cycle_by_cycle(tmp_path, block):
    config = dict(BLOCKS[block], schedule_batch_size=12)
    paths = [str(tmp_path / "rows.wal"), str(tmp_path / "rebuild.wal")]
    sides = [_scheduler(paths[0], nodes=2, incremental=True, **config),
             _scheduler(paths[1], nodes=2, incremental=False, **config)]
    rows_sched = sides[0][0]
    rng = np.random.default_rng(41)

    def both(fn):
        out = [fn(sched) for sched, _sim in sides]
        assert out[0] == out[1]
        return out[0]

    def submit(now, **kw):
        return both(lambda s: s.submit(spec(**kw), now=now))

    # a backlog deeper than the cluster and than the batch cut
    for i in range(30):
        submit(0.0, cpu=float(rng.integers(1, 5)),
               runtime=float(rng.integers(2, 7)), node_num=1 + i % 2)
    edge = submit(0.0, begin_time=9.5)      # the begin_time edge
    cut_seen = compacted = requeued = 0
    generation = rows_sched._ptable.generation
    for t in range(1, 37):
        now = float(t)
        # a submit every tick: the epoch moves, so the rows side never
        # skips a cycle and the two sides' priorities age alike
        for _ in range(2):
            submit(now, cpu=float(rng.integers(1, 5)),
                   runtime=float(rng.integers(2, 7)),
                   node_num=int(rng.integers(1, 3)),
                   held=bool(rng.random() < 0.15))
        pend = sorted(set(rows_sched.pending) - {edge})
        run = sorted(rows_sched.running)
        jid = int(pend[int(rng.integers(0, len(pend)))])
        roll = rng.random()
        if roll < 0.2:
            flip = not rows_sched.pending[jid].held   # hold or release
            both(lambda s: s.hold(jid, flip, now=now))
        elif roll < 0.35:
            both(lambda s: s.cancel(jid, now=now))
        elif roll < 0.5:
            limit = float(rng.integers(60, 7200))   # one draw, both sides
            both(lambda s: s.modify_job(jid, now=now, time_limit=limit))
        elif roll < 0.6:
            both(lambda s: s.modify_job(jid, now=now, priority=t * 10))
        elif roll < 0.7:
            both(lambda s: s.modify_job(jid, now=now, partition="other"))
        elif roll < 0.85 and run:
            victim = int(run[int(rng.integers(0, len(run)))])
            assert both(lambda s: s.requeue(victim, now=now)) == ""
            requeued += 1
        if t == 20:
            # a burst of short-lived rows: enough tombstones that the
            # table compacts and every row index moves
            burst = [submit(now, held=True) for _ in range(140)]
            for victim in burst:
                both(lambda s, v=victim: s.cancel(v, now=now))
        started = [_cycle(sched, sim, now) for sched, sim in sides]
        assert started[0] == started[1], f"t={t}: started ids differ"
        seen = [_observed(sched, path)
                for (sched, _sim), path in zip(sides, paths)]
        for key in seen[0]:
            assert seen[0][key] == seen[1][key], f"t={t}: {key}"
        # the rebuild still writes every candidate's priority on its Job:
        # the number the rows side scatters into the table and replies
        rebuild = sides[1][0]
        assert seen[0]["priority"] == {
            jid: job.priority
            for jid, job in {**rebuild.pending, **rebuild.running}.items()}
        row = rows_sched.cycle_trace.snapshot()[-1]
        assert row["now"] == now and row["solver"] != "skip"
        cut_seen += any(r == PendingReason.PRIORITY and not held
                        for r, held in seen[0]["pending"].values())
        compacted += rows_sched._ptable.generation != generation
        generation = rows_sched._ptable.generation
        if t in (9, 10):    # the edge at 9.5 passes with no event
            gated = seen[0]["pending"][edge][0] == PendingReason.BEGIN_TIME
            assert gated == (t == 9)
    # the script did reach what it is there for
    assert cut_seen and compacted and requeued
    assert len(rows_sched._ptable) == len(rows_sched.pending)
    # ... and the rows side did it without walking the jobs: over the
    # whole run it looked up fewer Jobs than the rebuild does in two cycles
    touched = [sum(r.get("prelude_jobs_touched", 0)
                   for r in sched.cycle_trace.snapshot())
               for sched, _sim in sides]
    assert touched[0] * 4 < touched[1]
    for sched, _sim in sides:
        sched.wal.close()


# ---------------------------------------------------------------------------
# (b) written while the solve was out: void at the commit
# ---------------------------------------------------------------------------

EVENTS = {
    "modify_time_limit": lambda s, jid: s.modify_job(
        jid, now=1.0, time_limit=1234.0),
    "modify_partition": lambda s, jid: s.modify_job(
        jid, now=1.0, partition="other"),
    # stricter than the spec's identity was: the job is solved again
    # next cycle with its new priority
    "modify_priority": lambda s, jid: s.modify_job(
        jid, now=1.0, priority=77),
    "hold": lambda s, jid: s.hold(jid, True, now=1.0),
    "hold_and_release": lambda s, jid: (s.hold(jid, True, now=1.0),
                                        s.hold(jid, False, now=1.0)),
    "cancel": lambda s, jid: s.cancel(jid, now=1.0),
}


@pytest.mark.parametrize("incremental", [True, False],
                         ids=["rows", "rebuild"])
@pytest.mark.parametrize("event", sorted(EVENTS))
def test_written_during_the_solve_is_void(event, incremental):
    sched, sim = _scheduler(incremental=incremental, backfill=False)
    ids = [sched.submit(spec(), now=0.0) for _ in range(4)]
    victim = ids[1]
    started = _cycle(sched, sim, 1.0,
                     during=lambda s: EVENTS[event](s, victim))
    # the solve placed all four; the commit takes three
    assert started == [j for j in ids if j != victim]
    assert victim not in sched.running
    if event == "cancel":
        assert victim not in sched.pending
        return
    job = sched.pending[victim]
    assert job.node_ids == []
    if event == "hold":
        assert job.pending_reason == PendingReason.HELD
        assert _cycle(sched, sim, 2.0) == []
    else:
        # the next cycle solves it again, with what the event wrote
        assert _cycle(sched, sim, 2.0) == [victim]


# ---------------------------------------------------------------------------
# (c) prelude_jobs_touched
# ---------------------------------------------------------------------------

def _backlog(sched, count, **kw):
    # every node taken whole, then a backlog that cannot start
    for _ in range(NODES):
        sched.submit(spec(CPU, runtime=1e6), now=0.0)
    return [sched.submit(spec(**kw), now=0.0) for _ in range(count)]


@pytest.mark.parametrize("block", ["multifactor-split", "basic-immediate"])
def test_default_route_touches_no_job(block):
    config = dict(BLOCKS[block])
    if config.get("backfill"):
        config["backfill_max_jobs"] = 64
    sched, sim = _scheduler(**config)
    _backlog(sched, 5000)
    _cycle(sched, sim, 1.0)
    first = sched.cycle_trace.snapshot()[-1]
    # the first sight of 5,006 rows: an "eligible" stamp and a mask
    # class each, and that is all
    assert first["candidates"] == 5000 + NODES
    assert first["prelude_jobs_touched"] == 2 * (5000 + NODES)
    for t in (2.0, 3.0):
        sched.submit(spec(), now=t)      # an event: no skipped cycle
        _cycle(sched, sim, t)
        row = sched.cycle_trace.snapshot()[-1]
        assert row["now"] == t and row["candidates"] >= 5000
        # the one fresh row: its stamp and its class
        assert row["prelude_jobs_touched"] == 2
    sched.hold(sched.submit(spec(), now=4.0), True, now=4.0)
    _cycle(sched, sim, 4.0)
    row = sched.cycle_trace.snapshot()[-1]
    assert row["candidates"] == 5002 and row["prelude_jobs_touched"] == 1
    sched.modify_job(next(iter(sched.pending)), now=5.0, time_limit=99.0)
    _cycle(sched, sim, 5.0)
    row = sched.cycle_trace.snapshot()[-1]
    # a steady cycle of 5,002 candidates: one class lookup, for the row
    # the modify rewrote
    assert row["candidates"] == 5002 and row["prelude_jobs_touched"] == 1


def test_steady_cycle_touches_exactly_nothing():
    sched, sim = _scheduler(**BLOCKS["multifactor-split"])
    _backlog(sched, 5000)
    _cycle(sched, sim, 1.0)
    # no event on any job: only the running set's ages move.  The
    # fingerprint would skip the cycle, so disarm it
    sched._noop_fp = None
    _cycle(sched, sim, 2.0)
    row = sched.cycle_trace.snapshot()[-1]
    assert row["now"] == 2.0 and row["candidates"] == 5000
    assert row["prelude_jobs_touched"] == 0


@pytest.mark.parametrize("route", ["packed", "rebuild", "reservation"])
def test_routes_that_walk_the_jobs_touch_every_candidate(route):
    sched, sim = _scheduler(incremental=route != "rebuild", backfill=False)
    _backlog(sched, 200)
    if route == "packed":
        sched.submit(spec(exclusive=True), now=0.0)
    elif route == "reservation":
        assert sched.meta.create_reservation(
            "maint", "default", ["n00"], 1e6, 2e6) is not None
    _cycle(sched, sim, 1.0)
    sched.submit(spec(), now=2.0)
    _cycle(sched, sim, 2.0)
    row = sched.cycle_trace.snapshot()[-1]
    assert row["now"] == 2.0
    if route == "packed":
        assert row["solver"] == "packed"
    # every candidate once (the rebuild walks them before it knows which
    # are candidates), plus the fresh row's stamp and class
    assert row["candidates"] <= row["prelude_jobs_touched"] \
        <= row["candidates"] + 2


# ---------------------------------------------------------------------------
# (d) the "eligible" stamp: once an incarnation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("incremental", [True, False],
                         ids=["rows", "rebuild"])
def test_eligible_stamp_lands_once_an_incarnation(incremental):
    sched, sim = _scheduler(incremental=incremental, backfill=False,
                            nodes=1)
    stamped = []
    stamp_many = sched.jobtrace.stamp_many

    def spy(edge, items, t):
        items = list(items)
        stamped.extend((edge, job_id, inc, t) for job_id, inc in items)
        return stamp_many(edge, items, t)

    sched.jobtrace.stamp_many = spy
    runner = sched.submit(spec(CPU, runtime=1e6), now=0.0)
    waiter = sched.submit(spec(CPU), now=0.0)
    held = sched.submit(spec(CPU, held=True), now=0.0)
    assert _cycle(sched, sim, 1.0) == [runner]
    assert stamped == [("eligible", runner, 0, 1.0),
                       ("eligible", waiter, 0, 1.0)]
    # a standing candidate, a modify and a hold flip keep the row and
    # its stamp; the held job gets its own on release
    sched.modify_job(waiter, now=2.0, time_limit=77.0)
    _cycle(sched, sim, 2.0)
    sched.hold(waiter, True, now=3.0)
    sched.hold(waiter, False, now=3.0)
    sched.hold(held, False, now=3.0)
    _cycle(sched, sim, 3.0)
    assert stamped[2:] == [("eligible", held, 0, 3.0)]
    # a requeue is a new incarnation, and a new row
    assert sched.requeue(runner, now=4.0) == ""
    _cycle(sched, sim, 4.0)
    assert ("eligible", runner, 1, 4.0) in stamped[3:]
    assert len(stamped) == len(set(stamped))
    spans = [s["edge"] for inc in
             sched.jobtrace.timeline(waiter)["incarnations"]
             for s in inc["spans"]]
    assert spans.count("eligible") == 1


# ---------------------------------------------------------------------------
# a pending job's priority lives in the table while it has a row
# ---------------------------------------------------------------------------

def test_priority_follows_the_job_out_of_the_table():
    sched, sim = _scheduler(backfill=False, nodes=1)
    runner = sched.submit(spec(CPU, runtime=2.0), now=0.0)
    waiter = sched.submit(spec(CPU, runtime=2.0), now=0.0)
    last = sched.submit(spec(CPU, runtime=2.0), now=0.0)
    # the factors are min-max normalised: the lowest job reads 0
    for jid, qos_priority in ((runner, 9), (waiter, 5), (last, 1)):
        assert sched.modify_job(jid, now=0.0, priority=qos_priority) == ""
    assert sched.job_priority(sched.pending[waiter]) == 0.0
    assert _cycle(sched, sim, 10.0) == [runner]
    shown = sched.job_priority(sched.pending[waiter])
    assert shown > 0.0                      # the cycle computed one
    assert sched._ptable.priority_of(waiter) == shown
    # the job that started took its priority with it
    assert sched._ptable.priority_of(runner) is None
    assert sched.running[runner].priority > 0.0
    assert sched.job_priority(sched.running[runner]) \
        == sched.running[runner].priority
    # a hold rewrites the row and keeps the number a reply shows
    sched.hold(waiter, True, now=11.0)
    assert sched.job_priority(sched.pending[waiter]) == shown
    # a cancelled job keeps the last one in history
    sched.cancel(waiter, now=12.0)
    assert sched.history[waiter].priority == shown
    # a requeue starts again from 0, as reset_for_requeue says
    assert sched.requeue(runner, now=12.0) == ""
    assert sched.job_priority(sched.pending[runner]) == 0.0


def test_both_query_rpcs_reply_the_table_s_priority():
    """The served path: QueryJobsInfo and QueryJobsStream show a pending
    job's priority from its table row (``Job.priority`` is stale while
    the job has one), a running job's from the Job."""
    meta = MetaContainer()
    meta.add_partition("hi", priority=5)
    meta.add_partition("mid", priority=3)
    meta.add_node("n00", meta.layout.encode(
        cpu=CPU, mem_bytes=16 << 30, memsw_bytes=16 << 30,
        is_capacity=True), partitions=("default", "mid", "hi"))
    meta.craned_up(0)
    sched = JobScheduler(meta, SchedulerConfig(backfill=False,
                                               cycle_idle_sleep=0.05))
    sim = SimCluster(sched)
    sim.wire(sched)
    server, port = serve(sched, sim=sim, address="127.0.0.1:0",
                         cycle_interval=0.05)
    client = CtldClient(f"127.0.0.1:{port}")
    try:
        with server._lock:
            now = time.time()
            runner = sched.submit(spec(CPU, runtime=1e6, partition="hi"),
                                  now=now)
            waiters = [sched.submit(spec(CPU, partition=p), now=now)
                       for p in ("default", "mid", "hi")]
        deadline = time.time() + 20.0
        while time.time() < deadline and not (
                runner in sched.running
                and sched._ptable.priority_of(waiters[2])):
            time.sleep(0.05)
        with server._lock:
            want = {jid: sched.job_priority(job) for jid, job in
                    {**sched.pending, **sched.running}.items()}
            stale = {jid: sched.pending[jid].priority for jid in waiters}
        assert want[waiters[2]] > want[waiters[1]] > want[waiters[0]] == 0.0
        assert want[runner] > 0.0
        assert set(stale.values()) == {0.0}     # never written on the Job
        replied = {j.job_id: j.priority for j in client.query_jobs().jobs}
        streamed = {j.job_id: j.priority for j in client.query_jobs_stream()}
        assert replied == streamed == want
    finally:
        server.stop()
