"""``_commit`` visits the rows a cycle placed or whose reason changed
(ISSUE 34): what it leaves out must be invisible.

The pass it replaced ran the commit's per-job body over EVERY candidate
and told each unplaced job its reason again, every cycle.  That pass is
the plain reference here (``cranesched_tpu/testing/commit_oracle.py``
``visit_every_row``: the same scheduler with the row map withheld from
its commits).  A scheduler and its reference are driven through one
script, route by route, with events between cycles and events while a
solve is out (the server lock released), and after every cycle all that
can be observed has to be equal: every pending job's ``pending_reason``
and hold, the started set, the ledger, the licence seats, and the WAL
byte for byte (its records carry the reasons).  On the scheduler under
test the stamps' own invariant is checked as well: a row whose stamp is
known carries the reason the commit writes for that code.
"""

import dataclasses

import numpy as np
import pytest

from cranesched_tpu.craned.sim import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    PendingReason,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.ctld.accounting import (
    Account,
    AccountManager,
    AdminLevel,
    Qos,
    User,
)
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.testing.commit_oracle import (
    stale_stamps,
    visit_every_row,
)

NODES = 4
CPU = 8.0

ROUTES = {
    "immediate": dict(backfill=False),
    "backfill": dict(backfill=True),
    # a head of two with reservations, the rest through the tail's commit
    "backfill-split": dict(backfill=True, backfill_max_jobs=2),
}


def _accounts(high_jobs_per_user):
    mgr = AccountManager()
    mgr.users["root"] = User(name="root", admin_level=AdminLevel.ROOT)
    mgr.add_qos("root", Qos(name="low", priority=0))
    mgr.add_qos("root", Qos(name="high", priority=1000, preempt={"low"},
                            max_jobs_per_user=high_jobs_per_user))
    mgr.add_account("root", Account(name="hpc", allowed_qos={"low", "high"},
                                    default_qos="low"))
    mgr.add_user("root", User(name="alice", uid=1), "hpc")
    return mgr


def spec(cpu=4.0, runtime=2.0, **kw):
    kw.setdefault("time_limit", 60.0)
    return JobSpec(res=ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                                    memsw_bytes=1 << 30),
                   sim_runtime=runtime, **kw)


@dataclasses.dataclass
class Side:
    sched: JobScheduler
    sim: SimCluster
    wal_path: str


class Pair:
    """The scheduler under test and its reference, one script for both."""

    def __init__(self, tmp_path, route, accounts=False,
                 high_jobs_per_user=1, **config):
        self.sides = []
        for name in ("sut", "ref"):
            meta = MetaContainer()
            for i in range(NODES):
                meta.add_node(f"n{i:02d}", meta.layout.encode(
                    cpu=CPU, mem_bytes=16 << 30, memsw_bytes=16 << 30,
                    is_capacity=True), partitions=("default", "other"))
                meta.craned_up(i)
            path = str(tmp_path / f"{name}.wal")
            sched = JobScheduler(
                meta, SchedulerConfig(**{**ROUTES[route], **config}),
                wal=WriteAheadLog(path),
                accounts=(_accounts(high_jobs_per_user)
                          if accounts else None))
            sched.licenses.configure("lic", total=1)
            sim = SimCluster(sched)
            sim.wire(sched)
            if name == "ref":
                visit_every_row(sched)
            self.sides.append(Side(sched, sim, path))
        self.sut = self.sides[0].sched
        self.visited = []          # commit_visited_pct of the SUT's cycles

    def both(self, fn):
        """An event between cycles, on both sides; whatever it wrote, the
        stamps must not claim more than the jobs carry."""
        out = [fn(side.sched) for side in self.sides]
        assert out[0] == out[1]
        assert stale_stamps(self.sut) == [], "stale stamps after an event"
        return out[0]

    def submit(self, now, *specs):
        return [self.both(lambda s, sp=sp: s.submit(sp, now=now))
                for sp in specs]

    def fill(self, now=0.0, backlog=8):
        """Every node taken whole by a job that ends at 3, 5, 7, 9 s, and
        a backlog of half-node jobs, every other one a gang of two."""
        self.submit(now, *(spec(CPU, runtime=float(rt))
                           for rt in (3, 5, 7, 9)))
        return self.submit(now, *(spec(node_num=1 + i % 2)
                                  for i in range(backlog)))

    def cycle(self, now, during=None):
        """One cycle on both sides; ``during`` runs once the first solve
        closure has returned and before the cycle takes its results,
        which is where a handler runs with the server lock free."""
        started = []
        for side in self.sides:
            side.sim.advance_to(now)
            gen = side.sched.cycle_phases(now)
            fired = during is None
            try:
                fn = next(gen)
                while True:
                    out = fn()
                    if not fired:
                        during(side.sched)
                        fired = True
                    fn = gen.send(out)
            except StopIteration as stop:
                started.append(stop.value or [])
        self.compare(started, now)
        rows = self.sut.cycle_trace.snapshot()
        if rows and rows[-1]["now"] == now and "commit_visited_pct" in rows[-1]:
            self.visited.append(rows[-1]["commit_visited_pct"])
        return started[0]

    def compare(self, started, now):
        assert started[0] == started[1], f"t={now}: started sets differ"
        states = []
        for side in self.sides:
            sched = side.sched
            avail, _total, alive = sched.meta.snapshot()
            with open(side.wal_path, encoding="utf-8") as fh:
                wal = fh.read()
            states.append({
                "pending": {jid: (job.pending_reason, job.held)
                            for jid, job in sched.pending.items()},
                "running": sorted(sched.running),
                "history": sorted(sched.history),
                "avail": np.asarray(avail).tolist(),
                "alive": np.asarray(alive).tolist(),
                "licenses": {n: lic.in_use for n, lic in
                             sched.licenses.licenses.items()},
                "wal": wal,
            })
        for key in states[0]:
            assert states[0][key] == states[1][key], f"t={now}: {key}"
        assert stale_stamps(self.sut) == [], f"t={now}: stale stamps"

    def run(self, start, stop, **kw):
        for t in range(start, stop):
            self.cycle(float(t), **kw)

    def close(self):
        for side in self.sides:
            side.sched.wal.close()


# ---------------------------------------------------------------------------
# the scripts: each is one case a route
# ---------------------------------------------------------------------------

def script_standing(pair):
    """Nothing happens but time: nodes free up, the backlog drains."""
    pair.fill()
    pair.run(1, 12)


def script_hold_release(pair):
    ids = pair.fill()
    pair.cycle(1.0)
    pair.both(lambda s: s.hold(ids[1], True, now=1.5))
    pair.cycle(2.0)
    # holds that land while the solve is out, on a row it placed (the
    # first node is free at t=4) and on one it did not: the placement,
    # or the reason, is void; the hold's own reason stands
    def holds(sched):
        sched.hold(ids[0], True, now=4.0)
        sched.hold(ids[7], True, now=4.0)

    pair.cycle(4.0, during=holds)
    assert ids[0] in pair.sut.pending
    pair.cycle(5.0)
    for jid in (ids[1], ids[0], ids[7]):
        pair.both(lambda s, jid=jid: s.hold(jid, False, now=5.5))
    pair.run(6, 14)


def script_cancel(pair):
    ids = pair.fill()
    pair.cycle(1.0)
    pair.both(lambda s: s.cancel(ids[2], now=1.5))
    pair.cycle(2.0)
    pair.cycle(4.0, during=lambda s: s.cancel(ids[0], now=4.0))
    pair.cycle(5.0, during=lambda s: s.cancel(ids[5], now=5.0))
    pair.run(6, 12)


def script_modify(pair):
    ids = pair.fill()
    pair.cycle(1.0)
    pair.both(lambda s: s.modify_job(ids[3], now=1.5, time_limit=120.0))
    pair.cycle(2.0)
    # the spec-epoch void: partition moves while the solve is out, of a
    # job it placed and of one it did not
    def moves(sched):
        sched.modify_job(ids[0], now=4.0, partition="other")
        sched.modify_job(ids[7], now=4.0, partition="other")

    pair.cycle(4.0, during=moves)
    assert ids[0] in pair.sut.pending
    pair.cycle(5.0, during=lambda s: s.modify_job(
        ids[4], now=5.0, priority=5000))
    pair.run(6, 14)


def script_requeue(pair):
    ids = pair.fill()
    pair.run(1, 5)                       # ids[0] and ids[2] start at t=4
    running = sorted(set(ids) & set(pair.sut.running))
    assert running
    assert pair.both(lambda s: s.requeue(running[0], now=5.0)) == ""
    pair.cycle(5.5)
    pair.run(6, 9)
    # ... and a requeue while a solve is out
    running = sorted(set(ids) & set(pair.sut.running))
    pair.cycle(9.0, during=(lambda s: s.requeue(running[-1], now=9.0))
               if running else None)
    pair.run(10, 16)


def script_gates_flip(pair):
    """A ``begin_time`` that passes and a licence seat that comes and
    goes: gate reasons written by the candidate pass, over rows the
    commit had stamped, and back."""
    ids = pair.fill(backlog=4)
    late, lic_a, lic_b = pair.submit(
        0.0, spec(begin_time=3.5), spec(licenses={"lic": 1}, runtime=3.0),
        spec(licenses={"lic": 1}))
    pair.run(1, 3)
    pair.both(lambda s: s.licenses.configure("lic", total=0))
    pair.run(3, 5)                       # both licensed jobs gated
    pair.both(lambda s: s.licenses.configure("lic", total=1))
    pair.run(5, 9)                       # candidates again; one seat
    pair.both(lambda s: s.licenses.configure("lic", total=2))
    pair.run(9, 15)
    assert ids and late and lic_a and lic_b


def script_constraint(pair):
    """A reason that changes between cycles with no event on the job:
    every node of the partition drained reads CONSTRAINT, then
    RESOURCE again."""
    pair.fill()
    pair.run(1, 3)
    for node in range(NODES):
        pair.both(lambda s, n=node: s.meta.drain(n, True))
    pair.run(3, 5)
    assert PendingReason.CONSTRAINT in {
        job.pending_reason for job in pair.sut.pending.values()}
    for node in range(NODES):
        pair.both(lambda s, n=node: s.meta.drain(n, False))
    pair.run(5, 13)


def script_batch_cut(pair):
    """``ScheduledBatchSize`` 3, cut in row order: rows the commit had
    stamped RESOURCE fall behind the cut when three older rows are
    released, are told PRIORITY by the cut and not by the commit, and
    are readmitted when those three go."""
    pair.fill(backlog=0)
    pair.run(1, 3)                       # the four fillers start, 3 + 1
    old = pair.submit(2.0, *(spec(held=True) for _ in range(3)))
    ids = pair.submit(2.0, *(spec(node_num=1 + i % 2) for i in range(6)))
    pair.cycle(2.2)

    def reasons():
        return [pair.sut.pending[j].pending_reason for j in ids]

    assert reasons() == [PendingReason.RESOURCE] * 3 \
        + [PendingReason.PRIORITY] * 3
    for jid in old:
        pair.both(lambda s, jid=jid: s.hold(jid, False, now=2.5))
    # t=2.9: still before the first filler ends, nothing can start
    pair.cycle(2.9)
    assert reasons() == [PendingReason.PRIORITY] * 6
    for jid in old:
        pair.both(lambda s, jid=jid: s.cancel(jid, now=2.95))
    pair.cycle(2.99)
    assert reasons() == [PendingReason.RESOURCE] * 3 \
        + [PendingReason.PRIORITY] * 3
    pair.run(3, 16)


def script_preemption(pair):
    """Preemption on: a high-QoS job evicts a low one, the victim comes
    back PREEMPTED, and the preemption's own commit refuses a second
    high job (one a user may run) with a reason the main commit did not
    write, on a row the main commit had stamped the same cycle."""
    def hpc(cpu, qos, **kw):
        return spec(cpu, user="alice", account="hpc", qos=qos, **kw)

    seen = set()

    def run(start, stop):
        for t in range(start, stop):
            pair.cycle(float(t))
            seen.update(job.pending_reason
                        for job in pair.sut.pending.values())

    pair.submit(0.0, *(hpc(CPU, "low", runtime=float(rt))
                       for rt in (20, 22, 24, 26)))
    run(1, 3)
    pair.submit(3.0, *(hpc(4.0, "low") for _ in range(4)))
    run(3, 5)
    pair.submit(5.0, hpc(CPU, "high", runtime=4.0),
                hpc(CPU, "high", runtime=4.0))
    run(5, 40)
    assert {PendingReason.PREEMPTED, PendingReason.QOS_LIMIT} <= seen
    assert not pair.sut.pending and not pair.sut.running


def script_compaction(pair):
    """A table compaction while the solve is out: the cancels of one
    handler burst move the rows the cycle took, so its commit cannot
    trust the row map and falls back to the full range.  The reasons
    change in that very cycle (every node drained), so the stamps the
    fallback does not keep must be forgotten, not left as they were."""
    pair.fill(backlog=0)
    pair.run(1, 2)
    ids = pair.submit(1.0, *(spec(CPU, runtime=1.0) for _ in range(140)))
    pair.run(2, 3)
    for node in range(NODES):
        pair.both(lambda s, n=node: s.meta.drain(n, True))
    gen0 = pair.sut._ptable.generation

    def burst(sched):
        for jid in ids[20:120]:
            sched.cancel(jid, now=3.5)

    pair.cycle(3.5, during=burst)
    assert pair.sut._ptable.generation > gen0
    assert pair.visited[-1] == 100.0
    assert {job.pending_reason for job in pair.sut.pending.values()} \
        == {PendingReason.CONSTRAINT}
    pair.cycle(3.6)
    for node in range(NODES):
        pair.both(lambda s, n=node: s.meta.drain(n, False))
    pair.run(4, 9)


SCRIPTS = {
    "standing": (script_standing, {}),
    "hold_release": (script_hold_release, {}),
    "cancel": (script_cancel, {}),
    "modify": (script_modify, {}),
    "requeue": (script_requeue, {}),
    "gates_flip": (script_gates_flip, {}),
    "constraint": (script_constraint, {}),
    "batch_cut": (script_batch_cut, dict(schedule_batch_size=3)),
    "preemption": (script_preemption,
                   dict(accounts=True, high_jobs_per_user=1,
                        preempt_mode="requeue")),
    "compaction": (script_compaction, {}),
    # no PendingTable pass, so no row map: every commit is the full range
    "not_incremental": (script_hold_release, dict(incremental=False)),
    # FIFO order: the row map is the candidate rows themselves
    "basic_priority": (script_cancel, dict(priority_type="basic")),
}


@pytest.mark.parametrize("script", sorted(SCRIPTS))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_commit_matches_the_pass_over_every_row(tmp_path, route, script):
    fn, config = SCRIPTS[script]
    pair = Pair(tmp_path, route, **config)
    try:
        fn(pair)
        if route != "immediate":     # whose label is its backend's
            assert route in {
                r["solver"] for r in pair.sut.cycle_trace.snapshot()}
        # the script did reach the path it is about: some cycle of the
        # scheduler under test left rows out (none may without a row map)
        if config.get("incremental", True) and route != "backfill":
            assert min(pair.visited) < 100.0, pair.visited
        if not config.get("incremental", True):
            assert set(pair.visited) == {100.0}
    finally:
        pair.close()
