"""The running jobs' priority columns are kept by the ``running`` dict's
hooks (ISSUE 45).

``_priority_sort`` used to walk ``self.running`` in Python, under the
server lock, on every cycle whose membership had moved, to rebuild seven
columns of which about fifteen rows in twenty thousand had changed.  The
rows now live in ``ctld/running_table.py``, written where the set
changes.  That walk survives HERE, as the plain reference (``_walk``:
``job_row`` + ``start_time``, in dict order):

(a) over seeded random sequences of start, finish, cancel, requeue, node
    death, preemption's eviction, ``recover`` from snapshot + WAL and the
    promotion refresh, with one account and with several, on both routes:
    after every step the table's row of every running job equals the
    walk's, no row is left for a job that is not running, and every
    cycle's priorities equal those the model gives for the walk's
    columns (exactly with one account; within float32 rounding with
    several, where the per-account sum runs in slot order);
(b) ``run_walked`` is 0 on a steady cycle and the whole running set on
    the cycle that makes the table (the first, and the first after
    ``rebuild_device_state``); the padded shapes are the walk's;
(c) jobs that never had a pending row (``recover``, an adoption) get
    their row from the ``Job``;
(d) under ``Priority: Type: basic`` no row is ever derived;
(e) the table alone: dense rows, a freed slot filled from the last.
"""

import numpy as np
import pytest

import cranesched_tpu.ctld.scheduler as scheduler_mod
from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
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
from cranesched_tpu.ctld.running_table import RunningTable
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.ha.snapshot import (
    SnapshotStore,
    capture_snapshot,
    recover_from_snapshot,
)
from cranesched_tpu.models.priority import RunningPriorityAttrs
from cranesched_tpu.ops.resources import DIM_CPU, DIM_MEM

NODES = 8
ROUTES = {"rows": True, "rebuild": False}


def _manager(accounts):
    mgr = AccountManager()
    mgr.users["root"] = User(name="root", admin_level=AdminLevel.ROOT)
    mgr.add_qos("root", Qos(name="low", priority=10))
    mgr.add_qos("root", Qos(name="high", priority=1000, preempt={"low"}))
    for a in range(accounts):
        mgr.add_account("root", Account(
            name=f"acct{a}", allowed_qos={"low", "high"},
            default_qos="low"))
        mgr.add_user("root", User(name=f"user{a}", uid=a + 1), f"acct{a}")
    return mgr


def _build(accounts, wal_path=None, **config):
    meta = MetaContainer()
    meta.add_partition("batch", priority=5)
    meta.add_partition("debug", priority=50)
    for i in range(NODES):
        parts = (("batch",) if i < 4 else ("debug",) if i >= 6
                 else ("batch", "debug"))
        meta.add_node(f"cn{i:02d}", meta.layout.encode(
            cpu=8, mem_bytes=16 << 30, memsw_bytes=16 << 30,
            is_capacity=True), partitions=parts)
        meta.craned_up(i)
    config.setdefault("backfill", False)
    sched = JobScheduler(
        meta, SchedulerConfig(preempt_mode="requeue", **config),
        accounts=_manager(accounts),
        wal=WriteAheadLog(wal_path, fsync=False) if wal_path else None)
    sim = SimCluster(sched)
    sim.wire(sched)
    return sched, sim


def _spec(rng, accounts, qos="low", cpu=None, nodes=None, partition=None):
    a = int(rng.integers(accounts))
    cpu = float(rng.choice([1, 2, 4])) if cpu is None else cpu
    return JobSpec(
        res=ResourceSpec(cpu=cpu, mem_bytes=int(cpu) << 29,
                         memsw_bytes=int(cpu) << 29),
        node_num=int(rng.integers(1, 3)) if nodes is None else nodes,
        partition=partition or str(rng.choice(["batch", "debug"])),
        user=f"user{a}", account=f"acct{a}", qos=qos,
        time_limit=600.0, sim_runtime=float(rng.integers(3, 40)))


# ---- the plain reference: the walk the prelude made every cycle ----

def _walk(sched):
    """``{job_id: (qos, part, nnum, cpus, mem, acct, start)}`` in the
    running dict's order, as ``_priority_sort`` derived it before the
    table: ``job_row`` on every running job and its ``start_time``."""
    rows = {}
    for job in sched.running.values():
        req = job.spec.res.encode(sched.meta.layout)
        rows[job.job_id] = (
            job.qos_priority,
            sched.meta.partitions[job.spec.partition].priority,
            job.spec.node_num,
            float(req[DIM_CPU]) / 256.0 * job.spec.node_num,
            float(req[DIM_MEM]) * job.spec.node_num,
            sched._account_index[job.spec.account],
            job.start_time if job.start_time is not None else np.inf)
    return rows


def _walk_attrs(sched, now):
    """The walk's rows as the padded device columns it built."""
    rows = list(_walk(sched).values())
    nR = len(rows)
    RP = JobScheduler._bucket(nR) if rows else 16

    def col(k, dt):
        arr = np.zeros(RP, dt)
        arr[:nR] = [r[k] for r in rows]
        return arr

    run_time = np.zeros(RP, np.int32)
    if nR:
        start = np.array([r[6] for r in rows])
        run_time[:nR] = np.maximum(now - start, 0.0)
    valid = np.zeros(RP, bool)
    valid[:nR] = True
    return RunningPriorityAttrs(
        qos_prio=col(0, np.int32), part_prio=col(1, np.int32),
        node_num=col(2, np.int32), cpus=col(3, np.float32),
        mem=col(4, np.float32), account=col(5, np.int32),
        run_time=run_time, valid=valid)


def _check_table(sched):
    """The table against the walk: every running job's row, and nothing
    else.  None: no cycle has ranked a queue since the table was
    dropped, so there is nothing to hold yet."""
    rt = sched._rtable
    if rt is None:
        return
    want = _walk(sched)
    assert len(rt) == len(sched.running) == len(want)
    assert sorted(rt.column("job_id").tolist()) == sorted(want)
    for job_id, row in want.items():
        got = rt.row_of(job_id)
        assert got[:3] == row[:3] and got[5:] == row[5:], job_id
        # the columns hold what the walk's float32 arrays held
        assert got[3] == np.float32(row[3]) and got[4] == np.float32(row[4])


@pytest.fixture
def compared(monkeypatch):
    """Every ``multifactor_priority`` call of a cycle is made twice: with
    the columns the scheduler assembled and with the walk's.  ``exact``
    (one account: no sum over accounts sees the row order) or within
    float32 rounding."""
    model = scheduler_mod.multifactor_priority
    state = {"sched": None, "exact": True, "cycles": 0}

    def both(pending, running, weights, num_accounts, **kw):
        sched = state["sched"]
        got = np.asarray(model(pending, running, weights, num_accounts,
                               **kw))
        now = sched._cycle_now
        ref = _walk_attrs(sched, now)
        # same shapes, same programs: the bucket, dtypes and fields
        for name in ("qos_prio", "part_prio", "node_num", "cpus", "mem",
                     "account", "run_time", "valid"):
            a, b = getattr(running, name), getattr(ref, name)
            assert a.shape == b.shape and a.dtype == b.dtype, name
        assert int(np.asarray(running.valid).sum()) == len(sched.running)
        want = np.asarray(model(pending, ref, weights, num_accounts, **kw))
        if state["exact"]:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-3)
        state["cycles"] += 1
        return got

    monkeypatch.setattr(scheduler_mod, "multifactor_priority", both)
    return state


def _cycle(sched, sim, now):
    """One cycle -> (started ids, its trace row; None where the cycle
    had no candidate and rang no row)."""
    sim.advance_to(now)
    started = sched.schedule_cycle(now)
    rows = sched.cycle_trace.snapshot()
    row = rows[-1] if rows and rows[-1]["now"] == now else None
    return started, row


def _recovered(sched, accounts, path, now, config):
    """Kill-and-recover: a snapshot, a WAL tail past it, a fresh
    scheduler on the same files."""
    sched.wal.close()
    fresh, fresh_sim = _build(accounts, wal_path=path, **config)
    recover_from_snapshot(fresh, WriteAheadLog, path, now)
    assert fresh._rtable is None    # no cycle yet: nothing derived
    for job in fresh.running.values():
        fresh_sim.dispatch(job, job.node_ids)
    return fresh, fresh_sim


@pytest.mark.parametrize("seed", [45, 4545])
@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("accounts", [1, 4])
def test_table_matches_the_walk_after_every_step(
        tmp_path, compared, accounts, route, seed):
    path = str(tmp_path / "ctld.wal")
    config = dict(incremental=ROUTES[route])
    sched, sim = _build(accounts, wal_path=path, **config)
    compared.update(sched=sched, exact=accounts == 1)
    rng = np.random.default_rng(seed)
    now = 0.0
    seen = {"steady": 0, "made": 0, "evicted": 0, "recovered": 0}
    steps = rng.choice(["submit", "cycle", "cycle", "cycle", "cancel",
                        "requeue", "preempt", "node_down"], 60)
    steps[[14, 44]] = "promote"
    steps[[29, 52]] = "recover"
    down = None
    for _ in range(4):          # a full cluster to start from
        sched.submit(_spec(rng, accounts, cpu=4.0, nodes=1), now=now)
    for step in ["cycle"] + list(steps):
        now += float(rng.integers(1, 4))
        running = list(sched.running)
        if step == "submit":
            for _ in range(int(rng.integers(1, 5))):
                sched.submit(_spec(rng, accounts), now=now)
        elif step == "cancel" and running:
            sched.cancel(int(rng.choice(running)), now=now)
        elif step == "requeue" and running:
            sched.requeue(int(rng.choice(running)), now=now)
        elif step == "preempt":
            # a whole node for a QoS that may evict: the cluster is full
            # of "low", so the cycle's preemption solve picks victims
            sched.submit(_spec(rng, accounts, qos="high", cpu=8.0,
                               nodes=1), now=now)
            _, row = _cycle(sched, sim, now)
            seen["evicted"] += row["preempted"]
        elif step == "node_down" and down is None:
            down = int(rng.integers(NODES))
            sched.on_craned_down(down, now)
        elif step == "promote":
            sched.rebuild_device_state()
            assert sched._rtable is None
            # one job so that the next cycle has a queue to rank
            sched.submit(_spec(rng, accounts), now=now)
            started, row = _cycle(sched, sim, now)
            # the cycle that makes the table walks the set as it stood
            # when the prelude ran: its own starts and evictions come
            # after
            assert row["run_walked"] == (len(sched.running) - len(started)
                                         + row["preempted"])
            seen["made"] += 1
        elif step == "recover":
            SnapshotStore(path).save(capture_snapshot(sched))
            sched.submit(_spec(rng, accounts), now=now)
            _cycle(sched, sim, now)         # the WAL's tail past it
            _check_table(sched)
            now += 1.0
            sched, sim = _recovered(sched, accounts, path, now, config)
            compared["sched"] = sched
            down = None
            seen["recovered"] += 1
        else:
            if down is not None:
                sched.meta.craned_up(down)
                down = None
            had = sched._rtable is not None
            _, row = _cycle(sched, sim, now)
            if had and row is not None and row["solver"] != "skip":
                assert row["run_walked"] == 0
                assert row["run_cols_ms"] >= 0.0
                seen["steady"] += 1
        _check_table(sched)
    # (a sequence that ends on a recovery has no table yet)
    sched.submit(_spec(rng, accounts), now=now + 1.0)
    _cycle(sched, sim, now + 1.0)
    _check_table(sched)
    assert compared["cycles"] >= 20
    assert seen["steady"] >= 5 and seen["made"] == 2, seen
    assert seen["recovered"] == 2, seen
    # rows were written by the hooks, not by a walk a cycle
    assert sched._rtable is not None and len(sched.history) > 0


def test_eviction_requeue_and_cancel_free_their_rows(compared):
    """Each way out of ``running``, one at a time and by name."""
    sched, sim = _build(2)
    compared.update(sched=sched, exact=False)
    rng = np.random.default_rng(7)
    # whole-node jobs: "debug" ranks first and fills its four nodes,
    # "batch" is left its other four
    ids = [sched.submit(_spec(rng, 2, cpu=8.0, nodes=1,
                              partition="batch" if i < 4 else "debug"),
                        now=0.0) for i in range(NODES)]
    started, row = _cycle(sched, sim, 0.0)
    # the first cycle made the table before any job ran: nothing walked
    assert row["run_walked"] == 0 and len(started) == NODES
    rt = sched._rtable
    assert len(rt) == NODES
    _check_table(sched)
    # preemption's eviction (requeue mode): the victim leaves the table
    # with the dict, the preemptor enters it
    high = sched.submit(_spec(rng, 2, qos="high", cpu=8.0, nodes=1,
                              partition="debug"), now=1.0)
    started, row = _cycle(sched, sim, 1.0)
    assert row["preempted"] == 1 and high in started
    victim = next(j for j in ids if j in sched.pending)
    assert rt.row_of(victim) is None and len(rt) == NODES
    assert rt.row_of(high)[0] == 1000 and rt.row_of(high)[6] == 1.0
    _check_table(sched)
    # operator requeue and cancel
    a, b = [j for j in ids if j in sched.running][:2]
    assert sched.requeue(a, now=2.0) == ""
    assert rt.row_of(a) is None and len(rt) == NODES - 1
    sched.cancel(b, now=2.0)
    _cycle(sched, sim, 3.0)
    assert rt.row_of(b) is None and b in sched.history
    _check_table(sched)
    # a finish through the status drain
    sim.advance_to(100.0)
    sched.process_status_changes()
    assert len(rt) == len(sched.running)
    _check_table(sched)
    assert rt is sched._rtable      # one table all along


@pytest.mark.parametrize("accounts", [1, 3])
def test_jobs_that_never_had_a_pending_row(tmp_path, compared, accounts):
    """``recover`` (and a federation's adoption) inserts RUNNING jobs
    straight into the dict: where a table stands, the hook derives the
    row from the ``Job``."""
    path = str(tmp_path / "ctld.wal")
    sched, sim = _build(accounts, wal_path=path)
    compared.update(sched=sched, exact=accounts == 1)
    rng = np.random.default_rng(3)
    for _ in range(10):
        sched.submit(_spec(rng, accounts), now=0.0)
    _cycle(sched, sim, 0.0)
    want = _walk(sched)
    assert len(want) >= 4
    sched.wal.close()

    fresh, fresh_sim = _build(accounts)
    compared["sched"] = fresh
    # a daemon that has ranked a queue already: its table stands
    fresh._rtable = RunningTable()
    fresh.recover(WriteAheadLog.replay(path), now=1.0)
    assert set(fresh.running) == set(want)
    assert not any(j in fresh._ptable for j in fresh.running)
    _check_table(fresh)
    got = {j: fresh._rtable.row_of(j) for j in want}
    for job_id, row in want.items():
        # the account index is this process's own; the rest is the Job's
        assert got[job_id][:3] == row[:3] and got[job_id][6] == row[6]
    for job in fresh.running.values():
        fresh_sim.dispatch(job, job.node_ids)
    fresh.submit(_spec(rng, accounts), now=2.0)
    _, row = _cycle(fresh, fresh_sim, 2.0)
    assert row["run_walked"] == 0 and compared["cycles"] == 2
    _check_table(fresh)


@pytest.mark.parametrize("backfill", [False, True])
def test_basic_priority_derives_no_row(monkeypatch, backfill):
    """``Priority: Type: basic`` never reads a running column, so its
    hooks write none: the flood cell on it pays nothing."""
    sched, sim = _build(2, priority_type="basic", backfill=backfill)
    puts = []
    monkeypatch.setattr(sched, "_running_put", puts.append)
    rng = np.random.default_rng(11)
    now = 0.0
    for _ in range(12):
        now += 1.0
        for _ in range(3):
            sched.submit(_spec(rng, 2), now=now)
        _, row = _cycle(sched, sim, now)
        assert row["run_walked"] == 0 and row["run_cols_ms"] == 0.0
        running = list(sched.running)
        if running:
            sched.cancel(running[0], now=now)
    sched.rebuild_device_state()
    _cycle(sched, sim, now + 1.0)
    assert len(sched.history) > 0 and len(sched.running) > 0
    assert sched._rtable is None and puts == []


def test_first_cycle_after_recovery_walks_once(tmp_path, compared):
    """A recovered daemon has no table until a cycle ranks a queue; that
    cycle walks the whole set once (``run_walked``), the next none."""
    path = str(tmp_path / "ctld.wal")
    sched, sim = _build(2, wal_path=path)
    compared.update(sched=sched, exact=False)
    rng = np.random.default_rng(5)
    for _ in range(24):
        sched.submit(_spec(rng, 2, cpu=4.0, nodes=1), now=0.0)
    _cycle(sched, sim, 0.0)
    n_run = len(sched.running)
    assert n_run >= 8
    sched, sim = _recovered(sched, 2, path, 1.0, {})
    compared["sched"] = sched
    assert len(sched.running) == n_run and len(sched.pending) >= 1
    _, row = _cycle(sched, sim, 1.0)
    assert row["run_walked"] == n_run
    _check_table(sched)
    sched.submit(_spec(rng, 2), now=2.0)
    _, row = _cycle(sched, sim, 2.0)
    assert row["run_walked"] == 0
    _check_table(sched)


# ---- the table alone ----

def test_rows_stay_dense_under_random_churn():
    rng = np.random.default_rng(0)
    rt = RunningTable(cap=8)
    live = {}
    for step in range(4000):
        if live and rng.random() < 0.48:
            job_id = int(rng.choice(list(live)))
            rt.remove(job_id)
            del live[job_id]
        else:
            job_id = int(rng.integers(1, 600))
            row = (int(rng.integers(100)), int(rng.integers(10)),
                   int(rng.integers(1, 9)), float(rng.integers(64)),
                   float(rng.integers(1 << 20)), int(rng.integers(5)),
                   float(step))
            rt.put(job_id, *row)        # a job that has a row: rewritten
            live[job_id] = row
        assert len(rt) == len(live)
    assert sorted(rt.column("job_id").tolist()) == sorted(live)
    for job_id, row in live.items():
        assert rt.row_of(job_id) == row
    rt.remove(10 ** 9)                  # no row: no-op
    assert len(rt) == len(live)


def test_epoch_moves_with_every_write_and_only_then():
    rt = RunningTable()
    assert (rt.epoch, len(rt)) == (0, 0)
    rt.put(1, 0, 0, 1, 1.0, 1.0, 0, 5.0)
    rt.put(2, 0, 0, 1, 1.0, 1.0, 0, np.inf)
    e = rt.epoch
    assert e == 2 and rt.column("start").tolist() == [5.0, np.inf]
    rt.remove(3)
    assert rt.epoch == e
    rt.remove(1)
    assert rt.epoch == e + 1 and rt.column("job_id").tolist() == [2]
    assert rt.row_of(1) is None and rt.row_of(2)[6] == np.inf
