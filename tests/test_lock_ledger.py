"""The handler side of the server lock (ISSUE 42).

``LockLedger`` (obs/trace.py) stands beside ``CycleClock``: each classed
site (SubmitBatchJob, each hold of SubmitBatchJobs, QueryJobsInfo and each
hold of QueryJobsStream, QueryStats, Snapshotter.snap_once) reads the
clock before its own plain ``with self._lock:``, calls ``enter`` as the
first statement inside and ``leave`` in a ``finally``; the cycle thread
drains the sums into the row it rings as its period closes.  These tests
drive a real ``CtldServer`` over the sim plane, as tests/test_cycle_ledger.py
does, and hold the ledger to its contract: a hold is booked as held and a
wait as a wait, by class, in the row of the cycle it ended in; work under
the lock, classed holds and the rest sum to the period on every route; a
handler that raises or returns inside its hold still books it; the lock
stays a plain ``threading.Lock``; the profiler is touched only inside a
capture."""

import threading
import time

import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    MetaContainer,
    SchedulerConfig,
)
from cranesched_tpu.obs import REGISTRY
from cranesched_tpu.obs.trace import CycleClock, LockLedger
from cranesched_tpu.rpc import crane_pb2 as pb
from cranesched_tpu.rpc.client import CtldClient
from cranesched_tpu.rpc.server import CtldServer, serve

pytestmark = pytest.mark.obs

HOLD_S = 0.05


def _cluster(wal=None, **config):
    meta = MetaContainer()
    for i in range(8):
        meta.add_node(
            f"cn{i:02d}",
            meta.layout.encode(cpu=16, mem_bytes=32 << 30,
                               memsw_bytes=32 << 30, is_capacity=True),
            partitions=("default",))
        meta.craned_up(i)
    config.setdefault("cycle_idle_sleep", 0.06)
    sched = JobScheduler(meta, SchedulerConfig(**config), wal=wal)
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    return meta, sched, cluster


def _served(sched, cluster):
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         cycle_interval=0.05)
    server.address = f"127.0.0.1:{port}"
    return server, CtldClient(server.address)


def _busy_served(wal=None):
    """A served cluster whose every cycle rings a row: eight nodes full
    and a ninth job that stays a candidate, with the no-op fingerprint
    (which would coalesce those cycles into one skip row) off.  A cycle
    with no candidate rings no row, and what the ledger drained in its
    period feeds the counter family alone."""
    meta, sched, cluster = _cluster(wal=wal, backfill=False,
                                    incremental=False)
    server, client = _served(sched, cluster)
    reply = client.submit_many(
        [_pbspec(cpu=16.0, runtime=600.0, user="filler")] * 9)
    assert all(r.job_id for r in reply.replies)
    assert _wait(lambda: len(sched.running) == 8
                 and len(_closed_rows(sched)) >= 2)
    return sched, cluster, server, client


def _pbspec(cpu=1.0, runtime=30.0, user="alice"):
    return pb.JobSpec(
        res=pb.ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                            memsw_bytes=1 << 30),
        time_limit=3600, partition="default", user=user,
        sim_runtime=runtime)


def _wait(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _closed_rows(sched, solver=None):
    """The ring's rows whose cycle has closed its ledger."""
    return [r for r in sched.cycle_trace.snapshot()
            if "period_ms" in r and solver in (None, r["solver"])]


def _total(rows, field):
    return sum(r.get(field, 0) for r in rows)


def _classed_hold(server, holder, seconds):
    """What a classed handler does, on this thread."""
    ledger = server.scheduler.lock_ledger
    t0 = time.perf_counter()
    with server._lock:
        ledger.enter(holder, t0)
        try:
            time.sleep(seconds)
        finally:
            ledger.leave()


def _slow_solve(sched, entered, until, left):
    """The immediate solve (a yielded closure: the lock is released) sets
    ``entered``, does not end before ``until`` is set, sets ``left``."""
    inner = sched._immediate_solve

    def slow(*a, **kw):
        entered.set()
        try:
            return inner(*a, **kw)
        finally:
            until.wait(10.0)
            left.set()

    sched._immediate_solve = slow


# ---------------------------------------------------------------------------
# the ledger alone
# ---------------------------------------------------------------------------

def _clock_fields(period_ms=100.0, work_ms=10.0, wait_ms=1.0):
    return {"period_ms": period_ms, "lock_held_work_ms": work_ms,
            "lock_wait_ms": wait_ms}


def test_ledger_books_wait_and_hold_by_class_and_drains_to_zero():
    ledger = LockLedger()
    t0 = time.perf_counter()
    time.sleep(0.004)                   # the wait: t0 to enter
    t1 = ledger.enter(ledger.QUERY, t0)
    assert ledger.holder == "query" and t1 >= t0 + 0.004
    time.sleep(0.006)                   # the hold: enter to leave
    ledger.add(ledger.QUERY_SNAPSHOT, 0.005)
    ledger.leave()
    assert ledger.holder == ""
    ledger.enter(ledger.QUERY, time.perf_counter())
    ledger.leave()
    fields = ledger.drain(_clock_fields())
    assert fields["rpc_query_n"] == 2
    assert 4.0 <= fields["rpc_query_wait_ms"] < 6.0 + 50.0
    assert fields["rpc_query_wait_max_ms"] >= 4.0
    assert fields["rpc_query_held_ms"] >= 6.0
    # the longest SINGLE hold, not the sum
    assert 6.0 <= fields["rpc_query_held_max_ms"] <= fields["rpc_query_held_ms"]
    assert fields["rpc_query_snapshot_ms"] == 5.0
    assert fields["rpc_query_convert_ms"] == 0.0
    # a class that took nothing writes nothing, its parts neither
    assert not any(k.startswith(("rpc_submit", "rpc_stats", "rpc_snapshot"))
                   for k in fields)
    assert fields["lock_held_rpc_ms"] == fields["rpc_query_held_ms"]
    assert fields["lock_unaccounted_ms"] == pytest.approx(
        100.0 - 10.0 - fields["lock_held_rpc_ms"], abs=1e-6)
    # fresh sums
    again = ledger.drain(_clock_fields())
    assert set(again) == {"lock_held_rpc_ms", "lock_unaccounted_ms"}
    assert again["lock_held_rpc_ms"] == 0.0
    assert again["lock_unaccounted_ms"] == 90.0


def test_ledger_slots_are_a_preallocated_flat_list_of_floats():
    ledger = LockLedger()
    acc = ledger._acc
    assert type(acc) is list and all(type(v) is float for v in acc)
    assert len(acc) == 5 * len(ledger.HOLDERS) + len(ledger.PARTS) \
        + len(ledger.COUNTS)
    for k in range(len(ledger.HOLDERS)):
        ledger.enter(k, time.perf_counter())
        ledger.leave()
    # a take builds nothing: the same list, the same length
    assert ledger._acc is acc and len(acc) == ledger._SIZE
    fields = ledger.drain(_clock_fields())
    assert {f"rpc_{name}_n" for name in ledger.HOLDERS} <= set(fields)
    assert {f"rpc_{name}_ms" for _, name in ledger.PARTS} <= set(fields)


def test_drain_feeds_one_counter_family_by_holder_and_kind():
    def value(holder, kind):
        series = REGISTRY.snapshot().get(
            "crane_server_lock_seconds_total", {}).get("values", {})
        return sum(v for k, v in series.items()
                   if f'holder="{holder}"' in k and f'kind="{kind}"' in k)

    ledger = LockLedger()
    before = {(h, k): value(h, k) for h in ("stats", "cycle", "query")
              for k in ("wait", "held")}
    t0 = time.perf_counter()
    time.sleep(0.002)
    ledger.enter(ledger.STATS, t0)
    time.sleep(0.003)
    ledger.leave()
    ledger.drain(_clock_fields(work_ms=10.0, wait_ms=4.0))
    assert value("stats", "wait") - before["stats", "wait"] >= 0.002
    assert value("stats", "held") - before["stats", "held"] >= 0.003
    assert value("cycle", "wait") - before["cycle", "wait"] \
        == pytest.approx(0.004)
    assert value("cycle", "held") - before["cycle", "held"] \
        == pytest.approx(0.010)
    assert value("query", "held") == before["query", "held"]


def test_clock_reads_the_holder_as_its_longest_wait_begins():
    ledger = LockLedger()
    clock = CycleClock(ledger)
    clock.close()
    ledger.holder = "stats"             # a short wait behind stats
    clock.mark("lock_wait")
    time.sleep(0.002)
    clock.mark("drain")
    ledger.holder = "query"             # the longest, behind a query; the
    clock.mark("lock_wait")             # tag is read as the wait BEGINS
    ledger.holder = "submit"
    time.sleep(0.008)
    clock.mark("drain")
    ledger.holder = ""
    clock.mark("lock_wait")
    time.sleep(0.001)
    fields = clock.close()
    assert fields["lock_wait_max_behind"] == "query"
    assert fields["lock_wait_max_ms"] >= 8.0
    assert clock.close()["lock_wait_max_behind"] == ""


def test_clock_books_the_process_cpu_time_of_the_period():
    clock = CycleClock()
    clock.close()
    t0 = time.process_time()
    while time.process_time() - t0 < 0.02:      # burn 20 ms of CPU
        sum(range(1000))
    busy = clock.close()
    assert busy["cpu_ms"] >= 20.0
    time.sleep(0.05)                            # 50 ms of nothing
    idle = clock.close()
    assert idle["period_ms"] >= 50.0
    assert idle["cpu_ms"] < 0.5 * idle["period_ms"]


# ---------------------------------------------------------------------------
# a hold is held, a wait is a wait, each in the row of its cycle
# ---------------------------------------------------------------------------

def test_a_classed_hold_shows_as_held_and_as_who_the_cycle_waited_behind():
    meta, sched, cluster = _cluster(backfill=False)
    entered, holding, left = (threading.Event() for _ in range(3))
    _slow_solve(sched, entered, holding, left)
    server, client = _served(sched, cluster)
    ledger = sched.lock_ledger

    def handler():
        # takes the lock under class query while the solve runs with it
        # released, and keeps it HOLD_S beyond the solve's end: the cycle
        # thread's retake begins its wait behind this hold
        assert entered.wait(10.0)
        t0 = time.perf_counter()
        with server._lock:
            ledger.enter(ledger.QUERY, t0)
            try:
                holding.set()
                assert left.wait(10.0)
                time.sleep(HOLD_S)
            finally:
                ledger.leave()

    thread = threading.Thread(target=handler)
    thread.start()
    try:
        assert client.submit(_pbspec()).job_id > 0
        thread.join(15.0)
        assert not thread.is_alive()
        assert _wait(lambda: _closed_rows(sched, "native"))
        row = _closed_rows(sched, "native")[0]
    finally:
        server.stop()
    # in the row of the cycle the hold ENDED in
    assert row["rpc_query_n"] == 1
    assert HOLD_S * 1e3 <= row["rpc_query_held_ms"] < 10e3
    assert row["rpc_query_held_max_ms"] == row["rpc_query_held_ms"]
    assert row["rpc_query_wait_ms"] < 0.5 * HOLD_S * 1e3
    assert row["lock_wait_max_ms"] >= 0.9 * HOLD_S * 1e3
    assert row["lock_wait_max_behind"] == "query"
    assert row["lock_held_rpc_ms"] >= row["rpc_query_held_ms"]
    assert ledger.holder == ""


def test_a_handler_kept_waiting_behind_the_cycle_books_a_wait_not_a_hold():
    meta, sched, cluster = _cluster(backfill=False)
    in_sim = threading.Event()
    inner = cluster.advance_to

    def slow_advance(now):
        # under the server lock, on the cycle thread: its `sim` phase
        in_sim.set()
        time.sleep(HOLD_S + 0.03)
        return inner(now)

    cluster.advance_to = slow_advance
    server, client = _served(sched, cluster)
    try:
        assert client.submit(_pbspec()).job_id > 0      # warms the channel
        in_sim.clear()
        assert in_sim.wait(10.0)
        t0 = time.perf_counter()
        assert client.submit(_pbspec()).job_id > 0
        took_ms = (time.perf_counter() - t0) * 1e3
        cluster.advance_to = inner
        n = len(_closed_rows(sched))
        assert _wait(lambda: len(_closed_rows(sched)) > n + 1)
        rows = _closed_rows(sched)
    finally:
        server.stop()
    assert _total(rows, "rpc_submit_n") == 2
    waited = max(r.get("rpc_submit_wait_max_ms", 0.0) for r in rows)
    assert HOLD_S * 1e3 <= waited <= took_ms
    # the hold itself is a submit and a WAL-less append: not the wait
    assert max(r.get("rpc_submit_held_max_ms", 0.0) for r in rows) \
        < 0.5 * HOLD_S * 1e3
    # what it waited behind was the cycle's own work under the lock
    assert max(r["lock_held_work_ms"] for r in rows) >= HOLD_S * 1e3


# ---------------------------------------------------------------------------
# work + classed holds + the rest = the period, route by route
# ---------------------------------------------------------------------------

ROUTES = {
    "backfill-split": dict(backfill=True, backfill_max_jobs=2),
    "native": dict(backfill=False),
}


def _assert_identity(row):
    assert (row["lock_held_work_ms"] + row["lock_held_rpc_ms"]
            + row["lock_unaccounted_ms"]) == pytest.approx(
                row["period_ms"], abs=0.01)
    classed = sum(row.get(f"rpc_{name}_held_ms", 0.0)
                  for name in LockLedger.HOLDERS)
    assert row["lock_held_rpc_ms"] == pytest.approx(classed, abs=0.01)
    # the cycle's work and the classed holds exclude one another in time
    assert row["lock_unaccounted_ms"] >= -0.01


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_lock_time_sums_to_the_period(route):
    meta, sched, cluster = _cluster(**ROUTES[route])
    server, client = _served(sched, cluster)
    stop = threading.Event()
    rounds = []

    def traffic():
        side = CtldClient(server.address)
        while not stop.is_set():
            side.query_jobs(user="alice", limit=10)
            side.submit(_pbspec(cpu=0.5, runtime=0.05))
            side.query_stats()
            rounds.append(1)

    thread = threading.Thread(target=traffic)
    thread.start()
    try:
        for wave in range(3):
            reply = client.submit_many(
                [_pbspec(cpu=12.0, runtime=0.1)] * 6)
            assert all(r.job_id for r in reply.replies)
            assert _wait(lambda: len(_closed_rows(sched, route)) > wave
                         and len(rounds) > 2 * wave, timeout=60.0)
        rows = _closed_rows(sched)
    finally:
        stop.set()
        thread.join(10.0)
        server.stop()
    assert len([r for r in rows if r["solver"] == route]) >= 3
    for row in rows:
        _assert_identity(row)
    # the side thread's single submits keep candidates coming, so most
    # periods ring a row and carry what ended in them
    for name in ("query", "submit", "submit_batch", "stats"):
        assert _total(rows, f"rpc_{name}_n") >= 1, name
        assert _total(rows, f"rpc_{name}_held_ms") > 0.0


def test_lock_time_sums_to_the_period_on_a_skip_row():
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    try:
        # a full cluster and one job more: the no-op fingerprint arms and
        # the ticks that follow short-circuit into ONE row
        reply = client.submit_many([_pbspec(cpu=16.0)] * 9)
        assert all(r.job_id for r in reply.replies)
        assert _wait(lambda: any(r.get("skips", 0) >= 2
                                 for r in _closed_rows(sched, "skip")))
        # a classed hold that ends in a skipped cycle's period shows on
        # the coalesced row for that period, and is gone with the next
        _classed_hold(server, LockLedger.QUERY, 0.02)
        seen = []

        def grab():
            seen.extend(dict(r) for r in _closed_rows(sched, "skip")
                        if r.get("rpc_query_n"))
            return bool(seen)

        assert _wait(grab)
        skips = seen[0]["skips"]
        assert _wait(lambda: _closed_rows(sched, "skip")[-1]["skips"]
                     > skips + 1)
        row = dict(_closed_rows(sched, "skip")[-1])
    finally:
        server.stop()
    assert seen[0]["solver"] == "skip"
    assert seen[0]["rpc_query_held_ms"] >= 20.0
    assert seen[0]["lock_held_rpc_ms"] == seen[0]["rpc_query_held_ms"]
    _assert_identity(seen[0])
    # the row carries its LATEST period only
    assert not any(k.startswith("rpc_") for k in row)
    assert row["lock_held_rpc_ms"] == 0.0
    _assert_identity(row)


def test_an_unclassed_site_lands_in_unaccounted():
    sched, cluster, server, client = _busy_served()
    try:
        last = _closed_rows(sched)[-1]["now"]
        # a site with no class (any of the ~40 other handlers): the bare lock
        # for longer than a tick, so the cycle thread comes to wait for it
        with server._lock:
            time.sleep(3 * HOLD_S)
        assert len(client.query_cluster().nodes) == 8      # and a real one
        assert _wait(lambda: _closed_rows(sched)[-1]["now"] > last + 0.2)
        rows = [r for r in _closed_rows(sched) if r["now"] > last]
    finally:
        server.stop()
    for row in rows:
        _assert_identity(row)
    assert _total(rows, "lock_held_rpc_ms") == 0.0
    # nobody named the holder: the cycle waited behind "", and while it
    # waited the lock was neither its own nor a class's
    waited = max(rows, key=lambda r: r["lock_wait_max_ms"])
    assert waited["lock_wait_max_ms"] >= 1.5 * HOLD_S * 1e3
    assert waited["lock_unaccounted_ms"] >= waited["lock_wait_ms"]
    assert all(r["lock_wait_max_behind"] == "" for r in rows)


# ---------------------------------------------------------------------------
# a handler that raises or returns inside its hold
# ---------------------------------------------------------------------------

def _boom(*a, **kw):
    raise RuntimeError("boom")


def _raising_query(server, sched, client):
    server._job_snapshot = _boom
    with pytest.raises(Exception):
        client.query_jobs(user="alice")
    return "query"


def _raising_stream(server, sched, client):
    server._job_snapshot = _boom
    with pytest.raises(Exception):
        list(client.query_jobs_stream(user="alice"))
    return "query"


def _raising_submit(server, sched, client):
    sched.submit = _boom
    with pytest.raises(Exception):
        client.submit(_pbspec())
    del sched.submit
    return "submit"


def _raising_batch(server, sched, client):
    sched.submit = _boom
    with pytest.raises(Exception):
        client.submit_many([_pbspec()] * 3)
    del sched.submit
    return "submit_batch"


def _raising_stats(server, sched, client):
    sched.flight.report = _boom
    with pytest.raises(Exception):
        client.query_stats()
    return "stats"


def _returning_query(server, sched, client):
    # QueryJobsInfo builds its reply and returns INSIDE its `with`
    assert len(client.query_jobs(user="alice").jobs) == 0
    return "query"


def _returning_snapshot(server, sched, client):
    # snap_once returns 0 inside its hold when nothing is new
    from cranesched_tpu.ha import Snapshotter

    class _Wal:
        durable_seq = 0

    snapper = Snapshotter(sched, _Wal(), server._lock, "unused",
                          min_records=1)
    assert snapper.snap_once() == 0
    return "snapshot"


LEAVERS = {f.__name__.lstrip("_"): f for f in (
    _raising_query, _raising_stream, _raising_submit, _raising_batch,
    _raising_stats, _returning_query, _returning_snapshot)}


@pytest.mark.parametrize("how", sorted(LEAVERS))
def test_a_hold_left_early_is_still_booked_and_the_tag_cleared(how):
    sched, cluster, server, client = _busy_served()
    try:
        last = _closed_rows(sched)[-1]["now"]
        name = LEAVERS[how](server, sched, client)
        assert sched.lock_ledger.holder == ""
        assert sched.lock_ledger._span is None
        assert _wait(lambda: _closed_rows(sched)[-1]["now"] > last + 0.2)
        rows = [r for r in _closed_rows(sched) if r["now"] >= last]
    finally:
        server.stop()
    assert _total(rows, f"rpc_{name}_n") == 1
    assert _total(rows, f"rpc_{name}_held_ms") > 0.0
    for row in rows:
        _assert_identity(row)


# ---------------------------------------------------------------------------
# the sites, one by one
# ---------------------------------------------------------------------------

def test_server_lock_is_still_the_bare_lock_and_the_ledger_no_wrapper():
    meta, sched, cluster = _cluster()
    server = CtldServer(sched, sim=cluster)
    assert type(server._lock) is type(threading.Lock())
    ledger = sched.lock_ledger
    assert sched.cycle_clock.ledger is ledger
    assert sched.flight.lock_ledger is ledger
    # nothing of the ledger stands between a thread and acquire: it has
    # no lock, and is no context manager
    assert not hasattr(ledger, "__enter__")
    assert not hasattr(ledger, "acquire")
    assert not any(isinstance(v, type(threading.Lock()))
                   for v in vars(ledger).values())


def test_query_stream_books_one_take_per_chunk(monkeypatch):
    monkeypatch.setattr(CtldServer, "QUERY_CHUNK", 2)
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    try:
        reply = client.submit_many([_pbspec(cpu=0.5, runtime=60.0)] * 5)
        assert all(r.job_id for r in reply.replies)
        assert _wait(lambda: len(sched.running) == 5)
        assert len(list(client.query_jobs_stream(user="alice"))) == 5
        # the snapshot's take and one for each chunk of 2, 2, 1
        assert _wait(lambda: _total(_closed_rows(sched),
                                    "rpc_query_n") == 4)
        rows = _closed_rows(sched)
    finally:
        server.stop()
    assert _total(rows, "rpc_query_snapshot_ms") > 0.0
    assert _total(rows, "rpc_query_convert_ms") > 0.0
    assert _total(rows, "rpc_query_snapshot_ms") \
        + _total(rows, "rpc_query_convert_ms") \
        <= _total(rows, "rpc_query_held_ms") + 0.01


def test_query_info_splits_its_hold_into_snapshot_and_convert():
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    inner = server._job_snapshot

    def slow_snapshot(request):
        time.sleep(0.02)
        return inner(request)

    server._job_snapshot = slow_snapshot
    try:
        assert client.submit(_pbspec(runtime=60.0)).job_id > 0
        assert len(client.query_jobs(user="alice", limit=500).jobs) == 1
        assert _wait(lambda: _total(_closed_rows(sched), "rpc_query_n") == 1)
        rows = _closed_rows(sched)
    finally:
        server.stop()
    snapshot = _total(rows, "rpc_query_snapshot_ms")
    convert = _total(rows, "rpc_query_convert_ms")
    held = _total(rows, "rpc_query_held_ms")
    assert snapshot >= 20.0 and 0.0 < convert < 20.0
    assert snapshot + convert <= held + 0.01
    assert held - snapshot - convert < 5.0


def test_a_user_query_with_a_limit_scans_no_more_than_it_returns():
    """``rpc_query_scanned``: the ``Job`` objects ``_job_snapshot``
    touched.  Over a 2,000-job queue a ``user`` + ``limit`` query looks up
    ``limit + 1`` of them; the read that names no source walks them all."""
    meta, sched, cluster = _cluster(backfill=False)
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         tick_mode=True)        # no cycle: nobody drains
    client = CtldClient(f"127.0.0.1:{port}")
    try:
        for user, n in (("alice", 1200), ("bob", 800)):
            reply = client.submit_many([_pbspec(user=user)] * n)
            assert all(r.job_id for r in reply.replies)
        ledger = sched.lock_ledger
        ledger.drain(_clock_fields())
        reply = client.query_jobs(user="alice", limit=500)
        assert len(reply.jobs) == 500 and reply.truncated
        fields = ledger.drain(_clock_fields())
        assert fields["rpc_query_n"] == 1
        assert fields["rpc_query_scanned"] == 501
        assert type(fields["rpc_query_scanned"]) is int
        # a page further on starts at its cursor, not at the user's first
        last = reply.jobs[-1].job_id
        assert len(client.query_jobs(user="alice", limit=500,
                                     after_job_id=last).jobs) == 500
        assert ledger.drain(_clock_fields())["rpc_query_scanned"] == 501
        assert len(client.query_jobs(user="alice").jobs) == 1200
        assert ledger.drain(_clock_fields())["rpc_query_scanned"] == 1200
        assert len(client.query_jobs(job_ids=[5, 7, 99999]).jobs) == 2
        assert ledger.drain(_clock_fields())["rpc_query_scanned"] == 2
        assert len(client.query_jobs(limit=500).jobs) == 500
        assert ledger.drain(_clock_fields())["rpc_query_scanned"] == 2000
        # a period with no query writes no count
        assert "rpc_query_scanned" not in ledger.drain(_clock_fields())
    finally:
        client.close()
        server.stop()


def _slow_fsync(monkeypatch, seconds):
    import cranesched_tpu.ctld.wal as wal_mod

    calls = []
    real = wal_mod.os.fsync

    def fsync(fd):
        calls.append(threading.get_ident())
        time.sleep(seconds)
        return real(fd)

    monkeypatch.setattr(wal_mod.os, "fsync", fsync)
    return calls


def test_the_wal_part_of_a_grouped_hold_is_one_fsync(tmp_path, monkeypatch):
    from cranesched_tpu.ctld.wal import WriteAheadLog

    wal = WriteAheadLog(str(tmp_path / "ctld.wal"))
    meta, sched, cluster = _cluster(wal=wal, backfill=False)
    server, client = _served(sched, cluster)
    fsync_s = 0.015
    calls = _slow_fsync(monkeypatch, fsync_s)
    try:
        before = wal.fsync_seconds
        reply = client.submit_many([_pbspec(cpu=0.5, runtime=60.0)] * 5)
        assert all(r.job_id for r in reply.replies)
        assert _wait(lambda: _total(_closed_rows(sched),
                                    "rpc_submit_batch_n") == 1)
        assert client.submit(_pbspec(cpu=0.5, runtime=60.0)).job_id > 0
        assert _wait(lambda: _total(_closed_rows(sched),
                                    "rpc_submit_n") == 1)
        rows = _closed_rows(sched)
    finally:
        server.stop()
        wal.close()
    # five specs, one hold, one group, ONE fsync inside the hold: a second
    # would read twice the sleep
    batch_wal = _total(rows, "rpc_submit_batch_wal_ms")
    assert fsync_s * 1e3 <= batch_wal < 2 * fsync_s * 1e3
    assert _total(rows, "rpc_submit_batch_held_ms") >= batch_wal
    # a single submit: one record, one fsync
    single_wal = _total(rows, "rpc_submit_wal_ms")
    assert fsync_s * 1e3 <= single_wal < 2 * fsync_s * 1e3
    # the WAL's own total holds the cycle's fsyncs too
    assert wal.fsync_seconds - before >= (batch_wal + single_wal) / 1e3
    assert wal.fsync_seconds - before >= len(calls) * fsync_s
    # no cycle compiled: the door was two reads
    assert _total(rows, "rpc_submit_batch_door_ms") < 1.0


def test_the_door_is_booked_beside_the_batchs_first_hold():
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)

    def door():
        time.sleep(0.03)

    sched.wait_out_compiling_cycle = door
    try:
        # 40 specs: two holds, one wait at the door
        reply = client.submit_many([_pbspec(cpu=0.1, runtime=60.0)] * 40)
        assert all(r.job_id for r in reply.replies)
        assert _wait(lambda: _total(_closed_rows(sched),
                                    "rpc_submit_batch_n") == 2)
        rows = _closed_rows(sched)
    finally:
        server.stop()
    assert 30.0 <= _total(rows, "rpc_submit_batch_door_ms") < 60.0
    # outside any hold: no part of what the lock was held for
    assert _total(rows, "rpc_submit_batch_held_ms") < 30.0


def test_stats_and_snapshot_takes_are_booked_under_their_classes(tmp_path):
    from cranesched_tpu.ctld.wal import WriteAheadLog
    from cranesched_tpu.ha import Snapshotter

    path = str(tmp_path / "ctld.wal")
    wal = WriteAheadLog(path)
    meta, sched, cluster = _cluster(wal=wal, backfill=False)
    server, client = _served(sched, cluster)
    try:
        assert client.submit(_pbspec(runtime=60.0)).job_id > 0
        snapper = Snapshotter(sched, wal, server._lock, path,
                              interval=3600.0)
        assert snapper.snap_once() > 0
        assert client.query_stats().json
        assert _wait(lambda: _total(_closed_rows(sched), "rpc_stats_n")
                     and _total(_closed_rows(sched), "rpc_snapshot_n"))
        rows = _closed_rows(sched)
    finally:
        server.stop()
        wal.close()
    assert _total(rows, "rpc_snapshot_n") == 1
    assert _total(rows, "rpc_snapshot_held_ms") > 0.0
    assert _total(rows, "rpc_stats_n") == 1
    assert _total(rows, "rpc_stats_held_ms") > 0.0


# ---------------------------------------------------------------------------
# the profiler: crane:rpc:* only inside a capture, on the handlers' threads
# ---------------------------------------------------------------------------

class _Span:
    built = []

    def __init__(self, name):
        self.name = name
        self.open = False
        self.thread = threading.get_ident()
        _Span.built.append(self)

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False


def _rpc_spans():
    return [s for s in _Span.built if s.name.startswith("crane:rpc:")]


def test_holds_are_annotated_only_inside_a_capture(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Span)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    _Span.built = []
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    try:
        assert client.submit(_pbspec(runtime=0.1)).job_id > 0
        client.query_jobs(user="alice")
        client.query_stats()
        assert _wait(lambda: len(_closed_rows(sched, "native")) >= 1)
        # outside a capture: not one profiler object from the ledger
        assert _rpc_spans() == []
        assert not sched.lock_ledger.annotate

        assert sched.profiler_window.request(4, out_dir=str(tmp_path))[0]
        for _ in range(60):
            if sched.profiler_window.captures_done:
                break
            assert client.submit(_pbspec(runtime=0.05)).job_id > 0
            client.query_jobs(user="alice")
            client.submit_many([_pbspec(runtime=0.05)] * 2)
            time.sleep(0.03)
        assert sched.profiler_window.captures_done == 1
        # the cycle after the stopping tick's clears the flag; none after
        for _ in range(60):
            if not sched.lock_ledger.annotate:
                break
            assert client.submit(_pbspec(runtime=0.05)).job_id > 0
            time.sleep(0.03)
        assert not sched.lock_ledger.annotate
        ended_with = len(_rpc_spans())
        assert client.submit(_pbspec(runtime=0.05)).job_id > 0
        client.query_jobs(user="alice")
        cycle_thread = sched._cycle_thread
    finally:
        server.stop()
    spans = _rpc_spans()
    assert len(spans) == ended_with
    assert not any(s.open for s in spans)
    assert sched.lock_ledger._span is None
    assert {s.name for s in spans} >= {
        "crane:rpc:submit", "crane:rpc:query", "crane:rpc:submit_batch"}
    # on the handlers' threads, beside the cycle thread's crane:cycle:*
    assert all(s.thread != cycle_thread for s in spans)
    assert any(s.name.startswith("crane:cycle:") and s.thread == cycle_thread
               for s in _Span.built)


# ---------------------------------------------------------------------------
# the operator's view
# ---------------------------------------------------------------------------

def test_cstats_cycles_shows_rpc_held_and_behind(capsys):
    from cranesched_tpu.cli import main as cli_main

    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    try:
        assert client.submit(_pbspec()).job_id > 0
        assert _wait(lambda: _closed_rows(sched, "native"))
        row = _closed_rows(sched, "native")[0]
        assert cli_main(["--server", server.address,
                         "cstats", "--cycles"]) == 0
        lines = capsys.readouterr().out.splitlines()
    finally:
        server.stop()
    header = lines[0].split()
    at = header.index("LOCK_WAIT_MS")
    assert header[at + 1:at + 4] == ["BEHIND", "RPC_HELD_MS", "PERIOD_MS"]
    shown = [ln.split() for ln in lines[1:] if ln.split()[1] == "native"]
    assert float(shown[0][header.index("RPC_HELD_MS")]) \
        == row["lock_held_rpc_ms"]
    assert shown[0][header.index("BEHIND")] \
        == (row["lock_wait_max_behind"] or "-")
