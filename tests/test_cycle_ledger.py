"""The cycle thread accounts for its whole period (ISSUE 26).

``CycleClock`` (obs/trace.py) partitions the cycle thread's timeline:
the server's loop marks the sleep and every wait for the server lock,
the scheduler marks the phases of the prelude, the solve closures and
the commit, and ``cycle_phases``' last act writes the parts into the row
the cycle ringed.  These tests drive a real ``CtldServer`` over the sim
plane and hold the ledger to its contract: the parts sum to the period
on every route, a wait for the lock is booked as a wait (and still sits
in the old remainder ``commit_ms``), the lock itself stays a plain
``threading.Lock``, the profiler is touched only inside a capture and
starts without its Python tracer, and the snapshotter times its hold."""

import gc
import statistics
import threading
import time

import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.obs import REGISTRY
from cranesched_tpu.obs.introspect import ProfilerWindow
from cranesched_tpu.obs.trace import (
    GC_PAUSES,
    LOCKED_PARTS,
    PARTS,
    CycleClock,
)
from cranesched_tpu.rpc import crane_pb2 as pb
from cranesched_tpu.rpc.client import CtldClient
from cranesched_tpu.rpc.server import CtldServer, serve

pytestmark = pytest.mark.obs

SOLVE_DELAY = 0.05


def _cluster(wal=None, **config):
    meta = MetaContainer()
    for i in range(8):
        meta.add_node(
            f"cn{i:02d}",
            meta.layout.encode(cpu=16, mem_bytes=32 << 30,
                               memsw_bytes=32 << 30, is_capacity=True),
            partitions=("default",))
        meta.craned_up(i)
    # an idle loop that still ticks (the sim plane's completions land in
    # a cycle), through _sleep_interval's own take of the lock
    config.setdefault("cycle_idle_sleep", 0.06)
    sched = JobScheduler(meta, SchedulerConfig(**config), wal=wal)
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    return meta, sched, cluster


def _slow_solve(sched, entered=None, until=None, left=None):
    """A sleep INSIDE the immediate solve, i.e. inside a yielded closure:
    cycles long enough that the loop's glue is a small share of them.
    ``entered`` is set as the solve begins; it does not end before
    ``until`` is set, and sets ``left`` as it ends."""
    inner = sched._immediate_solve

    def slow(*a, **kw):
        if entered is not None:
            entered.set()
        time.sleep(SOLVE_DELAY)
        try:
            return inner(*a, **kw)
        finally:
            if until is not None:
                until.wait(10.0)
            if left is not None:
                left.set()

    sched._immediate_solve = slow


def _pbspec(cpu=1.0, runtime=30.0):
    return pb.JobSpec(
        res=pb.ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                            memsw_bytes=1 << 30),
        time_limit=3600, partition="default", user="alice",
        sim_runtime=runtime)


def _wait(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _closed_rows(sched, solver):
    """The ring's rows of one route whose cycle has closed its ledger."""
    return [r for r in sched.cycle_trace.snapshot()
            if r["solver"] == solver and "period_ms" in r]


def _served(sched, cluster):
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         cycle_interval=0.05)
    server.address = f"127.0.0.1:{port}"
    return server, CtldClient(server.address)


# ---------------------------------------------------------------------------
# the clock alone
# ---------------------------------------------------------------------------

class _FakeTime:
    """``time`` as obs/trace.py sees it, with a ``perf_counter`` the test
    moves: the clock's arithmetic is held to exact numbers, not to what
    a host under six xdist workers makes of a 1 ms sleep."""

    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds

    def __getattr__(self, name):
        return getattr(time, name)


def test_clock_partitions_exactly(monkeypatch):
    fake = _FakeTime()
    monkeypatch.setattr("cranesched_tpu.obs.trace.time", fake)
    clock = CycleClock()
    clock.close()                      # whatever construction cost
    clock.mark("sleep")
    fake.sleep(0.004)
    for wait in (0.002, 0.008, 0.001):  # a phase that recurs accumulates
        clock.mark("lock_wait")
        fake.sleep(wait)
        clock.mark("drain")
        fake.sleep(0.0005)
    clock.mark("priority")
    fake.sleep(0.003)
    clock.mark("cut")
    fake.sleep(0.00025)
    clock.mark("dispatch")
    fake.sleep(0.001)
    clock.mark(CycleClock.GLUE)
    fake.sleep(0.0002)
    fields = clock.close()
    assert set(fields) == ({p + "_ms" for p in PARTS} | {
        "period_ms", "lock_wait_max_ms", "lock_held_work_ms",
        "unnamed_ms", "gc_ms", "cpu_ms", "lock_wait_max_behind"})
    assert "cut" in LOCKED_PARTS and "cut_ms" in fields
    want = dict(sleep_ms=4.0, lock_wait_ms=11.0, drain_ms=1.5,
                priority_ms=3.0, cut_ms=0.25,
                # the longest SINGLE wait, not the sum
                lock_wait_max_ms=8.0,
                # what ran under the lock: drain + priority + cut
                lock_held_work_ms=4.75,
                # "dispatch" has no field of its own (dispatch_ms stays
                # the closure's reading) and is no part of unnamed_ms,
                # which is the glue alone
                unnamed_ms=0.2, period_ms=20.95)
    for name, value in want.items():
        assert fields[name] == pytest.approx(value, abs=1e-6), name
    named = sum(fields[p + "_ms"] for p in PARTS)
    # the partition: every instant belongs to exactly one phase
    assert named + 1.0 + fields["unnamed_ms"] == pytest.approx(
        fields["period_ms"], abs=1e-6)
    assert fields["lock_held_work_ms"] == pytest.approx(
        sum(fields[p + "_ms"] for p in LOCKED_PARTS), abs=1e-6)
    # the ledger starts afresh
    fake.sleep(0.0003)
    again = clock.close()
    assert again["lock_wait_ms"] == 0.0 and again["lock_wait_max_ms"] == 0.0
    assert again["period_ms"] == pytest.approx(0.3, abs=1e-6)
    assert again["unnamed_ms"] == pytest.approx(0.3, abs=1e-6)


def test_a_phase_no_field_names_lands_in_unnamed():
    clock = CycleClock()
    clock.close()
    clock.mark("a_typo")
    time.sleep(0.003)
    fields = clock.close()
    assert "a_typo_ms" not in fields
    assert fields["unnamed_ms"] >= 3.0


# ---------------------------------------------------------------------------
# the parts sum to the period, route by route
# ---------------------------------------------------------------------------

ROUTES = {
    # more candidates than backfill_max_jobs: timed head + immediate tail,
    # two solves and two commits a cycle
    "backfill-split": dict(backfill=True, backfill_max_jobs=2),
    "native": dict(backfill=False),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_parts_sum_to_period(route):
    meta, sched, cluster = _cluster(**ROUTES[route])
    _slow_solve(sched)
    server, client = _served(sched, cluster)
    try:
        for wave in range(4):
            # one RPC, so one cycle meets all six: more candidates than
            # the head takes, the rest go to the tail
            reply = client.submit_many(
                [_pbspec(cpu=12.0, runtime=0.1)] * 6)
            assert all(r.job_id for r in reply.replies)
            assert _wait(lambda: len(_closed_rows(sched, route)) > wave,
                         timeout=60.0)
            assert _wait(lambda: not sched.running and not sched.pending)
        rows = _closed_rows(sched, route)
    finally:
        server.stop()
    for row in rows:
        parts = sum(row[p + "_ms"] for p in PARTS) + row["dispatch_ms"]
        # dispatch_ms is the closure's own timer, the parts are the
        # clock's: they agree to the closure's few microseconds of frame
        assert parts + row["unnamed_ms"] == pytest.approx(
            row["period_ms"], abs=0.25)
        assert row["lock_held_work_ms"] == pytest.approx(
            sum(row[p + "_ms"] for p in LOCKED_PARTS), abs=0.02)
        assert row["solve_enqueue_ms"] + row["solve_device_wait_ms"] \
            + row["solve_host_ms"] == pytest.approx(row["solve_ms"], abs=0.5)
    share = statistics.median(r["unnamed_ms"] / r["period_ms"] for r in rows)
    assert share < 0.02, rows
    if route == "backfill-split":
        # the tail's min-over-horizon round trip is host time of the solve
        assert all(r["solve_host_ms"] > 0.0 for r in rows)
    assert "solve_commit_ms" not in sched.stats["last_cycle"]


def test_skip_row_carries_its_own_period():
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    try:
        # a full cluster and one job more: its cycles place nothing, the
        # no-op fingerprint arms, and the ticks that follow short-circuit
        # into ONE row
        reply = client.submit_many([_pbspec(cpu=16.0)] * 9)
        assert all(r.job_id for r in reply.replies)
        assert _wait(lambda: any(r.get("skips", 0) >= 3
                                 for r in _closed_rows(sched, "skip")))
        row = _closed_rows(sched, "skip")[-1]
    finally:
        server.stop()
    parts = sum(row[p + "_ms"] for p in PARTS) + row["unnamed_ms"]
    assert parts == pytest.approx(row["period_ms"], abs=0.05)
    # the latest skip's own period: one tick of sleep, not the sum of all
    assert 40.0 <= row["period_ms"] < 40.0 * row["skips"]
    assert row["sleep_ms"] > 0.9 * row["period_ms"]
    assert row["unnamed_ms"] < 0.02 * row["period_ms"]
    assert row["solve_enqueue_ms"] == 0.0 and row["commit_apply_ms"] == 0.0


def test_idle_loop_wakes_for_the_sim_planes_next_completion():
    """The sim plane reports a finished job only inside a cycle.  A loop
    that may idle for 30 s has to wake for the plane's next completion,
    as a real craned's status RPC would kick it: found on the chip, where
    the flood's last jobs stayed Running behind an idle loop."""
    meta, sched, cluster = _cluster(backfill=False, cycle_idle_sleep=30.0)
    server, client = _served(sched, cluster)
    try:
        job_id = client.submit(_pbspec(runtime=0.4)).job_id
        assert _wait(lambda: job_id in sched.running)
        assert _wait(lambda: job_id in sched.history, timeout=5.0)
        job = sched.history[job_id]
    finally:
        server.stop()
    assert job.end_time - job.start_time == pytest.approx(0.4, abs=0.01)


# ---------------------------------------------------------------------------
# a wait for the lock is a wait, not commit
# ---------------------------------------------------------------------------

def test_lock_wait_is_split_from_commit():
    hold_s = 0.1
    meta, sched, cluster = _cluster(backfill=False)
    entered, holding, left = (threading.Event() for _ in range(3))
    _slow_solve(sched, entered, holding, left)
    server, client = _served(sched, cluster)
    held = []

    def handler():
        # what a submit handler does to the cycle: takes the lock while
        # the solve runs with it released, and still holds it when the
        # closure ends and the cycle thread wants it back.  The solve
        # does not end before the lock is held here, and the hold ends
        # ``hold_s`` after the solve does, however long the real solve
        # took beyond its injected delay (a first call builds or loads
        # the native library) and however late this thread woke: the
        # wait is hold_s less a closure's tail
        assert entered.wait(10.0)
        with server._lock:
            t0 = time.perf_counter()
            holding.set()
            assert left.wait(10.0)
            time.sleep(hold_s)
            held.append(time.perf_counter() - t0)

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
    assert held and held[0] >= hold_s
    assert row["lock_wait_ms"] >= 45.0
    assert row["lock_wait_max_ms"] >= 45.0
    assert row["lock_wait_max_ms"] <= row["lock_wait_ms"]
    # the commit itself took what a one-job commit takes: a small part
    # of the wait it used to be booked with, whatever the machine's load
    assert row["commit_apply_ms"] < 0.25 * row["lock_wait_ms"]
    assert row["lock_held_work_ms"] < row["lock_held_ms"] - 40.0
    # commit_ms and lock_held_ms keep their old arithmetic: the remainder
    # still books the wait (accepted metrics read them)
    assert row["commit_ms"] == pytest.approx(
        row["total_ms"] - row["prelude_ms"] - row["solve_ms"], abs=0.01)
    assert row["commit_ms"] >= 45.0
    assert row["lock_held_ms"] == pytest.approx(
        row["prelude_ms"] + row["commit_ms"], abs=0.01)


def test_server_lock_stays_a_plain_lock():
    meta, sched, cluster = _cluster()
    server = CtldServer(sched, sim=cluster)
    assert type(server._lock) is type(threading.Lock())


# ---------------------------------------------------------------------------
# the profiler: touched only inside a capture, no Python tracer
# ---------------------------------------------------------------------------

class _Span:
    built = []

    def __init__(self, name):
        self.name = name
        self.open = False
        _Span.built.append(self)

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False


def _cycle_spans():
    return [s for s in _Span.built if s.name.startswith("crane:cycle:")]


def test_phases_are_annotated_only_inside_a_capture(tmp_path, monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Span)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    _Span.built = []
    meta, sched, cluster = _cluster(backfill=False)
    server, client = _served(sched, cluster)
    try:
        assert client.submit(_pbspec(runtime=0.1)).job_id > 0
        assert _wait(lambda: len(_closed_rows(sched, "native")) >= 1)
        # outside a capture: the solve's own span as ever, and not one
        # profiler object from the clock
        assert any(s.name.startswith("crane:solve:") for s in _Span.built)
        assert _cycle_spans() == []

        assert sched.profiler_window.request(2, out_dir=str(tmp_path))[0]
        for k in range(40):
            if sched.profiler_window.captures_done:
                break
            assert client.submit(_pbspec(runtime=0.05)).job_id > 0
            time.sleep(0.05)
        assert sched.profiler_window.captures_done == 1
        assert calls == ["start", "stop"]
        # the stopping tick's cycle closes the last span; none after it
        time.sleep(0.2)
        ended_with = len(_cycle_spans())
        n = len(_closed_rows(sched, "native"))
        assert client.submit(_pbspec(runtime=0.05)).job_id > 0
        assert _wait(lambda: len(_closed_rows(sched, "native")) > n)
        rows = _closed_rows(sched, "native")
    finally:
        server.stop()
    spans = _cycle_spans()
    assert len(spans) == ended_with
    assert not any(s.open for s in spans)
    assert sched.cycle_clock._span is None
    names = {s.name[len("crane:cycle:"):] for s in spans}
    # every phase a served immediate cycle passes through, the waits too
    assert names >= {"sleep", "lock_wait", "sim", "drain", "candidates",
                     "snapshot", "priority", "build", "wal",
                     "solve_enqueue", "solve_device_wait", "solve_host",
                     "commit_apply", "preempt", "record", "dispatch"}
    assert CycleClock.GLUE not in names
    profiled = [r for r in rows if r.get("profiled")]
    assert 1 <= len(profiled) <= 2
    # one span a mark: each captured cycle that solves passes 16 phases
    assert len(spans) >= 16 * len(profiled)


def test_profiler_starts_without_its_python_tracer(tmp_path, monkeypatch):
    import jax

    seen = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: seen.update(kw, dir=d))
    release = threading.Event()
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: release.wait(10.0))
    window = ProfilerWindow(base_dir=str(tmp_path))
    assert not window.capturing
    assert window.request(1, out_dir=str(tmp_path / "cap"))[0]
    window.tick()
    assert window.capturing
    options = seen["profiler_options"]
    assert options.python_tracer_level == 0
    # TraceAnnotations are the host tracer's events: it stays on
    assert options.host_tracer_level > 0
    # the stop runs on a helper thread: the cycle thread (and the server
    # lock it ticks under) does not wait for the trace to be written
    window.tick()
    assert not window.capturing
    assert not window.request(1)[0]      # taken until the file is written
    assert window.captures_done == 0
    release.set()
    assert _wait(lambda: window.captures_done == 1)
    assert window.request(1)[0]


# ---------------------------------------------------------------------------
# GC pauses and the snapshot's hold
# ---------------------------------------------------------------------------

def test_gc_accumulator_grows_across_a_collection():
    CycleClock()
    CycleClock()
    assert gc.callbacks.count(GC_PAUSES) == 1    # one hook a process
    clock = CycleClock()
    clock.close()
    before = GC_PAUSES.total_s
    junk = []
    for _ in range(20000):
        a, b = [], []
        a.append(b)
        b.append(a)
        junk.append(a)
    del junk
    gc.collect()
    assert GC_PAUSES.total_s > before
    assert clock.close()["gc_ms"] == pytest.approx(
        (GC_PAUSES.total_s - before) * 1e3, abs=0.01)


def _histogram_count(name):
    series = REGISTRY.snapshot().get(name, {}).get("values", {})
    return sum(v["count"] for v in series.values())


def test_snap_once_times_its_hold_of_the_lock(tmp_path):
    from cranesched_tpu.ctld.wal import WriteAheadLog
    from cranesched_tpu.ha import Snapshotter

    path = str(tmp_path / "ctld.wal")
    wal = WriteAheadLog(path)
    meta, sched, cluster = _cluster(wal=wal, backfill=False)
    for _ in range(3):
        sched.submit(JobSpec(res=ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                              memsw_bytes=1 << 30),
                             sim_runtime=50.0), now=0.0)
    sched.schedule_cycle(now=0.0)
    held0 = _histogram_count("crane_snapshot_lock_held_seconds")
    took0 = _histogram_count("crane_snapshot_seconds")
    lock = threading.Lock()
    snapper = Snapshotter(sched, wal, lock, path, interval=3600.0)
    assert snapper.snap_once() > 0
    assert not lock.locked()
    assert _histogram_count("crane_snapshot_lock_held_seconds") == held0 + 1
    assert _histogram_count("crane_snapshot_seconds") == took0 + 1
    events = [e for e in sched.events.since(0) if e["type"] == "snapshot"]
    assert len(events) == 1 and "lock_held=" in events[0]["detail"]
    # nothing new: skipped, and a skipped pass observes nothing
    assert snapper.snap_once() == 0
    assert _histogram_count("crane_snapshot_seconds") == took0 + 1
    wal.close()


# ---------------------------------------------------------------------------
# the operator's view
# ---------------------------------------------------------------------------

def test_cstats_cycles_shows_lock_wait_and_period(capsys):
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
    assert "LOCK_WAIT_MS" in header and "PERIOD_MS" in header
    shown = [ln.split() for ln in lines[1:] if ln.split()[1] == "native"]
    assert float(shown[0][header.index("PERIOD_MS")]) == row["period_ms"]
    assert float(shown[0][header.index("LOCK_WAIT_MS")]) \
        == row["lock_wait_ms"]
