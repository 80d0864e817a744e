"""What a batch submit may cost in WAL barriers (ISSUE 36).

``SubmitBatchJobs`` inserts its specs in chunks of 32, one lock hold a
chunk, and every lock hold is ONE WAL group: the chunk's ``submit``
records leave in one write with one fsync before the lock is released
and before any reply of the RPC.  So a batch of 250 pays 8 fsyncs where
it paid 250, no group is ever open while the lock is free, and what was
acknowledged is on disk when the process dies.  ``SubmitBatchJob`` (one
spec) is one record and one fsync, as ever.  Counts, exact on any
platform, against a real fsyncing WAL."""

import itertools
import os
import signal
import subprocess
import sys
import textwrap
import threading

import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import JobScheduler, MetaContainer, SchedulerConfig
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.rpc import crane_pb2 as pb
from cranesched_tpu.rpc.server import CtldServer

NODES = 8
CHUNK = 32          # rpc/server.py SubmitBatchJobs


def _meta():
    meta = MetaContainer()
    for i in range(NODES):
        meta.add_node(f"cn{i}", meta.layout.encode(
            cpu=16.0, mem_bytes=64 << 30, memsw_bytes=64 << 30,
            is_capacity=True))
        meta.craned_up(i)
    return meta


def _spec(**kw):
    return pb.JobSpec(res=pb.ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                          memsw_bytes=1 << 30),
                      time_limit=600, sim_runtime=30.0, **kw)


def _request(count, bad=()):
    """``count`` specs; those at the positions of ``bad`` name a partition
    the cluster lacks, which ``submit`` rejects (job id 0, no record)."""
    return pb.SubmitJobsRequest(specs=[
        _spec(partition="nowhere") if i in bad else _spec()
        for i in range(count)])


def _chunks(count):
    return -(-count // CHUNK)


class WatchedLock:
    """The server's lock, the test's own: it looks at the WAL whenever the
    lock changes hands, which is what the next taker (the cycle thread, a
    query, the snapshotter, an HA follower's fetch) would find."""

    def __init__(self, wal):
        self._inner = threading.Lock()
        self.wal = wal
        self.seen = []      # (edge, seq, durable_seq, group_open)

    def _look(self, edge):
        wal = self.wal
        self.seen.append((edge, wal.seq, wal.durable_seq, wal.group_open))

    def whole(self):
        """At every edge so far: no group open, nothing buffered, and
        everything appended durable."""
        return all(not is_open and durable == seq
                   for _, seq, durable, is_open in self.seen)

    def __enter__(self):
        self._inner.acquire()
        self._look("taken")
        return self

    def __exit__(self, *exc):
        self._look("freed")
        self._inner.release()


@pytest.fixture()
def ctld(tmp_path):
    """A server over a real fsyncing WAL, not started: the handlers are
    called as gRPC would call them, on the test's thread."""
    wal = WriteAheadLog(str(tmp_path / "ctld.wal"))
    assert wal.fsync
    sched = JobScheduler(_meta(), SchedulerConfig(backfill=False), wal=wal)
    sim = SimCluster(sched)
    sim.wire(sched)
    server = CtldServer(sched, sim=sim, tick_mode=True)
    server._lock = WatchedLock(wal)
    yield server, sched, wal
    wal.close()


# ---------------------------------------------------------------------------
# (a) the counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("count", [250, 64, 33, 32, 1, 0])
def test_a_batch_pays_one_fsync_a_chunk(ctld, count):
    server, _, wal = ctld
    reply = server.SubmitBatchJobs(_request(count), None)
    assert [r.job_id for r in reply.replies] == list(range(1, count + 1))
    assert wal.fsync_total == _chunks(count)
    assert wal.groups_total == _chunks(count)
    assert wal.seq == wal.durable_seq == count
    assert len(WriteAheadLog.replay(wal.path)) == count


def test_a_single_submit_pays_one_fsync_and_no_group(ctld):
    server, _, wal = ctld
    for n in (1, 2, 3):
        reply = server.SubmitBatchJob(
            pb.SubmitJobRequest(spec=_spec()), None)
        assert reply.job_id == n
        assert (wal.fsync_total, wal.groups_total) == (n, 0)
        assert wal.durable_seq == wal.seq == n


def test_a_chunk_that_logs_nothing_pays_nothing(ctld):
    """Every spec of the second chunk is rejected: its group is empty, and
    an empty group is no write and no barrier."""
    server, _, wal = ctld
    reply = server.SubmitBatchJobs(
        _request(3 * CHUNK, bad=range(CHUNK, 2 * CHUNK)), None)
    assert sum(1 for r in reply.replies if r.job_id) == 2 * CHUNK
    assert (wal.fsync_total, wal.groups_total) == (2, 2)


def test_a_server_without_a_wal_ingests_as_before():
    sched = JobScheduler(_meta(), SchedulerConfig(backfill=False))
    assert sched.wal is None
    server = CtldServer(sched, tick_mode=True)
    reply = server.SubmitBatchJobs(_request(70, bad={40}), None)
    assert [r.job_id for r in reply.replies] == (
        list(range(1, 41)) + [0] + list(range(41, 70)))
    assert len(sched.pending) == 69


# ---------------------------------------------------------------------------
# (b) nothing open, nothing buffered, whenever the lock is free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("count", [250, 33])
def test_no_group_is_open_while_the_lock_is_free(ctld, count):
    server, _, wal = ctld
    server.SubmitBatchJobs(_request(count), None)
    seen = server._lock.seen
    holds = _chunks(count)
    assert [edge for edge, *_ in seen] == ["taken", "freed"] * holds
    # at either edge of a hold the log is whole: the group opens after
    # the lock is taken and closes, flushed, before it is given up
    assert server._lock.whole(), seen
    # the lock did go between the chunks: each hold ended one chunk on
    assert [seq for edge, seq, *_ in seen if edge == "freed"] == (
        [min(CHUNK * (k + 1), count) for k in range(holds)])


# ---------------------------------------------------------------------------
# (d) a rejected spec, or one that raises, in the middle of a chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fault", ["rejected", "raises"])
def test_a_fault_inside_a_chunk_leaves_its_mates_durable(ctld, fault,
                                                         monkeypatch):
    server, sched, wal = ctld
    at = CHUNK + 10                 # the 11th spec of the second chunk
    if fault == "rejected":
        reply = server.SubmitBatchJobs(_request(3 * CHUNK, bad={at}), None)
        ids = [r.job_id for r in reply.replies]
        assert ids[at] == 0 and reply.replies[at].error == "rejected"
        logged = 3 * CHUNK - 1
        assert sorted(i for i in ids if i) == list(range(1, logged + 1))
        assert (wal.fsync_total, wal.groups_total) == (3, 3)
    else:
        submit, calls = sched.submit, itertools.count()

        def failing(spec, now):
            if next(calls) == at:
                raise RuntimeError("a submit hook fell over")
            return submit(spec, now=now)

        monkeypatch.setattr(sched, "submit", failing)
        with pytest.raises(RuntimeError):
            server.SubmitBatchJobs(_request(3 * CHUNK), None)
        # the first chunk and the ten before the fault: two groups, and
        # the third chunk was never begun
        logged = at
        assert (wal.fsync_total, wal.groups_total) == (2, 2)
    assert not wal.group_open
    assert wal.durable_seq == wal.seq == logged
    assert server._lock.whole()
    # on disk, without a close: what a recovery after a kill would read
    assert sorted(WriteAheadLog.replay(wal.path)) == list(
        range(1, logged + 1))
    # and the next submit is a barrier of its own again
    monkeypatch.undo()
    assert server.SubmitBatchJob(
        pb.SubmitJobRequest(spec=_spec()), None).job_id == logged + 1
    assert wal.durable_seq == wal.seq == logged + 1


# ---------------------------------------------------------------------------
# (e) a cycle that runs while a batch is being ingested
# ---------------------------------------------------------------------------

CYCLE_ROUTES = {
    # route -> (config, the cycle's own WAL groups)
    "immediate": (dict(backfill=False), 1),
    "backfill-split": (dict(backfill=True, backfill_max_jobs=2), 2),
}


@pytest.mark.parametrize("route", sorted(CYCLE_ROUTES))
def test_a_cycle_beside_a_batch_still_pays_one_fsync_a_group(tmp_path,
                                                             route):
    """The cycle as ``_cycle_once`` runs it, lock-held phases and
    lock-released closures, with a batch of 70 (three chunks) ingested
    wherever the cycle has released the lock."""
    config, own_groups = CYCLE_ROUTES[route]
    wal = WriteAheadLog(str(tmp_path / "ctld.wal"))
    sched = JobScheduler(_meta(), SchedulerConfig(**config), wal=wal)
    sched.dispatch = lambda *a, **kw: None
    server = CtldServer(sched, tick_mode=True)
    lock = server._lock = WatchedLock(wal)
    server.SubmitBatchJobs(_request(6), None)
    base = (wal.fsync_total, wal.groups_total)
    assert base == (1, 1)

    batches = 0
    with lock:
        gen = sched.cycle_phases(1.0)
        fn = next(gen)
    while True:
        # the lock is free: a handler thread gets its turn
        server.SubmitBatchJobs(_request(70), None)
        batches += 1
        result = fn()
        with lock:
            try:
                fn = gen.send(result)
            except StopIteration as stop:
                started = stop.value
                break

    assert sorted(started) == [1, 2, 3, 4, 5, 6]
    row = sched.cycle_trace.snapshot()[-1]
    # no barrier outside a group, the handler's included ...
    assert row["wal_fsyncs"] == row["wal_groups"]
    assert (wal.fsync_total - base[0]) == (wal.groups_total - base[1]) == (
        own_groups + 3 * batches)
    # ... the row counts the handlers' that ran before its record
    assert own_groups + 3 <= row["wal_groups"] <= own_groups + 3 * batches
    # ... and whoever took the lock, cycle or handler, found the log whole
    assert lock.whole()
    assert len(sched.pending) == 70 * batches
    wal.close()


# ---------------------------------------------------------------------------
# (c) the crash drill: SIGKILL after the reply, then read the file
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import os, signal, sys
    sys.path.insert(0, {root!r})
    from tests.test_ingest_wal_group import _meta, _request, _spec
    from cranesched_tpu.ctld import JobScheduler, SchedulerConfig
    from cranesched_tpu.ctld.wal import WriteAheadLog
    from cranesched_tpu.rpc import CtldClient, serve

    wal = WriteAheadLog({path!r})
    sched = JobScheduler(_meta(), SchedulerConfig(backfill=False), wal=wal)
    server, port = serve(sched, tick_mode=True)
    client = CtldClient(f"127.0.0.1:{{port}}")
    acked = []
    for count in {batches!r}:
        if count == 1:
            acked.append(client.submit(_spec()).job_id)
        else:
            acked += [r.job_id
                      for r in client.submit_many(_request(count).specs).replies]
    # the acknowledgements (and the barriers they cost, counted on the
    # server's side of gRPC) are out of the process before it dies
    os.write(1, (" ".join(map(str, acked)) + "\\n"
                 + f"{{wal.fsync_total}} {{wal.groups_total}}\\n").encode())
    os.kill(os.getpid(), signal.SIGKILL)
""")


@pytest.mark.parametrize("batches", [(250,), (33, 1, 70)],
                         ids=["one_batch_of_250", "batches_and_a_single"])
def test_what_was_acknowledged_survives_a_kill(tmp_path, batches):
    path = str(tmp_path / "ctld.wal")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.run(
        [sys.executable, "-c",
         _CHILD.format(root=root, path=path, batches=batches)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert child.returncode == -signal.SIGKILL, child.stderr[-2000:]
    ids, counts = child.stdout.splitlines()
    acked = [int(tok) for tok in ids.split()]
    assert acked == list(range(1, sum(batches) + 1))
    # over the wire too: a barrier a chunk, and one for a single submit
    singles = sum(1 for n in batches if n == 1)
    groups = sum(_chunks(n) for n in batches if n > 1)
    assert [int(tok) for tok in counts.split()] == [groups + singles, groups]
    state = WriteAheadLog.replay(path)
    assert sorted(state) == acked
    assert {ev for ev, _ in state.values()} == {"submit"}


# ---------------------------------------------------------------------------
# the same under threads: handlers, the cycle thread and a reader, racing
# ---------------------------------------------------------------------------

def test_racing_batches_cycles_and_readers_never_see_an_open_group(tmp_path):
    """Four handler threads push batches beside the real cycle thread and
    a reader for a second and a half, switching every 100 µs.  Whoever takes
    the lock finds the log whole; every fsync of the run was a group's;
    every acknowledged job is on disk."""
    wal = WriteAheadLog(str(tmp_path / "ctld.wal"))
    sched = JobScheduler(_meta(), SchedulerConfig(backfill=False), wal=wal)
    sim = SimCluster(sched)
    sim.wire(sched)
    server = CtldServer(sched, sim=sim, cycle_interval=0.01)
    lock = server._lock = WatchedLock(wal)
    stop = threading.Event()
    acked, errors = [], []

    def pusher():
        try:
            while not stop.is_set():
                reply = server.SubmitBatchJobs(_request(70), None)
                acked.extend(r.job_id for r in reply.replies)
        except Exception as exc:                # read below, on the test's thread
            errors.append(exc)

    def reader():
        while not stop.is_set():
            with lock:
                pass

    workers = [threading.Thread(target=pusher) for _ in range(4)]
    workers.append(threading.Thread(target=reader))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        server.start("127.0.0.1:0")
        for w in workers:
            w.start()
        stop.wait(1.5)
        stop.set()
        for w in workers:
            w.join(30.0)
        alive = [w for w in workers if w.is_alive()]
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        server.stop()
    assert not alive and not errors, (alive, errors)
    assert sched.stats.get("cycle_crashes_total", 0) == 0
    assert len(lock.seen) > 4 * len(workers)
    assert lock.whole()
    assert wal.fsync_total == wal.groups_total > 0
    assert all(acked) and len(set(acked)) == len(acked) >= 70
    assert set(acked) <= set(WriteAheadLog.replay(wal.path))
    wal.close()
