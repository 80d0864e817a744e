"""A per-user query reads the user's jobs, not the whole queue (ISSUE 43).

``CtldServer._job_snapshot`` takes its live candidates from the narrowest
source a request names: ``job_ids`` are looked up, ``user`` reads
``JobScheduler._user_jobs`` (an index the four ``_ObservedDict`` hooks keep:
for each ``spec.user`` the ids of its jobs in ``pending`` or ``running``),
and only a request that names neither walks the queue; the walk goes in
ascending id and stops at the caller's ``limit + 1`` matches.

Two guards, over ONE churned state (submits by several users, starts,
finishes, a preemption, a ``modify_job`` to another partition, cancels, a
requeue, a held job, array children, a snapshot, a WAL tail, a restart
from snapshot + WAL with an archive behind a history cut to two rows, a
follower):

* after every step of the churn the index is exactly ``{user: ids of
  pending or running}`` with no empty entry, and recovery and a follower
  rebuild it equal;
* every filter combination, through ``QueryJobsInfo`` and
  ``QueryJobsStream`` both, answers row for row and ``truncated`` bit for
  bit what the pass it replaced answers (``testing/query_oracle``: the
  full walk, every filter over every job, the sort, then the handlers'
  cut)."""

import threading

import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    JobStatus,
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
from cranesched_tpu.ctld.archive import JobArchive
from cranesched_tpu.ctld.defs import ArraySpec
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.ha import snapshot as snapshot_mod
from cranesched_tpu.ha.follower import HaFollower
from cranesched_tpu.rpc import CtldClient, crane_pb2 as pb, serve
from cranesched_tpu.rpc.client import StreamResult
from cranesched_tpu.testing.query_oracle import reference_reply

USERS = ("alice", "bob", "carol", "dave", "erin", "frank")


def _accounts() -> AccountManager:
    mgr = AccountManager()
    mgr.users["root"] = User(name="root", admin_level=AdminLevel.ROOT)
    mgr.add_qos("root", Qos(name="low", priority=0))
    mgr.add_qos("root", Qos(name="high", priority=1000, preempt={"low"}))
    mgr.add_account("root", Account(name="hpc",
                                    allowed_qos={"low", "high"},
                                    default_qos="low"))
    for uid, name in enumerate(USERS, 1):
        mgr.add_user("root", User(name=name, uid=uid), "hpc")
    return mgr


def _build(tmp_path):
    """Four 4-cpu nodes in ``batch``, two in ``gpu``; preemption by
    requeue; WAL and archive under ``tmp_path`` (a second build over the
    same directory is the restarted daemon)."""
    meta = MetaContainer()
    for i in range(6):
        meta.add_node(
            f"cn{i:02d}",
            meta.layout.encode(cpu=4, mem_bytes=16 << 30,
                               memsw_bytes=16 << 30, is_capacity=True),
            partitions=("batch",) if i < 4 else ("gpu",))
        meta.craned_up(i)
    sched = JobScheduler(
        meta, SchedulerConfig(backfill=False, preempt_mode="requeue"),
        accounts=_accounts(),
        archive=JobArchive(str(tmp_path / "history.sqlite")))
    sim = SimCluster(sched)
    sim.wire(sched)
    return sched, sim


def _spec(user, partition="batch", cpu=2.0, runtime=1000.0, qos="low",
          **kw):
    return JobSpec(user=user, account="hpc", qos=qos, partition=partition,
                   res=ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                                    memsw_bytes=1 << 30),
                   sim_runtime=runtime, **kw)


def _live_by_user(sched) -> dict:
    want: dict = {}
    for jobs in (sched.pending, sched.running):
        for job_id, job in jobs.items():
            want.setdefault(job.spec.user, set()).add(job_id)
    return {user: sorted(ids) for user, ids in want.items()}


def _index(sched) -> dict:
    return {user: sorted(ids) for user, ids in sched._user_jobs.items()}


class _World:
    """The churned state, and what the index read after each step."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.wal_path = str(tmp_path / "wal.jsonl")
        self.sched, self.sim = _build(tmp_path)
        self.sched.wal = WriteAheadLog(self.wal_path, fsync=False)
        self.ids: dict = {}
        #: step -> (the index, what it has to be)
        self.seen: dict = {}
        self.closers: list = []

    def step(self, name, fn):
        fn(self)
        self.seen[name] = (_index(self.sched), _live_by_user(self.sched))

    def submit(self, key, spec, now):
        job_id = self.sched.submit(spec, now=now)
        assert job_id > 0, key
        self.ids[key] = job_id
        return job_id

    def close(self):
        for close in reversed(self.closers):
            close()


def _submits(w):
    for k in range(6):
        w.submit(f"a{k}", _spec("alice"), 0.0)
    for k in range(3):
        w.submit(f"b{k}", _spec("bob"), 0.0)
    w.submit("c0", _spec("carol", "gpu", cpu=1.0, runtime=5.0), 0.0)
    for k in range(2):
        w.submit(f"d{k}", _spec("dave", "gpu", cpu=4.0), 0.0)


def _starts(w):
    started = w.sched.schedule_cycle(now=1.0)
    # batch takes eight 2-cpu jobs, gpu carol's and one of dave's
    assert len(started) == 10 and w.ids["b2"] in w.sched.pending


def _held(w):
    w.submit("e0", _spec("erin", held=True), 2.0)
    w.sched.schedule_cycle(now=2.0)
    assert w.sched.pending[w.ids["e0"]].pending_reason == PendingReason.HELD


def _array(w):
    # the template stays pending; a cycle materialises one child of it
    w.submit("arr", _spec("bob", "gpu", cpu=1.0,
                          array=ArraySpec(start=0, end=2)), 3.0)
    w.sched.schedule_cycle(now=3.0)
    w.sched.schedule_cycle(now=4.0)
    children = w.sched.pending[w.ids["arr"]].array_children
    assert len(children) == 2
    assert all(w.sched.job_info(c).spec.user == "bob" for c in children)


def _finish(w):
    # carol's only job ends: her entry has to go
    w.sim.advance_to(8.0)
    w.sched.schedule_cycle(now=8.0)
    assert w.sched.history[w.ids["c0"]].status == JobStatus.COMPLETED


def _preempt(w):
    w.submit("hi", _spec("dave", cpu=4.0, qos="high"), 9.0)
    started = w.sched.schedule_cycle(now=9.0)
    assert w.ids["hi"] in started
    w.ids["victims"] = [
        i for i, j in w.sched.pending.items()
        if j.pending_reason == PendingReason.PREEMPTED]
    assert len(w.ids["victims"]) == 2


def _modify(w):
    assert w.sched.modify_job(w.ids["b2"], now=10.0, partition="gpu") == ""
    assert w.sched.pending[w.ids["b2"]].spec.partition == "gpu"


def _cancels(w):
    assert w.sched.cancel(w.ids["d1"], now=11.0)        # pending
    running = next(i for i in (w.ids[f"a{k}"] for k in range(6))
                   if i in w.sched.running)
    assert w.sched.cancel(running, now=11.0)
    w.ids["cancelled"] = running
    w.sched.schedule_cycle(now=12.0)
    assert w.sched.history[running].status == JobStatus.CANCELLED


def _requeue(w):
    running = next(i for i in (w.ids[f"b{k}"] for k in range(2))
                   if i in w.sched.running)
    assert w.sched.requeue(running, now=13.0) == ""
    assert running in w.sched.pending
    w.ids["requeued"] = running


def _snapshot(w):
    snapper = snapshot_mod.Snapshotter(
        w.sched, w.sched.wal, threading.Lock(), w.wal_path)
    assert snapper.snap_once() > 0


def _tail(w):
    """After the snapshot: what a restart reads from the WAL's tail."""
    for k in range(6, 9):
        w.submit(f"a{k}", _spec("alice", runtime=4.0), 14.0)
    w.submit("f0", _spec("frank", "gpu", cpu=1.0, runtime=4.0), 14.0)
    w.sched.schedule_cycle(now=14.0)
    w.sim.advance_to(20.0)
    w.sched.schedule_cycle(now=20.0)
    assert w.sched.history[w.ids["f0"]].status == JobStatus.COMPLETED


def _restart(w):
    """SIGKILL, as far as the program can tell: a fresh scheduler over
    the same WAL directory and archive, recovered from snapshot + tail.
    The snapshot kept two rows of history, so the archive is behind it."""
    before = _index(w.sched)
    terminal = set(w.sched.history)
    w.sched.wal.close()
    w.sched.archive.close()
    w.sched, w.sim = _build(w.tmp_path)
    for node in w.sched.meta.nodes.values():
        node.alive = True
    count, snap_seq = snapshot_mod.recover_from_snapshot(
        w.sched, WriteAheadLog, w.wal_path, now=21.0)
    assert count and snap_seq
    w.sched.wal = WriteAheadLog(w.wal_path, fsync=False)
    assert _index(w.sched) == before
    w.ids["archived_only"] = sorted(terminal - set(w.sched.history))
    assert w.ids["archived_only"]
    assert all(i in w.sched.archive for i in w.ids["archived_only"])


def _after_restart(w):
    w.submit("a9", _spec("alice", "gpu", cpu=1.0), 22.0)
    w.submit("e1", _spec("erin", cpu=1.0, runtime=3.0), 22.0)
    w.sched.schedule_cycle(now=22.0)
    assert w.sched.cancel(w.ids["e0"], now=23.0)         # the held one
    w.sched.schedule_cycle(now=23.0)


CHURN = (("submits", _submits), ("starts", _starts), ("held", _held),
         ("array", _array), ("finish", _finish), ("preempt", _preempt),
         ("modify", _modify), ("cancels", _cancels), ("requeue", _requeue),
         ("snapshot", _snapshot), ("tail", _tail), ("restart", _restart),
         ("after_restart", _after_restart))


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    patch = pytest.MonkeyPatch()
    patch.setattr(snapshot_mod, "MAX_HISTORY_JOBS", 2)
    w = _World(tmp_path_factory.mktemp("query_index"))
    try:
        for name, fn in CHURN:
            w.step(name, fn)
        w.leader, port = serve(w.sched, sim=w.sim, tick_mode=True)
        w.closers.append(w.leader.stop)
        w.client = CtldClient(f"127.0.0.1:{port}")
        w.closers.append(w.client.close)
        # a follower of that leader: snapshot pull + WAL tail
        (w.tmp_path / "standby").mkdir()
        standby_sched, _ = _build(w.tmp_path / "standby")
        w.standby, sport = serve(standby_sched, tick_mode=True,
                                 standby=True,
                                 peer_address=f"127.0.0.1:{port}")
        w.closers.append(w.standby.stop)
        w.follower = HaFollower(
            w.standby, f"127.0.0.1:{port}",
            str(w.tmp_path / "standby" / "wal.jsonl"),
            poll_interval=999.0, miss_threshold=99)
        w.standby.ha_follower = w.follower
        w.closers.append(w.follower.stop)
        assert w.follower.poll_once()
        # and what it applies from the log after the snapshot
        w.submit("a10", _spec("alice", cpu=1.0), 24.0)
        w.submit("g0", _spec("frank", "gpu", cpu=1.0), 24.0)
        w.sched.schedule_cycle(now=24.0)
        assert w.sched.cancel(w.ids["g0"], now=25.0)
        w.sched.schedule_cycle(now=25.0)
        assert w.follower.poll_once()
        w.seen["follower"] = (_index(standby_sched),
                              _live_by_user(standby_sched))
        w.seen["follower_of_leader"] = (_index(standby_sched),
                                        _index(w.sched))
        w.standby_client = CtldClient(f"127.0.0.1:{sport}")
        w.closers.append(w.standby_client.close)
        yield w
    finally:
        w.close()
        patch.undo()


# ---------------------------------------------------------------------------
# the index
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "step", [name for name, _ in CHURN] + ["follower", "follower_of_leader"])
def test_index_is_each_users_live_jobs_after(world, step):
    got, want = world.seen[step]
    assert got == want
    assert all(ids for ids in got.values())      # no empty entry
    assert want                                  # and the step left jobs


def test_index_drops_the_entry_with_the_users_last_job(world):
    assert "carol" in world.seen["starts"][0]
    assert "carol" not in world.seen["finish"][0]
    assert "frank" not in world.seen["tail"][0]
    assert world.sched.user_jobs("carol") == ()
    assert world.sched.user_jobs("nobody") == ()
    assert "nobody" not in world.sched._user_jobs    # a read opens nothing


def test_a_start_and_a_requeue_keep_the_id_through_the_move(tmp_path):
    """A start is ``del pending[id]`` THEN ``running[id] = job``, a
    requeue the reverse: in between the id is in neither dict, and for a
    user's only job the entry goes and comes back."""
    sched, sim = _build(tmp_path)
    only = sched.submit(_spec("erin"), now=0.0)
    assert _index(sched) == {"erin": [only]}
    assert sched.schedule_cycle(now=1.0) == [only]
    assert only in sched.running and _index(sched) == {"erin": [only]}
    assert sched.requeue(only, now=2.0) == ""
    assert only in sched.pending and _index(sched) == {"erin": [only]}
    assert sched.cancel(only, now=3.0)
    assert _index(sched) == {}
    # and a move made the other way round, the set before the del, which
    # no route makes today: the id is in BOTH for an instant
    moved = sched.submit(_spec("erin", held=True), now=4.0)
    job = sched.pending[moved]
    sched.running[moved] = job
    del sched.pending[moved]
    assert _index(sched) == {"erin": [moved]}
    sched.pending[moved] = job
    del sched.running[moved]
    assert _index(sched) == {"erin": [moved]}
    del sched.pending[moved]
    assert _index(sched) == {}
    sched.archive.close()


# ---------------------------------------------------------------------------
# parity with the full walk
# ---------------------------------------------------------------------------

#: symbolic values are resolved against the churned state: ``mid`` /
#: ``past`` cursors, ``all`` / ``all-1`` limits (the match count of the
#: same request without a limit, and one under it), ``mixed`` job ids (a
#: pending, a running, a finished, an archived-only and a missing one)
CASES = {
    "bare": dict(),
    "bare_limit": dict(limit=3),
    "bare_limit_all": dict(limit="all"),
    "bare_limit_under": dict(limit="all-1"),
    "bare_history": dict(history=True),
    "bare_history_limit": dict(history=True, limit=4),
    "bare_history_limit_all": dict(history=True, limit="all"),
    "bare_history_limit_under": dict(history=True, limit="all-1"),
    "cursor": dict(after="mid"),
    "cursor_history": dict(after="mid", history=True),
    "cursor_past": dict(after="past"),
    "cursor_limit": dict(after="mid", limit=3),
    "user": dict(user="alice"),
    "user_limit": dict(user="alice", limit=2),
    "user_limit_all": dict(user="alice", limit="all"),
    "user_limit_under": dict(user="alice", limit="all-1"),
    "user_limit_over": dict(user="alice", limit=500),
    "user_cursor": dict(user="alice", after="mid"),
    "user_cursor_limit": dict(user="alice", after="mid", limit=2),
    "user_cursor_past": dict(user="alice", after="past"),
    "user_cursor_past_limit": dict(user="alice", after="past", limit=2),
    "user_partition": dict(user="alice", partition="gpu"),
    "user_partition_limit": dict(user="alice", partition="gpu", limit=1),
    "user_partition_limit_all": dict(user="alice", partition="batch",
                                     limit="all"),
    "user_partition_limit_under": dict(user="alice", partition="batch",
                                       limit="all-1"),
    "user_partition_none": dict(user="erin", partition="gpu", limit=5),
    "user_history": dict(user="alice", history=True),
    "user_history_limit": dict(user="alice", history=True, limit=3),
    "user_history_limit_all": dict(user="alice", history=True,
                                   limit="all"),
    "user_history_limit_under": dict(user="alice", history=True,
                                     limit="all-1"),
    "user_history_cursor_limit": dict(user="alice", history=True,
                                      after="mid", limit=2),
    "user_history_partition": dict(user="dave", history=True,
                                   partition="batch"),
    "user_array_children": dict(user="bob"),
    "user_array_children_partition": dict(user="bob", partition="gpu",
                                          limit="all"),
    "user_last_job_finished": dict(user="carol"),
    "user_last_job_finished_limit": dict(user="carol", limit=5),
    "user_last_job_finished_history": dict(user="carol", history=True),
    "user_last_job_finished_history_limit": dict(user="carol",
                                                 history=True, limit=1),
    "user_no_jobs": dict(user="nobody"),
    "user_no_jobs_history_limit": dict(user="nobody", history=True,
                                       limit=5),
    "partition": dict(partition="gpu"),
    "partition_limit": dict(partition="gpu", limit=2),
    "partition_history_limit_under": dict(partition="gpu", history=True,
                                          limit="all-1"),
    "ids": dict(job_ids="mixed"),
    "ids_history": dict(job_ids="mixed", history=True),
    "ids_history_limit": dict(job_ids="mixed", history=True, limit=2),
    "ids_history_cursor": dict(job_ids="mixed", history=True,
                               after="mid"),
    "ids_user": dict(job_ids="mixed", user="alice"),
    "ids_user_history_partition": dict(job_ids="mixed", user="alice",
                                       history=True, partition="batch"),
    "ids_limit_all": dict(job_ids="mixed", limit="all"),
    "ids_finished": dict(job_ids="finished"),
    "ids_finished_history": dict(job_ids="finished", history=True),
    "ids_archived_only": dict(job_ids="archived"),
    "ids_archived_only_history": dict(job_ids="archived", history=True),
}

#: what a federation's query plane sends a follower
FOLLOWER_CASES = ("bare", "bare_history_limit", "user_limit",
                  "user_cursor_limit", "user_partition",
                  "user_last_job_finished", "ids_history", "partition_limit")


def _request(w, case, server) -> pb.QueryJobsRequest:
    ids = w.ids
    job_ids = {
        None: [],
        "mixed": [ids["e1"], ids["requeued"], ids["hi"], ids["c0"],
                  ids["archived_only"][0], ids["a9"], 9999,
                  ids["cancelled"]],
        "finished": [ids["f0"], ids["cancelled"]],
        "archived": ids["archived_only"][:2],
    }[case.get("job_ids")]
    top = max(max(w.sched.pending), max(w.sched.running))
    after = {None: 0, "mid": ids["a5"], "past": top + 5}[case.get("after")]
    request = pb.QueryJobsRequest(
        job_ids=job_ids, user=case.get("user", ""),
        partition=case.get("partition", ""), after_job_id=after,
        include_history=case.get("history", False))
    limit = case.get("limit", 0)
    if isinstance(limit, str):
        matches = len(reference_reply(server, request, streamed=True)[0])
        limit = matches - (limit == "all-1")
        assert limit > 0, "the case needs a match count above one"
    request.limit = limit
    return request


def _served(client, request, streamed):
    kw = dict(job_ids=list(request.job_ids), user=request.user,
              partition=request.partition,
              include_history=request.include_history,
              limit=request.limit, after_job_id=request.after_job_id)
    if streamed:
        result = StreamResult()
        rows = list(client.query_jobs_stream(result=result, **kw))
        return rows, result.truncated, None
    reply = client.query_jobs(**kw)
    return list(reply.jobs), reply.truncated, reply


def _assert_parity(w, case, server, client, streamed):
    request = _request(w, case, server)
    want_rows, want_truncated = reference_reply(server, request, streamed)
    rows, truncated, reply = _served(client, request, streamed)
    assert [r.job_id for r in rows] == [r.job_id for r in want_rows]
    assert rows == want_rows
    assert truncated is want_truncated
    if reply is not None:
        assert reply.durable_seq == server._durable_seq()
        assert reply.shard == server.shard_name


@pytest.mark.parametrize("rpc", ["info", "stream"])
@pytest.mark.parametrize("name", list(CASES))
def test_reply_is_the_full_walks(world, name, rpc):
    _assert_parity(world, CASES[name], world.leader, world.client,
                   streamed=rpc == "stream")


@pytest.mark.parametrize("rpc", ["info", "stream"])
@pytest.mark.parametrize("name", FOLLOWER_CASES)
def test_a_followers_reply_is_the_full_walks(world, name, rpc):
    _assert_parity(world, CASES[name], world.standby, world.standby_client,
                   streamed=rpc == "stream")


def test_the_cases_reach_what_they_name(world):
    """The churn left what the cases need: rows that only the archive
    holds, a truncated page, a name in every running row."""
    w = world
    reply = w.client.query_jobs(job_ids=w.ids["archived_only"][:2],
                                include_history=True)
    assert [j.job_id for j in reply.jobs] == w.ids["archived_only"][:2]
    assert not w.client.query_jobs(job_ids=w.ids["archived_only"][:2]).jobs
    page = w.client.query_jobs(user="alice", limit=2)
    assert len(page.jobs) == 2 and page.truncated
    whole = w.client.query_jobs(user="alice", limit=500)
    assert len(whole.jobs) == len(w.sched.user_jobs("alice"))
    assert not whole.truncated
    running = [j for j in whole.jobs if j.status == "Running"]
    assert running and all(
        j.node_names and not any(n.startswith("node#") for n in j.node_names)
        for j in running)


class _Context:
    """What a handler called in-process asks of its context."""

    def invocation_metadata(self):
        return ()

    def peer(self):
        return "ipv4:127.0.0.1:0"

    def auth_context(self):
        return {}


def test_a_job_started_between_two_chunks_of_a_stream_has_its_nodes_named(
        tmp_path):
    """The name map is built in the hold that converts a chunk, not with
    the candidates: a row whose job starts in between names its node."""
    sched, sim = _build(tmp_path)
    server, _ = serve(sched, sim=sim, tick_mode=True)
    chunk, server.QUERY_CHUNK = server.QUERY_CHUNK, 1
    try:
        first = sched.submit(_spec("erin"), now=0.0)
        assert sched.schedule_cycle(now=1.0) == [first]
        second = sched.submit(_spec("erin", "gpu"), now=2.0)
        stream = server.QueryJobsStream(
            pb.QueryJobsRequest(user="erin"), _Context())
        rows = list(next(stream).jobs)
        assert sched.schedule_cycle(now=3.0) == [second]
        rows += [j for reply in stream for j in reply.jobs]
    finally:
        server.QUERY_CHUNK = chunk
        server.stop()
        sched.archive.close()
    assert [j.job_id for j in rows] == [first, second]
    assert [j.status for j in rows] == ["Running", "Running"]
    assert list(rows[0].node_names) == ["cn00"]
    assert list(rows[1].node_names) == ["cn04"]
