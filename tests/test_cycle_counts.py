"""What a cycle may cost in WAL barriers, route by route.

``_cycle_body`` serves five routes (packed, topo, backfill,
backfill-split, immediate).  Each runs every lock-held segment inside one
WAL group and closes it before every yielded solve closure, so a cycle
pays one fsync a non-empty group and never holds a group open while the
server lock is released.  These are counts, exact on any platform: every
route, once in a cycle that places and once in a cycle that places
nothing, against a real fsyncing WAL.  They pin the numbers each route
has today, so that folding the routes' shared epilogue cannot move one
unseen."""

import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.topo.model import Topology

NODES = 8
CPU = 16.0


def _res(cpu):
    return ResourceSpec(cpu=cpu, mem_bytes=1 << 30, memsw_bytes=1 << 30)


# route -> (SchedulerConfig fields, the spec whose presence selects the
# route, jobs a cycle, the trace row's label, solves yielded a cycle,
# non-empty WAL groups of a cycle that places).  The split route commits
# its head and its tail apart: two groups, where every other route has one
# (the documented bound is three a cycle).
ROUTES = {
    "packed": (dict(backfill=True),
               dict(res=_res(1.0), exclusive=True), 4, "packed", 1, 1),
    "topo": (dict(backfill=True),
             dict(res=_res(2.0), node_num=2), 3, "topo", 1, 1),
    "backfill": (dict(backfill=True),
                 dict(res=_res(2.0)), 4, "backfill", 1, 1),
    "backfill-split": (dict(backfill=True, backfill_max_jobs=2),
                       dict(res=_res(2.0)), 6, "backfill-split", 2, 2),
    # the route fifo-1k serves: Backfill off, the Pallas kernel (here
    # through the interpreter) over the resident state
    "immediate": (dict(backfill=False, solver="pallas"),
                  dict(res=_res(2.0)), 4, "pallas", 1, 1),
}


def _meta():
    meta = MetaContainer()
    for i in range(NODES):
        meta.add_node(f"cn{i}", meta.layout.encode(
            cpu=CPU, mem_bytes=64 << 30, memsw_bytes=64 << 30,
            is_capacity=True), partitions=("default",))
        meta.craned_up(i)
    return meta


def _drive(sched, wal, now):
    """``schedule_cycle``, with the WAL looked at wherever the server
    would release its lock: at each yielded closure no group is open and
    nothing is buffered."""
    gen = sched.cycle_phases(now)
    yields = 0
    try:
        fn = next(gen)
        while True:
            assert not wal.group_open, "a WAL group open across a yield"
            yields += 1
            fn = gen.send(fn())
    except StopIteration as stop:
        return stop.value or [], yields


@pytest.mark.parametrize("places", [True, False],
                         ids=["places", "places_nothing"])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_wal_counts_of_a_cycle(tmp_path, route, places):
    config, spec, count, label, solves, groups = ROUTES[route]
    meta = _meta()
    if route == "topo":
        meta.set_topology(Topology.uniform_blocks(NODES, 4))
    wal = WriteAheadLog(str(tmp_path / "ctld.wal"))
    sched = JobScheduler(meta, SchedulerConfig(**config), wal=wal)
    sched.dispatch = lambda *a, **kw: None
    sched.pallas_interpret = True
    now = 0.0
    if not places:
        # every node taken whole by a job that outlasts the test
        for _ in range(NODES):
            sched.submit(JobSpec(res=_res(CPU), time_limit=86400), now=now)
        assert len(sched.schedule_cycle(now=1.0)) == NODES
        now = 2.0
    ids = [sched.submit(JobSpec(time_limit=600, **spec), now=now)
           for _ in range(count)]
    assert all(ids)

    started, yields = _drive(sched, wal, now + 1.0)

    row = sched.cycle_trace.snapshot()[-1]
    assert row["solver"] == label
    assert row["candidates"] == count
    assert sorted(started) == (ids if places else [])
    assert row["placed"] == len(started)
    # one fsync a non-empty group, and no barrier outside a group
    assert row["wal_fsyncs"] == row["wal_groups"]
    assert row["wal_groups"] == (groups if places else 0)
    # the solves, and one more yield for the dispatch ring where a job started
    assert yields == solves + (1 if places else 0)
    # the cycle's last act left nothing behind it either
    assert not wal.group_open
    assert wal.durable_seq == wal.seq
    wal.close()


# ---------------------------------------------------------------------------
# what a commit visits in Python (ISSUE 34): the rows the cycle placed or
# whose reason changed, never again a standing row that is told the same
# ---------------------------------------------------------------------------

VISIT_ROUTES = {
    "immediate": dict(backfill=False),
    # a head of ONE job: it holds a reservation (``backfilled`` 1), which
    # counts as placed in every cycle; the backlog goes through the tail
    "backfill-split": dict(backfill=True, backfill_max_jobs=1),
}


def _visit_cluster(route):
    sched = JobScheduler(_meta(), SchedulerConfig(**VISIT_ROUTES[route]))
    sim = SimCluster(sched)
    sim.wire(sched)
    return sched, sim


def _visited(sched, sim, now):
    """One cycle: (jobs started, rows its commits visited, candidates,
    reservations the head holds)."""
    sim.advance_to(now)
    started = sched.schedule_cycle(now=now)
    row = sched.cycle_trace.snapshot()[-1]
    assert row["now"] == now, "the cycle short-circuited"
    assert row["commit_scan_ms"] >= 0.0
    visited = row["commit_visited_pct"] * row["candidates"] / 100.0
    assert visited == pytest.approx(round(visited), abs=1e-3)
    return len(started), round(visited), row["candidates"], row["backfilled"]


def _whole(sched, now, count, runtime=3600.0):
    """``count`` jobs that each take a node whole."""
    return [sched.submit(JobSpec(res=_res(CPU), time_limit=600,
                                 sim_runtime=runtime), now=now)
            for _ in range(count)]


def _nudge(sched, now):
    """A held job's arrival: no candidate, but an event, so that the next
    cycle is a cycle and not a fingerprint skip."""
    assert sched.submit(JobSpec(res=_res(1.0), held=True), now=now)


@pytest.mark.parametrize("route", sorted(VISIT_ROUTES))
def test_standing_backlog_is_visited_once(route):
    sched, sim = _visit_cluster(route)
    head = 1 if route == "backfill-split" else 0
    _whole(sched, 0.0, NODES)
    assert _visited(sched, sim, 1.0) == (NODES, NODES, NODES, 0)
    _whole(sched, 1.0, 12)
    # the first cycle tells every candidate its reason (commit_visited_pct
    # 100) ...
    assert _visited(sched, sim, 2.0) == (0, 12, 12, head)
    # ... and the second nobody (0) but the head's reservation
    _nudge(sched, 2.5)
    assert _visited(sched, sim, 3.0) == (0, head, 12, head)


@pytest.mark.parametrize("route", sorted(VISIT_ROUTES))
def test_a_cycle_visits_what_it_places_and_what_arrived(route):
    sched, sim = _visit_cluster(route)
    head = 1 if route == "backfill-split" else 0
    _whole(sched, 0.0, 2, runtime=1.5)          # gone by t = 2.5
    _whole(sched, 0.0, NODES - 2)
    assert _visited(sched, sim, 1.0)[0] == NODES
    _whole(sched, 1.0, 12)
    assert _visited(sched, sim, 2.0) == (0, 12, 12, head)
    # m = 2 nodes come free and k = 3 jobs arrive: the cycle visits the
    # m it places and the k it has not told yet (the head's job is one
    # of the m, so nobody holds a reservation in this cycle)
    _whole(sched, 2.5, 3)
    assert _visited(sched, sim, 3.0) == (2, 2 + 3, 15, 0)
    # what is left stands again
    _nudge(sched, 3.5)
    assert _visited(sched, sim, 4.0) == (0, head, 13, head)


@pytest.mark.parametrize("route", sorted(VISIT_ROUTES))
def test_a_flood_shaped_cycle_visits_what_it_places(route):
    sched, sim = _visit_cluster(route)
    for _ in range(16):
        assert sched.submit(JobSpec(res=_res(2.0), time_limit=600), now=0.0)
    assert _visited(sched, sim, 1.0) == (16, 16, 16, 0)


# ---------------------------------------------------------------------------
# what a commit pulls (ISSUE 35): the node lists of the rows the cycle
# placed, gathered on the device, and not the solve's [J, K] whole
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["gathered", "too_many_rows", "small_array",
                                  "on_the_host", "none_placed"])
def test_pull_rows_is_nodes_at_idx(case, monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from cranesched_tpu.ctld import scheduler as sch

    gathers = []
    monkeypatch.setattr(sch, "_take_rows", lambda nodes, idx: (
        gathers.append(nodes.shape) or nodes[idx]))
    cap, width = sch._COMMIT_PULL_ROWS, 64
    rows = (sch._COMMIT_PULL_WHOLE // width if case == "small_array"
            else 2 * sch._COMMIT_PULL_WHOLE // width)
    host = np.arange(rows * width, dtype=np.int32).reshape(rows, width) - 5
    idx = {"gathered": np.arange(0, rows, 7)[:cap],
           "too_many_rows": np.arange(cap + 1),
           "small_array": np.array([3, rows - 1]),
           "on_the_host": np.array([0, 9, rows - 1]),
           "none_placed": np.zeros(0, np.intp)}[case]
    nodes = host if case == "on_the_host" else jnp.asarray(host)
    got = sch._pull_rows(nodes, idx)
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    np.testing.assert_array_equal(got, host[idx])
    assert gathers == ([(rows, width)] if case in ("gathered", "none_placed")
                       else [])


def test_a_cycle_over_more_candidates_than_the_gather_holds(monkeypatch):
    """1,100 candidates (a 2,048-row solve) of which 8 start, with the
    size up to which an array is pulled whole set below it: the commit
    takes the gather's path and gives each its own node."""
    sched = JobScheduler(_meta(), SchedulerConfig(
        backfill=False, solver="device"))    # the scan: device arrays
    sim = SimCluster(sched)
    sim.wire(sched)
    pulled = []
    from cranesched_tpu.ctld import scheduler as sched_mod
    monkeypatch.setattr(sched_mod, "_take_rows", lambda nodes, idx: (
        pulled.append(nodes.shape) or nodes[idx]))
    monkeypatch.setattr(sched_mod, "_COMMIT_PULL_WHOLE", 1024)
    ids = _whole(sched, 0.0, 1100)
    started, visited, candidates, _ = _visited(sched, sim, 1.0)
    assert (started, visited, candidates) == (NODES, 1100, 1100)
    nodes = sorted(sched.job_info(j).node_ids[0] for j in ids[:NODES])
    assert nodes == list(range(NODES))
    assert sched.cycle_trace.snapshot()[-1]["nodes_selected"] == NODES
    assert pulled == [(2048, 1)]
