"""The ctld lock must NOT be held across the solve (VERDICT r5 #4).

The cycle runs as ``cycle_phases``: state phases under the server
lock, each yielded solve closure with the lock released
(rpc/server.py::_cycle_loop).  These tests inject an artificially slow
solve and prove that (a) submits and queries landing mid-solve return
in milliseconds instead of waiting out the solve (the reference
reaches the same property with 9 scheduler threads + per-entry locks,
JobScheduler.h:1290-1335), and (b) mutations that land mid-solve —
cancel, node death — are honored by the commit revalidation
(_commit's pending/held guard + the ResReduceEvent window,
JobScheduler.cpp:1437-1540)."""

import threading
import time

import numpy as np
import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobStatus,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.rpc import crane_pb2 as pb
from cranesched_tpu.rpc.client import CtldClient
from cranesched_tpu.rpc.server import serve


def _cluster(num_nodes=8, solve_delay=0.0):
    meta = MetaContainer()
    for i in range(num_nodes):
        meta.add_node(
            f"cn{i:02d}",
            meta.layout.encode(cpu=16, mem_bytes=32 << 30,
                               memsw_bytes=32 << 30, is_capacity=True),
            partitions=("default",))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(backfill=False))
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    if solve_delay:
        # wrap the immediate solver with a sleep INSIDE the yielded
        # closure — i.e. inside the window where _cycle_loop has
        # dropped the lock.  This models a big (1 s-class) solve
        # without needing 50k jobs in a unit test.
        inner = sched._immediate_solve

        def slow(*a, **kw):
            time.sleep(solve_delay)
            return inner(*a, **kw)

        sched._immediate_solve = slow
    return meta, sched, cluster


def _pbspec(cpu=1.0, runtime=30.0):
    return pb.JobSpec(
        res=pb.ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                            memsw_bytes=1 << 30),
        time_limit=3600, partition="default", user="alice",
        sim_runtime=runtime)


def test_submit_and_query_latency_during_slow_cycle():
    meta, sched, cluster = _cluster(solve_delay=1.0)
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         cycle_interval=0.1)
    client = CtldClient(f"127.0.0.1:{port}")
    try:
        # seed pending work so every cycle actually solves
        for _ in range(4):
            client.submit(_pbspec())
        deadline = time.time() + 3.0
        lat = []
        while time.time() < deadline:
            t0 = time.perf_counter()
            client.submit(_pbspec())
            client.query_jobs()
            lat.append(time.perf_counter() - t0)
        lat.sort()
        p99 = lat[int(len(lat) * 0.99) - 1]
        # >=2 one-second solves ran inside this window; with the lock
        # held across solves p99 would be ~1 s.  50 ms is the
        # VERDICT r5 #4 budget.
        assert p99 < 0.05, f"submit+query p99 {p99 * 1e3:.1f} ms"
        assert len(lat) > 50  # the client genuinely ran during solves
    finally:
        server.stop()


def test_cycle_still_places_during_concurrent_submits():
    meta, sched, cluster = _cluster(solve_delay=0.2)
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         cycle_interval=0.05)
    client = CtldClient(f"127.0.0.1:{port}")
    try:
        ids = [client.submit(_pbspec()).job_id for _ in range(12)]
        deadline = time.time() + 8.0
        while time.time() < deadline:
            infos = client.query_jobs(job_ids=ids).jobs
            if sum(1 for j in infos
                   if j.status == "Running") >= 8:
                break
            time.sleep(0.05)
        infos = client.query_jobs(job_ids=ids).jobs
        running = [j for j in infos if j.status == "Running"]
        assert len(running) >= 8, [j.status for j in infos]
    finally:
        server.stop()


def test_cancel_mid_solve_voids_placement():
    """A job canceled while the solve runs must not start: _commit's
    pending-membership guard discards the stale placement."""
    meta, sched, cluster = _cluster(solve_delay=0.0)
    jid = sched.submit(_spec_native(), now=0.0)

    gen = sched.cycle_phases(now=1.0)
    fn = next(gen)          # prelude + snapshot done, solve pending
    sched.cancel(jid, now=1.0)     # lands "mid-solve"
    result = fn()
    with pytest.raises(StopIteration) as stop:
        while True:
            fn = gen.send(result)
            result = fn()
    assert stop.value.value == []  # nothing started
    job = sched.job_info(jid)
    assert job.status == JobStatus.CANCELLED
    # no resources leaked
    for node in meta.nodes.values():
        assert (node.avail == node.total).all()


def test_modify_mid_solve_voids_placement():
    """A partition move landing mid-solve must void the placement
    computed against the OLD partition (spec-epoch guard in _commit)."""
    meta, sched, cluster = _cluster(solve_delay=0.0)
    jid = sched.submit(_spec_native(), now=0.0)

    gen = sched.cycle_phases(now=1.0)
    fn = next(gen)
    result = fn()           # solve placed it in "default"
    err = sched.modify_job(jid, now=1.0, partition="default")
    assert err == ""        # spec replaced (same name, new object)
    with pytest.raises(StopIteration) as stop:
        while True:
            fn = gen.send(result)
            result = fn()
    assert stop.value.value == []
    assert sched.job_info(jid).status == JobStatus.PENDING
    # next cycle (fresh spec) places it normally
    assert sched.schedule_cycle(now=2.0) == [jid]


def test_node_death_mid_solve_revalidated():
    """All nodes die mid-solve: ResReduceEvents void every placement
    (the reference's validation at JobScheduler.cpp:1466-1540)."""
    meta, sched, cluster = _cluster(solve_delay=0.0, num_nodes=2)
    jid = sched.submit(_spec_native(), now=0.0)

    gen = sched.cycle_phases(now=1.0)
    fn = next(gen)
    result = fn()           # solve picked a node
    for nid in list(meta.nodes):
        meta.craned_down(nid)      # mid-cycle reduce events
    with pytest.raises(StopIteration) as stop:
        while True:
            fn = gen.send(result)
            result = fn()
    assert stop.value.value == []
    job = sched.job_info(jid)
    assert job.status == JobStatus.PENDING


def _spec_native(cpu=1.0):
    return JobSpec(res=ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                                    memsw_bytes=1 << 30),
                   sim_runtime=30.0)
