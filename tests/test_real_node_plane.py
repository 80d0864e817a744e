"""REAL node plane end to end: ctld gRPC server + craned daemons running
actual subprocess job steps (supervisor handshake, output files, status
upcalls, cancel/suspend signals, ping-timeout failure detection).

Reference counterparts: CranedServer.cpp:32-577, StepInstance.cpp:146-201
(spawn handshake), CtldClient.h:35-90 (registration/ping FSM),
TerminateSteps + freezer suspend (JobManager.h:105-152)."""

import os
import time

import pytest

from cranesched_tpu.craned.daemon import CranedDaemon, CranedState
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    JobStatus,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.rpc import serve
from cranesched_tpu.rpc.dispatcher import GrpcDispatcher


@pytest.fixture()
def plane(tmp_path):
    meta = MetaContainer()
    sched = JobScheduler(meta, SchedulerConfig(
        backfill=False, craned_timeout=3.0))
    dispatcher = GrpcDispatcher(sched)
    dispatcher.wire(sched)
    server, port = serve(sched, cycle_interval=0.15,
                         dispatcher=dispatcher)
    ctld_addr = f"127.0.0.1:{port}"
    craneds = []

    def add_craned(name, cpu=4.0):
        d = CranedDaemon(name, ctld_addr, cpu=cpu, mem_bytes=4 << 30,
                         workdir=str(tmp_path), ping_interval=0.5,
                         cgroup_root=str(tmp_path / "nocgroup"))
        d.start()
        craneds.append(d)
        return d

    yield sched, add_craned, tmp_path, ctld_addr
    for d in craneds:
        d.stop()
    dispatcher.close()
    server.stop()


def wait_for(pred, timeout=15.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def test_real_job_runs_and_writes_output(plane):
    sched, add_craned, tmp_path, _ = plane
    d = add_craned("rn00")
    assert wait_for(lambda: d.state == CranedState.READY)
    assert wait_for(lambda: sched.meta.nodes
                    and sched.meta.node_by_name("rn00").alive)

    out = tmp_path / "out_%j.txt"
    jid = sched.submit(JobSpec(
        res=ResourceSpec(cpu=1.0),
        script="echo hello-from-$CRANE_JOB_ID; echo line2",
        output_path=str(out)), now=time.time())
    assert jid > 0
    assert wait_for(
        lambda: (sched.job_info(jid) or None) is not None
        and sched.job_info(jid).status == JobStatus.COMPLETED)
    text = (tmp_path / f"out_{jid}.txt").read_text()
    assert f"hello-from-{jid}" in text and "line2" in text
    # ledger restored
    node = sched.meta.node_by_name("rn00")
    assert (node.avail == node.total).all()


def test_failing_script_reports_exit_code(plane):
    sched, add_craned, tmp_path, _ = plane
    d = add_craned("rn01")
    assert wait_for(lambda: d.state == CranedState.READY)
    jid = sched.submit(JobSpec(res=ResourceSpec(cpu=1.0),
                               script="exit 7"), now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.FAILED)
    assert sched.job_info(jid).exit_code == 7


def test_cancel_kills_real_process(plane):
    sched, add_craned, tmp_path, _ = plane
    d = add_craned("rn02")
    assert wait_for(lambda: d.state == CranedState.READY)
    marker = tmp_path / "never.txt"
    jid = sched.submit(JobSpec(
        res=ResourceSpec(cpu=1.0),
        script=f"sleep 60; touch {marker}"), now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.RUNNING)
    time.sleep(0.3)
    sched.cancel(jid, now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.CANCELLED)
    assert not marker.exists()


def test_time_limit_enforced_by_supervisor(plane):
    sched, add_craned, tmp_path, _ = plane
    d = add_craned("rn03")
    assert wait_for(lambda: d.state == CranedState.READY)
    jid = sched.submit(JobSpec(res=ResourceSpec(cpu=1.0),
                               script="sleep 30", time_limit=1),
                       now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.EXCEED_TIME_LIMIT,
        timeout=20.0)


def test_suspend_resume_real_process(plane):
    sched, add_craned, tmp_path, _ = plane
    d = add_craned("rn04")
    assert wait_for(lambda: d.state == CranedState.READY)
    stamp = tmp_path / "stamp.txt"
    writes = 15                     # one every 0.2 s: three seconds of them
    jid = sched.submit(JobSpec(
        res=ResourceSpec(cpu=1.0),
        script=f"for i in $(seq {writes}); do date +%s%N >> {stamp}; "
               "sleep 0.2; done"), now=time.time())

    def size():
        return stamp.stat().st_size if stamp.exists() else 0

    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.RUNNING)
    assert wait_for(lambda: size() > 0)
    line = size()                   # bytes a write (fixed-width stamps)
    sched.suspend(jid, now=time.time())
    # the stop travels ctld -> craned -> SIGSTOP on the group, so a write
    # or two may land after suspend() has returned: the size the test
    # holds the process to is the one read once the stop has landed
    time.sleep(0.6)
    size_at_suspend = size()
    time.sleep(1.0)
    # frozen: no new writes while suspended (five write periods), and not
    # because the script had run out of writes
    assert size() == size_at_suspend
    assert size_at_suspend < writes * line
    assert sched.job_info(jid).status == JobStatus.SUSPENDED
    sched.resume(jid, now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.COMPLETED)
    assert size() == writes * line  # it went on where it had stopped


def test_two_craneds_gang_job(plane):
    sched, add_craned, tmp_path, _ = plane
    d1 = add_craned("gn00")
    d2 = add_craned("gn01")
    assert wait_for(lambda: d1.state == CranedState.READY
                    and d2.state == CranedState.READY)
    out = tmp_path / "gang.txt"
    jid = sched.submit(JobSpec(
        res=ResourceSpec(cpu=4.0), node_num=2,
        script=f"echo ran-on-$CRANE_JOB_NODELIST >> {out}"),
        now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.COMPLETED)
    # both nodes executed the step (2 appends, possibly interleaved)
    assert wait_for(lambda: out.exists()
                    and out.read_text().count("ran-on-") == 2)


def test_gang_one_node_fails_kills_the_rest(plane):
    # multi-node job: one node's step fails fast, the other would run
    # 60s — the failure must kill the survivor and the job ends Failed
    # only after BOTH nodes reported (no early resource release)
    sched, add_craned, tmp_path, _ = plane
    d1 = add_craned("fn00")
    d2 = add_craned("fn01")
    assert wait_for(lambda: d1.state == CranedState.READY
                    and d2.state == CranedState.READY)
    jid = sched.submit(JobSpec(
        res=ResourceSpec(cpu=4.0), node_num=2,
        script='[ "$CRANE_NODE_NAME" = fn00 ] && exit 3; sleep 60'),
        now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.FAILED,
        timeout=20.0)
    job = sched.job_info(jid)
    assert job.exit_code == 3
    # both craneds' steps are gone and resources fully restored
    assert wait_for(lambda: not d1._steps and not d2._steps)
    for name in ("fn00", "fn01"):
        node = sched.meta.node_by_name(name)
        assert (node.avail == node.total).all()


def test_ping_timeout_marks_node_down_and_requeues(plane):
    sched, add_craned, tmp_path, _ = plane
    d = add_craned("pn00")
    assert wait_for(lambda: d.state == CranedState.READY)
    jid = sched.submit(JobSpec(res=ResourceSpec(cpu=1.0),
                               script="sleep 60",
                               time_limit=300), now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.RUNNING)
    # the step must actually land on the craned first (a dispatch still
    # in flight when the node dies is a dispatch FAILURE, not a requeue)
    assert wait_for(lambda: (jid, 0) in d._steps)
    # kill the craned silently: pings stop, ctld must declare it down
    d.stop(graceful=False)
    assert wait_for(
        lambda: not sched.meta.node_by_name("pn00").alive, timeout=15.0)
    job = sched.job_info(jid)
    assert job.status == JobStatus.PENDING and job.requeue_count == 1


def test_calloc_allocation_runs_three_real_steps(plane):
    """A calloc-style allocation on a REAL craned runs 3 crun steps —
    real supervisor processes, each with its own exit status — and the
    allocation outlives them until freed (reference: AllocJobs vs
    AllocSteps, JobScheduler.cpp:1732-1839; crun within calloc)."""
    from cranesched_tpu.ctld import StepSpec
    from cranesched_tpu.ctld.defs import StepStatus

    sched, add_craned, tmp_path, _ = plane
    d = add_craned("an00")
    assert wait_for(lambda: d.state == CranedState.READY)
    jid = sched.submit(JobSpec(res=ResourceSpec(cpu=4.0),
                               alloc_only=True, time_limit=300),
                       now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.RUNNING)
    # the explicit allocation lands on the craned without any supervisor
    assert wait_for(lambda: jid in d._allocs)
    assert not d._steps

    out = tmp_path / "steps.txt"
    share = ResourceSpec(cpu=1.0)
    s0 = sched.submit_step(jid, StepSpec(
        name="ok", res=share, script=f"echo step0 >> {out}; exit 0"),
        now=time.time())
    s1 = sched.submit_step(jid, StepSpec(
        name="fail", res=share, script="exit 9"), now=time.time())
    s2 = sched.submit_step(jid, StepSpec(
        name="ok2", res=share, script=f"echo step2 >> {out}; exit 0"),
        now=time.time())
    assert (s0, s1, s2) == (0, 1, 2)
    job = sched.job_info(jid)
    assert wait_for(lambda: all(
        job.steps[s].status.is_terminal for s in (s0, s1, s2)),
        timeout=20.0)
    assert job.steps[s0].status == StepStatus.COMPLETED
    assert job.steps[s0].exit_code == 0
    assert job.steps[s1].status == StepStatus.FAILED
    assert job.steps[s1].exit_code == 9
    assert job.steps[s2].status == StepStatus.COMPLETED
    assert out.read_text().count("step") == 2
    # allocation survives its steps (a failed crun must not kill it)
    assert jid in sched.running
    assert jid in d._allocs

    # free the allocation: craned drops it, ledger restores, job done
    assert sched.free_allocation(jid, now=time.time())
    assert sched.job_info(jid).status == JobStatus.COMPLETED
    assert wait_for(lambda: jid not in d._allocs)
    node = sched.meta.node_by_name("an00")
    assert wait_for(lambda: (node.avail == node.total).all())


def test_gang_rendezvous_env_lets_members_enumerate_each_other(plane):
    """Every gang member sees the full compressed nodelist, its own
    rank, the gang size, and a shared rendezvous endpoint — the
    jax.distributed-style bootstrap contract replacing the reference's
    PMIx fork-env (Pmix.h:54-57; SURVEY §2.4)."""
    sched, add_craned, tmp_path, _ = plane
    daemons = [add_craned(f"gv{i:02d}") for i in range(4)]
    assert wait_for(lambda: all(d.state == CranedState.READY
                                for d in daemons))
    out = tmp_path / "gang_env.txt"
    jid = sched.submit(JobSpec(
        res=ResourceSpec(cpu=2.0), node_num=4,
        script=(f"echo $CRANE_NODE_RANK/$CRANE_NNODES"
                f"@$CRANE_JOB_NODELIST@$CRANE_RENDEZVOUS"
                f"@$CRANE_NODE_NAME >> {out}")),
        now=time.time())
    assert wait_for(
        lambda: sched.job_info(jid).status == JobStatus.COMPLETED,
        timeout=20.0)
    assert wait_for(lambda: out.exists()
                    and len(out.read_text().splitlines()) == 4)
    lines = sorted(out.read_text().splitlines())
    ranks, nodelists, rdv = {}, set(), set()
    for line in lines:
        rank_part, nodelist, endpoint, node_name = line.split("@")
        rank, nnodes = rank_part.split("/")
        assert nnodes == "4"
        ranks[int(rank)] = node_name
        nodelists.add(nodelist)
        rdv.add(endpoint)
    assert set(ranks) == {0, 1, 2, 3}     # each member a distinct rank
    assert len(nodelists) == 1            # same gang view everywhere
    assert nodelists == {"gv[00-03]"}     # compressed hostlist
    assert len(rdv) == 1                  # one shared coordinator
    host, port = rdv.pop().split(":")
    # the coordinator IS the rank-0 member (whichever node that is —
    # placement orders the gang by cost, not by name)
    assert host == ranks[0] and port.isdigit()
