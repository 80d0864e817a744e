"""Gang rendezvous: fences + modex (the PMIx role).

Reference: embedded PMIx server per supervisor with ring/tree fence
collectives and direct modex (src/Utilities/Pmix/Pmix.h:44,
PmixCollRing.h:53, ReverseTree.cpp, PmixDModex.{h,cpp}).  Here the
rank-0 supervisor hosts a single coordinator (the jax.distributed /
torchrun bootstrap shape); these tests drive the service directly and
then a REAL two-craned gang whose members block on a cross-node
fence."""

import threading
import time

import grpc
import pytest

from cranesched_tpu.rpc.rendezvous import (
    RendezvousClient,
    RendezvousServer,
)


@pytest.fixture()
def service():
    server = RendezvousServer(token="s3cret")
    port = server.start("127.0.0.1:0")
    clients = []

    def client(token="s3cret"):
        c = RendezvousClient(f"127.0.0.1:{port}", token=token)
        clients.append(c)
        return c

    yield client
    for c in clients:
        c.close()
    server.stop()


def test_fence_allgather_and_epochs(service):
    n = 4
    results = [None] * n

    def member(rank):
        c = service()
        results[rank] = c.fence("ready", rank, n,
                                data=f"r{rank}".encode())

    threads = [threading.Thread(target=member, args=(r,))
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    expected = [b"r0", b"r1", b"r2", b"r3"]
    assert all(r == expected for r in results)

    # the name is reusable: a completed fence opens a new epoch
    c = service()
    out = []
    t = threading.Thread(
        target=lambda: out.append(c.fence("ready", 0, 2)))
    t.start()
    time.sleep(0.2)
    assert not out  # still waiting on rank 1 of the NEW epoch
    service().fence("ready", 1, 2)
    t.join(timeout=10)
    assert out and out[0] == [b"", b""]


def test_fence_rejects_bad_participants(service):
    c = service()
    with pytest.raises(RuntimeError, match="bad rank"):
        c.fence("f", 3, 2)
    # duplicate rank in one epoch (the parked rank is released with a
    # shutdown error at fixture teardown — expected, suppressed)
    def parked():
        import contextlib
        with contextlib.suppress(RuntimeError, grpc.RpcError):
            service().fence("g", 0, 2)

    threading.Thread(target=parked, daemon=True).start()
    time.sleep(0.2)
    with pytest.raises(RuntimeError, match="duplicate rank"):
        service().fence("g", 0, 2)


def test_fence_timeout_is_legible(service):
    # the missing-rank attribution (1/2 arrived) is what a multi-host
    # boot hang gets logged as — keep it structured, never a bare
    # deadline error
    with pytest.raises(RuntimeError,
                       match=r"fence timeout \(1/2 arrived\)"):
        service().fence("lonely", 0, 2, timeout=0.5)


def test_modex_put_get(service):
    c = service()
    assert c.get("missing") is None
    got = []
    t = threading.Thread(
        target=lambda: got.append(c.get("addr", timeout=10.0)))
    t.start()
    time.sleep(0.2)
    service().put("addr", b"10.0.0.5:9999")
    t.join(timeout=10)
    assert got == [b"10.0.0.5:9999"]


def test_token_gates_everything(service):
    rogue = service(token="wrong")
    with pytest.raises(grpc.RpcError):
        rogue.put("k", b"v")
    with pytest.raises((grpc.RpcError, RuntimeError)):
        rogue.fence("f", 0, 1)


def test_member_that_dials_first_waits_for_the_service():
    """Rank 0's supervisor hosts the service, and a member on another
    node can reach its fence before that supervisor has bound the port:
    the call waits for the listener, it does not fail on the refused
    connection (which made a gang's outcome a race between its nodes)."""
    import socket
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    early = RendezvousClient(f"127.0.0.1:{port}", token="t")
    out = []
    t = threading.Thread(target=lambda: out.append(
        early.fence("ready", 1, 2, data=b"r1", timeout=30)))
    t.start()
    time.sleep(0.5)                 # the dial has been refused by now
    assert t.is_alive() and not out
    server = RendezvousServer(token="t", nranks=2)
    server.start(f"127.0.0.1:{port}")
    late = RendezvousClient(f"127.0.0.1:{port}", token="t")
    try:
        assert late.fence("ready", 0, 2, data=b"r0",
                          timeout=30) == [b"r0", b"r1"]
        t.join(timeout=30)
        assert out == [[b"r0", b"r1"]]
    finally:
        early.close()
        late.close()
        server.stop()


def _job_id_with_its_own_port(dispatcher) -> int:
    """A job id whose gang port differs from that of every low id a
    fresh scheduler hands out, and is free on this host now."""
    import socket

    def port_of(job_id):
        return int(dispatcher._gang_ctx(
            job_id, [0], 0)["rendezvous"].rsplit(":", 1)[1])
    low = {port_of(j) for j in range(1, 513)}
    for job_id in range(700_001, 700_513):
        port = port_of(job_id)
        if port in low:
            continue
        with socket.socket() as probe:     # no SO_REUSEPORT: held = refused
            try:
                probe.bind(("0.0.0.0", port))
            except OSError:
                continue
        return job_id
    raise AssertionError("no free gang port among 512 job ids")


def test_real_gang_cross_node_fence(tmp_path):
    """Two craneds, one node_num=2 gang job: each member publishes its
    rank through the coord CLI and blocks on a fence — the job can
    only complete if the cross-node barrier actually works."""
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

    meta = MetaContainer()
    sched = JobScheduler(meta, SchedulerConfig(
        backfill=False, craned_timeout=30.0))
    dispatcher = GrpcDispatcher(sched)
    dispatcher.wire(sched)
    server, port = serve(sched, cycle_interval=0.15,
                         dispatcher=dispatcher)
    # The gang's rendezvous address is "<rank0-name>:<port hashed from
    # the job id>", and rank 0 serves it on every interface of this
    # host.  Both parts are made this test's own: node names that
    # resolve on any host (loopback literals, not names some
    # /etc/hosts happens to carry), and a job id whose port no other
    # scheduler on this host hands out — a gang of job 1 in another
    # xdist worker (tests/test_real_node_plane.py) would share port and
    # host with this one (gRPC binds SO_REUSEPORT), and a member's
    # fence then reaches the other gang's service: "bad rendezvous
    # token", exit 9.
    sched._next_job_id = _job_id_with_its_own_port(dispatcher)
    daemons = []
    for name in ("127.0.0.1", "127.0.0.2"):
        d = CranedDaemon(name, f"127.0.0.1:{port}", cpu=4.0,
                         mem_bytes=4 << 30, workdir=str(tmp_path),
                         ping_interval=0.5,
                         cgroup_root=str(tmp_path / "nocg"))
        d.start()
        daemons.append(d)
    try:
        deadline = time.time() + 15
        while time.time() < deadline and not all(
                d.state == CranedState.READY for d in daemons):
            time.sleep(0.05)
        assert all(d.state == CranedState.READY for d in daemons)

        # per-rank files written by the script (both nodes share this
        # host, so a %j output pattern would collide)
        script = (
            f"exec > {tmp_path}/gang_rank_$CRANE_NODE_RANK.log 2>&1\n"
            "echo rank=$CRANE_NODE_RANK rdzv=$CRANE_RENDEZVOUS\n"
            "python -m cranesched_tpu.coord fence ready "
            "--data r$CRANE_NODE_RANK --timeout 90 || exit 9\n"
            "echo fenced-$CRANE_NODE_RANK\n")
        jid = sched.submit(JobSpec(
            res=ResourceSpec(cpu=1.0), node_num=2,
            script=script, time_limit=300), now=time.time())
        assert jid > 0
        # room for two interpreters to start beside five busy xdist
        # workers; a run that passes waits for none of it
        deadline = time.time() + 150
        while time.time() < deadline:
            j = sched.job_info(jid)
            if j is not None and j.status.is_terminal:
                break
            time.sleep(0.1)
        j = sched.job_info(jid)
        logs = {}
        for r in (0, 1):
            p = tmp_path / f"gang_rank_{r}.log"
            logs[r] = p.read_text() if p.exists() else "<missing>"
        assert j is not None and j.status == JobStatus.COMPLETED, (
            j.status, j.exit_code, logs)
        # both members passed the barrier and saw BOTH contributions
        for r in (0, 1):
            assert f"fenced-{r}" in logs[r], logs
            assert "0:r0" in logs[r] and "1:r1" in logs[r], logs
    finally:
        for d in daemons:
            d.stop()
        dispatcher.close()
        server.stop()


def test_fence_timeout_then_retry_succeeds(service):
    """A timed-out rank withdraws its contribution, so retrying the
    SAME fence works once the stragglers arrive (review r4: the stale
    entry wedged the epoch on 'duplicate rank' forever)."""
    c = service()
    with pytest.raises(RuntimeError, match="fence timeout"):
        c.fence("slow", 0, 2, timeout=0.4)
    out = []
    t = threading.Thread(
        target=lambda: out.append(c.fence("slow", 0, 2, data=b"a",
                                          timeout=15)))
    t.start()
    time.sleep(0.2)
    service().fence("slow", 1, 2, data=b"b", timeout=15)
    t.join(timeout=10)
    assert out == [[b"a", b"b"]]


def test_stale_epoch_put_and_fence_rejected():
    """An epoch-aware coordinator rejects contributions from a
    previous incarnation: a member that missed the restart cannot
    poison the modex or skew a fresh barrier (ISSUE 17)."""
    server = RendezvousServer(token="s3cret", epoch=2)
    port = server.start("127.0.0.1:0")
    try:
        stale = RendezvousClient(f"127.0.0.1:{port}", token="s3cret",
                                 epoch=1)
        with pytest.raises(RuntimeError, match="stale epoch 1"):
            stale.put("addr", b"10.0.0.5:9")
        # the fence rejection is IMMEDIATE (no parking until timeout)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="stale epoch 1"):
            stale.fence("boot", 0, 2, timeout=30.0)
        assert time.monotonic() - t0 < 5.0
        stale.close()

        # current-incarnation and legacy (epoch 0) members still work
        cur = RendezvousClient(f"127.0.0.1:{port}", token="s3cret",
                               epoch=2)
        cur.put("addr", b"10.0.0.5:9")
        legacy = RendezvousClient(f"127.0.0.1:{port}", token="s3cret")
        legacy.put("other", b"x")
        assert cur.get("other") == b"x"
        cur.close()
        legacy.close()
    finally:
        server.stop()


def test_server_restart_mid_fence():
    """Coordinator restart while a rank is parked in a fence: the
    parked rank is released with a legible shutdown error (not a hung
    RPC), retries against the old incarnation are rejected as stale,
    and the full gang completes on the new incarnation."""
    server = RendezvousServer(token="s3cret", epoch=1)
    port = server.start("127.0.0.1:0")
    parked_err = []

    def parked():
        c = RendezvousClient(f"127.0.0.1:{port}", token="s3cret",
                             epoch=1)
        try:
            c.fence("step", 0, 2, timeout=30.0)
        except (RuntimeError, grpc.RpcError) as e:
            parked_err.append(str(e))
        finally:
            c.close()

    t = threading.Thread(target=parked)
    t.start()
    time.sleep(0.3)
    server.stop()          # restart: the coordinator dies mid-barrier
    t.join(timeout=10)
    assert parked_err and "shutting down" in parked_err[0]

    server2 = RendezvousServer(token="s3cret", epoch=2)
    port2 = server2.start("127.0.0.1:0")
    try:
        # a member that never heard about the restart keeps stamping
        # the old incarnation — typed rejection, not barrier skew
        old = RendezvousClient(f"127.0.0.1:{port2}", token="s3cret",
                               epoch=1)
        with pytest.raises(RuntimeError, match="stale epoch 1"):
            old.fence("step", 0, 2, timeout=30.0)
        old.close()

        # the re-bootstrapped gang fences cleanly at epoch 2
        results = [None, None]

        def member(rank):
            c = RendezvousClient(f"127.0.0.1:{port2}", token="s3cret",
                                 epoch=2)
            try:
                results[rank] = c.fence("step", rank, 2,
                                        data=f"r{rank}".encode(),
                                        timeout=15.0)
            finally:
                c.close()

        threads = [threading.Thread(target=member, args=(r,))
                   for r in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=10)
        assert results == [[b"r0", b"r1"], [b"r0", b"r1"]]
    finally:
        server2.stop()
