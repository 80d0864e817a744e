"""Stall forensics + federated observability (ISSUE 16): the flight
recorder's phase ring and stall sentry, the persistent XLA compilation
cache, the fed_forwarded / arbiter_reserve / arbiter_confirm spans on
job timelines, and the cluster-level SLO merge against the
single-controller oracle.

All tests run in the ``make tier1-flight`` lane (``-m flight``); they
are fast enough for tier-1 too.
"""

import json
import os
import socket
import sys
import time
import types

import pytest

from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.fed.arbiter import GangRequest
from cranesched_tpu.fed.shardmap import ShardMap, ShardSpec
from cranesched_tpu.fed.sim import FederatedCluster
from cranesched_tpu.obs import REGISTRY
from cranesched_tpu.obs.events import EventLog
from cranesched_tpu.obs.fedobs import (
    ClusterSlo,
    cluster_doc,
    merge_metric_snapshots,
)
from cranesched_tpu.obs.flight import FlightRecorder, dump_all_stacks
from cranesched_tpu.obs.introspect import ProfilerWindow
from cranesched_tpu.obs.jobtrace import (
    FED_EDGES,
    SPAN_EDGES,
    JobTraceRecorder,
    render_waterfall,
)
from cranesched_tpu.obs.slo import SloEngine, SloSpec
from cranesched_tpu.rpc import crane_pb2 as pb, serve
from cranesched_tpu.rpc.client import CtldClient

pytestmark = pytest.mark.flight


# ---------------------------------------------------------------------------
# flight recorder: phase ring + stall sentry
# ---------------------------------------------------------------------------

def test_ring_is_bounded_and_report_tails():
    fr = FlightRecorder(capacity=16)
    for i in range(50):
        fr.stamp("phase", detail=str(i))
    rep = fr.report(tail=8)
    assert len(rep["phases"]) == 8
    # the ring kept only the newest capacity stamps
    assert rep["phases"][-1]["detail"] == "49"
    assert rep["phases"][0]["detail"] == "42"
    assert rep["stalls_total"] == 0
    assert rep["last_stall"] is None
    assert rep["armed"] is False
    fr.close()


def test_stall_sentry_fires_once_with_stacks_and_event():
    events = []
    fr = FlightRecorder(event_sink=lambda type, sev, detail="":
                        events.append((type, sev, detail)))
    fr.stamp("cycle_begin")
    fr.stamp("prelude")
    fr.arm(0.15, label="cycle")
    assert fr.report()["armed"] is True
    deadline = time.monotonic() + 5.0
    while fr.stalls_total == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert fr.stalls_total == 1
    stall = fr.report()["last_stall"]
    assert stall["label"] == "cycle"
    # the ring tail rode along: the last stamped phase is named
    assert [p["phase"] for p in stall["phases"]][-1] == "prelude"
    # every live thread's stack was captured — this test's main thread
    # must be among them, with real frames
    assert stall["stacks"]
    main = [k for k in stall["stacks"] if k.startswith("MainThread")]
    assert main and any("test_flight" in ln
                        for ln in stall["stacks"][main[0]])
    # the sentry fired ONCE and disarmed itself
    assert fr.report()["armed"] is False
    time.sleep(0.3)
    assert fr.stalls_total == 1
    assert events == [("flight_stall", "error",
                       "cycle stalled; last phase prelude; "
                       f"{len(stall['stacks'])} thread stacks captured")]
    fr.close()


def test_disarm_before_deadline_never_fires():
    fr = FlightRecorder()
    fr.arm(0.2, label="cycle")
    fr.disarm()
    time.sleep(0.4)
    assert fr.stalls_total == 0
    # re-arming after a disarm works (the cycle loop's steady state)
    fr.arm(30.0)
    assert fr.report()["armed"] is True
    fr.disarm()
    fr.close()


def _stalled(fr):
    deadline = time.monotonic() + 5.0
    while fr.stalls_total == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    return fr.last_stall


@pytest.mark.parametrize("holder", ["query", ""])
def test_stall_records_who_holds_the_server_lock_and_for_how_long(holder):
    from cranesched_tpu.cli import _render_flight
    from cranesched_tpu.obs.trace import LockLedger

    events = []
    ledger = LockLedger()
    fr = FlightRecorder(lock_ledger=ledger,
                        event_sink=lambda type, sev, detail="":
                        events.append(detail))
    if holder:
        ledger.enter(ledger.QUERY, time.perf_counter())
    fr.arm(0.1, label="cycle")
    stall = _stalled(fr)
    assert stall["lock_holder"] == holder
    text = "\n".join(_render_flight(fr.report()))
    if holder:
        # the hold began before the sentry was armed
        assert stall["lock_held_s"] >= 0.1
        assert "lock held by query" in events[0]
        assert "server lock held by query for" in text
        ledger.leave()
    else:
        assert stall["lock_held_s"] == 0.0
        assert "lock held by" not in events[0]
        assert "server lock held by - for" in text
    fr.close()


def test_a_recorder_without_a_ledger_records_no_holder():
    fr = FlightRecorder()
    fr.arm(0.05)
    assert "lock_holder" not in _stalled(fr)
    fr.close()


@pytest.mark.parametrize("interval,idle,expect", [
    (1.0, 30.0, 3.0),       # every benchmark cell: 3 s, no longer 60
    (0.05, 0.06, 2.0),      # the floor
    (2.0, 0.0, 6.0),
])
def test_sentry_deadline_leaves_out_the_idle_sleep(interval, idle, expect):
    """The sentry is armed round ONE _cycle_once, inside which the loop
    never idles: the deadline is max(3 x cycle_interval, 2 s)."""
    from cranesched_tpu.craned import SimCluster

    meta = MetaContainer()
    meta.add_node("cn0", meta.layout.encode(
        cpu=4, mem_bytes=8 << 30, memsw_bytes=8 << 30, is_capacity=True))
    meta.craned_up(0)
    sched = JobScheduler(meta, SchedulerConfig(cycle_idle_sleep=idle))
    armed = []
    arm = sched.flight.arm
    sched.flight.arm = lambda timeout_s, label="cycle": (
        armed.append((timeout_s, label)), arm(timeout_s, label))
    server, port = serve(sched, sim=SimCluster(sched),
                         address="127.0.0.1:0", cycle_interval=interval)
    try:
        server._cycle_kick.set()
        deadline = time.monotonic() + 10.0
        while not armed and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        server.stop()
    assert armed and set(armed) == {(expect, "cycle")}
    assert sched.flight.stalls_total == 0


def test_a_cycle_kept_from_the_lock_for_seconds_fires_the_sentry():
    """What the 60 s deadline never saw: a cycle that waits over 2 s for
    the server lock behind a classed hold.  last_stall names the holder
    and the age of its hold, the cycle thread's stack shows it waiting
    in _cycle_once, and the row of that cycle says who it waited behind."""
    from cranesched_tpu.craned import SimCluster

    meta = MetaContainer()
    meta.add_node("cn0", meta.layout.encode(
        cpu=4, mem_bytes=8 << 30, memsw_bytes=8 << 30, is_capacity=True),
        partitions=("default",))
    meta.craned_up(0)
    # a second job stays a candidate and the no-op fingerprint is off:
    # every cycle rings a row
    sched = JobScheduler(meta, SchedulerConfig(backfill=False,
                                               incremental=False))
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    server, port = serve(sched, sim=cluster, address="127.0.0.1:0",
                         cycle_interval=0.05)
    client = CtldClient(f"127.0.0.1:{port}")
    ledger = sched.lock_ledger
    spec = pb.JobSpec(res=pb.ResourceSpec(
        cpu=4.0, mem_bytes=1 << 30, memsw_bytes=1 << 30),
        time_limit=600, partition="default", user="alice",
        sim_runtime=300.0)

    def closed():
        return [r for r in sched.cycle_trace.snapshot() if "period_ms" in r]

    try:
        assert all(r.job_id for r in
                   client.submit_many([spec] * 2).replies)
        deadline = time.monotonic() + 10.0
        while len(closed()) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        t0 = time.perf_counter()
        with server._lock:
            ledger.enter(ledger.SNAPSHOT, t0)
            try:
                # the next cycle arms the sentry (2 s) and waits here
                stall = _stalled(sched.flight)
                held_s = time.perf_counter() - t0
            finally:
                ledger.leave()
        assert stall is not None and 2.0 <= held_s < 4.5
        assert stall["lock_holder"] == "snapshot"
        assert 1.9 <= stall["lock_held_s"] <= held_s
        waiting = [frames for name, frames in stall["stacks"].items()
                   if any("_cycle_once" in f for f in frames)]
        assert len(waiting) == 1
        assert sched.flight.stalls_total == 1
        events = [e for e in sched.events.since(0)
                  if e["type"] == "flight_stall"]
        assert len(events) == 1
        assert "lock held by snapshot" in events[0]["detail"]
        # and the row of the cycle that waited says the same from inside
        deadline = time.monotonic() + 10.0
        rows = []
        while not rows and time.monotonic() < deadline:
            rows = [r for r in closed() if r["lock_wait_max_ms"] >= 1900.0]
            time.sleep(0.02)
    finally:
        server.stop()
    assert len(rows) == 1
    row = rows[0]
    assert row["lock_wait_max_behind"] == "snapshot"
    assert row["rpc_snapshot_held_ms"] >= 2000.0
    # the process slept through it: a stall with the CPU idle
    assert row["cpu_ms"] < 0.5 * row["period_ms"]


def test_dump_all_stacks_sees_this_thread():
    stacks = dump_all_stacks()
    me = [k for k in stacks if k.startswith("MainThread")]
    assert me
    assert any("dump_all_stacks" in ln or "test_flight" in ln
               for ln in stacks[me[0]])


# ---------------------------------------------------------------------------
# persistent XLA compilation cache
# ---------------------------------------------------------------------------

def test_enable_xla_cache_counts_misses_in_subprocess(tmp_path):
    """enable_xla_cache + xla_cache_stats wiring, in a subprocess so
    the persistent cache config never leaks into this pytest process
    (it would mask recompiles other lanes assert on)."""
    import subprocess
    code = (
        "from cranesched_tpu.obs.flight import enable_xla_cache, "
        "xla_cache_stats\n"
        "import json, sys\n"
        "d = sys.argv[1]\n"
        "assert enable_xla_cache() == d and enable_xla_cache() == d\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda v: v * 3.0)(jnp.arange(8.0))\n"
        "print(json.dumps(xla_cache_stats()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "xla")],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    st = json.loads(out.stdout.strip().splitlines()[-1])
    assert st["enabled"] and st["dir"] == str(tmp_path / "xla")
    assert st["misses"] >= 1 and st["entries"] >= 1
    assert st["hit_rate"] == 0.0  # cold dir: all misses


# ---------------------------------------------------------------------------
# federated spans: fed_forwarded + the arbiter pair
# ---------------------------------------------------------------------------

def test_fed_edges_stay_off_the_lifecycle_schema():
    # SPAN_EDGES is the happy-path contract other tests assert on; the
    # federation edges annotate timelines without joining it
    assert set(FED_EDGES).isdisjoint(SPAN_EDGES)
    assert FED_EDGES == ("fed_forwarded", "arbiter_reserve",
                        "arbiter_confirm")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _shard_sched(name, partitions, nodes_per=2):
    meta = MetaContainer()
    nid = 0
    for part in partitions:
        for i in range(nodes_per):
            meta.add_node(f"{name}-{part}-n{i}",
                          meta.layout.encode(cpu=8.0,
                                             mem_bytes=16 << 30,
                                             memsw_bytes=16 << 30,
                                             is_capacity=True),
                          partitions=(part,))
            meta.craned_up(nid)
            nid += 1
    return JobScheduler(meta, SchedulerConfig(backfill=False))


def test_forwarded_submit_stamps_fed_forwarded_span():
    """A misrouted submit forwarded east->west leaves an unbroken
    waterfall on the owning shard: the fed_forwarded span carries the
    forwarding shard's send time and the receive-side skew."""
    ports = {"east": _free_port(), "west": _free_port()}
    shard_map = ShardMap([
        ShardSpec("east", ("batch",),
                  address=f"127.0.0.1:{ports['east']}"),
        ShardSpec("west", ("gpu",),
                  address=f"127.0.0.1:{ports['west']}"),
    ])
    servers = {}
    east = None
    try:
        for name in ("east", "west"):
            sched = _shard_sched(name, shard_map.partitions_of(name))
            server, bound = serve(sched, tick_mode=True,
                                  address=f"127.0.0.1:{ports[name]}",
                                  shard_name=name, shard_map=shard_map)
            assert bound == ports[name]
            servers[name] = server
        east = CtldClient(f"127.0.0.1:{ports['east']}")
        spec = pb.JobSpec(res=pb.ResourceSpec(cpu=1.0,
                                              mem_bytes=1 << 30,
                                              memsw_bytes=1 << 30),
                          sim_runtime=30.0, partition="gpu")
        reply = east.submit(spec)
        assert reply.shard == "west" and not reply.error
        doc = servers["west"].scheduler.jobtrace.timeline(reply.job_id)
        assert doc is not None
        spans = doc["incarnations"][0]["spans"]
        by_edge = {s["edge"]: s for s in spans}
        assert "fed_forwarded" in by_edge and "submit" in by_edge
        fwd = by_edge["fed_forwarded"]
        # send-time stamp + receive-side skew, never a broken timeline
        assert fwd["t"] <= by_edge["submit"]["t"] + 1e-6
        assert fwd["skew"] >= 0.0
        # a local submit never gains the span
        local = east.submit(pb.JobSpec(
            res=pb.ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                memsw_bytes=1 << 30),
            sim_runtime=30.0, partition="batch"))
        ldoc = servers["east"].scheduler.jobtrace.timeline(
            local.job_id)
        ledges = {s["edge"]
                  for s in ldoc["incarnations"][0]["spans"]}
        assert "fed_forwarded" not in ledges
        # the waterfall renderer takes fed spans in stride
        text = "\n".join(render_waterfall(doc))
        assert "fed_forwarded" in text
    finally:
        if east is not None:
            east.close()
        for server in servers.values():
            server.stop()


def test_arbiter_gang_spans_reserve_confirm_placed(tmp_path):
    """Every gang member's timeline shows the two-phase commit:
    arbiter_reserve (lease grant) -> arbiter_confirm (commit, carrying
    the fencing epoch) -> placed, in time order, on each shard."""
    fc = FederatedCluster({"east": {"batch": 2}, "west": {"gpu": 2}},
                          wal_dir=str(tmp_path))
    fc.submit_gang(GangRequest(
        name="g1", node_num=4, partitions=("batch", "gpu"),
        spec=JobSpec(user="u",
                     res=ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                      memsw_bytes=1 << 30),
                     sim_runtime=5.0)))
    fc.run_until_drained()
    assert fc.arbiter.stats["commits"] == 1
    seen = 0
    for shard in fc.shards.values():
        for job in shard.scheduler.history.values():
            if not job.spec.name.startswith("g1@"):
                continue
            seen += 1
            doc = shard.scheduler.jobtrace.timeline(job.job_id)
            assert doc is not None, job.spec.name
            spans = doc["incarnations"][0]["spans"]
            by_edge = {s["edge"]: s for s in spans}
            for edge in ("arbiter_reserve", "arbiter_confirm",
                         "placed"):
                assert edge in by_edge, (job.spec.name, sorted(by_edge))
            assert (by_edge["arbiter_reserve"]["t"]
                    <= by_edge["arbiter_confirm"]["t"]
                    <= by_edge["placed"]["t"])
            # the confirm span carries the shard's fencing epoch
            assert (doc["incarnations"][0]["fencing_epoch"]
                    == shard.scheduler.fencing_epoch)
    assert seen == 2  # one member per partition


# ---------------------------------------------------------------------------
# cluster-level SLO merge vs the single-controller oracle
# ---------------------------------------------------------------------------

def _spec(name, windows=(3600.0,)):
    return SloSpec(name, "submit", "dispatched", 99.0, 0.5, windows)


def _feed(engine_or_recorders, samples, base):
    """Stamp (job_id, latency) samples through a recorder so the SLO
    engine sees them exactly as production does."""
    rec, jobs = engine_or_recorders
    for job_id, lat in samples:
        t0 = base + job_id * 1e-3
        rec.stamp(job_id, 0, "submit", t0)
        rec.stamp(job_id, 0, "dispatched", t0 + lat)


def test_two_shard_burn_merge_matches_single_controller_oracle():
    base = 1_000_000.0
    now = base + 100.0
    # 13/200 samples over the 0.5 s target; p99 allows 1% -> burn 6.5
    lats = [2.0 if i % 16 == 0 else 0.05 for i in range(200)]
    oracle = SloEngine([_spec("e2e-oracle")])
    ora_rec = JobTraceRecorder(capacity=1024, slo=oracle)
    _feed((ora_rec, None), list(enumerate(lats)), base)
    ora_row = oracle.evaluate(now)[0]

    shard_rows = {}
    for shard, beg in (("east", 0), ("west", 1)):
        eng = SloEngine([_spec("e2e-oracle")])
        rec = JobTraceRecorder(capacity=1024, slo=eng)
        _feed((rec, None),
              [(i, lats[i]) for i in range(beg, 200, 2)], base)
        shard_rows[shard] = eng.evaluate(now)
    clu = ClusterSlo().merge(shard_rows)
    assert len(clu) == 1
    row = clu[0]
    assert row["shards"] == ["east", "west"]
    for wk, win in ora_row["windows"].items():
        cwin = row["windows"][wk]
        assert cwin["count"] == win["count"] == 200
        assert cwin["shard_counts"] == {"east": 100, "west": 100}
        # the exact-merge contract: cluster burn == what one controller
        # holding every sample computes
        assert cwin["burn_rate"] == pytest.approx(
            win["burn_rate"], abs=1e-3)
        assert cwin["breaching"] == win["breaching"]
        # percentiles don't merge exactly: max over shards, flagged
        assert cwin["observed_is_max_over_shards"] is True
        assert cwin["observed"] >= win["observed"] - 1e-9


def test_cluster_breach_counter_edge_triggers_once_per_onset():
    name = "flight-breach-edge"
    breaches = REGISTRY.counter("crane_fed_slo_breaches_total")
    before = breaches.value(slo=name)

    def table(burn):
        return {"s1": [{"name": name, "from": "submit",
                        "to": "dispatched", "p": 99.0,
                        "target_seconds": 0.5,
                        "windows": {"60": {
                            "count": 100, "observed": 1.0,
                            "burn_rate": burn,
                            "breaching": burn >= 1.0}}}]}

    clu = ClusterSlo()
    assert clu.merge(table(2.0))[0]["windows"]["60"]["breaching"]
    assert breaches.value(slo=name) == before + 1
    clu.merge(table(3.0))  # still burning: no second bump
    assert breaches.value(slo=name) == before + 1
    assert not clu.merge(table(0.0))[0]["windows"]["60"]["breaching"]
    clu.merge(table(2.0))  # a fresh onset counts again
    assert breaches.value(slo=name) == before + 2
    # the cluster burn gauge tracked the latest merge
    assert REGISTRY.gauge("crane_fed_slo_burn_rate").value(
        slo=name, window="60") == pytest.approx(2.0, abs=1e-3)


def test_merge_metric_snapshots_by_kind():
    snaps = {
        "east": {
            "crane_jobs_total": {"type": "counter",
                                 "values": {"{}": 5.0}},
            "crane_lat": {"type": "histogram",
                          "values": {'{edge="submit"}':
                                     {"count": 4, "sum": 2.0}}},
            "crane_queue_depth": {"type": "gauge",
                                  "values": {"{}": 7.0}},
        },
        "west": {
            "crane_jobs_total": {"type": "counter",
                                 "values": {"{}": 3.0}},
            "crane_lat": {"type": "histogram",
                          "values": {'{edge="submit"}':
                                     {"count": 1, "sum": 0.5}}},
            "crane_queue_depth": {"type": "gauge",
                                  "values": {'{part="gpu"}': 2.0}},
        },
    }
    out = merge_metric_snapshots(snaps)
    # counters and histograms are extensive: summed per label set
    assert out["crane_jobs_total"]["values"] == {"{}": 8.0}
    assert out["crane_lat"]["values"] == {
        '{edge="submit"}': {"count": 5, "sum": 2.5}}
    # gauges are not: one row per shard, shard= label prefixed
    assert out["crane_queue_depth"]["values"] == {
        '{shard="east"}': 7.0,
        '{shard="west",part="gpu"}': 2.0}


def test_cluster_doc_staleness_and_degraded_shards():
    now = 5_000.0
    good = types.SimpleNamespace(
        json=json.dumps({
            "watchdog": {"now": now - 4.0},
            "slo": [{"name": "e2e", "from": "submit",
                     "to": "dispatched", "p": 99.0,
                     "target_seconds": 0.5,
                     "windows": {"60": {"count": 10, "observed": 0.1,
                                        "burn_rate": 0.0,
                                        "breaching": False}}}],
            "metrics": {"crane_jobs_total": {
                "type": "counter", "values": {"{}": 2.0}}},
            "flight": {"stalls_total": 1, "last_stall": None},
        }),
        durable_seq=7)
    bad = types.SimpleNamespace(json="not json{", durable_seq=0)
    fanout = types.SimpleNamespace(
        replies={"east": good, "bad": bad}, errors={"west": "down"})
    doc = cluster_doc(fanout, now=now, max_staleness=1.5)
    assert doc["max_staleness"] == 1.5
    east = doc["shards"]["east"]
    assert east["durable_seq"] == 7
    assert east["staleness_s"] == pytest.approx(4.0, abs=0.01)
    assert east["flight"]["stalls_total"] == 1
    # the dead shard and the garbled one degrade, never block
    assert doc["errors"]["west"] == "down"
    assert doc["errors"]["bad"] == "unparseable stats reply"
    assert "bad" not in doc["shards"]
    assert doc["slo"][0]["name"] == "e2e"
    assert doc["slo"][0]["windows"]["60"]["count"] == 10
    assert doc["metrics"]["crane_jobs_total"]["values"] == {"{}": 2.0}


# ---------------------------------------------------------------------------
# satellite 3: promotion re-seed — synthetic spans never feed the
# cluster SLO windows; the follower's event log re-seeds via ingest
# ---------------------------------------------------------------------------

def _recovered_job(job_id, submit_t, start_t):
    return types.SimpleNamespace(
        job_id=job_id, requeue_count=0, submit_time=submit_t,
        start_time=start_t,
        status=types.SimpleNamespace(is_terminal=False),
        end_time=None)


def test_promotion_reseed_excludes_synthetic_spans_from_cluster_slo():
    """A promoted standby re-seeds its jobtrace with synthetic
    back-dated spans (jobtrace.seed_recovered).  Those spans would read
    as huge submit->dispatched latencies; they must never enter the SLO
    windows — per-shard or cluster-merged — while post-promotion REAL
    spans still do."""
    base = 2_000_000.0
    now = base + 50.0
    # shard A: a healthy leader with real samples
    eng_a = SloEngine([_spec("promo-e2e")])
    rec_a = JobTraceRecorder(capacity=256, slo=eng_a)
    _feed((rec_a, None), [(i, 0.1) for i in range(20)], base)
    # shard B: a standby promoted mid-run, re-adopting started jobs
    eng_b = SloEngine([_spec("promo-e2e")])
    rec_b = JobTraceRecorder(capacity=256, slo=eng_b)
    for jid in range(100, 110):
        rec_b.seed_recovered(
            _recovered_job(jid, base - 3600.0, base - 1800.0), now)
    tl = rec_b.timeline(100)["incarnations"][0]
    assert {s["edge"] for s in tl["spans"]} >= {
        "submit", "eligible", "placed", "dispatched"}
    assert all(s.get("synthetic") for s in tl["spans"])
    row_b = eng_b.evaluate(now)[0]
    assert all(w["count"] == 0 for w in row_b["windows"].values())
    # a REAL post-promotion span on the promoted shard still counts
    rec_b.stamp(999, 0, "submit", now - 1.0)
    rec_b.stamp(999, 0, "dispatched", now - 0.9)
    row_b = eng_b.evaluate(now)[0]
    row_a = eng_a.evaluate(now)[0]
    clu = ClusterSlo().merge({"a": [row_a], "b": [row_b]})
    for wk, win in clu[0]["windows"].items():
        assert win["count"] == row_a["windows"][wk]["count"] + 1
        assert win["shard_counts"]["b"] == 1
        assert not win["breaching"]


def test_follower_event_log_reseeds_via_ingest():
    """The promotion path's event-log half: the follower ingests the
    leader's replicated events (cursor on the leader seq, duplicates
    dropped) and keeps emitting monotonically after promotion."""
    leader = EventLog()
    leader.emit("leader_elected", detail="epoch 3")
    leader.emit_node_transition("down", "n0", now=10.0)
    leader.emit("flight_stall", severity="error", detail="cycle wedged")
    records = leader.since()
    follower = EventLog()
    assert all(follower.ingest(r) for r in records)
    # at-least-once fetch: the duplicate batch is dropped wholesale
    assert not any(follower.ingest(r) for r in records)
    assert follower.remote_seq == records[-1]["seq"]
    got = follower.since()
    assert [r["type"] for r in got] == [
        "leader_elected", "node_down", "flight_stall"]
    assert [r["severity"] for r in got] == ["info", "warning", "error"]
    # post-promotion emissions stay monotone past the ingested seqs
    promoted = follower.emit("leader_elected", detail="epoch 4")
    assert promoted["seq"] > got[-1]["seq"]


# ---------------------------------------------------------------------------
# satellite 2: profiler capture dirs are shard-namespaced
# ---------------------------------------------------------------------------

def test_profiler_capture_dirs_never_collide_across_shards(tmp_path):
    """Two federated shards sharing one filesystem arm a capture in
    the same instant: the shard namespace (possibly learned late, via a
    callable) plus the per-process sequence keep the dirs distinct."""
    east = ProfilerWindow(base_dir=str(tmp_path), namespace="east")
    west = ProfilerWindow(base_dir=str(tmp_path),
                          namespace=lambda: "west")
    ok1, d1 = east.request(1)
    ok2, d2 = west.request(1)
    assert ok1 and ok2
    assert d1 != d2
    assert "capture-east-" in d1 and "capture-west-" in d2
    # same shard, back-to-back arms in the same millisecond: the
    # capture sequence still uniquifies
    east._armed = 0
    east._active_dir = ""
    ok3, d3 = east.request(1)
    assert ok3 and d3 != d1
    # a namespace callable that blows up degrades to the bare tag
    weird = ProfilerWindow(base_dir=str(tmp_path),
                           namespace=lambda: 1 / 0)
    ok4, d4 = weird.request(1)
    assert ok4 and "capture-" in d4 and "capture--" not in d4


# ---------------------------------------------------------------------------
# cflight: the forensics viewer
# ---------------------------------------------------------------------------

def test_cflight_renders_live_stall(capsys):
    from cranesched_tpu.cli import _render_flight
    fr = FlightRecorder()
    fr.stamp("cycle_begin")
    fr.arm(0.05, label="cycle")
    deadline = time.monotonic() + 5.0
    while fr.stalls_total == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    text = "\n".join(_render_flight(fr.report()))
    assert "cycle_begin" in text
    assert "LAST STALL label='cycle'" in text
    assert "-- thread MainThread" in text
    fr.close()
