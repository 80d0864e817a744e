"""Introspection plane (obs/introspect.py + obs/events.py +
ctld/explain.py): jit-compile observer, device-memory gauges, profiler
capture windows, the structured event ring (including follower
replication end-to-end), Prometheus exposition round-trip, the
``cexplain`` oracle-parity contract, and the SLO engine's edge cases.
"""

import collections
import json
import re
import time
import urllib.request

import numpy as np
import pytest

import cranesched_tpu.cli as crane_cli
from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.ctld.defs import Dependency, DepType, PendingReason
from cranesched_tpu.ctld.wal import WriteAheadLog
from cranesched_tpu.ha.follower import HaFollower
from cranesched_tpu.obs import introspect
from cranesched_tpu.obs.events import FLAP_WINDOW, EventLog
from cranesched_tpu.obs.introspect import ProfilerWindow, instrument_jit
from cranesched_tpu.obs.jobtrace import JobTraceRecorder
from cranesched_tpu.obs.metrics import MetricsRegistry, serve_metrics
from cranesched_tpu.obs.slo import SloEngine, SloSpec, _MET_BREACH
from cranesched_tpu.rpc import serve
from cranesched_tpu.rpc.client import CtldClient

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def _build(cpus=(8, 8, 8), wal=None, **cfg):
    meta = MetaContainer()
    for i, cpu in enumerate(cpus):
        meta.add_node(f"cn{i:02d}",
                      meta.layout.encode(cpu=cpu, mem_bytes=16 << 30,
                                         memsw_bytes=16 << 30,
                                         is_capacity=True))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(backfill=False, **cfg),
                         wal=wal)
    cluster = SimCluster(sched)
    sched.dispatch = cluster.dispatch
    sched.dispatch_terminate = cluster.terminate
    return meta, sched, cluster


def _spec(cpu=1.0, runtime=50.0, **kw):
    return JobSpec(res=ResourceSpec(cpu=cpu, mem_bytes=1 << 30,
                                    memsw_bytes=1 << 30),
                   sim_runtime=runtime, **kw)


# ---------------------------------------------------------------------------
# jit-compile observer
# ---------------------------------------------------------------------------

def test_instrument_jit_counts_fresh_compiles_only():
    import jax
    import jax.numpy as jnp

    jitted = jax.jit(lambda x: x * 2 + 1)
    obs = instrument_jit("t_introspect_probe", jitted)
    base = introspect.total_compiles()
    mbase = introspect._MET_COMPILES.value(fn="t_introspect_probe")

    out = obs(jnp.zeros(4))           # fresh shape -> one compile
    assert out.shape == (4,)
    assert introspect.total_compiles() == base + 1
    obs(jnp.ones(4))                  # cache hit -> no growth
    assert introspect.total_compiles() == base + 1
    obs(jnp.zeros(8))                 # new shape -> second compile
    assert introspect.total_compiles() == base + 2
    assert (introspect._MET_COMPILES.value(fn="t_introspect_probe")
            == mbase + 2)


def test_instrument_jit_preserves_jit_surface():
    import jax
    import jax.numpy as jnp

    def plain(x):
        return x + 1

    jitted = jax.jit(plain)
    obs = instrument_jit("t_surface", jitted)
    # donating twins re-jit the PLAIN python fn via __wrapped__
    assert obs.__wrapped__ is plain
    assert callable(obs._cache_size) and callable(obs.lower)
    obs(jnp.zeros(2))
    assert obs._cache_size() >= 1


def test_instrument_jit_degrades_without_cache_size():
    calls = []

    def no_probe(x):
        calls.append(x)
        return x * 2

    obs = instrument_jit("t_noprobe", no_probe)
    base = introspect.total_compiles()
    assert obs(21) == 42
    assert calls == [21]
    assert introspect.total_compiles() == base


def test_sample_device_memory_cpu_safe():
    import jax.numpy as jnp

    keep = jnp.zeros(16)  # at least one live array
    out = introspect.sample_device_memory()
    assert set(out) == {"bytes", "peak_bytes", "buffers"}
    # stock CPU client has no allocator stats -> -1; a stats-capable
    # backend reports real numbers — both are valid here
    assert out["bytes"] >= -1 and out["peak_bytes"] >= -1
    assert out["buffers"] >= 1
    del keep


# ---------------------------------------------------------------------------
# profiler capture windows
# ---------------------------------------------------------------------------

def test_profiler_window_lifecycle(tmp_path, monkeypatch):
    import jax

    traces = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: traces.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: traces.append(("stop", None)))
    sink = []
    pw = ProfilerWindow(base_dir=str(tmp_path),
                        event_sink=lambda *a, **kw: sink.append((a, kw)))
    d = str(tmp_path / "cap1")
    ok, got = pw.request(2, out_dir=d)
    assert ok and got == d
    # double-arm refused while a capture is pending
    ok2, err = pw.request(1)
    assert not ok2 and "in progress" in err

    pw.tick()  # starts the trace
    assert traces == [("start", d)]
    assert pw.status()["remaining"] == 2
    pw.tick()
    assert pw.status()["remaining"] == 1 and pw.captures_done == 0
    pw.tick()  # countdown hits zero -> a helper thread stops + records
    deadline = time.time() + 10.0
    while pw.captures_done == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert traces[-1] == ("stop", None)
    st = pw.status()
    assert st["captures_done"] == 1 and st["last_capture"] == d
    assert st["armed"] == 0 and st["remaining"] == 0
    # started + written events reached the sink
    details = [kw.get("detail", "") for a, kw in sink]
    assert any(s.startswith("started:") for s in details)
    assert any(s.startswith("written:") for s in details)
    # re-armable after completion
    assert pw.request(1)[0]


def test_profiler_window_never_raises_into_cycle(tmp_path, monkeypatch):
    import jax

    def boom(d, **kw):
        raise RuntimeError("no backend profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    pw = ProfilerWindow(base_dir=str(tmp_path))
    assert pw.request(3)[0]
    pw.tick()  # swallow the failure, disarm
    st = pw.status()
    assert "no backend profiler" in st["last_error"]
    assert st["armed"] == 0 and st["remaining"] == 0
    # and the window can be re-armed after the failure
    assert pw.request(1)[0]


def test_profiler_window_rejects_bad_cycles(tmp_path):
    pw = ProfilerWindow(base_dir=str(tmp_path))
    ok, err = pw.request(0)
    assert not ok and "cycles" in err


# ---------------------------------------------------------------------------
# event ring
# ---------------------------------------------------------------------------

def test_event_log_filters_and_limit():
    log = EventLog(capacity=64)
    log.emit("node_drain", "info", node="a", time=10.0)
    log.emit("fencing_rejection", "error", node="b", time=20.0)
    log.emit("preemption", "warning", job_id=7, time=30.0)
    log.emit("failover", "critical", time=40.0)

    assert [r["type"] for r in log.since()] == [
        "node_drain", "fencing_rejection", "preemption", "failover"]
    # min-severity rank
    assert [r["type"] for r in log.since(severity="warning")] == [
        "fencing_rejection", "preemption", "failover"]
    assert [r["type"] for r in log.since(severity="critical")] == [
        "failover"]
    # cursor, time, and type filters
    assert [r["type"] for r in log.since(after_seq=2)] == [
        "preemption", "failover"]
    assert [r["type"] for r in log.since(since_time=25.0)] == [
        "preemption", "failover"]
    assert [r["job_id"] for r in log.since(type="preemption")] == [7]
    # limit keeps the NEWEST matches
    assert [r["type"] for r in log.since(limit=2)] == [
        "preemption", "failover"]
    # unknown severity falls back to info
    rec = log.emit("requeue", "shouting")
    assert rec["severity"] == "info"


def test_event_log_ring_bounded():
    log = EventLog(capacity=4)
    for i in range(10):
        log.emit("requeue", job_id=i, time=float(i))
    out = log.since()
    assert len(out) == 4
    assert [r["job_id"] for r in out] == [6, 7, 8, 9]
    assert log.last_seq == 10  # seq keeps counting past evictions


def test_event_log_flap_detection():
    log = EventLog()
    log.emit_node_transition("down", "cn00", now=100.0)
    log.emit_node_transition("up", "cn00", now=100.0 + FLAP_WINDOW / 2)
    types = [r["type"] for r in log.since()]
    assert types == ["node_down", "node_up", "node_flap"]
    flap = log.since(type="node_flap")[0]
    assert flap["severity"] == "warning" and flap["node"] == "cn00"
    # an up long after the down is a clean recovery, not a flap
    log.emit_node_transition("node_down", "cn01", now=200.0)
    log.emit_node_transition("node_up", "cn01",
                             now=200.0 + FLAP_WINDOW + 1.0)
    assert len(log.since(type="node_flap")) == 1


def test_event_log_ingest_dedup_and_promotion_seq():
    leader, follower = EventLog(), EventLog()
    for i in range(3):
        leader.emit("requeue", job_id=i + 1)
    batch = leader.since()
    assert all(follower.ingest(r) for r in batch)
    assert follower.remote_seq == 3
    # at-least-once refetch: duplicates rejected by origin seq
    assert not any(follower.ingest(r) for r in batch)
    assert len(follower.since()) == 3
    # post-promotion local emission continues the LOCAL sequence
    rec = follower.emit("failover", "critical")
    assert rec["seq"] == 4
    assert [r["job_id"] for r in follower.since()][:3] == [1, 2, 3]


# ---------------------------------------------------------------------------
# exposition-format round trip
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s(\S+)$")


def _parse_labels(raw):
    """Parse 'k="v",k2="v2"' with full escape handling; raises on any
    malformed input (that IS the test)."""
    out = {}
    i = 0
    while i < len(raw):
        eq = raw.index("=", i)
        key = raw[i:eq]
        assert raw[eq + 1] == '"'
        j = eq + 2
        val = []
        while raw[j] != '"':
            if raw[j] == "\\":
                nxt = raw[j + 1]
                val.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                j += 2
            else:
                val.append(raw[j])
                j += 1
        out[key] = "".join(val)
        i = j + 1
        if i < len(raw):
            assert raw[i] == ","
            i += 1
    return out


def _parse_exposition(text):
    """Minimal 0.0.4 parser: returns (samples, help_counts, type_counts)
    and asserts every non-comment line is a well-formed sample."""
    samples = []
    helps = collections.Counter()
    types = collections.Counter()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            helps[line.split(" ", 3)[2]] += 1
            continue
        if line.startswith("# TYPE "):
            types[line.split(" ", 3)[2]] += 1
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, raw_labels, value = m.groups()
        labels = _parse_labels(raw_labels) if raw_labels else {}
        samples.append((name, labels, value))
    return samples, helps, types


def test_exposition_round_trip_escaping_and_headers():
    reg = MetricsRegistry()
    nasty = 'C:\\temp\n says "hello", ok'
    c = reg.counter("crane_rt_demo_total", 'help with "quotes" and a\nnewline')
    c.inc(2, path=nasty)
    c.inc(1, path="plain")
    g = reg.gauge("crane_rt_demo_bytes", "gauge help")
    g.set(-1)
    h = reg.histogram("crane_rt_demo_seconds", "hist help")
    h.observe(0.004, kind="x")
    h.observe(3.0, kind="x")

    text = reg.expose()
    samples, helps, types = _parse_exposition(text)

    # HELP/TYPE exactly once per family — promtool chokes on repeats
    assert set(helps) == set(types) == {
        "crane_rt_demo_total", "crane_rt_demo_bytes",
        "crane_rt_demo_seconds"}
    assert all(n == 1 for n in helps.values())
    assert all(n == 1 for n in types.values())

    # the escaped label value parses back to the ORIGINAL string
    by = {(n, tuple(sorted(l.items()))): v for n, l, v in samples}
    assert by[("crane_rt_demo_total", (("path", nasty),))] == "2"
    assert by[("crane_rt_demo_total", (("path", "plain"),))] == "1"
    assert by[("crane_rt_demo_bytes", ())] == "-1"

    # histogram families expose cumulative buckets + sum/count
    names = {n for n, _, _ in samples}
    assert {"crane_rt_demo_seconds_bucket", "crane_rt_demo_seconds_sum",
            "crane_rt_demo_seconds_count"} <= names
    count = [v for n, l, v in samples
             if n == "crane_rt_demo_seconds_count"]
    assert count == ["2"]
    inf = [v for n, l, v in samples
           if n == "crane_rt_demo_seconds_bucket"
           and l.get("le") == "+Inf"]
    assert inf == ["2"]


def test_metrics_http_content_type():
    reg = MetricsRegistry()
    reg.counter("crane_rt_http_total", "x").inc()
    srv = serve_metrics(0, host="127.0.0.1", registry=reg)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as rep:
            assert rep.headers["Content-Type"] == \
                "text/plain; version=0.0.4; charset=utf-8"
            body = rep.read().decode()
        assert "crane_rt_http_total 1" in body
        _parse_exposition(body)  # the whole page parses
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# pending-reason explainability
# ---------------------------------------------------------------------------

def test_explain_simple_gates():
    _, sched, _ = _build()
    now = 0.0
    held = sched.submit(_spec(held=True), now=now)
    future = sched.submit(_spec(begin_time=500.0), now=now)
    blocker = sched.submit(_spec(cpu=8.0, runtime=1e6), now=now)
    dep = sched.submit(
        _spec(dependencies=(Dependency(blocker, DepType.AFTER_OK),)),
        now=now)
    sched.schedule_cycle(now=now)

    ex = sched.explain_pending(held, now)
    assert (ex["reason"], ex["gate"]) == ("Held", "held")
    ex = sched.explain_pending(future, now)
    assert (ex["reason"], ex["gate"]) == ("BeginTime", "begin_time")
    ex = sched.explain_pending(dep, now)
    assert ex["reason"] == "Dependency" and ex["gate"] == "dependency"
    assert str(blocker) in ex["detail"]
    # running / unknown jobs answer without a gate walk
    ex = sched.explain_pending(blocker, now)
    assert ex["state"] == "RUNNING" and "not pending" in ex["detail"]
    ex = sched.explain_pending(9999, now)
    assert ex["gate"] == "exists" and ex["detail"] == "no such job"
    # every failing explain ships the full checks breakdown
    ex = sched.explain_pending(held, now)
    assert [c["gate"] for c in ex["checks"]] == ["held"]


def test_explain_resource_and_priority_gates():
    _, sched, _ = _build(cpus=(8, 8))
    now = 0.0
    for _ in range(2):
        sched.submit(_spec(cpu=8.0, runtime=1e6), now=now)
    queued = sched.submit(_spec(cpu=4.0), now=now)
    sched.schedule_cycle(now=now)

    ex = sched.explain_pending(queued, now)
    assert (ex["reason"], ex["gate"]) == ("Resource", "resources")
    assert "cpu" in ex["detail"]  # the binding dimension is named
    passed = {c["gate"] for c in ex["checks"] if c["ok"]}
    assert {"held", "begin_time", "dependency", "eligibility",
            "alive", "capacity"} <= passed

    # free one node: the job is feasible NOW, it just lost the race
    info = sched.job_info(1)
    sched.meta.free_resource(1, info.node_ids, sched.meta.layout.encode(
        cpu=8.0, mem_bytes=1 << 30, memsw_bytes=1 << 30))
    ex = sched.explain_pending(queued, now)
    assert (ex["reason"], ex["gate"]) == ("Priority", "priority")
    assert "feasible now" in ex["detail"]


def test_explain_alive_gate_after_node_loss():
    meta, sched, _ = _build(cpus=(8, 8))
    gang = sched.submit(_spec(cpu=4.0, node_num=2), now=0.0)
    meta.craned_down(1)
    ex = sched.explain_pending(gang, 1.0)
    assert (ex["reason"], ex["gate"]) == ("Constraint", "alive")
    assert "gang needs 2" in ex["detail"]


def _oracle_reason(sched, job, now):
    """Independent recomputation of the first failing gate from RAW
    cluster state (per-node dict walk, no _mask_for/_job_row/snapshot),
    for the single-partition no-reservation clusters built here."""
    spec = job.spec
    if job.held:
        return "Held"
    if spec.begin_time is not None and spec.begin_time > now:
        return "BeginTime"
    dep = sched._deps_runnable(job, now)
    if dep is not None:
        return dep.value
    req = np.asarray(sched.meta.layout.encode(
        cpu=spec.res.cpu, mem_bytes=spec.res.mem_bytes,
        memsw_bytes=spec.res.memsw_bytes), np.int64)
    nn = max(int(spec.node_num), 1)
    alive = [n for n in sched.meta.nodes.values() if n.alive]
    if len(alive) < nn:
        return "Constraint"
    cap = [n for n in alive
           if np.all(np.asarray(n.total, np.int64) >= req)]
    if len(cap) < nn:
        return "Constraint"
    fit = [n for n in cap
           if np.all(np.asarray(n.avail, np.int64) >= req)]
    if len(fit) < nn:
        return "Resource"
    return "Priority"


def test_explain_oracle_parity_randomized():
    """Acceptance criterion: on a randomized cluster, cexplain's reason
    matches an oracle that recomputes the first failing gate straight
    from per-node state."""
    import random

    rng = random.Random(140814)
    cpus = [rng.choice((2, 4, 8, 16)) for _ in range(8)]
    meta, sched, _ = _build(cpus=cpus)
    now = 0.0
    blockers = []
    # pin down most of the cluster so later jobs queue on resources
    for i, cpu in enumerate(cpus):
        if rng.random() < 0.7:
            blockers.append(sched.submit(
                _spec(cpu=float(cpu), runtime=1e6), now=now))
    sched.schedule_cycle(now=now)
    assert blockers and all(
        sched.job_info(b).status.name == "RUNNING" for b in blockers)

    jobs = []
    for _ in range(40):
        kw = {}
        r = rng.random()
        if r < 0.15:
            kw["held"] = True
        elif r < 0.30:
            kw["begin_time"] = now + rng.uniform(100.0, 1000.0)
        elif r < 0.45:
            kw["dependencies"] = (Dependency(
                rng.choice(blockers), DepType.AFTER_OK),)
        jid = sched.submit(_spec(cpu=float(rng.choice((1, 2, 4, 8, 16))),
                                 node_num=rng.choice((1, 1, 1, 2, 3)),
                                 runtime=1e6, **kw), now=1.0)
        if jid:  # submit-time validation rejects never-fits specs
            jobs.append(jid)
    sched.schedule_cycle(now=1.0)
    # knock two nodes out AFTER the cycle to exercise the alive gate
    for nid in rng.sample(range(len(cpus)), 2):
        meta.craned_down(nid)

    seen = set()
    checked = 0
    for jid in jobs:
        job = sched.pending.get(jid)
        if job is None:
            continue  # started in the cycle
        ex = sched.explain_pending(jid, 2.0)
        want = _oracle_reason(sched, job, 2.0)
        assert ex["reason"] == want, (
            f"job {jid}: explain said {ex['reason']!r} "
            f"(gate {ex['gate']}, {ex['detail']!r}), oracle says "
            f"{want!r}")
        # the failing gate must be the first non-ok check, and every
        # check before it must have passed
        fails = [c["gate"] for c in ex["checks"] if not c["ok"]]
        assert fails[:1] == [ex["gate"]]
        seen.add(ex["reason"])
        checked += 1
    assert checked >= 15
    # the randomized mix actually exercised distinct gates
    assert len(seen) >= 4, f"only saw reasons {seen}"


# ---------------------------------------------------------------------------
# SLO engine edge cases
# ---------------------------------------------------------------------------

def test_slo_empty_window_no_breach():
    eng = SloEngine([SloSpec("t_empty", "a", "b", 99, 1.0,
                             windows=(60.0,))])
    base = _MET_BREACH.value(slo="t_empty")
    table = eng.evaluate(now=100.0)
    w = table[0]["windows"]["60"]
    assert w == {"count": 0, "observed": 0.0, "burn_rate": 0.0,
                 "breaching": False}
    assert _MET_BREACH.value(slo="t_empty") == base


def test_slo_burn_exactly_at_threshold_breaches():
    # p=50 -> allowed budget 0.5; 2 of 4 over target -> burn exactly 1.0
    eng = SloEngine([SloSpec("t_edge", "a", "b", 50, 1.0,
                             windows=(60.0,))])
    edges = []
    eng.event_sink = lambda *a: edges.append(a)
    for lat in (0.5, 0.5, 2.0, 2.0):
        eng.record("b", {"a": 10.0 - lat}, now=10.0)
    w = eng.evaluate(now=10.0)[0]["windows"]["60"]
    assert w["burn_rate"] == 1.0 and w["breaching"]
    assert edges == [("t_edge", 60.0, 1.0, True)]


def test_slo_breach_counter_monotonic_across_rotation():
    eng = SloEngine([SloSpec("t_rot", "a", "b", 99, 1.0,
                             windows=(60.0,))])
    edges = []
    eng.event_sink = lambda name, w, burn, br: edges.append(br)
    base = _MET_BREACH.value(slo="t_rot")

    for _ in range(5):
        eng.record("b", {"a": 0.0}, now=10.0)  # latency 10 >> target
    eng.evaluate(now=10.0)
    assert _MET_BREACH.value(slo="t_rot") == base + 1
    # sustained breach: same edge, no second count
    eng.evaluate(now=20.0)
    assert _MET_BREACH.value(slo="t_rot") == base + 1
    # window rotation ages the samples out -> clear edge, counter holds
    eng.evaluate(now=200.0)
    assert _MET_BREACH.value(slo="t_rot") == base + 1
    # a fresh breach after recovery is a NEW edge
    for _ in range(3):
        eng.record("b", {"a": 190.0}, now=200.0)
    eng.evaluate(now=200.5)
    assert _MET_BREACH.value(slo="t_rot") == base + 2
    assert edges == [True, False, True]


def test_slo_synthetic_spans_excluded_from_burn():
    """HA-recovery back-dated spans (seed_recovered) must not torch the
    error budget: a promoted standby's synthetic timeline would
    otherwise read as massive latencies."""
    eng = SloEngine([SloSpec("t_synth", "submit", "dispatched", 99, 1.0,
                             windows=(60.0,))])
    rec = JobTraceRecorder(capacity=64, slo=eng)
    # synthetic replay of a job that "took" 50s
    rec.stamp(1, 0, "submit", 0.0, synthetic=True)
    rec.stamp(1, 0, "dispatched", 50.0, synthetic=True)
    w = eng.evaluate(now=50.0)[0]["windows"]["60"]
    assert w["count"] == 0 and not w["breaching"]
    # a real span IS recorded
    rec.stamp(2, 0, "submit", 51.0)
    rec.stamp(2, 0, "dispatched", 51.5)
    w = eng.evaluate(now=52.0)[0]["windows"]["60"]
    assert w["count"] == 1 and w["observed"] == 0.5


# ---------------------------------------------------------------------------
# scheduler wiring: cycle trace fields + steady-state recompile events
# ---------------------------------------------------------------------------

def test_cycle_trace_has_introspection_fields():
    _, sched, cluster = _build()
    for i in range(5):
        sched.submit(_spec(runtime=1e6), now=float(i))
        sched.schedule_cycle(now=float(i))
    tr = sched.cycle_trace.snapshot()[-1]
    for key in ("recompiles", "device_bytes", "device_peak_bytes",
                "device_buffers"):
        assert key in tr, f"cycle trace lost {key!r}"
    # warm cycles on repeated identical shapes pay nothing
    assert tr["recompiles"] == 0


def test_scheduler_emits_requeue_and_preemption_style_events():
    _, sched, cluster = _build()
    jid = sched.submit(_spec(cpu=2.0, runtime=1e6), now=0.0)
    sched.schedule_cycle(now=0.0)
    assert sched.requeue(jid, now=1.0) == ""  # "" = success
    types = [r["type"] for r in sched.events.since()]
    assert "requeue" in types
    rq = sched.events.since(type="requeue")[-1]
    assert rq["job_id"] == jid


# ---------------------------------------------------------------------------
# follower replication e2e (the cevents acceptance criterion)
# ---------------------------------------------------------------------------

def test_cevents_served_from_follower_e2e(tmp_path, capsys):
    """Leader emits fencing / flap / SLO-breach events; one replication
    poll later the STANDBY serves them over QueryEvents and cevents."""
    wal = WriteAheadLog(str(tmp_path / "leader.wal"))
    _, sched1, _ = _build(wal=wal)
    leader, lport = serve(sched1, tick_mode=True)
    _, sched2, _ = _build()
    standby, sport = serve(sched2, tick_mode=True, standby=True,
                           peer_address=f"127.0.0.1:{lport}")
    follower = HaFollower(standby, f"127.0.0.1:{lport}",
                          str(tmp_path / "standby.wal"),
                          poll_interval=999.0, miss_threshold=99)
    cli = None
    try:
        # the three event families the acceptance criterion names, from
        # their real emitters' shapes
        sched1.events.emit("fencing_rejection", "error", node="cn00",
                           detail="push fenced: epoch 1 < current 2")
        sched1.events.emit_node_transition("down", "cn01", now=100.0)
        sched1.events.emit_node_transition("up", "cn01", now=130.0)
        sched1._slo_event("submit-to-dispatch", 60.0, 3.5, True)

        assert follower.poll_once()
        assert sched2.events.remote_seq == sched1.events.last_seq

        cli = CtldClient(f"127.0.0.1:{sport}")  # DIRECT to the standby
        evs = cli.query_events(severity="warning").events
        got = {e.type: e for e in evs}
        assert {"fencing_rejection", "node_down", "node_flap",
                "slo_breach"} <= set(got)
        assert got["fencing_rejection"].severity == "error"
        assert got["node_flap"].node == "cn01"
        assert "30.0s after down" in got["node_flap"].detail
        assert got["slo_breach"].severity == "error"
        assert "burn=3.50" in got["slo_breach"].detail
        # type + cursor filters work over the wire
        only = cli.query_events(type="node_flap").events
        assert [e.type for e in only] == ["node_flap"]
        last = max(e.seq for e in evs)
        assert not cli.query_events(after_seq=last).events

        # a second poll is a no-op: the cursor dedups the refetch
        n0 = len(sched2.events.since())
        assert follower.poll_once()
        assert len(sched2.events.since()) == n0

        # and the operator CLI against the standby renders the table
        rc = crane_cli.main(["--server", f"127.0.0.1:{sport}",
                             "cevents", "--severity", "error"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fencing_rejection" in out and "slo_breach" in out
        assert "node_flap" not in out  # below the severity floor
    finally:
        if cli is not None:
            cli.close()
        follower.stop()
        standby.stop()
        leader.stop()
        wal.close()


# ---------------------------------------------------------------------------
# RPC + CLI surface: cexplain / cprofile / cstats --metrics
# ---------------------------------------------------------------------------

def test_explain_profile_and_metrics_filter_over_rpc(tmp_path, capsys):
    _, sched, _ = _build()
    held = sched.submit(_spec(held=True), now=0.0)
    server, port = serve(sched, tick_mode=True)
    addr = f"127.0.0.1:{port}"
    cli = None
    try:
        cli = CtldClient(addr)
        # explain_json rides QueryJobSummary
        doc = json.loads(cli.query_job_summary(job_id=held).explain_json)
        assert doc["reason"] == "Held" and doc["gate"] == "held"

        rc = crane_cli.main(["--server", addr, "cexplain", str(held)])
        out = capsys.readouterr().out
        assert rc == 0 and "blocked at: held" in out

        rc = crane_cli.main(["--server", addr, "cexplain", str(held),
                             "--json"])
        assert rc == 0
        assert json.loads(capsys.readouterr().out)["reason"] == "Held"

        # cprofile arms the window; a second arm is refused
        d = str(tmp_path / "prof")
        rc = crane_cli.main(["--server", addr, "cprofile", "-n", "2",
                             "--dir", d])
        assert rc == 0 and d in capsys.readouterr().out
        assert sched.profiler_window.status()["armed"] == 2
        rc = crane_cli.main(["--server", addr, "cprofile"])
        assert rc == 1
        assert "in progress" in capsys.readouterr().err

        # cstats --metrics PREFIX filters the family table
        rc = crane_cli.main(["--server", addr, "cstats", "--metrics",
                             "crane_jit"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "crane_jit_compiles_total" in out
        assert "crane_cycles_total" not in out
        rc = crane_cli.main(["--server", addr, "cstats", "--metrics",
                             "crane_nope"])
        assert rc == 1
        assert "no metric family" in capsys.readouterr().err
    finally:
        if cli is not None:
            cli.close()
        server.stop()
