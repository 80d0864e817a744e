"""Benchmark: scheduling decisions/sec of the placement solve on the TPU.

Shapes mirror BASELINE.json's north-star workload (100k pending jobs x 10k
nodes).  The baseline number is the reference's published ">100,000
scheduling decisions per second" (reference README_EN.md:29; see
BASELINE.md) — ``vs_baseline`` is measured decisions/sec divided by that.

Prints exactly ONE JSON line on stdout, stamped with the platform,
``device_kind`` and device count it ran on.  A run that finds no TPU
exits non-zero unless ``JAX_PLATFORMS=cpu`` was set explicitly (the CPU
is for counts and correctness, never for a device metric); a leg that
raises makes the exit code non-zero.

Env overrides: BENCH_JOBS, BENCH_NODES, BENCH_REPEATS, BENCH_SCHED_JOBS,
BENCH_SCHED_NODES.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BASELINE_DECISIONS_PER_SEC = 100_000.0


def _build_sched(num_jobs: int, num_nodes: int, wal_dir=None):
    """Cluster + scheduler at a reduced shape, shared by the cycle and
    commit benches; with ``wal_dir`` a REAL fsyncing WAL is attached so
    the traces carry honest durability-barrier counts."""
    from cranesched_tpu.ctld import (
        JobScheduler,
        JobSpec,
        MetaContainer,
        ResourceSpec,
        SchedulerConfig,
    )
    from cranesched_tpu.ctld.wal import WriteAheadLog

    rng = np.random.default_rng(1)
    meta = MetaContainer()
    for i in range(num_nodes):
        meta.add_node(
            f"b{i:05d}",
            meta.layout.encode(cpu=float(rng.integers(32, 129)),
                               mem_bytes=int(rng.integers(64, 513)) << 30,
                               is_capacity=True),
            partitions=(f"p{i % 4}",))
        meta.craned_up(i)
    wal = None
    if wal_dir is not None:
        wal = WriteAheadLog(os.path.join(wal_dir, "bench.wal"),
                            fsync=True)
    sched = JobScheduler(meta, SchedulerConfig(
        schedule_batch_size=num_jobs, backfill_max_jobs=num_jobs,
        solver=os.environ.get("BENCH_SCHED_SOLVER", "auto")),
        wal=wal)

    def submit(k, now):
        for _ in range(k):
            sched.submit(JobSpec(
                res=ResourceSpec(cpu=float(rng.integers(1, 17)),
                                 mem_bytes=int(rng.integers(1, 33)) << 30),
                node_num=int(rng.integers(1, 3)),
                time_limit=int(rng.integers(60, 86400)),
                partition=f"p{rng.integers(0, 4)}"), now=now)

    return sched, submit


def _measure_sched_cycle(num_jobs: int, num_nodes: int) -> dict:
    """One REAL scheduler cycle at a reduced shape: builds a cluster
    spread over four partitions, submits a queue, runs two cycles (the
    first pays jit compiles) and reports the second cycle's phase split
    straight from the cycle trace — the prelude/solve/commit numbers
    the device-resident mask table is accountable for.  A real
    fsyncing WAL (temp dir) is attached so ``wal_fsyncs_per_cycle``
    measures actual durability barriers under group commit."""
    import tempfile

    with tempfile.TemporaryDirectory() as wal_dir:
        sched, submit = _build_sched(num_jobs, num_nodes,
                                     wal_dir=wal_dir)
        # three cycles: the first pays the solver compiles, the second
        # the recompiles from the running-set bucket jumping off zero;
        # topping the queue back up between cycles holds every jit
        # shape constant, so the third cycle is the steady state the
        # trace should describe
        submit(num_jobs, 0.0)
        for c in range(3):
            sched.schedule_cycle(now=float(c + 1))
            submit(num_jobs - len(sched.pending), float(c + 1) + 0.5)
        trace = sched.cycle_trace.snapshot()[-1]
        sched.wal.close()
    out = {k: trace[k] for k in ("solver", "prelude_ms", "solve_ms",
                                 "commit_ms", "dispatch_ms", "total_ms",
                                 "num_streams", "wal_groups",
                                 "recompiles", "device_bytes",
                                 "device_peak_bytes", "device_buffers")
           if k in trace}
    out["jobs"] = num_jobs
    out["nodes"] = num_nodes
    out["wal_fsyncs_per_cycle"] = int(trace.get("wal_fsyncs", 0))
    # device-resident pipeline shape (ctld/resident.py): zero bytes /
    # "off" when the configured backend never acquires the resident
    # state (e.g. the native CPU solver)
    res = getattr(sched, "_resident", None)
    out["resident_mode"] = trace.get(
        "resident", (res.last_mode or "off") if res else "off")
    out["host_to_device_bytes_per_cycle"] = int(
        trace.get("h2d_bytes", 0) or 0)
    out["patch_overlap_share"] = round(
        res.overlap_share() if res else 0.0, 4)
    total = max(float(trace.get("total_ms", 0.0)), 1e-9)
    out["prelude_share"] = round(
        float(trace.get("prelude_ms", 0.0)) / total, 4)
    out["lock_held_share"] = round(
        (float(trace.get("prelude_ms", 0.0))
         + float(trace.get("commit_ms", 0.0))) / total, 4)
    return out


def _measure_commit(num_jobs: int = 10_000,
                    num_nodes: int = 1_024) -> dict:
    """Commit-path microbench: place ``num_jobs`` single-node jobs in
    one cycle against a real fsyncing WAL (temp dir — tmpfs on CI) and
    report the lock-held commit time plus the fsync count.  Group
    commit's acceptance bar: fsyncs per cycle == WAL groups (<= 3),
    not one per started job."""
    import tempfile

    # warm the jit caches on a throwaway scheduler with the SAME shapes
    # so the measured instance's first cycle — an empty cluster taking
    # the full placed wave — is commit-dominated, not compile-dominated
    warm, warm_submit = _build_sched(num_jobs, num_nodes)
    warm_submit(num_jobs, 0.0)
    warm.schedule_cycle(now=1.0)
    with tempfile.TemporaryDirectory() as wal_dir:
        sched, submit = _build_sched(num_jobs, num_nodes,
                                     wal_dir=wal_dir)
        wal = sched.wal
        submit(num_jobs, 0.0)
        f0, g0 = wal.fsync_total, wal.groups_total
        sched.schedule_cycle(now=1.0)
        trace = sched.cycle_trace.snapshot()[-1]
        fsyncs = wal.fsync_total - f0
        groups = wal.groups_total - g0
        wal.close()
    return {
        "jobs": num_jobs, "nodes": num_nodes,
        "placed": int(trace.get("placed", 0)),
        "commit_ms": trace.get("commit_ms"),
        "dispatch_ms": trace.get("dispatch_ms"),
        "total_ms": trace.get("total_ms"),
        "wal_fsyncs": int(fsyncs),
        "wal_groups": int(groups),
        "fsyncs_equal_groups": bool(fsyncs == groups),
        "groups_le_3": bool(groups <= 3),
    }


def _build_churn_sched(num_jobs: int, num_nodes: int,
                       incremental: bool, solver: str = "auto",
                       resident: bool = True, job_trace: bool = True):
    """Small cluster + big queue for the churn scenario: after the
    first cycle fills the nodes, the residual queue is steady-state
    pending — exactly the shape where the incremental prelude should
    scale with dirty rows, not queue depth."""
    from cranesched_tpu.ctld import (
        JobScheduler,
        JobSpec,
        MetaContainer,
        ResourceSpec,
        SchedulerConfig,
    )

    meta = MetaContainer()
    for i in range(num_nodes):
        meta.add_node(
            f"c{i:05d}",
            meta.layout.encode(cpu=64.0, mem_bytes=256 << 30,
                               is_capacity=True),
            partitions=("default",))
        meta.craned_up(i)
    # backfill off: future-start reservations would re-solve every
    # cycle and keep the no-op fingerprint from ever arming — the
    # scenario measures the immediate-fit steady state
    sched = JobScheduler(meta, SchedulerConfig(
        schedule_batch_size=num_jobs, backfill=False,
        incremental=incremental, solver=solver,
        resident_state=resident, job_trace=job_trace))
    rng = np.random.default_rng(42)

    def spec():
        return JobSpec(
            res=ResourceSpec(cpu=float(rng.integers(1, 9)),
                             mem_bytes=int(rng.integers(1, 17)) << 30),
            node_num=1,
            time_limit=int(rng.integers(3600, 86400)))

    return sched, spec, rng


def _measure_churn(num_jobs: int = 100_000, num_nodes: int = 512,
                   churn: float = 0.01, cycles: int = 5) -> dict:
    """The incremental-cycle acceptance scenario (ISSUE 8): a steady
    queue with ``churn`` fraction cancelled+resubmitted per tick, run
    twice — PendingTable path vs ``incremental=False`` full rebuild —
    with identical seeds.  Reports the median prelude per cycle for
    both, the dirty-row counts, and the cost of a fingerprint-hit idle
    tick relative to a full cycle."""

    def run(incremental: bool, solver: str = "auto",
            resident: bool = True, job_trace: bool = True) -> dict:
        sched, spec, rng = _build_churn_sched(num_jobs, num_nodes,
                                              incremental, solver,
                                              resident, job_trace)
        for _ in range(num_jobs):
            sched.submit(spec(), now=0.0)
        started = len(sched.schedule_cycle(now=1.0))  # fills + compiles
        sched.schedule_cycle(now=2.0)  # steady-state (zero-place) shape
        k = max(int(len(sched.pending) * churn), 1)
        preludes, totals, dirty = [], [], []
        h2d_bytes, h2d_rows, dirty_nodes, modes = [], [], [], []
        trace_ms, recompiles, flight_ms = [], [], []
        from cranesched_tpu.obs import introspect
        introspect_s0 = introspect.self_time_s()
        now = 3.0
        for _ in range(cycles):
            pend_ids = list(sched.pending.keys())
            for i in rng.choice(len(pend_ids), size=k, replace=False):
                sched.cancel(int(pend_ids[int(i)]), now=now)
            for _ in range(k):
                sched.submit(spec(), now=now)
            ts0 = (sched.jobtrace.self_time_s
                   if sched.jobtrace is not None else 0.0)
            fs0 = sched.flight.self_time_s
            sched.schedule_cycle(now=now + 0.5)
            flight_ms.append((sched.flight.self_time_s - fs0) * 1e3)
            if sched.jobtrace is not None:
                trace_ms.append(
                    (sched.jobtrace.self_time_s - ts0) * 1e3)
            tr = sched.cycle_trace.snapshot()[-1]
            preludes.append(float(tr.get("prelude_ms", 0.0)))
            totals.append(float(tr.get("total_ms", 0.0)))
            dirty.append(int(tr.get("dirty_jobs") or 0))
            h2d_bytes.append(int(tr.get("h2d_bytes") or 0))
            h2d_rows.append(int(tr.get("h2d_rows") or 0))
            dirty_nodes.append(int(tr.get("dirty_nodes") or 0))
            modes.append(tr.get("resident", "off"))
            recompiles.append(int(tr.get("recompiles") or 0))
            now += 1.0
        introspect_ms = (introspect.self_time_s() - introspect_s0) * 1e3
        # idle tick: the last cycle placed nothing, so the fingerprint
        # is armed on the incremental path; the next no-event cycle
        # should short-circuit before building anything
        skipped0 = sched.stats.get("skipped_cycles", 0)
        t0 = time.perf_counter()
        sched.schedule_cycle(now=now)
        idle_ms = (time.perf_counter() - t0) * 1e3
        res = sched._resident
        return {
            "num_dims": int(sched.meta.layout.num_dims),
            "first_cycle_started": started,
            "prelude_ms": round(float(np.median(preludes)), 3),
            "total_ms": round(float(np.median(totals)), 3),
            "dirty_rows": int(np.median(dirty)),
            "dirty_nodes": int(np.median(dirty_nodes)),
            "h2d_bytes_per_cycle": int(np.median(h2d_bytes)),
            "h2d_rows_per_cycle": int(np.median(h2d_rows)),
            "resident_modes": modes,
            "full_rebuilds": int(res.full_rebuilds),
            "patch_cycles": int(res.patch_cycles),
            "ledger_cycles": int(res.ledger_cycles),
            "patch_overlap_share": round(res.overlap_share(), 4),
            "idle_tick_ms": round(idle_ms, 3),
            "skipped_cycles": (sched.stats.get("skipped_cycles", 0)
                               - skipped0),
            "trace_ms": round(float(np.median(trace_ms)), 4)
            if trace_ms else 0.0,
            "flight_ms": round(float(np.median(flight_ms)), 4)
            if flight_ms else 0.0,
            "recompiles": recompiles,
            "introspect_ms": round(introspect_ms, 4),
        }

    # persistent XLA compilation cache (ISSUE 16): report this leg's
    # hit rate — warm runs of the same bench shapes should hit, proving
    # the cache works across processes
    from cranesched_tpu.obs.flight import (
        enable_xla_cache, xla_cache_stats)
    enable_xla_cache()
    xla0 = xla_cache_stats()

    inc = run(True)
    base = run(False)
    # tracing-overhead leg (ISSUE 12): the in-cycle stamp cost (fresh
    # eligible/placed/dispatched edges on the churned k jobs) must
    # stay <= 2% of the churn cycle.  The share is the recorder's own
    # accumulated self-time inside schedule_cycle over the cycle wall
    # time — a direct measurement; differencing whole trace-on/off
    # runs at this shape just reads scheduler jitter (observed both
    # signs at up to 20% on identical seeds).  A trace-off leg still
    # runs as the jitter-bounded sanity context.
    tr_off = run(True, job_trace=False)
    on_ms = max(inc["total_ms"], 1e-9)
    tracing = {
        "cycle_ms_trace_on": inc["total_ms"],
        "cycle_ms_trace_off": tr_off["total_ms"],
        "trace_ms_per_cycle": inc["trace_ms"],
        "trace_overhead_share": round(inc["trace_ms"] / on_ms, 4),
    }
    tracing["overhead_ok"] = bool(
        tracing["trace_overhead_share"] <= 0.02)
    # flight-recorder leg (ISSUE 16): the always-on phase ring stamps
    # ~6 entries per cycle inside schedule_cycle — its accumulated
    # self-time must stay <= 1% of the churn cycle wall time (same
    # direct self-time measurement as the tracing leg).  The XLA cache
    # stats ride along so tier1_perf can assert the hit rate is
    # reported (and a warm second run shows hits > 0).
    xla1 = xla_cache_stats()
    flight = {
        "flight_ms_per_cycle": inc["flight_ms"],
        "flight_overhead_share": round(inc["flight_ms"] / on_ms, 4),
        "xla_cache": {
            "enabled": xla1["enabled"],
            "dir": xla1["dir"],
            "hits": xla1["hits"] - xla0["hits"],
            "misses": xla1["misses"] - xla0["misses"],
            "entries": xla1["entries"],
            "hit_rate": xla1["hit_rate"],
        },
    }
    flight["overhead_ok"] = bool(
        flight["flight_overhead_share"] <= 0.01)
    # introspection-plane leg (ISSUE 14): warm churn cycles must pay
    # ZERO fresh jit compiles (the bucketed-padding contract, now
    # measured rather than assumed), and the observer probes + device
    # memory sampling must cost <= 2% of the cycle.  Same direct
    # self-time measurement as the tracing leg, same jitter rationale.
    steady_ms = max(inc["total_ms"] * cycles, 1e-9)
    introspection = {
        "recompiles_per_cycle": inc["recompiles"],
        "zero_steady_recompiles": bool(
            all(r == 0 for r in inc["recompiles"])),
        "introspect_ms_total": inc["introspect_ms"],
        "introspect_overhead_share": round(
            inc["introspect_ms"] / steady_ms, 4),
    }
    introspection["overhead_ok"] = bool(
        introspection["introspect_overhead_share"] <= 0.02)
    # resident-state acceptance legs (ISSUE 11): same seed/event stream
    # on the device scan solver, resident patching vs per-cycle rebuild
    res_on = run(True, solver="device", resident=True)
    res_off = run(True, solver="device", resident=False)
    full_ms = max(inc["total_ms"], 1e-9)
    from cranesched_tpu.ctld.resident import (
        full_state_bytes, padded_rows, patch_row_bytes)
    num_dims = res_on["num_dims"]
    steady = res_on["resident_modes"]
    # BENCH_r10 anomaly (ISSUE 17): every steady churn cycle here has
    # an EMPTY delta — nothing places in steady state, so no node row
    # is dirtied and the only H2D traffic is the time-dependent [N]
    # cost ledger (exactly 4*N bytes).  Those cycles used to report
    # mode "patch", which read as patch traffic with dirty_nodes=0 and
    # a speedup of ~1.0 against a bound derived from a phantom dirty
    # row.  They now report mode "ledger", and an all-ledger steady
    # state is held to the EXACT ledger size instead of the padded
    # dirty-row formula.
    ledger_only = bool(steady and all(m == "ledger" for m in steady))
    if ledger_only:
        bound = 4 * num_nodes
    else:
        # dirty-rows bound: the rows the delta snapshot itself re-read
        # this cycle (trace dirty_nodes) plus the full [N] cost seed —
        # a silent full-rebuild regression blows straight past it
        bound = (padded_rows(max(res_on["dirty_nodes"], 1), num_nodes)
                 * patch_row_bytes(num_dims) + 4 * num_nodes)
    resident = {
        "cycle_ms": res_on["total_ms"],
        "rebuild_cycle_ms": res_off["total_ms"],
        "speedup_vs_rebuild": round(
            res_off["total_ms"] / max(res_on["total_ms"], 1e-9), 2),
        "h2d_bytes_per_cycle": res_on["h2d_bytes_per_cycle"],
        "h2d_rows_per_cycle": res_on["h2d_rows_per_cycle"],
        "dirty_nodes": res_on["dirty_nodes"],
        "dirty_bound_bytes": int(bound),
        "full_state_bytes": int(
            full_state_bytes(num_nodes, num_dims)),
        # "no steady cycle fell back to a rebuild" — ledger counts:
        # it ships strictly less than a patch
        "steady_state_patch": bool(
            steady and all(m in ("patch", "ledger") for m in steady)),
        "steady_state_ledger_only": ledger_only,
        "steady_state_modes": {
            m: steady.count(m) for m in sorted(set(steady))},
        "full_rebuilds": res_on["full_rebuilds"],
        "patch_cycles": res_on["patch_cycles"],
        "ledger_cycles": res_on["ledger_cycles"],
        "patch_overlap_share": res_on["patch_overlap_share"],
        "placements_match": bool(
            res_on["first_cycle_started"]
            == res_off["first_cycle_started"]
            == inc["first_cycle_started"]),
    }
    return {
        "jobs": num_jobs, "nodes": num_nodes, "churn": churn,
        "cycles": cycles,
        "incremental": inc, "full_rebuild": base,
        "resident": resident, "tracing": tracing,
        "introspection": introspection, "flight": flight,
        # same seed + same event stream: identical first-wave placement
        # is the in-bench parity check (the real oracle lives in
        # tests/test_delta_cycle.py)
        "placements_match": bool(inc["first_cycle_started"]
                                 == base["first_cycle_started"]),
        "prelude_speedup": round(
            base["prelude_ms"] / max(inc["prelude_ms"], 1e-9), 2),
        "idle_tick_share": round(inc["idle_tick_ms"] / full_ms, 4),
        "idle_skipped": bool(inc["skipped_cycles"] >= 1),
    }


def _build_gang_sched(num_jobs: int, num_nodes: int, block: int):
    """Gang-heavy cluster + scheduler for the topology scenario; the
    same seeded queue is replayed with and without a topology so the
    cycle-time delta is apples to apples.  ``block=0`` = no topology."""
    from cranesched_tpu.ctld import (
        JobScheduler,
        JobSpec,
        MetaContainer,
        ResourceSpec,
        SchedulerConfig,
    )

    meta = MetaContainer()
    for i in range(num_nodes):
        meta.add_node(
            f"t{i:05d}",
            meta.layout.encode(cpu=64.0, mem_bytes=256 << 30,
                               is_capacity=True),
            partitions=("default",))
        meta.craned_up(i)
    if block:
        from cranesched_tpu.topo.model import Topology
        meta.set_topology(Topology.uniform_blocks(num_nodes, block))
    # solver="device": the base run must use the device scan (the same
    # solver family solve_greedy_topo extends) — comparing the topo scan
    # against the native C++ treap would measure backend choice, not the
    # cost of the topology restriction
    sched = JobScheduler(meta, SchedulerConfig(
        schedule_batch_size=num_jobs, backfill=False,
        max_nodes_per_job=8, solver="device"))
    rng = np.random.default_rng(7)

    def submit(k, now):
        for _ in range(k):
            sched.submit(JobSpec(
                res=ResourceSpec(cpu=float(rng.integers(1, 9)),
                                 mem_bytes=int(rng.integers(1, 17)) << 30),
                node_num=int(rng.integers(2, 9)),
                time_limit=int(rng.integers(60, 3600))), now=now)

    return sched, submit


def _measure_topology(num_jobs: int = 256, num_nodes: int = 512,
                      block: int = 64) -> dict:
    """Topology overhead + locality: the same gang-heavy queue solved
    with and without a generated block topology.  Reports the
    intra-block placement rate and the topo solve's cycle/solve-time
    ratio vs the plain solve (acceptance: <= 1.05)."""

    def run(with_topo):
        sched, submit = _build_gang_sched(
            num_jobs, num_nodes, block if with_topo else 0)
        submit(num_jobs, 0.0)
        traces = []
        for c in range(10):
            sched.schedule_cycle(now=float(c + 1))
            submit(num_jobs - len(sched.pending), float(c + 1) + 0.5)
            traces.append(sched.cycle_trace.snapshot()[-1])
        steady = traces[5:]   # first cycles pay the jit compiles
        # min over the steady cycles: the least noise-contaminated
        # sample — cycle walls here are ~15 ms, well inside OS jitter
        return sched, {
            "solver": steady[-1].get("solver"),
            "solve_ms": float(min(
                t.get("solve_ms", 0.0) for t in steady)),
            "total_ms": float(min(
                t.get("total_ms", 0.0) for t in steady)),
        }

    base_sched, base = run(False)
    topo_sched, topo = run(True)
    in_block = int(topo_sched.stats.get("topo_in_block_total", 0))
    cross = int(topo_sched.stats.get("topo_cross_block_total", 0))
    gangs = max(in_block + cross, 1)
    return {
        "jobs": num_jobs, "nodes": num_nodes, "block": block,
        "base": base, "topo": topo,
        "intra_block_rate": round(in_block / gangs, 4),
        "cross_block_gangs": cross,
        "solve_overhead": round(
            topo["solve_ms"] / max(base["solve_ms"], 1e-9), 3),
        "cycle_overhead": round(
            topo["total_ms"] / max(base["total_ms"], 1e-9), 3),
    }


# one controller shard in its own PROCESS: the federated submit-
# throughput comparison must measure real parallelism, and in-process
# shards would share one GIL.  The script serves a full shard (sim node
# plane + background cycles, so queries run against a concurrent solve)
# and prints READY when bound.
_SHARD_SERVER_SRC = r"""
import json, sys, time
cfg = json.loads(sys.argv[1])
from cranesched_tpu.craned.sim import SimCluster
from cranesched_tpu.ctld import JobScheduler, MetaContainer, \
    SchedulerConfig
from cranesched_tpu.fed.shardmap import ShardMap
from cranesched_tpu.rpc.server import serve
meta = MetaContainer()
nid = 0
for part in sorted(cfg["partitions"]):
    for i in range(cfg["partitions"][part]):
        meta.add_node("%s-%s-n%04d" % (cfg["name"], part, i),
                      meta.layout.encode(cpu=16.0, mem_bytes=64 << 30,
                                         memsw_bytes=64 << 30,
                                         is_capacity=True),
                      partitions=(part,))
        meta.craned_up(nid)
        nid += 1
sched = JobScheduler(meta, SchedulerConfig(backfill=False))
sim = SimCluster(sched)
sim.wire(sched)
# boot-time jit warmup: pre-trace the priority model for every queue
# bucket the storm will cross, so no XLA compile ever runs under the
# server lock mid-measurement (see JobScheduler.warm_jit_buckets)
sched.warm_jit_buckets(cfg.get("warm_pending", 8192),
                       max_running=16 * nid)
shard_map = (ShardMap.from_doc(cfg["shards"])
             if cfg.get("shards") else None)
server, port = serve(sched, sim=sim,
                     address="127.0.0.1:%d" % cfg["port"],
                     cycle_interval=cfg.get("cycle_interval", 0.05),
                     shard_name=cfg["name"], shard_map=shard_map)
print("READY", port, flush=True)
while True:
    time.sleep(1)
"""

# query-latency measurer in its OWN process: inside the storming bench
# process the reader thread shares the GIL with protobuf-serializing
# submit threads, which inflates measured latency ~100x with artifacts
# that are the bench client's, not the server's.  Runs until a line
# arrives on stdin, then prints the sample list as JSON.
_QUERY_CLIENT_SRC = r"""
import json, sys, threading, time
from cranesched_tpu.rpc.client import CtldClient
cli = CtldClient(sys.argv[1], timeout=60.0)
stop = threading.Event()
threading.Thread(target=lambda: (sys.stdin.readline(), stop.set()),
                 daemon=True).start()
lat = []
while not stop.is_set():
    t0 = time.perf_counter()
    cli.query_job_summary()
    lat.append((time.perf_counter() - t0) * 1e3)
print(json.dumps(lat), flush=True)
cli.close()
"""


def _measure_federation(n_specs: int = 4_000,
                        nodes_per_part: int = 32) -> dict:
    """Federated control-plane numbers (ISSUE 15): submit throughput of
    two subprocess shards over disjoint partitions vs ONE controller
    over the union, query p99 under the concurrent background solve,
    and the arbiter's share of placements from the closed-loop
    federation sim.

    Method: each controller is measured IN ISOLATION (one server
    process alive at a time, identical client concurrency and identical
    total submitted work per scenario), and the federated figure is the
    sum of the per-shard isolated rates.  Shards share no state and
    deploy on separate controller hosts, so the aggregate is additive
    by construction; running both shard processes concurrently on this
    host would only time-slice its cores and measure the bench box, not
    the control plane."""
    import socket
    import subprocess
    import threading

    from cranesched_tpu.rpc import crane_pb2 as pb
    from cranesched_tpu.rpc.client import CtldClient

    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn(cfg):
        proc = subprocess.Popen(
            [sys.executable, "-c", _SHARD_SERVER_SRC, json.dumps(cfg)],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"shard {cfg['name']} died rc={proc.returncode}")
            try:
                socket.create_connection(
                    ("127.0.0.1", cfg["port"]), timeout=0.5).close()
                return proc
            except OSError:
                time.sleep(0.1)
        proc.kill()
        raise RuntimeError(f"shard {cfg['name']} never bound")

    def spec(partition):
        return pb.JobSpec(
            res=pb.ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                memsw_bytes=1 << 30),
            partition=partition, sim_runtime=5.0)

    def storm(address, partitions, total_specs):
        """Saturate ONE live controller: one submit thread per entry in
        `partitions` (identical client concurrency in every scenario),
        plus a dedicated query-client PROCESS measuring read latency
        while the server is solving + absorbing writes.  A warmup wave
        runs first so the background cycles pay their jit compiles
        before the clock starts."""
        per = total_specs // len(partitions)
        walls = [0.0] * len(partitions)
        accepted = [0] * len(partitions)

        warm = CtldClient(address, timeout=60.0)
        for _ in range(per // 250):
            # full-volume warmup: walk the pending queue through every
            # padding bucket the measured storm will hit
            warm.submit_many([spec(partitions[0])] * 250)
            time.sleep(0.4)
        time.sleep(4.0)  # background cycles compile + settle
        warm.close()

        qp = subprocess.Popen(
            [sys.executable, "-c", _QUERY_CLIENT_SRC, address],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)
        time.sleep(0.3)  # let the query client connect + start looping

        def submit(i, partition):
            cli = CtldClient(address, timeout=60.0)
            batch = [spec(partition)] * 250
            t0 = time.perf_counter()
            for _ in range(per // 250):
                replies = cli.submit_many(batch).replies
                accepted[i] += sum(1 for r in replies if r.job_id)
            walls[i] = time.perf_counter() - t0
            cli.close()

        threads = [threading.Thread(target=submit, args=(i, p))
                   for i, p in enumerate(partitions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        qp.stdin.write("stop\n")
        qp.stdin.flush()
        q_lat = json.loads(qp.stdout.readline() or "[]")
        qp.wait(timeout=30)
        total = sum(accepted)
        wall = max(walls)
        lat = np.asarray(q_lat) if q_lat else np.zeros(1)
        return {
            "jobs_accepted": total,
            "wall_s": round(wall, 3),
            "submits_per_s": round(total / wall, 1) if wall else 0.0,
            "query_samples": len(q_lat),
            "query_p50_ms": round(float(np.percentile(lat, 50)), 2),
            "query_p99_ms": round(float(np.percentile(lat, 99)), 2),
        }

    ports = {"solo": free_port(), "east": free_port(),
             "west": free_port()}
    shards_doc = [
        {"name": "east", "partitions": ["batch"],
         "address": f"127.0.0.1:{ports['east']}", "followers": []},
        {"name": "west", "partitions": ["gpu"],
         "address": f"127.0.0.1:{ports['west']}", "followers": []},
    ]

    def isolated(cfg, partitions, total_specs):
        proc = spawn(cfg)
        try:
            return storm(f"127.0.0.1:{cfg['port']}", partitions,
                         total_specs)
        finally:
            proc.kill()
            proc.wait()

    # one controller over the union of partitions, saturated by two
    # submit threads (one per partition)
    single = isolated(
        {"name": "solo", "port": ports["solo"],
         "partitions": {"batch": nodes_per_part,
                        "gpu": nodes_per_part}},
        ["batch", "gpu"], n_specs)
    # each shard alone, same two-thread saturation, half the work each
    # (the same n_specs total lands on the federation)
    east = isolated(
        {"name": "east", "port": ports["east"],
         "partitions": {"batch": nodes_per_part},
         "shards": shards_doc},
        ["batch", "batch"], n_specs // 2)
    west = isolated(
        {"name": "west", "port": ports["west"],
         "partitions": {"gpu": nodes_per_part},
         "shards": shards_doc},
        ["gpu", "gpu"], n_specs // 2)
    federated = {
        "jobs_accepted": east["jobs_accepted"] + west["jobs_accepted"],
        "submits_per_s": round(
            east["submits_per_s"] + west["submits_per_s"], 1),
        "query_p50_ms": max(east["query_p50_ms"],
                            west["query_p50_ms"]),
        "query_p99_ms": max(east["query_p99_ms"],
                            west["query_p99_ms"]),
        "per_shard": {"east": east, "west": west},
    }

    # arbiter share from the closed-loop federation sim (the same drill
    # REPLAY_r07 records, including the mid-storm shard SIGKILL)
    from cranesched_tpu.replay import replay_federation
    drill = replay_federation(0.1, np.random.default_rng(0))
    locals_finished = drill["jobs_submitted"] - drill["gangs"]
    members = drill["jobs_finished"] - locals_finished
    speedup = (federated["submits_per_s"]
               / max(single["submits_per_s"], 1e-9))
    return {
        "specs_per_scenario": n_specs,
        "nodes_per_partition": nodes_per_part,
        "method": "each controller saturated in isolation (one server "
                  "process at a time, identical client concurrency); "
                  "federated = sum of per-shard isolated rates — "
                  "shards share nothing and run on separate hosts",
        "single": single,
        "federated": federated,
        "submit_speedup": round(speedup, 2),
        "speedup_ge_2x": bool(speedup >= 2.0),
        "query_p99_lt_50ms": bool(
            federated["query_p99_ms"] < 50.0),
        "arbiter": {
            "gang_share_submitted": drill["gang_share"],
            "commits": drill["gang_commits"],
            "aborts": drill["gang_aborts"],
            "members_placed": members,
            "arbiter_share_of_placements": round(
                members / max(drill["jobs_finished"], 1), 3),
            "ledger_ok": drill["ok"],
        },
    }


# one rank of the multi-host solve: loads the shared problem, slices
# its node slab, bootstraps a ProcessMesh over the parent's rendezvous
# (CRANE_RENDEZVOUS/_TOKEN env), runs the solve twice — cold (pays the
# two per-shape jit compiles) and warm on a rebuilt slab state — and
# reports the warm wall plus its fence share from the mesh histogram.
_MULTIHOST_CHILD_SRC = r"""
import json, os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from cranesched_tpu.models.solver import make_cluster_state
from cranesched_tpu.parallel.distributed import (
    _MET_FENCE, bootstrap_process_mesh, solve_greedy_sharded_classes_mp)

rank = int(os.environ["CRANE_MP_RANK"])
nprocs = int(os.environ["CRANE_MP_NPROCS"])
pb = dict(np.load(sys.argv[1]))
max_nodes = int(pb.pop("max_nodes"))
n = pb["avail"].shape[0]
slab = n // nprocs
lo, hi = rank * slab, (rank + 1) * slab
jargs = [jnp.asarray(pb[k]) for k in
         ("req", "node_num", "time_limit", "valid", "job_class")]
cmask = jnp.asarray(pb["class_masks"][:, lo:hi])


def slab_state():
    return make_cluster_state(pb["avail"][lo:hi], pb["total"][lo:hi],
                              pb["alive"][lo:hi], pb["cost"][lo:hi])


def fence_totals():
    return [sum(v[k] for v in _MET_FENCE.snapshot().values())
            for k in ("count", "sum")]


pmesh = bootstrap_process_mesh(rank, nprocs, slab)
t0 = time.perf_counter()
p, s = solve_greedy_sharded_classes_mp(
    pmesh, slab_state(), *jargs, cmask, max_nodes=max_nodes)
jax.block_until_ready((p.placed, s.avail))
cold_s = time.perf_counter() - t0
f0 = fence_totals()
t0 = time.perf_counter()
p, s = solve_greedy_sharded_classes_mp(
    pmesh, slab_state(), *jargs, cmask, max_nodes=max_nodes)
jax.block_until_ready((p.placed, s.avail))
warm_s = time.perf_counter() - t0
f1 = fence_totals()
print(json.dumps({
    "rank": rank, "mesh": pmesh.describe(),
    "cold_s": round(cold_s, 4), "warm_s": round(warm_s, 4),
    "fence_count": int(f1[0] - f0[0]),
    "fence_s": round(f1[1] - f0[1], 4),
    "placed": np.asarray(p.placed).tolist(),
    "nodes": np.asarray(p.nodes).tolist(),
    "reason": np.asarray(p.reason).tolist(),
    "avail": np.asarray(s.avail).tolist()}), flush=True)
pmesh.close()
"""


def _measure_multihost(num_jobs: int = 512, num_nodes: int = 256,
                       num_classes: int = 8, nprocs: int = 2,
                       local_devices: int = 4,
                       max_nodes: int = 2) -> dict:
    """First multi-host solve number (ISSUE 17): ``nprocs`` real OS
    processes — separate jax runtimes with ``local_devices`` forced
    host devices each, node slabs split between them — bootstrap over
    a RendezvousServer and run the hierarchical
    ``solve_greedy_sharded_classes_mp``.  The CI stand-in for a pod
    slice: same code path, CPU devices, rendezvous on loopback.

    Reports the warm per-cycle wall (max over ranks — the solve
    completes when the slowest rank does), its host-fence share, and
    asserts bit-exact parity against the single-process
    ``solve_greedy_sharded_classes`` oracle computed in THIS process."""
    import subprocess
    import tempfile

    import jax
    import jax.numpy as jnp

    from cranesched_tpu.models.solver import make_cluster_state
    from cranesched_tpu.ops.resources import ResourceLayout
    from cranesched_tpu.parallel.sharded import (
        make_node_mesh,
        shard_cluster_state,
        solve_greedy_sharded_classes,
    )
    from cranesched_tpu.rpc.rendezvous import RendezvousServer

    num_nodes -= num_nodes % (nprocs * local_devices)  # even slabs
    rng = np.random.default_rng(17)
    lay = ResourceLayout()
    total = np.stack([
        lay.encode(cpu=int(rng.integers(8, 65)),
                   mem_bytes=int(rng.integers(16, 257)) << 30,
                   is_capacity=True)
        for _ in range(num_nodes)])
    used = np.stack([
        lay.encode(cpu=float(rng.integers(0, 8)),
                   mem_bytes=int(rng.integers(0, 8)) << 30)
        for _ in range(num_nodes)])
    pb = dict(
        avail=total - np.minimum(used, total), total=total,
        alive=rng.random(num_nodes) >= 0.05,
        cost=rng.random(num_nodes).astype(np.float32) * 10,
        req=np.stack([
            lay.encode(cpu=float(rng.integers(1, 17)),
                       mem_bytes=int(rng.integers(1, 33)) << 30)
            for _ in range(num_jobs)]),
        node_num=rng.integers(1, max_nodes + 1,
                              size=num_jobs).astype(np.int32),
        time_limit=rng.integers(60, 86400,
                                size=num_jobs).astype(np.int32),
        valid=(rng.random(num_jobs) > 0.05),
        job_class=rng.integers(0, num_classes,
                               size=num_jobs).astype(np.int32),
        class_masks=(rng.random((num_classes, num_nodes)) > 0.25))

    # single-process oracle over this process's own device mesh
    mesh = make_node_mesh()
    state = make_cluster_state(pb["avail"], pb["total"], pb["alive"],
                               pb["cost"])
    p_ref, s_ref = solve_greedy_sharded_classes(
        shard_cluster_state(state, mesh), jnp.asarray(pb["req"]),
        jnp.asarray(pb["node_num"]), jnp.asarray(pb["time_limit"]),
        jnp.asarray(pb["valid"]), jnp.asarray(pb["job_class"]),
        jnp.asarray(pb["class_masks"]), mesh, max_nodes=max_nodes)
    jax.block_until_ready(p_ref.placed)

    server = RendezvousServer(token="bench-mh", nranks=nprocs, epoch=1)
    port = server.start("127.0.0.1:0")
    procs, outs = [], []
    with tempfile.TemporaryDirectory() as tmp:
        npz = os.path.join(tmp, "problem.npz")
        np.savez(npz, max_nodes=max_nodes, **pb)
        try:
            for rank in range(nprocs):
                env = dict(os.environ)
                # the children are the CPU stand-in for a pod slice
                env.update({
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": ("--xla_force_host_platform_device_"
                                  f"count={local_devices}"),
                    "CRANE_RENDEZVOUS": f"127.0.0.1:{port}",
                    "CRANE_RENDEZVOUS_TOKEN": "bench-mh",
                    "CRANE_MP_RANK": str(rank),
                    "CRANE_MP_NPROCS": str(nprocs),
                })
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", _MULTIHOST_CHILD_SRC, npz],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, env=env))
            for p in procs:
                out, err = p.communicate(timeout=540)
                if p.returncode != 0:
                    raise RuntimeError(
                        f"multihost rank died rc={p.returncode}: "
                        f"{err[-2000:]}")
                outs.append(json.loads(out.strip().splitlines()[-1]))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            server.stop()

    # every rank computes the same global placements; they must match
    # the single-process oracle bit for bit (the acceptance contract —
    # a multi-host number for a DIFFERENT schedule would be worthless)
    ref_placed = np.asarray(p_ref.placed).tolist()
    ref_nodes = np.asarray(p_ref.nodes).tolist()
    ref_reason = np.asarray(p_ref.reason).tolist()
    parity = all(o["placed"] == ref_placed and o["nodes"] == ref_nodes
                 and o["reason"] == ref_reason for o in outs)
    avail_mp = np.concatenate([np.asarray(o["avail"]) for o in outs])
    parity = parity and bool(
        np.array_equal(avail_mp, np.asarray(s_ref.avail)))
    if not parity:
        raise AssertionError(
            "multi-host solve diverged from the single-process oracle")
    warm = max(o["warm_s"] for o in outs)
    fence_s = max(o["fence_s"] for o in outs)
    return {
        "jobs": num_jobs, "nodes": num_nodes, "classes": num_classes,
        "max_nodes": max_nodes,
        "procs": nprocs, "local_devices_per_proc": local_devices,
        "mesh": outs[0]["mesh"],
        "cold_cycle_s": round(max(o["cold_s"] for o in outs), 4),
        "warm_cycle_s": round(warm, 4),
        "decisions_per_sec": round(num_jobs / max(warm, 1e-9), 1),
        "fence_count_per_cycle": outs[0]["fence_count"],
        "fence_seconds_per_cycle": round(fence_s, 4),
        "fence_share": round(fence_s / max(warm, 1e-9), 4),
        "parity_with_single_process": True,
        "placed": int(sum(ref_placed)),
        "note": "CPU pod-slice stand-in: real processes + rendezvous "
                "fences on loopback; on TPU the same path rides ICI "
                "inside slabs and the host fence between hosts",
    }


def _measure_rebalance(n_jobs: int = 600,
                       nodes_per_part: int = 24) -> dict:
    """Elastic-federation handoff numbers (ISSUE 18): seal a LOADED
    partition on one shard mid-storm and hand it to another — measure
    the submit-outage window (seal→flip, the only interval where the
    partition refuses work), the per-job handoff cost of the
    seal→export→import→flip→commit sequence, and one gossip round of
    the cluster-wide UsageBook.  The run audits itself BY NAME across
    shards afterwards: a handoff that loses or doubles a single job is
    a failed measurement, not a slow one."""
    import shutil
    import tempfile

    from cranesched_tpu.ctld.defs import JobSpec, ResourceSpec
    from cranesched_tpu.fed.sim import FederatedCluster
    from cranesched_tpu.fed.usage import GlobalLimits

    tmp = tempfile.mkdtemp(prefix="crane-rebalance-bench-")
    try:
        fc = FederatedCluster(
            {"east": {"batch": nodes_per_part,
                      "debug": max(nodes_per_part // 4, 2)},
             "west": {"gpu": nodes_per_part}},
            cpu=16.0, mem_gb=64, wal_dir=tmp,
            global_limits=GlobalLimits(
                max_submit_jobs_per_user=n_jobs * 2),
            publish_slack=32)
        # waves sized to the publish slack with a gossip pump between:
        # the conservative gate only admits `slack` unpublished jobs,
        # so a pumpless bulk submit would measure the throttle, not
        # the handoff
        names = []
        wave, i = 32, 0
        while i < n_jobs:
            for _ in range(min(wave, n_jobs - i)):
                name = f"rb{i:05d}"
                i += 1
                _, jid = fc.submit(JobSpec(
                    name=name, user="bench", partition="batch",
                    res=ResourceSpec(cpu=2.0, mem_bytes=2 << 30,
                                     memsw_bytes=2 << 30),
                    sim_runtime=20.0))
                if jid:
                    names.append(name)
            fc.tick()
            fc.pump_usage(fc.now)
        running = len(fc.shards["east"].scheduler.running)

        t0 = time.perf_counter()
        res = fc.migrate("batch", "west")
        handoff_s = time.perf_counter() - t0
        moved = res["jobs_imported"]

        t0 = time.perf_counter()
        docs = fc.pump_usage(fc.now)
        gossip_ms = (time.perf_counter() - t0) * 1e3

        # post-flip the map must route new work to the adopter
        routed_to = fc.shard_map.shard_for_partition("batch")
        _, jid = fc.submit(JobSpec(
            name="rb-post-flip", user="bench", partition="batch",
            res=ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                             memsw_bytes=1 << 30), sim_runtime=1.0))
        if jid:
            names.append("rb-post-flip")
        # drain with the gossip pump running — the conservative gate
        # needs fresh summaries to keep admitting run slots (in a real
        # federation the pump is a background loop, never paused)
        for _ in range(100_000):
            fc.tick()
            fc.pump_usage(fc.now)
            if all(s.drained() for s in fc.shards.values()):
                break
        audit = fc.ledger_by_name(names)
        ok = (res["committed"] and audit["lost"] == []
              and audit["doubled"] == [] and audit["still_live"] == []
              and routed_to == "west" and jid > 0)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "jobs_submitted": len(names),
        "running_at_handoff": running,
        "jobs_moved": moved,
        "handoff_s": round(handoff_s, 4),
        "per_job_ms": round(handoff_s / max(moved, 1) * 1e3, 3),
        "submit_outage_s": round(handoff_s, 4),
        "map_epoch": fc.shard_map.epoch,
        "usage_gossip_docs": docs,
        "usage_gossip_ms": round(gossip_ms, 3),
        "audit": {k: (len(v) if isinstance(v, list) else v)
                  for k, v in audit.items()},
        "exactly_once": ok,
        "note": "in-process two-shard drill over real WALs; the "
                "outage window IS the handoff (flip precedes commit, "
                "so clients see at most one sealed-partition retry)",
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--topology", action="store_true",
        default=bool(os.environ.get("BENCH_TOPOLOGY")),
        help="also run the topology scenario: gang-heavy queue with and "
             "without a generated block topology (intra-block placement "
             "rate + cycle-time delta; env BENCH_TOPOLOGY)")
    ap.add_argument(
        "--federation", action="store_true",
        default=bool(os.environ.get("BENCH_FEDERATION")),
        help="also run the federated control-plane scenario: 2-shard "
             "subprocess submit throughput vs one controller, query "
             "p99 under concurrent solve, and the arbiter's placement "
             "share (env BENCH_FEDERATION; shape via BENCH_FED_SPECS/"
             "BENCH_FED_NODES)")
    ap.add_argument(
        "--multihost", action="store_true",
        default=bool(os.environ.get("BENCH_MULTIHOST")),
        help="also run the multi-host solve scenario: 2 real processes "
             "(forced CPU host devices) bootstrap a ProcessMesh over a "
             "rendezvous and run the hierarchical sharded-classes "
             "solve, bit-exact vs the single-process oracle (env "
             "BENCH_MULTIHOST; shape via BENCH_MH_JOBS/BENCH_MH_NODES/"
             "BENCH_MH_PROCS/BENCH_MH_DEVICES)")
    ap.add_argument(
        "--rebalance", action="store_true",
        default=bool(os.environ.get("BENCH_REBALANCE")),
        help="also run the elastic-federation scenario: migrate a "
             "loaded partition between two live shards mid-storm and "
             "report the handoff latency (submit-outage window), "
             "per-job move cost, usage-gossip round time, and the "
             "exactly-once-by-name audit (env BENCH_REBALANCE; shape "
             "via BENCH_RB_JOBS/BENCH_RB_NODES)")
    ap.add_argument(
        "--churn", action="store_true",
        default=bool(os.environ.get("BENCH_CHURN")),
        help="also run the incremental-cycle churn scenario: steady 1%% "
             "queue churn, PendingTable vs full-rebuild prelude, plus "
             "the fingerprint-hit idle-tick cost (env BENCH_CHURN; "
             "shape via BENCH_CHURN_JOBS/BENCH_CHURN_NODES)")
    args = ap.parse_args()

    num_jobs = int(os.environ.get("BENCH_JOBS", 100_000))
    num_nodes = int(os.environ.get("BENCH_NODES", 10_000))
    repeats = int(os.environ.get("BENCH_REPEATS", 3))

    # one process touches the chip: JAX comes up here, and a run that
    # did not get the platform it asked for (a TPU unless
    # JAX_PLATFORMS=cpu was set explicitly) stops before measuring
    from cranesched_tpu.parallel.acquire import (
        BackendUnavailable,
        acquire_backend,
    )
    try:
        device = acquire_backend()
    except BackendUnavailable as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3

    import jax
    import jax.numpy as jnp

    from cranesched_tpu.models.solver import (
        JobBatch,
        make_cluster_state,
        solve_greedy,
    )
    from cranesched_tpu.ops.resources import ResourceLayout

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    lay = ResourceLayout()

    total = np.stack([
        lay.encode(cpu=int(rng.integers(32, 129)),
                   mem_bytes=int(rng.integers(64, 513)) << 30,
                   is_capacity=True)
        for _ in range(num_nodes)
    ])
    state = make_cluster_state(total.copy(), total,
                               rng.random(num_nodes) > 0.02,
                               rng.random(num_nodes).astype(np.float32))

    req = np.stack([
        lay.encode(cpu=float(rng.integers(1, 17)),
                   mem_bytes=int(rng.integers(1, 33)) << 30)
        for _ in range(num_jobs)
    ])
    # Partition eligibility computed on device (a [J, N] host transfer at
    # this scale would dominate; real cycles also build it device-side).
    node_part = jnp.asarray(rng.integers(0, 4, num_nodes), jnp.int32)
    job_part = jnp.asarray(rng.integers(0, 4, num_jobs), jnp.int32)
    part_mask = job_part[:, None] == node_part[None, :]

    jobs = JobBatch(
        req=jnp.asarray(req),
        node_num=jnp.asarray(rng.integers(1, 3, num_jobs), jnp.int32),
        time_limit=jnp.asarray(rng.integers(60, 86400, num_jobs), jnp.int32),
        part_mask=part_mask,
        valid=jnp.ones(num_jobs, bool))

    state = jax.device_put(state, dev)
    jobs = jax.device_put(jobs, dev)

    from cranesched_tpu.models.pallas_solver import (
        plan_streams,
        solve_greedy_pallas,
        solve_greedy_pallas_auto,
    )
    from cranesched_tpu.models.speculative import solve_blocked
    from cranesched_tpu.utils import native

    node_part_np = np.asarray(node_part)
    job_part_np = np.asarray(job_part)
    node_num_np = np.asarray(jobs.node_num)
    time_limit_np = np.asarray(jobs.time_limit)
    alive_np = np.asarray(state.alive).astype(np.uint8)
    avail_np = np.asarray(state.avail)
    cost_np = np.asarray(state.cost)

    def run_native():
        out = native.solve_greedy_native(
            avail_np, total, alive_np, cost_np, req, node_num_np,
            time_limit_np, np.ones(num_jobs, np.uint8), max_nodes=2,
            job_part=job_part_np, node_part=node_part_np)
        if out is None:
            raise RuntimeError("native library unavailable")

        class _P:  # placements shim matching the device solvers' shape
            placed = out[0]
        return _P, None

    # the Pallas path takes eligibility as (job_class, class_masks)
    # instead of the dense [J, N] part_mask (see models/pallas_solver.py)
    class_masks = jnp.asarray(
        np.stack([np.asarray(node_part) == c for c in range(4)]))

    def run_pallas():
        return solve_greedy_pallas(
            state, jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
            job_part, class_masks, max_nodes=2)

    # the production routing: class-disjoint partitions decompose into
    # S independent streams (models/pallas_solver.plan_streams), solved
    # by one multi-stream kernel.  The bench workload's 4 partitions are
    # disjoint by construction, so this is the streamed kernel.  No
    # donation here: the timing loop reuses `state` across repeats
    # (the scheduler donates, because it rebuilds state every cycle).
    stream_plan = plan_streams(job_part_np, np.asarray(class_masks))
    bench_streams = stream_plan[1] if stream_plan is not None else 1

    def run_pallas_stream():
        return solve_greedy_pallas_auto(
            state, jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
            job_part, class_masks, max_nodes=2, plan=stream_plan)

    def run_backfill():
        # the time-axis solve at the same shape (VERDICT r3 #5: a
        # recorded backfill number).  T=64 buckets, idle-cluster map
        # (the map build is measured separately in real cycles).
        from cranesched_tpu.models.solver_time import (
            TimeGrid, TimedJobBatch, make_timed_state, solve_backfill)
        tstate = make_timed_state(
            state.avail, state.total, state.alive,
            np.zeros((0, 1), np.int32), np.zeros((0, req.shape[1]),
                                                 np.int32),
            np.zeros(0, np.int32), num_buckets=64, cost=state.cost)
        tjobs = TimedJobBatch(
            req=jobs.req, node_num=jobs.node_num,
            time_limit=jobs.time_limit,
            part_mask=jobs.part_mask, valid=jobs.valid)
        return solve_backfill(tstate, tjobs,
                              edges=TimeGrid(64, 60.0).jnp_edges,
                              max_nodes=2, group=8)

    def run_backfill_split(bf_max=1024):
        # the production composition for time-axis cycles at scale
        # (SchedulerConfig.backfill_max_jobs): full timed solve for the
        # top bf_max priority jobs, Pallas immediate solve for the tail
        # against the min-over-horizon availability (reservation-safe)
        from cranesched_tpu.models.solver_time import (
            TimeGrid, TimedJobBatch, make_timed_state, solve_backfill)
        tstate = make_timed_state(
            state.avail, state.total, state.alive,
            np.zeros((0, 1), np.int32), np.zeros((0, req.shape[1]),
                                                 np.int32),
            np.zeros(0, np.int32), num_buckets=64, cost=state.cost)
        head = jax.tree.map(lambda x: x[:bf_max], jobs)
        tjobs = TimedJobBatch(
            req=head.req, node_num=head.node_num,
            time_limit=head.time_limit,
            part_mask=head.part_mask, valid=head.valid)
        tp, tstate = solve_backfill(tstate, tjobs,
                                    edges=TimeGrid(64, 60.0).jnp_edges,
                                    max_nodes=2, group=8)
        min_avail = jnp.min(tstate.time_avail, axis=1)
        tail_state = state.replace(avail=min_avail, cost=tstate.cost)
        p2, _ = solve_greedy_pallas(
            tail_state, jobs.req[bf_max:], jobs.node_num[bf_max:],
            jobs.time_limit[bf_max:], jobs.valid[bf_max:],
            job_part[bf_max:], class_masks, max_nodes=2)

        class _P:
            placed = jnp.concatenate([tp.placed, p2.placed])
        return _P, None

    solvers = {
        "greedy": lambda: solve_greedy(state, jobs, max_nodes=2),
        "blocked": lambda: solve_blocked(state, jobs, max_nodes=2,
                                         block_size=128),
        "backfill": run_backfill,
    }
    if dev.platform == "tpu":
        solvers["backfill_split"] = run_backfill_split
    if dev.platform == "tpu":
        # the single-kernel Pallas solve is the TPU hot path (VMEM-
        # resident cluster state, no per-job dispatch); it does not
        # lower on the CPU backend (interpret mode is test-only)
        solvers["pallas"] = run_pallas
        solvers["pallas-stream"] = run_pallas_stream
    if dev.platform == "cpu" and native.available():
        # the host C++ solver only competes for the headline number when
        # the measurement is a CPU measurement anyway — on a real TPU the
        # reported decisions/sec must be a device number
        solvers["native"] = run_native
    which = os.environ.get("BENCH_SOLVER", "auto")
    if which != "auto":
        if which not in solvers:
            print(json.dumps({"error": f"BENCH_SOLVER={which!r} invalid; "
                              f"use one of {['auto', *solvers]}"}))
            return 1
        solvers = {which: solvers[which]}
    elif num_jobs * num_nodes > 10_000_000:
        # the blocked solver's parallel validation measured ~17 s/cycle
        # on TPU and worse on CPU at the north-star shape (BENCH_r04);
        # auto mode drops it there, and the time-axis backfill (~T x
        # heavier per step) runs only when explicitly requested
        # (BENCH_SOLVER=backfill — recorded in BENCH_r04_backfill.json).
        # The scan greedy stays as the reference point against the
        # Pallas kernel.
        solvers.pop("blocked", None)
        solvers.pop("backfill", None)
        solvers.pop("backfill_split", None)

    results = {}
    placed_by = {}
    for name, fn in solvers.items():
        def ready(pl):
            if hasattr(pl.placed, "block_until_ready"):
                pl.placed.block_until_ready()

        p, _ = fn()           # warmup / compile
        ready(p)
        times = []
        budget = time.perf_counter() + 120.0  # per-solver wall budget
        for _ in range(repeats):
            t0 = time.perf_counter()
            p, _ = fn()
            ready(p)
            times.append(time.perf_counter() - t0)
            if time.perf_counter() > budget:
                break
        results[name] = float(np.median(times))
        placed_by[name] = int(np.asarray(p.placed).sum())

    best = min(results, key=results.get)
    placements_placed = placed_by[best]
    cycle_s = results[best]
    decisions_per_sec = num_jobs / cycle_s

    # a leg that raises still lands in the JSON, and fails the run
    failed_legs = []

    def leg_failed(exc: Exception) -> dict:
        failed_legs.append(f"{type(exc).__name__}: {exc}")
        return {"error": failed_legs[-1]}

    # full-cycle phase split from the production scheduler's own trace
    # (prelude = drains + sort + batch build; the factored mask table
    # keeps it a small share of the cycle)
    sched_cycle = None
    sj = int(os.environ.get("BENCH_SCHED_JOBS", 4_096))
    sn = int(os.environ.get("BENCH_SCHED_NODES", 512))
    if sj > 0 and sn > 0:
        try:
            sched_cycle = _measure_sched_cycle(sj, sn)
        except Exception as exc:
            sched_cycle = leg_failed(exc)

    # commit-path microbench: group-commit fsync amortization +
    # lock-held commit time on a place-everything cycle
    commit_bench = None
    cj = int(os.environ.get("BENCH_COMMIT_JOBS", 10_000))
    cn = int(os.environ.get("BENCH_COMMIT_NODES", 1_024))
    if cj > 0 and cn > 0:
        try:
            commit_bench = _measure_commit(cj, cn)
        except Exception as exc:
            commit_bench = leg_failed(exc)

    topo_bench = None
    if args.topology:
        try:
            topo_bench = _measure_topology()
        except Exception as exc:
            topo_bench = leg_failed(exc)

    fed_bench = None
    if args.federation:
        try:
            # 32 nodes/partition keeps the storm queue-saturated like
            # the north-star shape (jobs >> free slots); with more
            # slots than specs every wave places instantly and the
            # scenario measures commit churn, not scheduling ingest
            fed_bench = _measure_federation(
                n_specs=int(os.environ.get("BENCH_FED_SPECS", 4_000)),
                nodes_per_part=int(os.environ.get("BENCH_FED_NODES",
                                                  32)))
        except Exception as exc:
            fed_bench = leg_failed(exc)

    mh_bench = None
    if args.multihost:
        try:
            mh_bench = _measure_multihost(
                num_jobs=int(os.environ.get("BENCH_MH_JOBS", 512)),
                num_nodes=int(os.environ.get("BENCH_MH_NODES", 256)),
                num_classes=int(os.environ.get("BENCH_MH_CLASSES", 8)),
                nprocs=int(os.environ.get("BENCH_MH_PROCS", 2)),
                local_devices=int(os.environ.get("BENCH_MH_DEVICES",
                                                 4)))
        except Exception as exc:
            mh_bench = leg_failed(exc)

    rb_bench = None
    if args.rebalance:
        try:
            rb_bench = _measure_rebalance(
                n_jobs=int(os.environ.get("BENCH_RB_JOBS", 600)),
                nodes_per_part=int(os.environ.get("BENCH_RB_NODES",
                                                  24)))
        except Exception as exc:
            rb_bench = leg_failed(exc)

    churn_bench = None
    if args.churn:
        try:
            churn_bench = _measure_churn(
                num_jobs=int(os.environ.get("BENCH_CHURN_JOBS",
                                            100_000)),
                num_nodes=int(os.environ.get("BENCH_CHURN_NODES", 512)),
                churn=float(os.environ.get("BENCH_CHURN_RATE", 0.01)),
                cycles=int(os.environ.get("BENCH_CHURN_CYCLES", 5)))
        except Exception as exc:
            churn_bench = leg_failed(exc)

    print(json.dumps({
        "metric": "decisions_per_sec",
        "value": round(decisions_per_sec, 1),
        "unit": "decisions/s",
        "vs_baseline": round(decisions_per_sec / BASELINE_DECISIONS_PER_SEC,
                             3),
        "detail": {
            "jobs": num_jobs, "nodes": num_nodes,
            "solver": best,
            "cycle_seconds_by_solver": {k: round(v, 4)
                                        for k, v in results.items()},
            "placed": placements_placed,
            "num_streams": bench_streams,
            "sched_cycle": sched_cycle,
            "commit": commit_bench,
            "topology": topo_bench,
            "churn": churn_bench,
            "federation": fed_bench,
            "multihost": mh_bench,
            "rebalance": rb_bench,
            "device": str(dev), "repeats": repeats,
            "platform": device["platform"],
            "device_kind": device["device_kind"],
            "device_count": device["device_count"],
        },
    }))
    return 1 if failed_legs else 0


if __name__ == "__main__":
    sys.exit(main())
