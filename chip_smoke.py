#!/usr/bin/env python3
"""chip_smoke.py — prove the served scheduling path still starts on the chip.

    python3 chip_smoke.py             # needs one TPU; ~5 min cold
    env JAX_PLATFORMS=cpu python3 chip_smoke.py --dry-run   # CPU, tiny

Two phases, each run by a child process that is the sole owner of the
chip while it lives; this parent never imports jax.

1. *Served path.*  ``python -m cranesched_tpu.ctld_main -c <yaml> --sim``
   over 10,000 nodes in 4 disjoint partitions with the default
   ``Scheduler:`` block and a WAL; a 100,000-job backlog goes in through
   ``CtldClient.submit_many`` behind one held gate job (``after:`` gate —
   releasing it makes the whole backlog eligible at once, so the first
   cycle is the north-star 100k x 10k shape whatever the host's ingest
   rate); cycles run; the queue, the nodes and the stats are queried;
   a handful of jobs are cancelled; SIGTERM; exit code 0.  It fails
   unless the daemon holds the expected platform, the cycles solved with
   ``backfill`` plus a Pallas kernel and never ``native``, nothing
   crashed, jobs reached the sim plane and completed, the last measured
   cycle paid no recompile, and a numpy check of the queried placements
   finds no violation.

2. *Kernel parity.*  On the device, at the same node axis and backlog
   size and from the same seed: the serial and the S-stream Pallas
   kernels against the ``solve_greedy`` scan for gang bounds 1, 2, 4, 8 —
   ``placed``, ``nodes``, ``reason``, final ``avail`` and ``cost`` must
   be identical.  The host C++ solver runs beside them for the record
   (never for the verdict): a chip-vs-host difference is reported with
   the f32 cost increments that disagree.  The same child then drives
   the one route the default configuration never takes — immediate-fit
   cycles over the device-resident, donated ``ClusterState`` — against
   its rebuild-every-cycle reference: a use-after-donate raises only on
   a backend that really deletes donated buffers.

Prints one JSON report, then as the LAST line of stdout
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and exits 0.  Any failed phase, a missing accelerator, or a directory
that holds this script without its repository exits non-zero and prints
no result line.  Output (logs, report.json) goes to
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

NUM_PARTITIONS = 4
GANG_BOUNDS = (1, 2, 4, 8)      # _bucket(max node_num) under the default
                                # MaxNodesPerJob 8 (16, 32, 64 compile for
                                # a v5e in tests/test_pallas_lowering.py)
BACKFILL_MAX_JOBS = 1024        # SchedulerConfig default: head of a split cycle
WALL_LIMIT_S = 1150             # the contract allows 1200


class SmokeFailure(Exception):
    """A phase did not meet its bar; the message says which check."""


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# the deployment, from the seed (shared by both phases)
# ---------------------------------------------------------------------------

def make_cluster(seed: int, num_nodes: int):
    """Node capacities: cpu 32-128 cores, memory 64-512 GiB, node i in
    partition p{i % 4}."""
    rng = np.random.default_rng(seed)
    cpu = rng.integers(32, 129, num_nodes)
    mem_gib = rng.integers(64, 513, num_nodes)
    part = np.arange(num_nodes) % NUM_PARTITIONS
    return cpu, mem_gib, part


def make_jobs(seed: int, num_jobs: int, max_gang: int = 2):
    """The backlog: cpu 1-16, memory 1-32 GiB, gang width 1-max_gang,
    time limit 60-86,400 s, a partition each."""
    rng = np.random.default_rng(seed + 1)
    return {
        "cpu": rng.integers(1, 17, num_jobs),
        "mem_gib": rng.integers(1, 33, num_jobs),
        "node_num": rng.integers(1, max_gang + 1, num_jobs),
        "time_limit": rng.integers(60, 86_401, num_jobs),
        "part": rng.integers(0, NUM_PARTITIONS, num_jobs),
        # short enough that completions flow back during the smoke,
        # long enough that the backlog stays in one padding bucket for
        # several cycles (a bucket change is a legitimate recompile)
        "sim_runtime": rng.integers(5, 601, num_jobs),
    }


# ---------------------------------------------------------------------------
# phase 1: the served path (parent = the client; child = ctld)
# ---------------------------------------------------------------------------

def write_config(path: str, wal: str, cpu, mem_gib, part) -> None:
    lines = ["ClusterName: chip-smoke", "Listen: 127.0.0.1:0",
             f"Wal: {wal}", "Partitions:"]
    lines += [f"  - name: p{p}" for p in range(NUM_PARTITIONS)]
    # the default Scheduler block: Backfill on, gangs to 8, no Solver key
    lines += ["Scheduler:", "  Backfill: true", "  MaxNodesPerJob: 8",
              "Nodes:"]
    lines += [f"  - {{name: cn{i:05d}, cpu: {int(cpu[i])}, "
              f"memory: {int(mem_gib[i])}G, partitions: [p{int(part[i])}]}}"
              for i in range(len(cpu))]
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(lines) + "\n")


def wait_for_banner(proc, stdout_path: str, timeout_s: float) -> str:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"ctld exited with code {proc.returncode} before "
                "listening (see ctld.stderr.log)")
        with open(stdout_path, encoding="utf-8") as fh:
            for line in fh:
                if "listening on port" in line and line.endswith("\n"):
                    return line
        time.sleep(0.25)
    raise SmokeFailure(f"ctld did not listen within {timeout_s:.0f} s")


def metric(doc: dict, name: str) -> dict:
    return doc.get("metrics", {}).get(name, {}).get("values", {})


def backlog_cycles(stats: dict) -> list[dict]:
    """Traces of the cycles that solved the backlog (wider than the
    backfill head, so both the timed solve and the tail solve ran)."""
    return [t for t in stats["cycle_trace"]
            if t.get("solver") != "skip"
            and t.get("candidates", 0) > BACKFILL_MAX_JOBS]


def check_placements(rows, jobs, gate_id, first_id, cpu, mem_gib,
                     part, t_query: float) -> dict:
    """numpy reference for what the scheduler may never do: on every
    node, the jobs that held it at the instant the query was sent ask
    for no more than it has, on every dimension; every node of a job is
    in the job's partition; every gang has its width, on distinct
    nodes.  (A job counts from its start to its recorded end; the ctld
    frees a node no earlier than that, so this can only under-count.)"""
    num_nodes = len(cpu)
    used_cpu = np.zeros(num_nodes, np.int64)
    used_mem = np.zeros(num_nodes, np.int64)
    violations: list[str] = []
    held = 0
    by_status: dict[str, int] = {}
    for row in rows:
        by_status[row.status] = by_status.get(row.status, 0) + 1
        if row.job_id == gate_id or not row.node_names:
            continue
        k = row.job_id - first_id
        nodes = [int(name[2:]) for name in row.node_names]
        if len(nodes) != int(jobs["node_num"][k]) or \
                len(set(nodes)) != len(nodes):
            violations.append(f"job {row.job_id}: gang of "
                              f"{int(jobs['node_num'][k])} got {nodes}")
        if any(int(part[n]) != int(jobs["part"][k]) for n in nodes):
            violations.append(f"job {row.job_id}: partition "
                              f"p{int(jobs['part'][k])} got {nodes}")
        holds = (0 < row.start_time <= t_query
                 and (row.end_time == 0 or row.end_time > t_query))
        if holds:
            held += 1
            used_cpu[nodes] += int(jobs["cpu"][k])
            used_mem[nodes] += int(jobs["mem_gib"][k])
    over = np.flatnonzero((used_cpu > cpu) | (used_mem > mem_gib))
    violations += [f"node cn{n:05d}: cpu {used_cpu[n]}/{cpu[n]} "
                   f"mem {used_mem[n]}/{mem_gib[n]} GiB" for n in over[:8]]
    return {"jobs_by_status": by_status, "jobs_holding_nodes": held,
            "nodes_in_use": int((used_cpu > 0).sum()),
            "cpu_in_use_share": round(float(used_cpu.sum() / cpu.sum()), 4),
            "violations": len(violations), "examples": violations[:8]}


def served_phase(args, expected: str, env: dict) -> dict:
    from cranesched_tpu.rpc import crane_pb2 as pb
    from cranesched_tpu.rpc.client import CtldClient

    work = os.path.join(OUT_DIR, "served")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    cpu, mem_gib, part = make_cluster(args.seed, args.nodes)
    jobs = make_jobs(args.seed, args.jobs)
    cfg = os.path.join(work, "config.yaml")
    write_config(cfg, os.path.join(work, "wal", "ctld.wal"),
                 cpu, mem_gib, part)

    out: dict = {"nodes": args.nodes, "backlog": args.jobs}
    stdout_path = os.path.join(work, "ctld.stdout.log")
    t_boot = time.monotonic()
    with open(stdout_path, "w") as so, \
            open(os.path.join(work, "ctld.stderr.log"), "w") as se:
        ctld = subprocess.Popen(
            [sys.executable, "-m", "cranesched_tpu.ctld_main", "-c", cfg,
             "--sim", "--snapshot-interval", "0"],
            stdout=so, stderr=se, cwd=HERE, env=env,
            start_new_session=True)
    client = None
    try:
        banner = wait_for_banner(ctld, stdout_path, 300.0)
        out["boot_s"] = round(time.monotonic() - t_boot, 2)
        log(banner.strip())
        port = int(banner.split("port")[1].split()[0])
        client = CtldClient(f"127.0.0.1:{port}", timeout=300.0)
        device = json.loads(client.query_stats().json).get("device", {})
        out["device"] = device
        if device.get("platform") != expected:
            raise SmokeFailure(
                f"the daemon holds {device.get('platform')!r}, "
                f"expected {expected!r}")

        # the gate, then the backlog behind it
        gate = client.submit(pb.JobSpec(
            name="gate", partition="p0", held=True, node_num=1,
            res=pb.ResourceSpec(cpu=1, mem_bytes=1 << 30),
            time_limit=60, sim_runtime=1.0)).job_id
        if not gate:
            raise SmokeFailure("the gate job was rejected")
        dep = pb.Dependency(job_id=gate, type="after")
        t_submit = time.monotonic()
        ids: list[int] = []
        for lo in range(0, args.jobs, 2000):
            specs = [pb.JobSpec(
                name="smoke", partition=f"p{int(jobs['part'][k])}",
                res=pb.ResourceSpec(cpu=float(jobs["cpu"][k]),
                                    mem_bytes=int(jobs["mem_gib"][k]) << 30),
                node_num=int(jobs["node_num"][k]),
                time_limit=int(jobs["time_limit"][k]),
                sim_runtime=float(jobs["sim_runtime"][k]),
                dependencies=[dep])
                for k in range(lo, min(lo + 2000, args.jobs))]
            ids += [r.job_id for r in client.submit_many(specs).replies]
        out["submit_s"] = round(time.monotonic() - t_submit, 2)
        first_id = gate + 1
        if ids != list(range(first_id, first_id + args.jobs)):
            raise SmokeFailure(
                f"{sum(1 for i in ids if not i)} of {args.jobs} submits "
                "were rejected or numbered out of order")
        log(f"submitted {args.jobs} jobs in {out['submit_s']} s; "
            "releasing the gate")
        if not client.hold(gate, held=False).ok:
            raise SmokeFailure("could not release the gate job")

        # let the backlog cycles run: at least three, the last of them
        # paying no compile, with completions flowing back
        t_cycles = time.monotonic()
        deadline = t_cycles + args.cycle_budget
        while True:
            if ctld.poll() is not None:
                raise SmokeFailure(
                    f"ctld died with code {ctld.returncode} mid-run")
            stats = json.loads(client.query_stats().json)
            measured = backlog_cycles(stats)
            done = stats.get("jobs_finished_total", 0)
            if (len(measured) >= 3 and measured[-1]["recompiles"] == 0
                    and done > 0):
                break
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"after {args.cycle_budget:.0f} s: {len(measured)} "
                    f"backlog cycles, last recompiles="
                    f"{measured[-1]['recompiles'] if measured else None}, "
                    f"{done} jobs finished")
            time.sleep(1.0)
        out["cycles_s"] = round(time.monotonic() - t_cycles, 2)
        log(f"{len(measured)} backlog cycles in {out['cycles_s']} s")

        # what an operator would ask next
        t_query = time.time()
        tq = time.monotonic()
        rows = list(client.query_jobs_stream())
        out["query_jobs_s"] = round(time.monotonic() - tq, 2)
        tq = time.monotonic()
        nodes = client.query_cluster().nodes
        out["query_nodes_s"] = round(time.monotonic() - tq, 2)
        if len(nodes) != args.nodes:
            raise SmokeFailure(f"cinfo shows {len(nodes)} nodes")
        out["node_states"] = {}
        for n in nodes:
            out["node_states"][n.state] = \
                out["node_states"].get(n.state, 0) + 1
        out["placements"] = check_placements(
            rows, jobs, gate, first_id, cpu, mem_gib, part, t_query)

        # cancel a handful: three that run, three that wait
        running = [r.job_id for r in rows if r.status == "Running"][:3]
        waiting = [r.job_id for r in rows if r.status == "Pending"][-3:]
        for jid in running + waiting:
            if not client.cancel(jid).ok:
                raise SmokeFailure(f"cancel of job {jid} was refused")
        cancel_deadline = time.monotonic() + 120.0
        while True:
            got = client.query_jobs(job_ids=running + waiting,
                                    include_history=True).jobs
            if got and all(j.status == "Cancelled" for j in got):
                break
            if time.monotonic() > cancel_deadline:
                raise SmokeFailure(
                    "cancelled jobs still show "
                    f"{[(j.job_id, j.status) for j in got]}")
            time.sleep(0.5)
        out["cancelled"] = len(got)

        stats = json.loads(client.query_stats().json)
        events = [e.type for e in client.query_events(limit=0).events]
    finally:
        if client is not None:
            client.close()
        if ctld.poll() is None:
            ctld.send_signal(signal.SIGTERM)
            try:
                ctld.wait(timeout=120)
            except subprocess.TimeoutExpired:
                pass
        stop_group(ctld)
    out["exit_code"] = ctld.returncode

    # the verdict, from the daemon's own records
    measured = backlog_cycles(stats)
    keep = ("solver", "candidates", "placed", "backfilled", "num_streams",
            "prelude_ms", "solve_ms", "commit_ms", "dispatch_ms",
            "total_ms", "recompiles", "wal_fsyncs", "device_peak_bytes")
    out["cycle_traces"] = [{k: t.get(k) for k in keep} for t in measured]
    out["solve_backends"] = metric(stats, "crane_solve_seconds")
    out["compile_seconds_by_fn"] = {
        k: round(v["sum"], 2) for k, v in
        metric(stats, "crane_jit_compile_seconds").items()}
    out["compile_s"] = round(sum(out["compile_seconds_by_fn"].values()), 2)
    out["xla_cache"] = {
        "dir": stats.get("device", {}).get("xla_cache_dir"),
        "hits": sum(metric(stats, "crane_xla_cache_hits_total").values()),
        "misses": sum(metric(stats,
                             "crane_xla_cache_misses_total").values())}
    watchdog = stats["watchdog"]
    out["cycle_crashes_total"] = watchdog["cycle_crashes_total"]
    out["jobs_started_total"] = stats["jobs_started_total"]
    out["jobs_finished_total"] = stats["jobs_finished_total"]
    reached = metric(stats, "crane_job_latency_seconds")
    out["reached_sim_plane"] = sum(
        v["count"] for k, v in reached.items() if "craned_received" in k)
    out["events"] = {t: events.count(t) for t in sorted(set(events))}
    wal_dir = os.path.join(work, "wal")
    out["wal_bytes"] = sum(
        os.path.getsize(os.path.join(wal_dir, f))
        for f in os.listdir(wal_dir)) if os.path.isdir(wal_dir) else 0
    shutil.rmtree(wal_dir, ignore_errors=True)   # large; it did its work

    backends = " ".join(out["solve_backends"])
    checks = {
        "solved the head with backfill": "backfill" in backends,
        "solved the tail with a Pallas kernel": "pallas" in backends,
        "never solved on the host": "native" not in backends,
        "no cycle crashed": (watchdog["cycle_crashes_total"] == 0
                             and not watchdog["last_crash"]),
        "no backend_degraded event": "backend_degraded" not in events,
        "jobs started": out["jobs_started_total"] > 0,
        "jobs reached the sim plane": out["reached_sim_plane"] > 0,
        "jobs completed": out["jobs_finished_total"] > 0,
        "first backlog cycle saw the whole backlog":
            bool(measured) and measured[0]["candidates"] == args.jobs,
        "no placement violation": out["placements"]["violations"] == 0,
        "ctld exited 0 on SIGTERM": ctld.returncode == 0,
    }
    if expected == "cpu":
        # the dry run: auto keeps the host order off the chip
        checks.pop("solved the tail with a Pallas kernel")
        checks.pop("never solved on the host")
    out["checks"] = checks
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SmokeFailure("served path: NOT " + "; NOT ".join(failed)
                           + f" — {json.dumps(out)}")
    return out


def stop_group(proc) -> None:
    """Nothing this script started outlives it."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    proc.wait()


# ---------------------------------------------------------------------------
# phase 2: kernel parity on the device (runs in its own child)
# ---------------------------------------------------------------------------

def parity_child(args) -> int:
    from cranesched_tpu.parallel.acquire import acquire_backend
    device = acquire_backend()
    from cranesched_tpu.obs.flight import enable_xla_cache, xla_cache_stats
    enable_xla_cache()

    import jax
    import jax.numpy as jnp

    from cranesched_tpu.models.pallas_solver import (
        plan_streams,
        solve_greedy_pallas,
        solve_greedy_pallas_auto,
    )
    from cranesched_tpu.models.solver import (
        JobBatch,
        make_cluster_state,
        solve_greedy,
    )
    from cranesched_tpu.ops.resources import CPU_SCALE

    interpret = args.dry_run      # the harness's choice, never the platform's
    cpu, mem_gib, part = make_cluster(args.seed, args.nodes)
    total = np.stack([cpu * CPU_SCALE, mem_gib * 1024, mem_gib * 1024],
                     axis=1).astype(np.int32)
    rng = np.random.default_rng(args.seed + 2)
    alive = rng.random(args.nodes) > 0.02
    cost0 = rng.integers(0, 64, args.nodes).astype(np.int32)
    state = make_cluster_state(total.copy(), total, alive, cost0)
    class_masks_np = np.stack([part == p for p in range(NUM_PARTITIONS)])
    class_masks = jnp.asarray(class_masks_np)

    def timed(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        return out, round(time.perf_counter() - t0, 4)

    @jax.jit
    def differing(a, b):
        """Per-field count of differing elements + first differing job."""
        (pa, sa), (pb_, sb) = a, b
        fields = {"placed": (pa.placed, pb_.placed),
                  "nodes": (pa.nodes, pb_.nodes),
                  "reason": (pa.reason, pb_.reason),
                  "avail": (sa.avail, sb.avail),
                  "cost": (sa.cost, sb.cost)}
        counts = {k: jnp.sum(x != y) for k, (x, y) in fields.items()}
        job_bad = ((pa.placed != pb_.placed) | (pa.reason != pb_.reason)
                   | jnp.any(pa.nodes != pb_.nodes, axis=1))
        first = jnp.where(jnp.any(job_bad), jnp.argmax(job_bad), -1)
        return counts, first

    results = []
    ok = True
    for K in GANG_BOUNDS:
        jobs = make_jobs(args.seed, args.jobs, max_gang=max(K, 2))
        req_np = np.stack([jobs["cpu"] * CPU_SCALE, jobs["mem_gib"] * 1024,
                           np.zeros(args.jobs, np.int64)],
                          axis=1).astype(np.int32)
        req = jnp.asarray(req_np)
        node_num = jnp.asarray(jobs["node_num"], jnp.int32)
        time_limit = jnp.asarray(jobs["time_limit"], jnp.int32)
        job_class_np = jobs["part"].astype(np.int32)
        job_class = jnp.asarray(job_class_np)
        valid = jnp.ones(args.jobs, bool)
        dense = JobBatch(req=req, node_num=node_num, time_limit=time_limit,
                         part_mask=class_masks[job_class], valid=valid)
        plan = plan_streams(job_class_np, class_masks_np)
        if plan is None:
            raise SmokeFailure("the 4 disjoint partitions did not plan "
                               "into streams")

        def scan():
            return solve_greedy(state, dense, max_nodes=K)

        def serial():
            return solve_greedy_pallas(
                state, req, node_num, time_limit, valid, job_class,
                class_masks, max_nodes=K, interpret=interpret)

        def streamed():
            return solve_greedy_pallas_auto(
                state, req, node_num, time_limit, valid, job_class,
                class_masks, max_nodes=K, plan=plan, interpret=interpret)

        row = {"max_nodes": K, "num_streams": plan[1]}
        ref, row["scan_first_call_s"] = timed(scan)
        row["placed"] = int(ref[0].placed.sum())
        for name, fn in (("serial", serial), ("streamed", streamed)):
            got, first_s = timed(fn)
            _, again_s = timed(fn)
            counts, first_bad = jax.device_get(differing(got, ref))
            counts = {k: int(v) for k, v in counts.items()}
            row[name] = {"first_call_s": first_s, "second_call_s": again_s,
                         "compile_s": round(first_s - again_s, 4),
                         "differs_from_scan": counts,
                         "first_differing_job": int(first_bad)}
            ok = ok and not any(counts.values())
        if K == 2:
            _, row["scan_second_call_s"] = timed(scan)
            row["host_vs_device"] = host_vs_device(
                total, alive, cost0, req_np, jobs, job_class_np, part,
                ref, K)
        log(f"parity K={K}: {json.dumps(row)}")
        results.append(row)

    resident = resident_drill(args, cpu, mem_gib, part)
    log(f"resident drill: {json.dumps(resident)}")
    ok = ok and resident["identical"]

    print(json.dumps({
        "ok": ok, "device": device, "jobs": args.jobs, "nodes": args.nodes,
        "interpret": interpret, "bounds": results, "resident": resident,
        "xla_cache": xla_cache_stats(),
        "peak_device_bytes": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")}))
    return 0 if ok else 1


def resident_drill(args, cpu, mem_gib, part, num_nodes: int = 1024,
                   ticks: int = 8, per_tick: int = 192) -> dict:
    """Immediate-fit cycles (Backfill off) through ``JobScheduler`` with
    the resident, donated device state, against the same script with
    ``resident_state=False``: same jobs started on the same nodes, same
    ledger, tick by tick, while completions dirty rows in between."""
    from cranesched_tpu.craned.sim import SimCluster
    from cranesched_tpu.ctld import (
        JobScheduler,
        JobSpec,
        MetaContainer,
        ResourceSpec,
        SchedulerConfig,
    )
    num_nodes = min(num_nodes, args.nodes)

    def build(resident: bool):
        meta = MetaContainer()
        for i in range(num_nodes):
            meta.add_node(
                f"cn{i:05d}",
                meta.layout.encode(cpu=float(cpu[i]),
                                   mem_bytes=int(mem_gib[i]) << 30,
                                   is_capacity=True),
                partitions=(f"p{int(part[i])}",))
            meta.craned_up(i)
        # on the chip "auto" must pick the Pallas solve by itself; the
        # CPU dry run names it and interprets (the harness's choice)
        sched = JobScheduler(meta, SchedulerConfig(
            backfill=False, resident_state=resident,
            solver="pallas" if args.dry_run else "auto"))
        sched.pallas_interpret = args.dry_run
        sim = SimCluster(sched)
        sim.wire(sched)
        return sched, sim

    pairs = (build(True), build(False))
    rng = np.random.default_rng(args.seed + 3)
    identical = True
    for tick in range(1, ticks + 1):
        now = 30.0 * tick
        specs = [JobSpec(
            res=ResourceSpec(cpu=float(rng.integers(1, 17)),
                             mem_bytes=int(rng.integers(1, 33)) << 30),
            node_num=int(rng.integers(1, 3)),
            time_limit=int(rng.integers(60, 86_401)),
            partition=f"p{int(rng.integers(0, NUM_PARTITIONS))}",
            sim_runtime=float(rng.integers(10, 120)))
            for _ in range(per_tick)]
        outcome = []
        for sched, sim in pairs:
            for spec in specs:
                sched.submit(spec, now=now)
            sim.advance_to(now)
            started = sched.schedule_cycle(now=now)
            avail, _, _ = sched.meta.snapshot()
            outcome.append((
                [(j, sorted(sched.running[j].node_ids)) for j in started],
                np.asarray(avail).copy()))
        identical = (identical and outcome[0][0] == outcome[1][0]
                     and np.array_equal(outcome[0][1], outcome[1][1]))
    sched = pairs[0][0]
    traces = sched.cycle_trace.snapshot()
    return {"identical": bool(identical), "ticks": ticks,
            "nodes": num_nodes,
            "started": sched.stats["jobs_started_total"],
            "finished": sched.stats["jobs_finished_total"],
            "solvers": sorted({t["solver"] for t in traces}),
            "resident_modes": [t.get("resident") for t in traces],
            "patch_cycles": sched._resident.patch_cycles,
            "full_rebuilds": sched._resident.full_rebuilds}


def host_vs_device(total, alive, cost0, req_np, jobs, job_class_np, part,
                   ref, K) -> dict:
    """For the record, not the verdict: the host C++ solver on the same
    problem, and the f32 cost increment round(tl * cpu * 16 / cpu_total)
    evaluated by this device and by numpy for every (job, distinct
    cpu_total) pair — the one float expression in the solve."""
    import jax
    import jax.numpy as jnp

    from cranesched_tpu.models.solver import COST_SCALE, quantized_dcost
    from cranesched_tpu.utils import native

    tl = jobs["time_limit"].astype(np.int32)
    cpu_req = req_np[:, 0]
    cputot = np.unique(total[:, 0]).astype(np.float32)
    on_device = np.asarray(jax.jit(quantized_dcost)(
        jnp.asarray(tl)[:, None], jnp.asarray(cpu_req)[:, None],
        jnp.asarray(cputot)[None, :]))
    on_host = np.round(
        tl.astype(np.float32)[:, None] * cpu_req.astype(np.float32)[:, None]
        * np.float32(COST_SCALE) / cputot[None, :]).astype(np.int32)
    delta = on_device.astype(np.int64) - on_host
    out = {"dcost_pairs": int(delta.size),
           "dcost_pairs_differing": int((delta != 0).sum()),
           "dcost_max_abs_delta": int(np.abs(delta).max())}
    host = native.solve_greedy_native(
        total.copy(), total, alive.astype(np.uint8), cost0, req_np,
        jobs["node_num"].astype(np.int32), tl,
        np.ones(len(tl), np.uint8), max_nodes=K,
        job_part=job_class_np, node_part=part.astype(np.int32))
    if host is None:
        out["host_solver"] = "unavailable"
        return out
    placed_dev = np.asarray(ref[0].placed)
    nodes_dev = np.asarray(ref[0].nodes)
    job_bad = (host[0] != placed_dev) | (host[1] != nodes_dev).any(axis=1)
    out.update(
        host_placed=int(host[0].sum()), device_placed=int(placed_dev.sum()),
        jobs_placed_differently=int(job_bad.sum()),
        first_differing_job=int(np.argmax(job_bad)) if job_bad.any() else -1,
        cost_ledger_nodes_differing=int(
            (host[4] != np.asarray(ref[1].cost)).sum()))
    return out


def parity_phase(args, env: dict) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--parity-child",
           "--seed", str(args.seed), "--nodes", str(args.nodes),
           "--jobs", str(args.jobs)] + (["--dry-run"] if args.dry_run
                                        else [])
    with open(os.path.join(OUT_DIR, "parity.stderr.log"), "w") as se:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=se,
                                text=True, cwd=HERE, env=env,
                                start_new_session=True)
        try:
            stdout, _ = proc.communicate(timeout=args.parity_budget)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"kernel parity did not finish in "
                f"{args.parity_budget:.0f} s") from None
        finally:
            stop_group(proc)
    try:
        doc = json.loads(stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        raise SmokeFailure(
            f"kernel parity child exited {proc.returncode} without a "
            "report (see parity.stderr.log)") from None
    if proc.returncode != 0 or not doc.get("ok"):
        raise SmokeFailure("kernel parity: the Pallas kernels differ "
                           f"from the scan — {json.dumps(doc)}")
    return doc


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU dry run at a tiny size (children get "
                         "JAX_PLATFORMS=cpu; Pallas runs interpreted)")
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--cycle-budget", type=float, default=420.0,
                    help="seconds the backlog cycles may take")
    ap.add_argument("--parity-budget", type=float, default=600.0)
    ap.add_argument("--parity-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.nodes is None:
        args.nodes = 256 if args.dry_run else 10_000
    if args.jobs is None:
        args.jobs = 4_096 if args.dry_run else 100_000

    if not os.path.isdir(os.path.join(HERE, "cranesched_tpu")):
        print("chip_smoke.py drives the repository it ships with; "
              f"{HERE} does not hold it", file=sys.stderr)
        return 2
    if args.parity_child:
        return parity_child(args)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [HERE, env.get("PYTHONPATH")]))
    if args.dry_run:
        expected = env["JAX_PLATFORMS"] = "cpu"
    else:
        expected = "tpu"
        if env.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
            print("JAX is held to the CPU here (JAX_PLATFORMS=cpu): no "
                  "accelerator, no result.  The CPU dry run is "
                  "--dry-run.", file=sys.stderr)
            return 3

    def out_of_time(*_):
        raise SmokeFailure(f"wall limit of {WALL_LIMIT_S} s reached")
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(WALL_LIMIT_S)

    os.makedirs(OUT_DIR, exist_ok=True)
    report: dict = {
        "seed": args.seed, "dry_run": args.dry_run,
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
    }
    try:
        t0 = time.monotonic()
        report["served"] = served_phase(args, expected, env)
        report["served"]["wall_s"] = round(time.monotonic() - t0, 2)
        t0 = time.monotonic()
        report["parity"] = parity_phase(args, env)
        report["parity"]["wall_s"] = round(time.monotonic() - t0, 2)
        served_dev, parity_dev = (report["served"]["device"],
                                  report["parity"]["device"])
        same = all(served_dev[k] == parity_dev[k]
                   for k in ("platform", "device_kind", "device_count"))
        if not same or "jax" in sys.modules:
            raise SmokeFailure(
                f"phases disagree on the device ({served_dev} vs "
                f"{parity_dev}) or the parent imported jax")
    except SmokeFailure as exc:
        report["failed"] = str(exc)
        with open(os.path.join(OUT_DIR, "report.json"), "w") as fh:
            json.dump(report, fh, indent=1)
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
    report["wall_s"] = round(time.monotonic() - _T0, 2)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    print(json.dumps({"ok": True, "device": {
        "platform": served_dev["platform"],
        "kind": served_dev["device_kind"],
        "count": served_dev["device_count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
