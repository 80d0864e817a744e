#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It starts the daemon (`python -m cranesched_tpu.ctld_main -c <yaml> --sim`)
as a child that alone owns the chip, drives it through `CtldClient` over
gRPC with the cell's traffic, and prints one JSON object as the last line
of its standard output.  This process never imports jax.  No TPU, or fewer
chips than the cell asks for: exit 3 and no result line.

Everything that belongs to one cell is data that BENCHMARK.json names:
benchmark/configs/<config>.json, benchmark/traffic/<mix>.json,
benchmark/metrics/<metric>.json (-> benchmark/readers/<reader>.py).
See benchmark/README.md."""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse          # noqa: E402
import glob              # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import re                # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import check, controls, stats          # noqa: E402
from lib.deploy import DeployError, ServedSystem, make_cluster   # noqa: E402
from lib.spec import Benchmark, SpecError       # noqa: E402
from lib.traffic import Ledger, last_words, make_stream, preload   # noqa: E402,E501

# between arming the streams and the window's opening
LEAD_S = 1.0
# a window that is to end before the next snapshot begins, its drain
# included, ends this long before it
SLOT_MARGIN_S = 2.0

PCT_RE = re.compile(r"^(start|submit|query)_p(\d{1,2})_ms$")
PRINTED_PCTS = (50, 90, 95)


class BenchFailure(RuntimeError):
    """The run cannot give a result; the message says why."""


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T_PROCESS_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def shrink(cfg: dict, traffic: dict, by: int) -> None:
    """The dry run's tiny size: the cluster and the preload divided by
    `by`, everything else as the files have it."""
    cfg["nodes"] = sum(max(2, p["nodes"] // by) for p in cfg["partitions"])
    for p in cfg["partitions"]:
        p["nodes"] = max(2, p["nodes"] // by)
    pre = traffic.get("setup", {}).get("preload")
    if pre:
        for key in ("pending_target", "fill_estimate", "max_jobs", "chunk"):
            pre[key] = max(1, int(pre[key]) // by)
    for s in traffic["streams"]:
        if "batch" in s:
            s["batch"] = max(1, s["batch"] // 10)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def stats_of(client) -> dict:
    return json.loads(client.query_stats().json)


def gauge(doc: dict, name: str) -> float:
    values = doc.get("metrics", {}).get(name, {}).get("values", {})
    return float(next(iter(values.values()), 0.0))


def real_cycles(doc: dict) -> list[dict]:
    return [c for c in doc.get("cycle_trace", ())
            if c.get("solver") != "skip"]


def settled_pending(client, t_after: float, timeout_s: float = 180.0) -> int:
    """The daemon's pending count once a cycle that began after `t_after`
    has placed nothing: what is left really waits."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        doc = stats_of(client)
        late = [c for c in doc.get("cycle_trace", ())
                if c["now"] > t_after]
        if late and late[-1].get("placed", 0) == 0:
            return int(gauge(doc, "crane_pending_jobs"))
        time.sleep(0.5)
    raise BenchFailure("the preload did not settle within "
                       f"{timeout_s:.0f} s")


def wait_settled(client, n: int, timeout_s: float) -> None:
    """Until the newest n cycles that did work paid no compile."""
    deadline = time.time() + timeout_s
    while True:
        cycles = real_cycles(stats_of(client))[-n:]
        if len(cycles) == n and all(c.get("recompiles", 0) == 0
                                    for c in cycles):
            return
        if time.time() > deadline:
            raise BenchFailure(
                f"no {n} consecutive compile-free cycles within "
                f"{timeout_s:.0f} s: recompiles "
                f"{[c.get('recompiles') for c in cycles]}")
        time.sleep(1.0)


class Idle:
    """The spans in which the harness only waits for a pinned instant and
    offers no load.  `setup_s` leaves them out: it times set-up WORK
    (boot, compile, ingest, warm-up), so that a gain or a loss there
    shows, and a wait the harness chose hides neither."""

    def __init__(self):
        self.total = 0.0
        self.by: dict[str, float] = {}

    def add(self, why: str, seconds: float) -> None:
        self.total += seconds
        self.by[why] = round(self.by.get(why, 0.0) + seconds, 3)

    def until(self, t: float, why: str) -> None:
        wait = t - time.time()
        if wait > 0:
            time.sleep(wait)
            self.add(why, wait)


def wait_for_landing(system, after: float, timeout_s: float) -> float:
    """The instant the next snapshot lands: the rename of `<wal>.snap`
    is the last thing a snapshot does."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        landed = system.snapshot_mtime()
        if landed > after:
            return landed
        time.sleep(0.05)
    raise BenchFailure("no snapshot landed to open the window after")


def open_at_phase(system, cfg: dict, setup: dict, streams, client,
                  idle: Idle, info: dict, busy_s: float) -> None:
    """Bring the run to the instant LEAD_S before its window opens:
    `open_at_phase_s` after the daemon's snapshot period began (its
    banner, then each snapshot's landing), so that a snapshot falls at
    the same place in every run's window, or in none.  The streams start
    as soon as a period is chosen that leaves them `warm_seconds` or more
    before that instant; waiting for such a period the harness offers no
    load, and the wait is idle.  `busy_s` is the window with its drain.
    Where that fits between two snapshots and the warm cycles still
    compiled for so long that it no longer would: the next period, the
    streams running on.  Where a snapshot falls into the window anyway, a
    late opening only moves it there, and is reported."""
    phase = float(setup["open_at_phase_s"])
    warm = float(setup.get("warm_seconds", 0.0))
    pinned = bool(cfg.get("wal", True))
    interval = float(cfg.get("snapshot_interval_s", 60.0))
    room = interval - phase - busy_s
    late_most = room - SLOT_MARGIN_S if room >= 0 else float("inf")
    start = system.snapshot_mtime() or system.t_banner
    running = False
    periods = 0
    while True:
        target = start + phase - LEAD_S
        if not running:
            if pinned and time.time() > target - warm:
                t_wait = time.time()
                landed = wait_for_landing(system, start, 3.0 * interval)
                idle.add("snapshot_slot", time.time() - t_wait)
                info["snapshot_took_s"] = round(landed - start - interval, 3)
                start = landed
                periods += 1
                continue
            for s in streams:
                s.start()
            running = True
        time.sleep(max(0.0, target - time.time()))
        wait_settled(client, int(setup.get("settle_cycles", 5)), 240.0)
        late = time.time() - target
        if not pinned or late <= late_most:
            break
        # load is offered all through this wait: it is warm-up, not idle
        start = wait_for_landing(system, start, 3.0 * interval)
        periods += 1
    info["periods_waited"] = periods
    info["late_for_open_s"] = round(max(0.0, late), 3)
    info["open_after_period_start_s"] = round(
        time.time() + LEAD_S - start, 3)


def run_cell(bench: Benchmark, cell: str, seed: int, seconds: float,
             trace: bool, system_factory=ServedSystem, dry_run: int = 0,
             with_controls: bool = False, control: str = ""):
    cfg = bench.config_file(cell)
    traffic = bench.traffic_file(cell)
    if dry_run:
        shrink(cfg, traffic, dry_run)
    setup = traffic.get("setup", {})
    base_seed = int(traffic["base_seed"])
    drain_s = float(traffic.get("drain_seconds", 0.0))
    cluster = make_cluster(cfg, seed)
    system = (system_factory(cfg, cluster, cell, control=control)
              if control else system_factory(cfg, cluster, cell))
    info: dict = {"cell": cell, "seed": seed, "seconds": seconds}
    if control:
        info["control"] = control
    idle = Idle()
    streams = []
    try:
        device = system.start()
        client = system.client
        info["banner_s"] = round(time.time() - T_PROCESS_START, 3)
        chips = bench.cell(cell)["chips"]
        if not dry_run and (device.get("platform") != "tpu"
                            or int(device.get("device_count", 0)) < chips):
            raise BenchFailure(
                f"the daemon holds {device.get('platform')!r} x"
                f"{device.get('device_count')}, the cell needs "
                f"tpu x{chips}: no result")
        log(f"daemon up on {device.get('platform')} "
            f"{device.get('device_kind')} x{device.get('device_count')}")

        for n in cluster["drained"]:
            if not client.modify_node(cluster["names"][n], "drain").ok:
                raise BenchFailure(f"could not drain {cluster['names'][n]}")
        t_drained = time.time()

        ledger = Ledger()
        if setup.get("preload"):
            info["preload"] = preload(
                setup["preload"], traffic["mixes"], client, ledger,
                base_seed, seed,
                lambda t_all: settled_pending(client, t_all), log,
                lambda t: idle.until(t, "release"))

        for s in traffic["streams"]:
            if s.get("partitions") == ["*"]:
                s["partitions"] = [p["name"] for p in cfg["partitions"]]
        streams = [make_stream(s, traffic["mixes"], client, ledger,
                               base_seed, seed, seconds)
                   for s in traffic["streams"]]
        open_at_phase(system, cfg, setup, streams, client, idle, info,
                      seconds + drain_s)

        # ---- the window -------------------------------------------------
        stats_open = stats_of(client)
        t_stats_open = time.time()
        snap_open = system.snapshot_mtime()
        t0 = time.time() + LEAD_S
        t1 = t0 + seconds
        for s in streams:
            s.open_window(t0)
        setup_s = t0 - T_PROCESS_START - idle.total
        info["setup_wall_s"] = round(t0 - T_PROCESS_START, 3)
        info["setup_idle_s"] = dict(idle.by)
        log(f"window opens in {LEAD_S} s, {t0 - T_PROCESS_START:.1f} s "
            f"after the start: set-up {setup_s:.1f} s, idle waits "
            f"{idle.by}; {seconds:.0f} s long")
        cycles: dict[float, dict] = {}
        trace_dir = ""
        # what a traced run reads per layer, it reads up to the instant
        # the profiler is armed: starting it stalls the daemon too
        t_cut = t1
        if trace:
            # the ring holds 64 cycles: poll it; and profile the window's
            # LAST cycles, because stopping the profiler stalls the daemon
            # for a long while and that stall has to fall after the window
            tcfg = traffic.get("trace", {})
            poll = float(tcfg.get("poll_s", 5.0))
            t_arm = t1 - min(seconds, float(tcfg.get("before_end_s", 5.0)))
            while time.time() < t_arm - poll:
                time.sleep(min(poll, max(0.0, t_arm - poll - time.time())))
                for c in stats_of(client).get("cycle_trace", ()):
                    cycles[c["now"]] = c
            time.sleep(max(0.0, t_arm - time.time()))
            t_cut = t_arm
            reply = client.capture_profile(
                cycles=int(tcfg.get("cycles", 6)), dir=system.profile_dir)
            trace_dir = reply.dir if reply.ok else ""
            if not reply.ok:
                log(f"CaptureProfile refused: {reply.error}")
        time.sleep(max(0.0, t1 - time.time()))
        for s in streams:
            if s.cfg.get("loop") == "closed":
                s.stop()
        t_stats_close = time.time()
        stats_close = stats_of(client)
        info["stats_close_wait_s"] = round(time.time() - t1, 3)
        for c in stats_close.get("cycle_trace", ()):
            cycles[c["now"]] = c
        if trace_dir:
            # stopping the profiler stalls the daemon: let the trace land
            # before the drain, so that no request of the window fails
            # for the tracing's sake
            deadline = time.time() + 200.0
            while time.time() < deadline and not glob.glob(os.path.join(
                    trace_dir, "plugins", "profile", "*", "*.xplane.pb")):
                time.sleep(0.5)
            info["trace_landed_after_s"] = round(time.time() - t1, 3)
        time.sleep(drain_s)
        for s in streams:
            s.stop()
        joined = all([s.join(30.0) for s in streams])
        if not joined:
            raise BenchFailure("a load-generator thread did not end")
        # a snapshot that began in the window lands (rename) a little
        # after it began: count landings from the window's opening to a
        # few seconds past its end, by the file's mtime
        snapshots = int(system.snapshot_mtime() != snap_open)

        # ---- the answers, once the window has closed ----------------------
        t_query = time.time()
        rows = check.rows_from_pb(
            client.query_jobs_stream(include_history=True))
        info["rows"] = len(rows)
        info["rows_s"] = round(time.time() - t_query, 3)
        final = stats_of(client)
        crashes = int(final.get("watchdog", {}).get("cycle_crashes_total", 0))
        peak = gauge(final, "crane_device_peak_bytes")
        device = dict(final.get("device", device))
        # ---- the host dies ------------------------------------------------
        # a last request through each submitting stream, and SIGKILL the
        # instant its acknowledgement is here: no graceful stop, no flush
        info["last_words"] = last_words(traffic["streams"], traffic["mixes"],
                                        client, ledger, base_seed, seed)
    except Exception:
        log("daemon stderr: " + system.stderr_tail())
        system.kill()
        raise
    died = system.kill()
    durable = system.durable_state()

    # ---- the comparison -------------------------------------------------
    can_start = [s["name"] for s in traffic["streams"]
                 if s.get("timed") and s["kind"] != "query"]
    sync = None
    if cfg.get("wal", True):
        spans = []
        for s in streams:
            if s.cfg.get("loop") == "closed":
                spans += [(b[1], b[2]) for b in s.batches
                          if b[0] == "window" and b[3]]
            elif s.kind != "query":
                spans += [(s.sent[k], s.done[k])
                          for k in range(len(s.offsets)) if s.ok[k]]
        sync = {"spans": [(a, b) for a, b in spans
                          if a >= t_stats_open and b <= t_stats_close],
                "fsyncs": (gauge(stats_close, "crane_wal_fsync_total")
                           - gauge(stats_open, "crane_wal_fsync_total"))}
        info["serial_acks"] = check.serial_acks(sync["spans"])
        info["fsyncs_in_window"] = sync["fsyncs"]
    compared = check.compare(cluster, ledger, rows, durable, t_query,
                             t_drained, can_start, sync)
    compared["cycle_crashes"] = {"value": crashes, "limit": 0}
    compared["daemon_died"] = {"value": int(died), "limit": 0}
    correct = check.verdict(compared)
    # a reading, not compared: the same count over the jobs no mix
    # promises a start (a backlog behind reservations may hold nodes back)
    info["idle_fit_all_streams"] = check.idle_fit_jobs(
        cluster, ledger.acks, rows, t_query)
    control_out = {}
    if with_controls:
        for name, (target, fn) in controls.CONTROLS.items():
            answer = fn(cluster, ledger, rows, durable)
            if answer is None:
                control_out[name] = {"applies": False}
                continue
            c_cmp = check.compare(cluster, ledger, answer[0], answer[1],
                                  t_query, t_drained, can_start, sync)
            control_out[name] = {
                "correct": check.verdict(c_cmp),
                target: c_cmp[target]["value"]}
        log("controls (each has to be NOT correct): "
            + json.dumps(control_out))

    # ---- the metrics ----------------------------------------------------
    by_id = {r.job_id: r for r in rows}
    samples: dict[str, list[float]] = {"start": [], "submit": [],
                                       "query": []}
    lag = []
    attempted = failed = 0
    for s in streams:
        if s.cfg.get("loop") == "closed":
            sent = sum(s.batch for b in s.batches if b[0] == "window")
            acked = sum(b[3] for b in s.batches if b[0] == "window")
            attempted += sent
            failed += sent - acked
            continue
        attempted += len(s.offsets)
        for k in range(len(s.offsets)):
            if not s.ok[k]:
                failed += 1
                continue
            due = s.due[k]
            sampled = due < t_cut
            if sampled:
                lag.append(stats.since_due_ms(due, s.sent[k]))
                samples["query" if s.kind == "query" else "submit"].append(
                    stats.since_due_ms(due, s.done[k]))
            if s.kind != "query" and s.cfg.get("timed"):
                row = by_id.get(s.job_ids[k])
                if row is None or row.start_time <= 0:
                    failed += 1       # never started: failed, not fast
                elif sampled:
                    samples["start"].append(
                        stats.since_due_ms(due, row.start_time))
    started_in_window = sum(1 for r in rows if t0 <= r.start_time < t1)
    window_cycles = [c for c in cycles.values() if t0 <= c["now"] < t_cut]
    compile_s = (
        sum(v["sum"] for v in stats_close["metrics"].get(
            "crane_jit_compile_seconds", {}).get("values", {}).values())
        - sum(v["sum"] for v in stats_open["metrics"].get(
            "crane_jit_compile_seconds", {}).get("values", {}).values()))
    harness = {
        "loadgen_lag_p95_ms": stats.percentile(lag, 95) if lag else None,
        "snapshots_in_window": snapshots,
        "compile_s_in_window": compile_s,
        "pending_open": int(gauge(stats_open, "crane_pending_jobs")),
        "pending_close": int(gauge(stats_close, "crane_pending_jobs")),
        "cycles_seen": len(window_cycles),
        "started_in_window": started_in_window,
        "refused_total": ledger.refused,
        "rpc_errors_total": ledger.rpc_errors,
    }
    for kind, values in samples.items():
        for p in PRINTED_PCTS:
            if values:
                harness[f"{kind}_p{p}_ms"] = stats.percentile(values, p)
        harness[f"{kind}_n"] = len(values)
    info.update(harness)
    print(json.dumps({"info": info}), flush=True)

    def e2e(name: str):
        if name == "setup_s":
            return setup_s
        if name == "started_jobs_per_s":
            return started_in_window / seconds
        m = PCT_RE.match(name)
        if m and samples[m.group(1)]:
            return stats.percentile(samples[m.group(1)], int(m.group(2)))
        return None

    metrics = {}
    device_out = {"platform": device.get("platform"),
                  "kind": device.get("device_kind"),
                  "count": int(device.get("device_count", 0)),
                  "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device_out}
    if not trace:
        for m in bench.metrics_for(cell, "end_to_end"):
            value = e2e(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reduced = reduce_trace(trace_dir)
        ctx = {"cycles": window_cycles, "window": (t0, t_cut),
               "stats_open": stats_open, "stats_close": stats_close,
               "harness": harness, "trace": reduced}
        for m in bench.metrics_for(cell, "per_layer"):
            spec = bench.metric_file(m["name"])
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced:
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    if with_controls:
        result["controls"] = control_out
    if control:
        result["control"] = control
    result["compared"] = compared
    return result


def reduce_trace(trace_dir: str) -> dict:
    """lib/xplane.py over the newest trace under `trace_dir`, in a child
    that may import jax (on the CPU: the daemon, and the chip, are gone)."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))) \
        if trace_dir else []
    if not found:
        log("no profiler trace was written")
        return {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "xplane.py"), found[-1]],
        capture_output=True, text=True, env=env, timeout=240, check=False)
    if done.returncode != 0:
        log("trace reduction failed: " + done.stderr[-800:])
        return {}
    return json.loads(done.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", type=int, default=0, metavar="N",
                    help="CPU rehearsal: cluster and preload divided by N; "
                         "prints counts and `correct`, never a metric")
    ap.add_argument("--controls", action="store_true",
                    help="also put each control in the program's place on "
                         "this run's answers; each must be NOT correct")
    ap.add_argument("--control", default="",
                    choices=("", "fsync_off", "late_write"),
                    help="run the daemon with this weakened path switched "
                         "on (lib/control_daemon.py); the run must come "
                         "out NOT correct")
    args = ap.parse_args(argv)
    try:
        bench = Benchmark(ROOT)
        bench.cell(args.workload)
        import cranesched_tpu.rpc.client  # noqa: F401  the system under test
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), dry_run=args.dry_run,
                          with_controls=args.controls, control=args.control)
    except (SpecError, DeployError, BenchFailure, ImportError) as exc:
        print(f"benchmark/run.py: {exc}", file=sys.stderr, flush=True)
        return 3
    compared = result["compared"]
    for name, c in compared.items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    if args.dry_run:
        # a CPU rehearsal: counts and the verdict, no number under a
        # device metric's name
        print(json.dumps({"dry_run": True, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "platform": result["device"]["platform"],
                          "compared": compared}))
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
