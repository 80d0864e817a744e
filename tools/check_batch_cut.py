#!/usr/bin/env python3
"""The batch cut at full size, against the plain reference (ISSUE 44).

    chiprun -- python3 tools/check_batch_cut.py [--seed N] [--shrink N]

Brings up the `deepqueue10k-backlog300k` cell's deployment with the
benchmark's own pieces (benchmark/lib: the cluster from the seed, the
daemon as a child that owns the chip, the preload of 300,000 pending
gangs), lets the queue settle, and compares what the daemon's FINAL ROWS
say (`query_jobs_stream(include_history=True)`: `pending_reason`,
`priority`, `submit_time`) with `cranesched_tpu/testing/
batch_cut_reference.py`, computed from what this script submitted (the
sizes are its own record; nothing of the scheduler is imported):

1. the pending rows NOT stamped "Priority" number ScheduledBatchSize,
   less those of the backfill head's reservations that surface
   "Priority" themselves (a future start can, cpp:6795-6835: such a row
   lies inside the reference's cut, among the head's 1,024, and is no
   tie at the edge); the cycle's row says `ranked` = the pending rows and
   `cut` = the rest;
2. no stamped row outside the reference's cut has a higher priority
   than an unstamped one;
3. for the last cycle that did work, at its own `now` (the cycle trace
   gives it; the rows' `submit_time` the ages), the ids inside the cut
   equal the reference's: exact on the set but for ties at the cut's
   edge in float32, which are listed and counted;
4. every row's `priority` is the reference's within float32's rounding.

`--shrink N` is the CPU rehearsal: cluster, preload AND
ScheduledBatchSize divided by N (it writes the key, which the cell's
file leaves at its default).  Prints one JSON line, `ok` true or false;
exit 0 only if ok."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np                                   # noqa: E402

from cranesched_tpu.testing.batch_cut_reference import (   # noqa: E402
    cut_by_priority, ties_at_the_edge)
from lib.deploy import ServedSystem, make_cluster    # noqa: E402
from lib.spec import Benchmark                       # noqa: E402
from lib.traffic import Ledger, preload              # noqa: E402
from run import settled_pending, shrink, stats_of    # noqa: E402

CELL = "deepqueue10k-backlog300k"
DEFAULT_BATCH = 100_000
#: the backfill head's length (SchedulerConfig.backfill_max_jobs): the
#: only jobs inside the cut that can carry "Priority" (reservations)
HEAD_JOBS = 1_024


def log(msg: str) -> None:
    print(f"[cut {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def last_real_cycle(client) -> dict:
    rows = [c for c in stats_of(client).get("cycle_trace", ())
            if c.get("solver") != "skip"]
    return rows[-1] if rows else {}


def quiet_rows(client):
    """The rows, read between two looks at the cycle trace that show the
    same last working cycle: every stamp and priority is that cycle's."""
    for _ in range(40):
        before = last_real_cycle(client)
        rows = list(client.query_jobs_stream(include_history=True))
        after = last_real_cycle(client)
        if before and before.get("now") == after.get("now"):
            return rows, after
        log("a cycle ran while the rows were read; again")
        time.sleep(2.0)
    raise RuntimeError("the queue never stood still")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tools/check_batch_cut.py")
    ap.add_argument("--seed", type=int, default=2_147_480_144)
    ap.add_argument("--shrink", type=int, default=0)
    args = ap.parse_args(argv)
    bench = Benchmark(ROOT)
    cfg = bench.config_file(CELL)
    traffic = bench.traffic_file(CELL)
    limit = int(cfg["scheduler"].get("ScheduledBatchSize", DEFAULT_BATCH))
    if args.shrink:
        shrink(cfg, traffic, args.shrink)
        limit = max(1, limit // args.shrink)
        cfg["scheduler"]["ScheduledBatchSize"] = limit
    weights = {"job_size": float(cfg["priority"].get("WeightJobSize", 0))}
    cluster = make_cluster(cfg, args.seed)
    system = ServedSystem(cfg, cluster, "batchcut-check")
    out: dict = {"seed": args.seed, "limit": limit, "ok": False}
    try:
        device = system.start()
        client = system.client
        out["device"] = [device.get("platform"), device.get("device_kind")]
        log(f"daemon up on {out['device']}")
        for n in cluster["drained"]:
            client.modify_node(cluster["names"][n], "drain")
        ledger = Ledger()
        out["preload"] = preload(
            traffic["setup"]["preload"], traffic["mixes"], client, ledger,
            int(traffic["base_seed"]), args.seed,
            lambda t_all: settled_pending(client, t_all, 600.0), log,
            lambda t: time.sleep(max(0.0, t - time.time())))
        rows, cycle = quiet_rows(client)
        log(f"{len(rows)} rows; the last working cycle: now {cycle['now']}, "
            f"ranked {cycle.get('ranked')}, cut {cycle.get('cut')}, "
            f"backfilled {cycle.get('backfilled')}")
    finally:
        system.kill()

    now = float(cycle["now"])
    sent = {jid: ack.job for jid, ack in ledger.acks.items()}

    def as_ref(row, running=False):
        job = sent[row.job_id]
        ref = dict(id=row.job_id, cpu=float(job.cpu),
                   mem=int(job.mem_gib) * 1024, node_num=int(job.node_num),
                   submit_time=row.submit_time)
        if running:
            ref["run_time"] = int(max(now - row.start_time, 0.0))
        return ref

    pending_rows = sorted((r for r in rows if r.status == "Pending"),
                          key=lambda r: r.job_id)
    # a job submitted with a begin_time is a candidate once it has passed:
    # all of the preload by now (the queue settled after the release)
    pending = [as_ref(r) for r in pending_rows]
    running = [as_ref(r, True) for r in rows if r.status == "Running"]
    inside, pri = cut_by_priority(pending, running, weights, now, limit)
    ref_in = np.zeros(len(pending), bool)
    ref_in[inside] = True
    stamped = np.array([r.pending_reason == "Priority"
                        for r in pending_rows])
    shown = np.array([r.priority for r in pending_rows])
    backfilled = int(cycle.get("backfilled", 0))

    # 1. the count
    out["pending"] = len(pending)
    out["running"] = len(running)
    out["unstamped"] = int((~stamped).sum())
    out["backfilled"] = backfilled
    out["ranked"], out["cut"] = cycle.get("ranked"), cycle.get("cut")
    # 2. no stamped row outside the reference's cut above an unstamped one
    outside = stamped & ~ref_in
    out["lowest_unstamped"] = float(shown[~stamped].min())
    out["highest_stamped_outside"] = (float(shown[outside].max())
                                      if outside.any() else None)
    order_ok = (not outside.any()
                or out["lowest_unstamped"] >= out["highest_stamped_outside"])
    # 3. the set, for that cycle's now
    daemon_in = ~stamped
    missing = np.nonzero(daemon_in & ~ref_in)[0]     # the daemon's, not ours
    extra = np.nonzero(ref_in & ~daemon_in)[0]       # ours, stamped there
    taken, left = ties_at_the_edge(pri, inside, limit)
    edge = float(pri[inside[-1]])
    out["edge_priority"] = edge
    out["exact_ties_at_edge"] = {"inside": taken, "outside": left}
    # "at the edge": within float32's rounding of the cut's last priority
    tol = 1e-3 + 4e-6 * abs(edge)
    near = np.abs(pri.astype(np.float64) - edge) <= tol
    out["near_ties_at_edge"] = int(near.sum())
    rank = np.empty(len(pending), np.int64)
    rank[np.argsort(-pri, kind="stable")] = np.arange(len(pending))
    # a row of the reference's cut that the daemon stamped "Priority" is
    # one of the head's reservations (the top of the order) or a tie
    head = [int(i) for i in extra if not near[i]]
    out["in_daemon_not_reference"] = len(missing)
    out["in_reference_stamped_by_daemon"] = len(extra)
    out["of_them_at_the_edge"] = [int(near[missing].sum()),
                                  int(near[extra].sum())]
    out["listed_ties"] = [pending[i]["id"]
                          for i in list(missing) + list(extra)
                          if near[i]][:40]
    out["head_reservations"] = len(head)
    out["head_deepest_rank"] = max((int(rank[i]) for i in head), default=-1)
    set_ok = (bool(near[missing].all())
              and len(head) <= backfilled
              and out["head_deepest_rank"] < HEAD_JOBS
              and len(missing) == int(near[extra].sum()))
    count_ok = (out["unstamped"] + len(head) == min(limit, len(pending))
                and cycle.get("ranked") == len(pending)
                and cycle.get("cut") == len(pending) - limit)
    # 4. the priorities shown
    err = np.abs(shown - pri.astype(np.float64))
    out["priority_max_abs_err"] = float(err.max())
    out["priority_rows_off"] = int((err > 1e-3 + 2e-6 * np.abs(shown)).sum())
    pri_ok = out["priority_rows_off"] == 0
    out["checks"] = {"count": bool(count_ok), "order": bool(order_ok),
                     "set": bool(set_ok), "priority": bool(pri_ok)}
    out["ok"] = all(out["checks"].values())
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_batch_cut.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
