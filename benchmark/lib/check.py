"""The comparison that decides `correct`, and the plain reference it
compares with.

A scheduler's answers are not unique (ties, order), so the reference is
not a second scheduler whose placements must match: it is a plain replay
of what the served path itself answered in this run — every row the
daemon returned for every job it acknowledged, and the WAL it left —
against the guarantees the deployment's file states:

* ingest: every acknowledged submit is there, once, as it was sent;
* solve + commit: on every node, at every instant, the jobs that held it
  ask for no more cpu and memory than it has; every job sits in its own
  partition, on as many distinct nodes as it asked for, never on a node
  that was drained before it started;
* WAL: the daemon is killed (SIGKILL, no graceful stop) the instant its
  last acknowledgement arrives, and every acknowledged job that is not yet
  finished is read back from the snapshot + log left on disk, with the
  sizes sent; and the submits that were acknowledged one after the other
  (no two of them in flight together, so no two can share a group) are no
  more than the fsyncs the daemon counted over the same span;
* solve: no job that the mix says can start still waits, seconds after it
  was acknowledged, while as many nodes of its partition as it asks for
  have room for it by the harness's own books;
* dispatch: every started job reached the node plane — a job whose
  simulated runtime has passed is Completed, with an end exactly that
  runtime after its start, which only the node plane's own completion
  gives — and no job has two start records.

It imports nothing of the program and takes node sizes and job sizes from
the harness's own records (the seed), never from the daemon.  Every
number is a count of violations; every limit is 0."""

from __future__ import annotations

import collections
import glob
import json
import os

Row = collections.namedtuple(
    "Row", "job_id status partition user node_names start_time end_time")

LIMITS = {"acked_lost": 0, "acked_dup": 0, "overcommit": 0, "misplaced": 0,
          "idle_fit": 0, "wal_lost": 0, "unsynced_acks": 0,
          "double_start": 0, "undispatched": 0}

# the phase of the submits sent only to be the last thing acknowledged
# before the kill: they are in the log or lost, and in no row
LAST_WORDS = "last_words"

LIVE = ("Pending", "Running", "Suspended")


def rows_from_pb(jobs) -> list[Row]:
    return [Row(j.job_id, j.status, j.partition, j.user,
                tuple(j.node_names), j.start_time, j.end_time)
            for j in jobs]


def read_durable_state(wal_path: str) -> dict:
    """What a restart would find: the snapshot's jobs overlaid with every
    record of the sealed segments and the active log, last writer wins.
    Returns {"jobs": {id: {"partition", "user", "cpu", "mem_bytes"}},
    "starts": {id: start records with no requeue between}}."""
    jobs: dict[int, dict] = {}
    starts: collections.Counter = collections.Counter()

    def note(job: dict) -> None:
        spec = job["spec"]
        jobs[int(job["job_id"])] = {
            "partition": spec["partition"], "user": spec["user"],
            "cpu": float(spec["res"]["cpu"]),
            "mem_bytes": int(spec["res"]["mem_bytes"])}

    try:
        with open(wal_path + ".snap", encoding="utf-8") as fh:
            for job in json.load(fh).get("jobs", ()):
                note(job)
    except (OSError, json.JSONDecodeError):
        pass
    for path in sorted(glob.glob(wal_path + ".seg.*")) + [wal_path]:
        if not os.path.exists(path):
            continue
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue          # a torn tail is not a record
                job = rec.get("job")
                if job is None:
                    continue
                note(job)
                if rec.get("ev") == "start":
                    starts[int(job["job_id"])] += 1
                elif rec.get("ev") == "requeue":
                    starts[int(job["job_id"])] = 0
    return {"jobs": jobs, "starts": dict(starts)}


def overcommitted_nodes(cluster: dict, acks: dict, rows) -> int:
    """Nodes on which, at some instant, the jobs holding them asked for
    more cpu or memory than the node has.  A job holds its nodes from its
    start to its recorded end (open if it has none); at one instant an
    end frees before a start takes."""
    index = {name: i for i, name in enumerate(cluster["names"])}
    events: dict[int, list] = collections.defaultdict(list)
    for row in rows:
        ack = acks.get(row.job_id)
        if ack is None or not row.node_names or row.start_time <= 0:
            continue
        cpu, mem = ack.job.cpu, ack.job.mem_gib
        end = row.end_time if row.end_time > 0 else float("inf")
        for name in row.node_names:
            n = index.get(name)
            if n is None:
                continue              # counted under `misplaced`
            events[n].append((row.start_time, 1, cpu, mem))
            events[n].append((end, 0, -cpu, -mem))
    bad = 0
    for n, evs in events.items():
        evs.sort()
        cpu = mem = 0
        for _, _, dc, dm in evs:
            cpu += dc
            mem += dm
            if cpu > cluster["cpu"][n] or mem > cluster["mem_gib"][n]:
                bad += 1
                break
    return bad


def misplaced_jobs(cluster: dict, acks: dict, rows, t_drained: float) -> int:
    """Jobs outside their partition, with the wrong gang width, twice on
    one node, or started on a drained node after it was drained."""
    index = {name: i for i, name in enumerate(cluster["names"])}
    drained = set(cluster["drained"])
    bad = 0
    for row in rows:
        ack = acks.get(row.job_id)
        if ack is None or not row.node_names:
            continue
        nodes = [index.get(name) for name in row.node_names]
        if (None in nodes or len(set(nodes)) != len(nodes)
                or len(nodes) != ack.job.node_num
                or any(cluster["part"][n] != ack.job.partition
                       for n in nodes)
                or (row.start_time > t_drained
                    and any(n in drained for n in nodes))):
            bad += 1
    return bad


# the node plane's completions reach the rows with the next cycle, and a
# daemon nobody submits to (the drain) cycles once a second: a job is held
# to its end only this long after it was due
END_SLACK_S = 5.0


def undispatched_jobs(acks: dict, rows, t_query: float) -> int:
    """Started jobs whose runtime ran out well before the rows were read
    and that the node plane never reported back as it should have."""
    bad = 0
    for row in rows:
        ack = acks.get(row.job_id)
        if ack is None or row.start_time <= 0:
            continue
        due = row.start_time + ack.job.sim_runtime
        if due > t_query - END_SLACK_S:
            continue
        if (row.status != "Completed" or row.end_time <= 0
                or abs(row.end_time - due) > 0.01):
            bad += 1
    return bad


# a job that can start is held to it this long after its acknowledgement:
# a dozen cycles of the slowest cell
IDLE_FIT_AGE_S = 5.0


def idle_fit_jobs(cluster: dict, acks: dict, rows, t_query: float,
                  streams=None) -> int:
    """Jobs still pending when the rows were read, acknowledged more than
    IDLE_FIT_AGE_S before, for which as many non-drained nodes of their
    partition as they ask for each had the cpu and memory free then.
    `streams`: only the jobs of these streams (None: of all).  Free room
    is the node's size less what the rows' running jobs hold; each job is
    asked about alone, so two that want the same gap both count."""
    index = {name: i for i, name in enumerate(cluster["names"])}
    free = [[c, m] for c, m in zip(cluster["cpu"], cluster["mem_gib"])]
    waiting = []
    for row in rows:
        ack = acks.get(row.job_id)
        if ack is None:
            continue
        if row.start_time > 0 and not 0 < row.end_time <= t_query:
            for name in row.node_names:
                n = index.get(name)
                if n is not None:
                    free[n][0] -= ack.job.cpu
                    free[n][1] -= ack.job.mem_gib
        elif (row.status == "Pending" and ack.done < t_query - IDLE_FIT_AGE_S
              and (streams is None or ack.stream in streams)):
            waiting.append(ack.job)
    drained = set(cluster["drained"])
    room: dict[str, list] = collections.defaultdict(list)
    for n, part in enumerate(cluster["part"]):
        if n not in drained and free[n][0] >= 1 and free[n][1] >= 1:
            room[part].append(free[n])
    bad = 0
    for job in waiting:
        fits = 0
        for cpu, mem in room[job.partition]:
            if cpu >= job.cpu and mem >= job.mem_gib:
                fits += 1
                if fits == job.node_num:
                    bad += 1
                    break
    return bad


def serial_acks(spans) -> int:
    """The most of these (sent, acknowledged) spans that lie one after
    the other with no overlap.  Two submits that share one WAL group are
    in flight together; so this many acknowledgements took at least this
    many groups, each with an fsync of its own."""
    n = 0
    free_at = float("-inf")
    for sent, done in sorted(spans, key=lambda s: s[1]):
        if sent >= free_at:
            n += 1
            free_at = done
    return n


def compare(cluster: dict, ledger, rows, durable: dict, t_query: float,
            t_drained: float, can_start=(), sync=None) -> dict:
    """Every number compared, beside its limit.  `can_start`: the streams
    whose every job can start; `sync`: {"spans": the (sent, acknowledged)
    spans of the submits between two readings of the daemon's fsync
    counter, "fsyncs": the counter's growth}."""
    acks = ledger.acks
    by_id: dict[int, Row] = {}
    dup_rows = 0
    for row in rows:
        if row.job_id in by_id:
            dup_rows += 1
        by_id[row.job_id] = row
    lost = 0
    wal_lost = 0
    for job_id, ack in acks.items():
        row = by_id.get(job_id)
        if ack.phase == LAST_WORDS:
            pass                      # sent after the rows were read
        elif (row is None or row.partition != ack.job.partition
                or row.user != ack.job.user):
            lost += 1
            continue
        elif row.status not in LIVE:
            continue                  # finished: the log may drop it
        kept = durable["jobs"].get(job_id)
        if (kept is None or kept["partition"] != ack.job.partition
                or kept["user"] != ack.job.user
                or kept["cpu"] != float(ack.job.cpu)
                or kept["mem_bytes"] != int(ack.job.mem_gib) << 30):
            wal_lost += 1
    numbers = {
        "acked_lost": lost,
        "acked_dup": ledger.duplicate_ids + dup_rows,
        "overcommit": overcommitted_nodes(cluster, acks, rows),
        "misplaced": misplaced_jobs(cluster, acks, rows, t_drained),
        "idle_fit": idle_fit_jobs(cluster, acks, rows, t_query,
                                  set(can_start)),
        "wal_lost": wal_lost,
        "unsynced_acks": (max(0, serial_acks(sync["spans"])
                              - int(sync["fsyncs"])) if sync else 0),
        "double_start": sum(1 for n in durable["starts"].values() if n > 1),
        "undispatched": undispatched_jobs(acks, rows, t_query),
    }
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}


def verdict(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
