"""Controls: the answers of a system that breaks ONE guarantee the
deployment states, put in the program's place.  The comparison has to
call each of them not correct; a run with `--controls` shows that it
does, at the cell's own size, on the answers of that very run.  A control
that would answer as the program did (it does not apply to this run)
returns None.

The `durable_ack` guarantee has no control here: what is on disk after a
kill cannot be made up from answers.  Its controls are the daemon itself
with a weakened log, `--control fsync_off|late_write`
(lib/control_daemon.py), driven through the whole of a run."""

from __future__ import annotations

import bisect
import copy


def memory_blind(cluster: dict, ledger, rows, durable):
    """The plain reference placer with the memory check left out: every
    job that held nodes at the end of the run is placed again, first fit
    in node order within its partition, by cpu alone.  Breaks "no node is
    over-committed" wherever a node is short of memory for its cores."""
    free_cpu = list(cluster["cpu"])
    drained = set(cluster["drained"])
    by_part: dict[str, list[int]] = {}
    for n, part in enumerate(cluster["part"]):
        if n not in drained:
            by_part.setdefault(part, []).append(n)
    out = []
    for row in rows:
        ack = ledger.acks.get(row.job_id)
        if (ack is None or not row.node_names or row.start_time <= 0
                or row.end_time > 0):
            out.append(row)
            continue
        chosen = []
        for n in by_part.get(ack.job.partition, ()):
            if free_cpu[n] >= ack.job.cpu:
                chosen.append(n)
                if len(chosen) == ack.job.node_num:
                    break
        if len(chosen) < ack.job.node_num:
            out.append(row._replace(node_names=(), start_time=0.0))
            continue
        for n in chosen:
            free_cpu[n] -= ack.job.cpu
        out.append(row._replace(
            node_names=tuple(cluster["names"][n] for n in chosen)))
    return out, durable


def short_sighted(cluster, ledger, rows, durable, sees: int = 1024):
    """A placer that looks at the first `sees` candidates of the queue
    and no further: a job with that many older jobs still waiting when it
    was acknowledged, and when the rows were read, was never looked at,
    and waits.  Breaks "a job that can run, runs" behind a deep queue;
    where the queue never gets that deep it answers as the program."""
    acked = sorted((ledger.acks[r.job_id].done, r.job_id) for r in rows
                   if r.job_id in ledger.acks)
    started = {r.job_id for r in rows if r.start_time > 0}
    # jobs that never started, by the instant they were acknowledged:
    # they are ahead of every later job for the whole run
    stuck = [t for t, job_id in acked if job_id not in started]
    out, changed = [], False
    for row in rows:
        ack = ledger.acks.get(row.job_id)
        if (ack is not None and row.start_time > 0
                and bisect.bisect_left(stuck, ack.done) >= sees):
            row = row._replace(status="Pending", node_names=(),
                               start_time=0.0, end_time=0.0)
            changed = True
        out.append(row)
    return (out, durable) if changed else None


def double_dispatch(cluster, ledger, rows, durable):
    """One job started twice: a second start record in the log.  Breaks
    "a job starts exactly once"."""
    durable = copy.deepcopy(durable)
    started = [r.job_id for r in rows if r.start_time > 0]
    if started:
        job_id = started[len(started) // 2]
        durable["starts"][job_id] = durable["starts"].get(job_id, 1) + 1
    return rows, durable


def lost_dispatch(cluster, ledger, rows, durable):
    """A started job that never reached the node plane: it never ends.
    Breaks "every started job reaches the node plane"."""
    rows = list(rows)
    ended = [i for i, r in enumerate(rows) if r.end_time > 0]
    if ended:
        i = min(ended, key=lambda k: rows[k].end_time)
        rows[i] = rows[i]._replace(status="Running", end_time=0.0)
    return rows, durable


def lost_ack(cluster, ledger, rows, durable):
    """An acknowledged job the daemon no longer knows."""
    rows = list(rows)
    if rows:
        del rows[len(rows) // 3]
    return rows, durable


CONTROLS = {"memory_blind": ("overcommit", memory_blind),
            "short_sighted": ("idle_fit", short_sighted),
            "double_dispatch": ("double_start", double_dispatch),
            "lost_dispatch": ("undispatched", lost_dispatch),
            "lost_ack": ("acked_lost", lost_ack)}
