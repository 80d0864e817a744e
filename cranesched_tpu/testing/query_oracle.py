"""The job query's plain reference: the full walk.

``CtldServer._job_snapshot`` takes its live candidates from the
narrowest source a request names (``job_ids``, the scheduler's per-user
index, else the whole queue), in ascending id, and stops at the caller's
``limit + 1`` matches.  The pass it replaced copied the WHOLE queue (and
all of history, where asked), ran every filter over every job, sorted
what was left, and left the cut to the handlers; its name map covered
every node.  ``reference_reply`` is that pass, kept as it was, with the
two handlers' own cut and ``truncated`` arithmetic behind it; a served
``QueryJobsInfo`` / ``QueryJobsStream`` and the reference must agree row
for row and bit for bit on every request (tests/test_query_index.py).
"""

from __future__ import annotations

from cranesched_tpu.rpc import crane_pb2 as pb
from cranesched_tpu.rpc.convert import job_to_pb


def full_walk(server, request) -> tuple[list, dict]:
    """Every filter over every job, then the sort: no index, no cut.
    Normalises ``request.limit`` for a bare cursor, as the served
    function does."""
    sched = server.scheduler
    if request.after_job_id and not request.limit:
        request.limit = server.DEFAULT_PAGE
    names = {i: n.name for i, n in sched.meta.nodes.items()}
    jobs = list(sched.pending.values()) + list(sched.running.values())
    if request.include_history:
        jobs += list(sched.history.values())
        if sched.archive is not None:
            seen = {j.job_id for j in jobs}
            paged = bool(request.limit or request.after_job_id)
            jobs += [j for j in sched.archive.query(
                         job_ids=list(request.job_ids),
                         user=request.user,
                         partition=request.partition,
                         limit=(request.limit + 1 if paged
                                else server.DEFAULT_PAGE),
                         after_job_id=request.after_job_id,
                         keyset=paged)
                     if j.job_id not in seen]
    if request.job_ids:
        wanted = set(request.job_ids)
        jobs = [j for j in jobs if j.job_id in wanted]
    if request.user:
        jobs = [j for j in jobs if j.spec.user == request.user]
    if request.partition:
        jobs = [j for j in jobs if j.spec.partition == request.partition]
    if request.after_job_id:
        jobs = [j for j in jobs if j.job_id > request.after_job_id]
    jobs.sort(key=lambda j: j.job_id)
    return jobs, names


def reference_reply(server, request: pb.QueryJobsRequest,
                    streamed: bool) -> tuple[list, bool]:
    """``(rows, truncated)`` as ``QueryJobsInfo`` (``streamed`` False)
    or the whole of ``QueryJobsStream`` would answer ``request``; takes
    the server lock, and leaves ``request`` as it was."""
    copy = pb.QueryJobsRequest()
    copy.CopyFrom(request)
    # the unary handler reads the limit BEFORE a bare cursor is given
    # its default page, the stream after
    limit = copy.limit
    priority_of = server.scheduler.job_priority
    with server._lock:
        jobs, names = full_walk(server, copy)
        if streamed:
            limit = copy.limit or len(jobs)
        truncated = bool(limit) and len(jobs) > limit
        if limit:
            jobs = jobs[:limit]
        return ([job_to_pb(j, names, priority_of(j)) for j in jobs],
                truncated)
