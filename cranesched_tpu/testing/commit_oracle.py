"""The commit's plain reference: every candidate visited in Python.

``JobScheduler._commit`` runs its per-job body over the rows a cycle
placed or whose reason changed (PendingTable ``stamped``).  The pass it
replaced ran the same body over EVERY candidate, placed or not, and told
each unplaced job its reason again.  ``visit_every_row`` gives a
scheduler that pass back by withholding the row map from its commits,
so that no stamp is ever read or kept; a scheduler and such a reference
driven through one script must agree on all that can be observed: every
pending job's ``pending_reason``, the started set, the WAL
(tests/test_commit_reasons.py).
"""

from __future__ import annotations

from cranesched_tpu.ctld.defs import PendingReason
from cranesched_tpu.ctld.pending_table import STAMP_NONE
from cranesched_tpu.ctld.scheduler import (
    _REASON_MAP,
    JobScheduler,
    _CycleJobs,
)


def visit_every_row(sched: JobScheduler) -> JobScheduler:
    """Make ``sched`` the reference: its commits loop over the full
    range of their ``ordered``, as the pass before the stamps did."""
    commit = sched._commit

    def full_range(ordered, placements, now, start_buckets=None,
                   tasks=None):
        plain = _CycleJobs(ordered.pending, None, ordered.ids, ordered.jobs)
        return commit(plain, placements, now, start_buckets, tasks)

    sched._commit = full_range
    return sched


def stale_stamps(sched: JobScheduler) -> list[int]:
    """Job ids that break the stamps' invariant: a row whose stamp is
    known while the job carries another reason than the commit writes
    for that code.  Empty on a sound scheduler, at any instant the
    server lock would be free."""
    pt = sched._ptable
    bad = []
    for job_id, row in pt._row.items():
        code = int(pt.stamped[row])
        if code == STAMP_NONE:
            continue
        told = _REASON_MAP.get(code, PendingReason.RESOURCE)
        if sched.pending[job_id].pending_reason != told:
            bad.append(job_id)
    return bad
