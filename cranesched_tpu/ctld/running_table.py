"""Dense structure-of-arrays over the running jobs' priority attributes.

``multifactor_priority`` reads seven values of every RUNNING job: its
qos and partition priority, node count, total cpus and memory, account
index (the factor bounds and the per-account service sum run over
pending and running alike) and its start time (``run_time`` ages).
None of the first six changes while a job runs, and ``start_time`` is
written once, before the job enters ``scheduler.running``.  So the rows
are written where the set changes (the ``running`` dict's two hooks:
one row a start, one move a finish) and a cycle reads columns: it never
walks the jobs.

Rows are DENSE: the live rows are ``[:n]``, and a removal fills the
freed slot from the last row.  The row ORDER is therefore slot order,
not the dict's; the priority model reduces over the running set
(minima, maxima, a per-account sum), so the order shows at most in the
last bits of a float32 sum over several accounts.

``epoch`` bumps on every write: whoever keeps copies of the columns
(the scheduler's padded device arrays) knows by it when they are stale.
"""

from __future__ import annotations

import numpy as np

_ROW = np.dtype([("job_id", np.int64), ("qos", np.int32),
                 ("part", np.int32), ("nnum", np.int32),
                 ("cpus", np.float32), ("mem", np.float32),
                 ("acct", np.int32), ("start", np.float64)])


class RunningTable:
    """One ``_ROW`` a running job; the scheduler derives the values (it
    owns the Job/JobSpec semantics), this class owns the storage."""

    def __init__(self, cap: int = 64):
        self.epoch = 0
        self.n = 0
        self._slot: dict[int, int] = {}     # job_id -> row index
        self._rows = np.zeros(max(int(cap), 8), _ROW)

    def __len__(self) -> int:
        return self.n

    def put(self, job_id: int, qos: int, part: int, nnum: int,
            cpus: float, mem: float, acct: int, start: float) -> None:
        """Write the job's row (a job that has one is rewritten)."""
        slot = self._slot.get(job_id)
        if slot is None:
            if self.n == len(self._rows):
                rows = np.zeros(2 * self.n, _ROW)
                rows[:self.n] = self._rows
                self._rows = rows
            slot = self._slot[job_id] = self.n
            self.n += 1
        self._rows[slot] = (job_id, qos, part, nnum, cpus, mem, acct,
                            start)
        self.epoch += 1

    def remove(self, job_id: int) -> None:
        """Free the job's row (no-op for a job that has none): the last
        row moves into its slot, so ``[:n]`` stays dense."""
        slot = self._slot.pop(job_id, None)
        if slot is None:
            return
        self.n -= 1
        if slot != self.n:
            rows = self._rows
            rows[slot] = rows[self.n]
            self._slot[int(rows["job_id"][slot])] = slot
        self.epoch += 1

    def column(self, name: str) -> np.ndarray:
        """The live rows' values of one field, in slot order (a view:
        read it under the lock the writers hold)."""
        return self._rows[name][:self.n]

    def row_of(self, job_id: int) -> tuple | None:
        """``(qos, part, nnum, cpus, mem, acct, start)`` of a job that
        has a row (None: it has none)."""
        slot = self._slot.get(job_id)
        return None if slot is None else self._rows[slot].item()[1:]
