"""Structured per-cycle traces, the cycle thread's partitioning clock,
and the profiler span helper.

``CycleTraceRing`` keeps the last N cycle traces (plain dicts, schema
below) in a bounded deque — cheap enough to run always-on, queryable
over RPC through QueryStats (``cstats --cycles`` renders it).

Cycle-trace schema (ARCHITECTURE.md "Observability"):

    now              float   scheduler clock the cycle ran at
    solver           str     backend ("native", "pallas", "backfill"...)
    prelude_ms       float   _cycle_body's start to the first solve
                             closure's start (drains, candidates,
                             snapshot, priority, batch build)
    solve_ms         float   the solve closures, on their own timers
    commit_ms        float   REMAINDER: total_ms - prelude_ms -
                             solve_ms.  Includes the cycle thread's
                             waits to retake the server lock after each
                             closure, so an upper bound of the commit
                             (commit_apply_ms + wal_ms + preempt_ms +
                             record_ms are the commit itself)
    dispatch_ms      float   lock-RELEASED post-commit push fan-out
    total_ms         float   _cycle_body's start to the end of
                             _record_cycle_stats' inputs; leaves out the
                             first lock take, the sim plane's advance
                             and the dispatch closure
    lock_held_ms     float   prelude_ms + commit_ms: an upper bound
                             too, it books the retakes' WAITING as
                             holding (lock_held_work_ms is the holding)
    wal_fsyncs       int     durability barriers between this cycle's
                             opening and its record: its own groups
                             and the RPC handlers' that ran beside it
                             (a SubmitBatchJobs chunk is one group; a
                             single SubmitBatchJob a barrier outside
                             any group)
    wal_groups       int     WAL groups flushed in the same span (the
                             cycle's own <= 3)
    candidates       int     jobs considered this cycle: the batch the
                             solve was given (<= ScheduledBatchSize)
    ranked           int     rows _priority_sort ranked: every candidate
                             the gate pass returned, the whole queue's
    cut              int     ranked - candidates: the rows past the
                             batch cut, waiting on "Priority" with
                             their priority written (0 where the queue
                             fits one batch)
    run_walked       int     running jobs whose priority row Python
                             derived inside this cycle's prelude: 0 on a
                             steady cycle (the running dict's hooks keep
                             the rows, ctld/running_table.py), the whole
                             running set on the cycle that makes the
                             table (the first, and the first after
                             rebuild_device_state); 0 under basic
                             priority, which reads no running column
    run_cols_ms      float   the part of priority_ms on the running
                             jobs' columns: that walk where there is
                             one, the padded device copies when the
                             membership has moved, run_time (one
                             perf_counter pair, no clock phase)
    gang_bound       int     the static gang bound K the cycle's solves
                             ran with: the bucket of its widest
                             candidate, capped at MaxNodesPerJob; the
                             width of their [J, K] node lists (neither
                             the head's sort nor the tail's passes
                             cost K a job any more)
    gang_fill_pct    float   100 * sum of the candidates' node_num /
                             (candidates * gang_bound): the share of
                             those node lists a job can fill
    tail_pass_pct    float   100 * selection passes the cycle's Pallas
                             kernel ran / (its slots * gang_bound):
                             after pass 0 a slot stops at its widest
                             node_num and at the first infinite minimum
                             (models/pallas_solver.py); 100.0 when
                             no Pallas kernel ran in the cycle (no
                             pass was left out)
    decisions_per_s  float   candidates / solve_ms: BASELINE's
                             yardstick as the served path pays it
    commit_visited_pct float 100 * rows the cycle's commits visited in
                             Python / candidates (head + tail on the
                             split route): the rows the solve placed
                             (a backfill reservation counts) or told
                             another reason than the one last stamped
                             on them (ctld/pending_table.py stamped);
                             100.0 is the pass over every candidate
    commit_scan_ms   float   the part of commit_apply_ms in _commit's
                             array pulls and that visit; the rest is
                             the ledger batch, WAL records and the
                             dispatch queue of the jobs that start
    prelude_jobs_touched int Job objects the prelude looked up: the
                             rows whose gate flipped, the first-sight
                             "eligible" stamps, the batch cut's newly
                             cut rows, the rows whose mask class went
                             stale; every candidate on a route that
                             walks the jobs (packed, topology,
                             reservations, the non-incremental
                             rebuild).  About 0 on a steady cycle of
                             the default route, whatever the backlog:
                             the cycle carries PendingTable rows
    nodes_selected   int     sum of node_num over the jobs the cycle
                             started and the backfill head's
                             reservations: the nodes its solves chose
                             and its commits carried
    placed           int     jobs started (incl. backfill tail)
    preempted        int     victims killed by this cycle
    backfilled       int     placed with start_bucket > 0 (future start)
    queue_depth      int     pending queue size at cycle start
    dirty_jobs       int     PendingTable rows dirtied since last cycle
    dirty_nodes      int     node rows patched into the cached snapshot
                             (0 on a cache hit; == all nodes on rebuild)
    skip_reason      str     only on solver="skip" rows: why the cycle
                             short-circuited ("fingerprint")
    skips            int     only on solver="skip" rows: consecutive
                             skipped cycles coalesced into this row
                             (idle clusters would otherwise flush the
                             ring with identical no-op entries)

The cycle ledger (``CycleClock``; all ms, one clock, written into the
ringed dict when the cycle closes, so a row read while its dispatch
closure still runs lacks them).  The parts PARTITION the cycle thread's
period: with dispatch_ms and unnamed_ms they sum to period_ms.

    period_ms        the close of the previous cycle's ledger to the
                     close of this one: the sleep before the cycle, the
                     cycle, its dispatch closure, the retake after it
    sleep_ms         in the loop's event wait
    lock_wait_ms     waiting for the server lock, over every take of
                     the cycle (the first and one after each closure)
    lock_wait_max_ms the longest single one of those waits
    lock_wait_max_behind  the lock ledger's holder tag as that longest
                     wait began: "submit", "submit_batch", "query",
                     "stats", "snapshot", or "" (the lock was free, or
                     held at a site with no class).  The lock is not
                     fair: it can pass through other holders before the
                     cycle gets it, so read a long wait together with
                     the row's rpc_<c>_held_max_ms (a 2 s wait that
                     began behind "submit_batch" beside a 2 s
                     rpc_snapshot_held_max_ms was the snapshot's)
    sim_ms           first lock take to cycle_phases: the sim node
                     plane's advance_to (0 on a real node plane) and
                     the federation's lease expiry
    record_ms        the instruments themselves: the profiler window's
                     tick and _record_cycle_stats (device-memory
                     sample, histograms, ring push)
    drain_ms         process_status_changes ... array children, the
                     no-op fingerprint
    candidates_ms    _pending_candidates + the "eligible" stamps
    snapshot_ms      meta.start_logging + meta.snapshot
    priority_ms      _priority_sort (the device priority and its wait)
                     over EVERY candidate; run_cols_ms is its part on
                     the running jobs' columns
    cut_ms           the batch cut: the order's first ScheduledBatchSize
                     rows sliced off as the batch, _cut_batch's stamps
                     on the rest
    build_ms         _build_batch, cost0, route set-up, up to the first
                     WAL flush before a solve
    solve_enqueue_ms inside the closures: fn() until it returns
    solve_device_wait_ms  block_until_ready on the placements
    solve_host_ms    the rest of the closures (the split route's
                     min-over-horizon round trip to numpy)
    commit_apply_ms  from a solve's results to the end of _commit
                     (both calls on the split route), and the resident
                     state's staging
    wal_ms           the cycle's _wal_flush / _wal_begin, fsync included
    preempt_ms       _try_preemption
    lock_held_work_ms  the sum of the parts that ran under the lock
                     (LOCKED_PARTS): what a submit or a query waits
                     behind
    unnamed_ms       period_ms less every part: the loop's glue
    gc_ms            growth over the period of the process-wide
                     collector-pause accumulator (``GC_PAUSES``); lies
                     INSIDE the parts above, on whichever thread paid
    cpu_ms           the process's CPU time over the period (one
                     time.process_time() read a cycle, every thread):
                     a period of seconds with a cpu_ms of tens is a
                     process that did not run or slept in a syscall,
                     cpu_ms about the period a thread that kept the
                     interpreter
    profiled         bool, only on rows of cycles that ran inside a
                     ProfilerWindow capture

The lock ledger (``LockLedger``; all ms, the handlers' side of the
server lock, drained by the cycle thread into the same row as the
period closes).  A class ``<c>`` is one of submit (SubmitBatchJob),
submit_batch (each 32-spec hold of SubmitBatchJobs), query
(QueryJobsInfo, each hold of QueryJobsStream), stats (QueryStats),
snapshot (Snapshotter.snap_once); its fields are written only on rows
of periods in which it took the lock.

    rpc_<c>_n            takes of the lock that ENDED in the period
    rpc_<c>_wait_ms      sum of their waits for the lock
    rpc_<c>_wait_max_ms  the longest single one
    rpc_<c>_held_ms      sum of their holds
    rpc_<c>_held_max_ms  the longest single one
    rpc_submit_wal_ms, rpc_submit_batch_wal_ms
                         the part of the holds inside os.fsync (growth
                         of the WAL's fsync_seconds over each hold)
    rpc_submit_batch_door_ms  OUTSIDE any hold: batches waiting out a
                         cycle that compiles (wait_out_compiling_cycle)
    rpc_query_snapshot_ms    _job_snapshot: the live candidates from the
                         narrowest source the request names (job_ids,
                         the user's index, else the whole queue) in
                         ascending id, the filters, the cut at limit + 1
    rpc_query_scanned    a COUNT, not a time: Job objects _job_snapshot
                         touched in Python in the period's query holds
                         (lookups on the way to the cut; every job of
                         the queue, history or an archive page where the
                         request names no narrower source)
    rpc_query_convert_ms     the name map of the rows' own nodes and
                         job_to_pb over the rows
    lock_held_rpc_ms     sum of rpc_<c>_held_ms over the classes
    lock_unaccounted_ms  period_ms - lock_held_work_ms -
                         lock_held_rpc_ms: the lock free, or held at a
                         site with no class (by subtraction, so that a
                         holder nobody named shows); on every row

``solve_span`` wraps a solve closure in ``jax.profiler.TraceAnnotation``
so a captured device trace lines up with cycle phases; it degrades to a
no-op when the profiler is unavailable (CPU CI containers).  Inside a
capture (and only then) ``CycleClock`` puts each phase on the
profiler's clock too, as ``crane:cycle:<phase>``, and ``LockLedger``
each classed hold, as ``crane:rpc:<class>`` on the handler's thread.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time
from typing import Iterator

from cranesched_tpu.obs.metrics import REGISTRY


class CycleTraceRing:
    """Thread-safe bounded ring of per-cycle trace dicts."""

    def __init__(self, size: int = 64):
        self._ring = collections.deque(maxlen=max(int(size), 1))
        self._lock = threading.Lock()

    def push(self, trace: dict) -> None:
        with self._lock:
            self._ring.append(trace)

    def snapshot(self, last: int | None = None) -> list[dict]:
        """Newest-last copy of the ring (optionally only the last N)."""
        with self._lock:
            out = list(self._ring)
        return out if last is None else out[-last:]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


class GcPauses:
    """Seconds the collector has paused the process, summed by one
    ``gc.callbacks`` hook (two clock reads a collection).  Collections
    never overlap, so one start slot suffices."""

    def __init__(self):
        self.total_s = 0.0
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.total_s += time.perf_counter() - self._t0
            self._t0 = None

    def install(self) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)


#: the process's one accumulator: the collector is the process's too
GC_PAUSES = GcPauses()

#: the phases that run under the server lock (lock_held_work_ms)
LOCKED_PARTS = ("sim", "record", "drain", "candidates", "snapshot",
                "priority", "cut", "build", "commit_apply", "wal",
                "preempt")
#: every named phase; with "dispatch" and the glue they tile a period
PARTS = ("sleep", "lock_wait") + LOCKED_PARTS + (
    "solve_enqueue", "solve_device_wait", "solve_host")

_MET_LOCK_SECONDS = REGISTRY.counter(
    "crane_server_lock_seconds_total",
    "seconds threads waited for (kind=wait) and held (kind=held) the "
    "server lock, by holder class; fed once a cycle from the lock "
    "ledger, holder=cycle from the cycle thread's own clock")


class LockLedger:
    """The handlers' side of the server lock.  A thread that takes the
    lock at a classed site reads the clock before its own plain
    ``with lock:``, calls ``enter`` as the first statement inside and
    ``leave`` as the last (a ``finally``); both run WHILE THE THREAD
    HOLDS THE SERVER LOCK, so the accumulators need no lock of their
    own: one fixed slot a class in a flat list of floats.  The lock
    stays the caller's bare ``threading.Lock``; nothing here stands
    between a thread and ``acquire``.  The cycle thread drains the
    sums into its row as the period closes (``drain``, under the lock
    too, so no hold straddles a row's edge).

    ``holder`` names the class that holds the lock now ("" for none, or
    for a site with no class): what the cycle thread reads as it starts
    to wait, and the stall sentry when it fires.  While ``annotate`` is
    set (the scheduler sets it once a cycle, as it sets
    ``CycleClock.annotate``) each hold is also a ``crane:rpc:<class>``
    TraceAnnotation on the holder's thread."""

    HOLDERS = ("submit", "submit_batch", "query", "stats", "snapshot")
    SUBMIT, SUBMIT_BATCH, QUERY, STATS, SNAPSHOT = range(5)
    #: a class's slot: n, wait sum, wait max, held sum, held max
    _SLOT = 5
    #: sums booked within a class's holds (door: beside them), in the
    #: slots after the classes': (the class, <class>_<part>)
    PARTS = ((SUBMIT, "submit_wal"), (SUBMIT_BATCH, "submit_batch_wal"),
             (SUBMIT_BATCH, "submit_batch_door"),
             (QUERY, "query_snapshot"), (QUERY, "query_convert"))
    #: counts booked within a class's holds, in the slots after the
    #: parts': (the class, <class>_<what>), written without a unit
    COUNTS = ((QUERY, "query_scanned"),)
    _SIZE = _SLOT * len(HOLDERS) + len(PARTS) + len(COUNTS)
    (SUBMIT_WAL, SUBMIT_BATCH_WAL, SUBMIT_BATCH_DOOR, QUERY_SNAPSHOT,
     QUERY_CONVERT, QUERY_SCANNED) = range(_SLOT * len(HOLDERS), _SIZE)

    def __init__(self):
        self.annotate = False
        self.holder = ""
        self._acc = [0.0] * self._SIZE
        self._slot = 0
        self._t_enter = 0.0
        self._span = None
        self._cells = [
            (_MET_LOCK_SECONDS.labels(holder=name, kind="wait"),
             _MET_LOCK_SECONDS.labels(holder=name, kind="held"))
            for name in self.HOLDERS + ("cycle",)]

    def enter(self, holder: int, t0: float) -> float:
        """First statement inside the hold: ``t0`` is the caller's clock
        read before its ``with``.  Returns the instant the hold began."""
        t1 = time.perf_counter()
        acc = self._acc
        i = holder * self._SLOT
        wait = t1 - t0
        acc[i] += 1.0
        acc[i + 1] += wait
        if wait > acc[i + 2]:
            acc[i + 2] = wait
        self._slot = i
        self._t_enter = t1
        self.holder = self.HOLDERS[holder]
        if self.annotate:
            from jax.profiler import TraceAnnotation
            self._span = TraceAnnotation("crane:rpc:" + self.holder)
            self._span.__enter__()
        return t1

    def add(self, part: int, amount: float) -> None:
        """Book seconds to a part, or a number to a count; call it
        inside the hold."""
        self._acc[part] += amount

    def leave(self) -> None:
        """Last act inside the hold."""
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self.holder = ""
        acc = self._acc
        i = self._slot
        held = time.perf_counter() - self._t_enter
        acc[i + 3] += held
        if held > acc[i + 4]:
            acc[i + 4] = held

    def current(self) -> tuple[str, float]:
        """The holder now and how long its hold has lasted (the stall
        sentry's read, from another thread: a torn pair is possible and
        harmless)."""
        holder = self.holder
        if not holder:
            return "", 0.0
        return holder, time.perf_counter() - self._t_enter

    def drain(self, clock_fields: dict) -> dict:
        """The period's fields, given the clock's, and fresh sums; under
        the lock.  Also feeds crane_server_lock_seconds_total, here, off
        every handler's path."""
        acc, self._acc = self._acc, [0.0] * self._SIZE
        fields: dict = {}
        held_rpc = 0.0
        for k, name in enumerate(self.HOLDERS):
            i = k * self._SLOT
            if not acc[i]:
                continue
            held_ms = round(acc[i + 3] * 1e3, 3)
            held_rpc += held_ms
            prefix = "rpc_" + name
            fields[prefix + "_n"] = int(acc[i])
            fields[prefix + "_wait_ms"] = round(acc[i + 1] * 1e3, 3)
            fields[prefix + "_wait_max_ms"] = round(acc[i + 2] * 1e3, 3)
            fields[prefix + "_held_ms"] = held_ms
            fields[prefix + "_held_max_ms"] = round(acc[i + 4] * 1e3, 3)
            wait_cell, held_cell = self._cells[k]
            wait_cell.inc(acc[i + 1])
            held_cell.inc(acc[i + 3])
        for k, (holder, name) in enumerate(self.PARTS, self.SUBMIT_WAL):
            if acc[holder * self._SLOT]:
                fields["rpc_" + name + "_ms"] = round(acc[k] * 1e3, 3)
        for k, (holder, name) in enumerate(self.COUNTS, self.QUERY_SCANNED):
            if acc[holder * self._SLOT]:
                fields["rpc_" + name] = int(acc[k])
        wait_cell, held_cell = self._cells[-1]
        wait_cell.inc(clock_fields["lock_wait_ms"] / 1e3)
        held_cell.inc(clock_fields["lock_held_work_ms"] / 1e3)
        # from the rounded fields, so that the three sum to period_ms on
        # the row as it is read
        fields["lock_held_rpc_ms"] = round(held_rpc, 3)
        fields["lock_unaccounted_ms"] = round(
            clock_fields["period_ms"] - clock_fields["lock_held_work_ms"]
            - fields["lock_held_rpc_ms"], 3)
        return fields


class CycleClock:
    """The cycle thread's partitioning clock: ``mark(name)`` reads the
    clock once, charges the time since the previous mark to the phase
    that mark opened, and opens ``name``.  Every instant of the thread
    therefore belongs to exactly one phase and the parts sum to the
    period with no remainder arithmetic; a phase that recurs (the split
    route solves and commits twice) accumulates.  Owned by the
    scheduler, touched by the cycle thread alone.

    While ``annotate`` is set (the scheduler sets it from the
    ProfilerWindow once per cycle) each mark also closes the previous
    and opens the next ``crane:cycle:<phase>`` TraceAnnotation, so the
    host phases land in the profiler's trace beside the device ops.
    Outside a capture no profiler object is touched.

    With a ``LockLedger`` beside it, a mark that opens a ``lock_wait``
    reads the ledger's ``holder`` tag once: who the cycle thread began
    to wait behind."""

    GLUE = "unnamed"

    def __init__(self, ledger: LockLedger | None = None):
        GC_PAUSES.install()
        self.annotate = False
        self.ledger = ledger
        self._span = None
        self._phase = self.GLUE
        self._parts: dict[str, float] = {}
        self._lock_wait_max = 0.0
        self._behind = self._behind_max = ""
        self._t = self._t_open = time.perf_counter()
        self._gc_open = GC_PAUSES.total_s
        self._cpu_open = time.process_time()

    def mark(self, name: str) -> None:
        t = time.perf_counter()
        dt = t - self._t
        self._t = t
        phase = self._phase
        self._parts[phase] = self._parts.get(phase, 0.0) + dt
        if phase == "lock_wait" and dt > self._lock_wait_max:
            self._lock_wait_max = dt
            self._behind_max = self._behind
        if name == "lock_wait" and self.ledger is not None:
            self._behind = self.ledger.holder
        self._phase = name
        if self._span is not None or self.annotate:
            self._annotate(name)

    def _annotate(self, name: str) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self.annotate and name != self.GLUE:
            from jax.profiler import TraceAnnotation
            self._span = TraceAnnotation("crane:cycle:" + name)
            self._span.__enter__()

    def close(self) -> dict:
        """End the period: its fields, in ms, and a fresh ledger."""
        self.mark(self.GLUE)
        parts, self._parts = self._parts, {}
        ms = {name: parts.get(name, 0.0) * 1e3 for name in PARTS}
        fields = {name + "_ms": round(v, 3) for name, v in ms.items()}
        period_ms = (self._t - self._t_open) * 1e3
        cpu = time.process_time()
        fields.update(
            period_ms=round(period_ms, 3),
            cpu_ms=round((cpu - self._cpu_open) * 1e3, 3),
            lock_wait_max_ms=round(self._lock_wait_max * 1e3, 3),
            lock_wait_max_behind=self._behind_max,
            lock_held_work_ms=round(
                sum(ms[name] for name in LOCKED_PARTS), 3),
            # by subtraction, so a phase no field names shows up here
            unnamed_ms=round(period_ms - sum(ms.values())
                             - parts.get("dispatch", 0.0) * 1e3, 3),
            gc_ms=round((GC_PAUSES.total_s - self._gc_open) * 1e3, 3),
        )
        self._t_open = self._t
        self._lock_wait_max = 0.0
        self._behind_max = ""
        self._gc_open = GC_PAUSES.total_s
        self._cpu_open = cpu
        return fields


@contextlib.contextmanager
def solve_span(name: str) -> Iterator[None]:
    """jax.profiler.TraceAnnotation span, no-op without a profiler.

    Used around the lock-released solve closures so a captured device
    trace (CaptureProfile / jax.profiler.trace) shows one named span
    per cycle phase, so kernel attribution lines up with the cycle
    trace timings."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:       # pragma: no cover - jax always importable here
        yield
        return
    with TraceAnnotation(name):
        yield
