"""JobScheduler: the submit → cycle → dispatch → status-change loop.

TPU-native counterpart of the reference's JobScheduler/ScheduleThread_
(reference: src/CraneCtld/JobScheduler.cpp — submit path
SubmitJobToScheduler :3405, the 1 Hz scheduling cycle :1321-1981, batched
status changes CleanJobStatusChangeQueueCb_ :5318-5488, requeue
:6950-6965).  Differences by design, not omission:

* The per-cycle placement math (priority sort + greedy node selection) is
  a jit-compiled device solve (models/priority + models/solver, or the
  node-sharded parallel/sharded at scale), not a C++ loop.
* The cycle is an explicit ``schedule_cycle(now)`` call driven by the
  daemon loop (or tests), with virtual time — no hidden threads.  The
  reference's nine worker threads exist to multiplex queues onto cores;
  here the queues are drained inline and the heavy math is on device.
* Two-phase commit is kept: the device solve sees a snapshot; the host
  ledger (MetaContainer) is authoritative at commit and re-validates
  against mid-cycle ResReduceEvents, exactly like NodeSelect's
  post-validation (cpp:1466-1540).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Iterable

import jax
import numpy as np
import jax.numpy as jnp

from cranesched_tpu.ctld.defs import (
    DEP_NEVER,
    DepType,
    Job,
    JobSpec,
    JobStatus,
    PendingReason,
    Step,
    StepSpec,
    StepStatus,
)
from cranesched_tpu.ctld.accounting import AccountMetaContainer
from cranesched_tpu.ctld.licenses import LicenseManager
from cranesched_tpu.ctld.meta import MetaContainer
from cranesched_tpu.ctld.pending_table import (
    GATE_BEGIN,
    GATE_CANDIDATE,
    GATE_DEP,
    GATE_DEP_NEVER,
    GATE_HELD,
    GATE_LICENSE,
    STAMP_NONE,
    PendingTable,
)
from cranesched_tpu.ctld.resident import ResidentClusterState
from cranesched_tpu.ctld.runledger import RunLedger
from cranesched_tpu.ctld.running_table import RunningTable
from cranesched_tpu.models.priority import (
    PendingPriorityAttrs,
    PriorityWeights,
    RunningPriorityAttrs,
    multifactor_priority,
    priority_order,
)
from cranesched_tpu.models.solver import (
    COST_SCALE,
    REASON_CONSTRAINT,
    REASON_PRIORITY,
    REASON_RESOURCE,
    ClusterState,
    FactoredJobBatch,
    JobBatch,
    Placements,
    make_cluster_state,
    solve_greedy,
    solve_greedy_donating,
)
from cranesched_tpu.models.packing import PackedJobBatch, solve_packed
from cranesched_tpu.models.solver_time import (
    TimeGrid,
    TimedJobBatch,
    make_timed_state,
    solve_backfill,
)
from cranesched_tpu.obs import REGISTRY as _OBS
from cranesched_tpu.obs import introspect
from cranesched_tpu.obs.events import EventLog
from cranesched_tpu.obs.flight import FlightRecorder
from cranesched_tpu.obs.jobtrace import JobTraceRecorder
from cranesched_tpu.obs.slo import SloEngine
from cranesched_tpu.obs.trace import (
    CycleClock,
    CycleTraceRing,
    LockLedger,
    solve_span,
)
from cranesched_tpu.topo.place import solve_greedy_topo
from cranesched_tpu.ops.resources import CPU_SCALE, DIM_CPU, DIM_MEM

# cycle-plane metrics (naming: ARCHITECTURE.md "Observability")
_MET_CYCLES = _OBS.counter(
    "crane_cycles_total", "scheduling cycles completed")
_MET_PHASE = _OBS.histogram(
    "crane_cycle_phase_seconds",
    "wall time per cycle phase "
    "(label phase=prelude|solve|commit|dispatch)")
_MET_COMMIT_BATCH = _OBS.histogram(
    "crane_commit_batch_jobs", "jobs committed per _commit batch",
    buckets=tuple(float(2 ** k) for k in range(18)))
_MET_LOCK = _OBS.histogram(
    "crane_lock_held_seconds",
    "server-lock-held time per cycle (prelude + commit, never solve)")
_MET_SOLVE = _OBS.histogram(
    "crane_solve_seconds",
    "lock-released solve closure time (label backend)")
_MET_STARTED = _OBS.counter(
    "crane_jobs_started_total", "jobs started by the scheduler")
_MET_PREEMPTED = _OBS.counter(
    "crane_preempted_total", "running jobs evicted by preemption")
_MET_PENDING = _OBS.gauge(
    "crane_pending_jobs",
    "pending queue depth (updated on submit/finish events)")
_MET_RUNNING = _OBS.gauge(
    "crane_running_jobs",
    "running job count (updated on start/finish events)")
_MET_SKIPS = _OBS.counter(
    "crane_cycle_skips_total",
    "cycles short-circuited by the no-op fingerprint (label reason)")
_MET_TOPO_FRAG = _OBS.gauge(
    "crane_topo_fragmentation",
    "free-capacity fragmentation per topology level "
    "(1 - largest free group / total free; label level)")
_MET_TOPO_CROSS = _OBS.counter(
    "crane_topo_cross_block_gangs_total",
    "gangs placed across blocks by the spanning fallback")
_MET_H2D = _OBS.counter(
    "crane_solver_h2d_bytes_total",
    "host->device bytes shipped for the solve's cluster state "
    "(label mode=rebuild|patch)")
_MET_RESIDENT = _OBS.counter(
    "crane_resident_cycles_total",
    "immediate-fit cycles served by the device-resident state "
    "(label mode=rebuild|patch)")
_MET_OVERLAP = _OBS.gauge(
    "crane_resident_patch_overlap_share",
    "share of resident patch cycles whose delta upload was pre-staged "
    "(double-buffered) by the previous cycle")

#: the rows of a solve's ``nodes`` that ``_commit`` gathers on the
#: device before it pulls them: ONE compiled shape a [J, K], padded;
#: and the cells (1 MiB of int32) up to which it pulls the array whole
_COMMIT_PULL_ROWS = 1024
_COMMIT_PULL_WHOLE = 1 << 18


_take_rows = introspect.instrument_jit(
    "commit_take_rows", jax.jit(lambda nodes, idx: nodes[idx]))


def _pull_rows(nodes, idx: np.ndarray) -> np.ndarray:
    """``nodes[idx]`` on the host.  A solve's [J, K] node lists stay on
    the device but for the rows a cycle placed: at K = 64 and 131,072
    candidates the whole array is 33.5 MB, pulled and compared under
    the server lock every cycle, for a few dozen rows that hold
    anything.  An array that is on the host already, a small one (a
    flood's few thousand candidates at K = 1: the pull is cheaper than
    a gather, and a new J bucket compiles nothing more) and a cycle
    that placed more rows than the gather holds (the first after a
    start) are indexed whole."""
    if (isinstance(nodes, np.ndarray) or len(idx) > _COMMIT_PULL_ROWS
            or nodes.size <= _COMMIT_PULL_WHOLE):
        return np.asarray(nodes)[idx]
    padded = np.zeros(_COMMIT_PULL_ROWS, np.int32)
    padded[:len(idx)] = idx
    return np.asarray(_take_rows(nodes, padded))[:len(idx)]


_REASON_MAP = {
    REASON_RESOURCE: PendingReason.RESOURCE,
    REASON_CONSTRAINT: PendingReason.CONSTRAINT,
    # never a solver's answer: the batch cut's own stamp (_cut_batch)
    REASON_PRIORITY: PendingReason.PRIORITY,
}

# PendingTable gate code -> the pending reason the old Python candidate
# loop would have written for the same blocked job
_GATE_REASON = {
    GATE_HELD: PendingReason.HELD,
    GATE_BEGIN: PendingReason.BEGIN_TIME,
    GATE_DEP: PendingReason.DEPENDENCY,
    GATE_DEP_NEVER: PendingReason.DEPENDENCY_NEVER_SATISFIED,
    GATE_LICENSE: PendingReason.LICENSE,
}


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Reference scheduler knobs (etc/config.yaml:97-112,190-198;
    CtldPublicDefs.h:42-60)."""

    schedule_batch_size: int = 100_000
    pending_queue_max_size: int = 900_000
    max_nodes_per_job: int = 8          # static gang bound of the solve
    priority_type: str = "multifactor"  # or "basic" (FIFO)
    priority_weights: PriorityWeights = dataclasses.field(
        default_factory=PriorityWeights)
    max_requeue_count: int = 3
    # time axis: duration-aware fit + conservative backfill (reference
    # TimeAvailResMap + EarliestStartSubsetSelector; the grid analog of
    # Slurm's bf_resolution).  Each solve step costs O(N * time_buckets
    # * R) vs O(N * R) for the immediate solver — ~time_buckets× heavier.
    # At very large scale either lower time_buckets or set backfill=False
    # (Slurm similarly separates its sched and bf passes).
    backfill: bool = True
    time_resolution: float = 60.0       # seconds per bucket
    time_buckets: int = 64              # horizon = resolution * buckets
    # optional geometric far horizon (TimeGrid, models/solver_time.py):
    # None keeps the uniform resolution*buckets grid; a value larger
    # than resolution*buckets stretches the tail buckets geometrically
    # so e.g. 7-day jobs reserve at day scale instead of saturating the
    # last uniform bucket (the 60x over-reservation fixed in round 6)
    time_horizon: float | None = None
    # bounded backfill lookahead (the Slurm bf_max_job_test analog,
    # default 1000; the reference bounds the same scan with
    # ScheduledBatchSize): cycles larger than this run the timed solve
    # only for the top-priority slice and place the tail with the fast
    # immediate solver against the MIN-over-horizon availability — a
    # tail job that fits the tightest bucket can never violate any
    # reservation, so the split is strictly conservative.
    backfill_max_jobs: int = 1024
    # real node plane: a craned that misses pings for this long is down
    # (reference kCranedTimeoutSec = 30, PublicHeader.h:146)
    craned_timeout: float = 30.0
    # QoS preemption (reference TryPreempt_, JobScheduler.cpp:6378-6505;
    # config PreemptType/PreemptMode etc/config.yaml:280-290):
    # "off" | "requeue" | "cancel" — what happens to the victims
    preempt_mode: str = "off"
    # bounded ring of structured per-cycle traces (obs/trace.py),
    # queryable via QueryStats / `cstats --cycles`
    cycle_trace_ring: int = 64
    # solver backend for immediate-fit cycles: "auto" chooses from the
    # platform JAX reports — on a TPU backend the single-kernel Pallas
    # solve (models/pallas_solver.py: resident state, donated buffers),
    # elsewhere the native C++ treap solver when its library builds,
    # else the device scan; "device" forces the JAX scan; "native"
    # requires the C++ library; "pallas" requires a backend that runs
    # the Mosaic kernel (a TPU) and fails loudly elsewhere; "sharded"
    # runs the node-axis-sharded multi-chip solve over every visible
    # device (parallel/sharded.py).  Backfill and packed cycles always
    # run on device.  On one backend all five place bit-identically
    # (tests/ on the CPU; Pallas vs the scan on a v5e: chip_smoke.py).
    # ACROSS backends they do not: the int32 ledgers are exact, but
    # quantized_dcost's f32 divide is not the same function on a TPU
    # as on a host (one ledger unit apart in ~0.1% of increments, which
    # reorders cost ties and moves placements — PERF.md, PR 21).
    solver: str = "auto"
    # post-commit dispatch fan-out width (YAML ``DispatchWorkers``).
    # None sizes the dispatcher pool from the cluster:
    # max(8, nodes // 64), capped at 128 — a 10k-node cluster gets 128
    # concurrent pushes instead of the historical hardcoded 8.
    dispatch_workers: int | None = None
    # incremental cycle state: the PendingTable candidate pass, delta
    # meta snapshots, and the no-op-cycle fingerprint short-circuit.
    # TEST-ONLY reference, not a deployment setting (no YAML key reads
    # it): False restores the from-scratch rebuild every cycle, the
    # parity oracle of tests/test_delta_cycle.py.
    incremental: bool = True
    # event-driven loop (YAML ``CycleIdleSleep``): the longest the
    # server's cycle loop may sleep when the no-op fingerprint is armed
    # and no event arrives.  Bounds staleness of anything outside the
    # event/edge model (e.g. remote license syncs, which deliberately
    # do not kick the loop).
    cycle_idle_sleep: float = 30.0
    # device-resident cluster state: keep the immediate-fit solve's
    # ClusterState buffers on device across cycles and scatter-patch
    # only the dirty rows instead of re-uploading [N, R] every tick
    # (ctld/resident.py).  Effective for solver "device" and "pallas"
    # and only with ``incremental`` (the dirty feed is the
    # delta-snapshot machinery).  TEST-ONLY reference like
    # ``incremental`` (no YAML key): False rebuilds from the host
    # snapshot every cycle, the parity oracle of
    # tests/test_resident_state.py.
    resident_state: bool = True
    # per-job lifecycle tracing (YAML ``Observability: JobTrace``):
    # event-sourced timelines (obs/jobtrace.py) stamped at submit /
    # candidate / commit / durable-dispatch / terminal edges plus the
    # craned-side spans shipped back with StepStatusChange.  False
    # removes every stamp from the hot path.
    job_trace: bool = True
    # bounded timeline store size (live + closed, each)
    job_trace_capacity: int = 4096
    # SLO targets over trace edges (YAML ``Observability: SLO``),
    # frozen-dataclass form: tuple of
    # (name, from_edge, to_edge, percentile, target_seconds, windows)
    slo: tuple = ()

    def __post_init__(self):
        if self.preempt_mode not in ("off", "requeue", "cancel"):
            raise ValueError(
                f"preempt_mode must be off|requeue|cancel, "
                f"got {self.preempt_mode!r}")
        if self.solver not in ("auto", "device", "native", "pallas",
                               "sharded"):
            raise ValueError(
                "solver must be auto|device|native|pallas|sharded, "
                f"got {self.solver!r}")


@dataclasses.dataclass
class StatusChange:
    """One craned→ctld step status report (reference StepStatusChange
    queue, JobScheduler.cpp:5294)."""

    job_id: int
    status: JobStatus
    exit_code: int
    time: float
    # incarnation (requeue_count) the report belongs to; None = trust the
    # caller (pre-aggregated).  A report queued for incarnation k must not
    # finalize incarnation k+1 — a node death can requeue + re-place the
    # job between the enqueue and the drain.
    incarnation: int | None = None


class _ObservedDict(dict):
    """dict with membership hooks: every insert/removal notifies the
    scheduler so derived indexes (the PendingTable, the template and
    alloc_only sets, the queue-depth gauges, the event-loop kick) stay
    in sync at the MUTATION SITE instead of being rebuilt per cycle.
    Hooks fire after the dict mutation, with the key's final value."""

    def __init__(self, on_set, on_del):
        super().__init__()
        self._on_set = on_set
        self._on_del = on_del

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self._on_set(key, value)

    def __delitem__(self, key):
        value = super().pop(key)
        self._on_del(key, value)

    def pop(self, key, *default):
        if key in self:
            value = super().pop(key)
            self._on_del(key, value)
            return value
        if default:
            return default[0]
        raise KeyError(key)

    def popitem(self):
        key, value = super().popitem()
        self._on_del(key, value)
        return key, value

    def clear(self):
        while self:
            self.popitem()

    def update(self, *args, **kwargs):
        for key, value in dict(*args, **kwargs).items():
            self[key] = value

    def setdefault(self, key, default=None):
        if key not in self:
            self[key] = default
        return super().__getitem__(key)


class _CycleJobs:
    """One cycle's candidates, in order, without a list of ``Job``s.

    On the trusted route (``incremental``) they are PendingTable
    ``rows`` with the job ``ids`` read from them while the prelude held
    the lock: a compaction during a solve moves rows, never ids.  A Job
    is looked up only at an index Python really visits (``job``), and
    ``jobs`` stays None unless a route walks every job (packed,
    topology, reservations: ``JobScheduler._materialise``).  The rebuild
    route, the tests' oracle, starts from the list: ``jobs`` is given
    and ``rows`` is None."""

    __slots__ = ("pending", "rows", "ids", "jobs")

    def __init__(self, pending, rows=None, ids=None, jobs=None):
        self.pending = pending
        self.rows = rows
        self.ids = ids
        self.jobs = jobs

    def __len__(self) -> int:
        return len(self.ids) if self.jobs is None else len(self.jobs)

    def __getitem__(self, cut):
        """A slice, or the rows an index array picks, in its order."""
        if self.jobs is None:
            jobs = None
        elif isinstance(cut, slice):
            jobs = self.jobs[cut]
        else:
            jobs = [self.jobs[i] for i in cut]
        if self.rows is None:
            return _CycleJobs(self.pending, jobs=jobs)
        return _CycleJobs(self.pending, self.rows[cut], self.ids[cut], jobs)

    def job(self, i: int) -> Job | None:
        """The i-th job; None once it has left ``pending``."""
        if self.jobs is not None:
            return self.jobs[i]
        return self.pending.get(int(self.ids[i]))

    def still_pending(self) -> list[Job]:
        """The jobs that have not left ``pending``, in order."""
        if self.jobs is not None:
            return [j for j in self.jobs if j.job_id in self.pending]
        get = self.pending.get
        return [j for j in map(get, self.ids.tolist()) if j is not None]


class _MaskTable:
    """Device-resident ``[C, N]`` eligibility-row table — the factored
    form of the per-job ``part_mask``.

    Rows are pure functions of a job's *class key* (partition +
    include/exclude lists + reservation identity/activity + the set of
    reservations overlapping the job's runtime window — see
    ``JobScheduler._class_key``), deduplicated by CONTENT so distinct
    keys with identical masks share one row.  The device table is
    bucketed (power-of-two row count, all-False padding) so solver jit
    shapes stay stable as classes appear, and row 0 is ALWAYS the
    all-False row: padding jobs gather an empty mask, exactly matching
    the dense builder's zero rows.

    Invalidation: a ``resv_epoch`` bump or node-count change drops
    everything (the same rule as the scalar ``_mask_cache``); within an
    epoch rows never mutate, so the [C, N] host→device transfer happens
    only when a NEW class appears — the per-cycle upload shrinks from
    O(J·N) to O(J + changed rows).
    """

    def __init__(self):
        self.epoch = -1
        self.num_nodes = -1
        # monotonic reset counter: PendingTable rows cache their class
        # id stamped with this, so a reset invalidates every cached id
        # without touching the rows
        self.generation = 0
        self.key_to_class: dict[tuple, int] = {}
        self._bytes_to_class: dict[bytes, int] = {}
        self.rows: list[np.ndarray] = []
        self.rows_np: np.ndarray | None = None  # padded [Cpad, N] mirror
        self.table = None                       # jnp twin of rows_np
        self.disjoint = True      # no node is in 2+ rows (see node_class)
        self._node_class: np.ndarray | None = None
        self.h2d_rows = 0         # rows shipped to device (observability)
        self.refreshes = 0        # full invalidations (observability)

    def reset(self, epoch: int, num_nodes: int) -> None:
        self.epoch = epoch
        self.num_nodes = num_nodes
        self.generation += 1
        self.key_to_class.clear()
        self._bytes_to_class.clear()
        row0 = np.zeros(max(num_nodes, 1), bool)
        self.rows = [row0]
        self._bytes_to_class[row0.tobytes()] = 0
        self.rows_np = None
        self.table = None
        self.disjoint = True
        self._node_class = None
        self.refreshes += 1

    def class_for(self, key: tuple, row_fn) -> int:
        """Class id for ``key``; ``row_fn()`` builds the [N] bool row
        only on first sight of the key."""
        cid = self.key_to_class.get(key)
        if cid is None:
            row = np.ascontiguousarray(row_fn(), dtype=bool)
            b = row.tobytes()
            cid = self._bytes_to_class.get(b)
            if cid is None:
                cid = len(self.rows)
                self.rows.append(row)
                self._bytes_to_class[b] = cid
                self.rows_np = None   # grew: rebuild the mirrors lazily
                self.table = None
                self._node_class = None
            self.key_to_class[key] = cid
        return cid

    def tables(self):
        """``(host [Cpad, N] bool, device twin)`` — padded to a
        power-of-two row count with all-False rows."""
        if self.rows_np is None or self.table is None:
            c = 1
            while c < len(self.rows):
                c *= 2
            padded = np.zeros((c, self.rows[0].shape[0]), bool)
            padded[: len(self.rows)] = self.rows
            self.rows_np = padded
            self.disjoint = bool(
                (padded.sum(axis=0, dtype=np.int64) <= 1).all())
            self.table = jnp.asarray(padded)
            self.h2d_rows += len(self.rows)
        return self.rows_np, self.table

    def node_class(self) -> np.ndarray | None:
        """Per-node owner class id iff the rows are pairwise disjoint —
        then ``rows[c] == (node_class == c)`` exactly, which feeds the
        native solver's partition-id fast path (no dense [J, N] mask
        materialized at all).  Unowned nodes get a label no job carries.
        None when rows overlap (caller falls back to a dense gather)."""
        rows_np, _ = self.tables()
        if not self.disjoint:
            return None
        if self._node_class is None:
            owner = np.full(rows_np.shape[1], rows_np.shape[0], np.int32)
            cls, node = np.nonzero(rows_np)
            owner[node] = cls
            self._node_class = owner
        return self._node_class


class JobScheduler:
    """Owns the pending/running maps and drives scheduling cycles.

    ``dispatch`` is called with (job, node_ids) for every committed
    placement — the seam where the real system fans out AllocJobs RPCs and
    tests plug a simulated cluster (the reference's testing seam is the
    same shape: intents out, transport elsewhere).
    """

    def __init__(self, meta: MetaContainer,
                 config: SchedulerConfig | None = None,
                 dispatch: Callable[[Job, list[int]], None] | None = None,
                 wal=None, accounts=None, submit_hook=None,
                 archive=None):
        self.meta = meta
        self.config = config or SchedulerConfig()
        self.dispatch = dispatch or (lambda job, nodes: None)
        # optional batched dispatch seam (GrpcDispatcher.wire sets it):
        # one call for the whole post-commit ring with per-craned
        # coalescing; None falls back to per-job self.dispatch
        self.dispatch_batch = None
        # ordered post-commit dispatch ring: (job, node_ids) queued
        # under the lock by _commit/_commit_preemption, drained with
        # the lock RELEASED by the cycle's final phase — and only after
        # the WAL group's fsync returned (durable-before-dispatch)
        self._dispatch_ring: collections.deque = collections.deque()
        self.wal = wal
        # HA fencing: this ctld's leadership term, stamped into every
        # craned push/registration by the dispatcher + server so craneds
        # can reject a deposed leader's in-flight RPCs after failover.
        # 0 = HA not configured (craneds skip the check).
        self.fencing_epoch = 0
        # durable history (ctld/archive.JobArchive): terminal jobs are
        # appended BEFORE any WAL purge can drop them (reference
        # PersistAndTransferJobsToMongodb_, JobScheduler.cpp:6918-6948);
        # None = RAM-only history (tests/simulations).  Attached at the
        # END of __init__ — attach_archive seeds _next_job_id.
        self.archive = None
        # accounting (reference AccountManager + AccountMetaContainer):
        # None = open system, no limit enforcement
        self.accounts = accounts
        self.account_meta = (AccountMetaContainer(meta.layout)
                             if accounts is not None else None)
        # cluster-wide accounting (fed/usage.py UsageBook): conservative
        # global MaxJobs/MaxSubmitJobs gate + fair-share service input.
        # None = per-shard limits only (single-controller behavior).
        self.global_usage = None
        # live partition migration (fed/rebalance.py): a sealed
        # partition stops admitting — its jobs are mid-handoff to
        # another shard and a new local submit would be stranded
        self.sealed_partitions: set[str] = set()
        self.licenses = LicenseManager()
        # submit hook (the reference's Lua JobSubmitLuaScript seam,
        # LuaJobHandler.h:39: rewrite the spec or reject with a message):
        # JobSpec -> JobSpec (possibly modified) | None (reject)
        self.submit_hook = submit_hook
        # persistent SoA mirror of the pending queue (ctld/
        # pending_table.py): event hooks below keep it current, the
        # cycle masks it vectorially instead of walking Job objects
        self._ptable = PendingTable(meta.layout.num_dims)
        # membership indexes maintained by the dict hooks so per-cycle
        # scans iterate exactly the rows they need, never O(pending) /
        # O(running): array templates awaiting materialization,
        # alloc_only jobs whose time limit ctld itself enforces, and
        # each user's live jobs (pending or running; no entry for a
        # user with none), what a per-user query reads (user_jobs)
        self._array_templates: set[int] = set()
        self._alloc_only: set[int] = set()
        self._user_jobs: dict[str, set[int]] = collections.defaultdict(set)
        # event-driven loop plumbing: the server points cycle_kick at
        # its wakeup event; mutations that can change the next cycle's
        # outcome call _kick() so a sleeping loop wakes immediately
        self.cycle_kick: Callable[[], None] | None = None
        # no-op short-circuit state: fingerprint + nearest time edge,
        # armed after a zero-placement cycle (_arm_noop / _cycle_body)
        self._noop_fp: tuple | None = None
        self._noop_edge: float = float("inf")
        self._cycle_fp0: tuple | None = None
        self._cycle_usage_denied0: int = 0
        self._skip_trace: dict | None = None
        # PendingTable.generation the in-flight cycle's rows were taken
        # under (a compaction since, a cancel while a solve ran, moved
        # them) and the table epoch its prelude read (a row written
        # since is void at the commit)
        self._rows_gen = -1
        self._plan_epoch = 0
        # the running jobs' priority columns (ctld/running_table.py):
        # made by the first _priority_sort that needs them (so never
        # under ``Priority: Type: basic``, whose hooks then derive no
        # row) from one walk over ``running``, and kept from then on by
        # the ``running`` dict's hooks, one row a start, one move a
        # finish; rebuild_device_state drops them.  _run_dev is (the
        # table epoch they were put at, the padded device copies of the
        # attribute columns): stale once the membership has moved
        self._rtable: RunningTable | None = None
        self._run_dev: tuple | None = None
        meta.delta_snapshot = self.config.incremental
        # job_id -> Job; insertion = id order (the hooks mirror
        # membership into the table/indexes/gauges at mutation time)
        self.pending: dict[int, Job] = _ObservedDict(
            self._on_pending_set, self._on_pending_del)
        self.running: dict[int, Job] = _ObservedDict(
            self._on_running_set, self._on_running_del)
        self.history: dict[int, Job] = {}    # terminal jobs
        self._status_queue: collections.deque[StatusChange] = (
            collections.deque())
        # step-level reports arriving from transport pool threads: deque
        # appends are thread-safe; the mutations happen when the cycle
        # (or an RPC holding the server lock) drains them.  Transport
        # code must NEVER call step_report directly — it mutates
        # job.steps / the WAL / _try_start_steps without the lock.
        self._step_report_queue: collections.deque[tuple] = (
            collections.deque())
        self._next_job_id = 1
        self._account_index: dict[str, int] = {}
        self._mask_cache: dict[tuple, np.ndarray] = {}
        self._mask_cache_epoch = -1
        # factored eligibility classes: the [C, N] row table lives on
        # device across cycles; per-cycle H2D is job_class[J] only
        self._mask_table = _MaskTable()
        self._mesh = None  # lazy device mesh for solver == "sharded"
        # test-harness hook: run the Pallas solve under the Pallas
        # interpreter so CPU-only tests can drive _solve_pallas.  The
        # program never sets it — production compiles the kernel for
        # the backend it runs on or fails.
        self.pallas_interpret = False
        self._dependents: dict[int, set[int]] = {}  # dep job -> waiters
        # job_id -> last kill-send time for unconfirmed cancel intents
        self._cancel_kill_sent: dict[int, float] = {}
        # (job_id, step_id) -> last kill-send time for unconfirmed
        # step-level cancels (same lost-kill race as whole-job cancel:
        # dispatch_terminate_step swallows transport errors, so a single
        # send can vanish and the cancelled step would run to completion)
        self._step_cancel_sent: dict[tuple[int, int], float] = {}
        # job_id -> (new time limit, last send) for unconfirmed
        # ChangeTimeLimit pushes: the update can beat the supervisor
        # spawn on the craned (which then refuses it), so it re-sends
        # each cycle until the dispatcher confirms every node took it
        self._limit_intents: dict[int, tuple[float, float]] = {}
        self._finalized_since_compact = 0
        # incremental per-cycle state of running allocations: the cost
        # seed + backfill release rows come from O(rows) numpy instead
        # of an O(running) Python loop every cycle (VERDICT r2 weak #4)
        self._ledger = RunLedger(meta.layout.num_dims)
        # device-resident ClusterState across cycles (ctld/resident.py):
        # registers a dirty listener on meta so immediate-fit cycles
        # scatter-patch dirty rows instead of re-uploading [N, R]
        self._resident = ResidentClusterState(
            meta, enabled=(config.resident_state and config.incremental))
        # one shared time axis for every duration-aware solve: batch
        # time_limits stay in SECONDS and the solver derives occupancy
        # windows from these edges (uniform when time_horizon is None)
        self._grid = TimeGrid(config.time_buckets,
                              config.time_resolution,
                              horizon=config.time_horizon)
        # node lifecycle event seam (reference NodeEventHook,
        # Plugin.proto:75-95 — the plugin daemon's node-event surface):
        # callable(event_dict) fired on up/down/drain/undrain/power
        # transitions, async (never under the RPC lock's critical
        # path); plus a bounded in-RAM event log for observability
        self.node_event_hook = None
        self.node_events: list[dict] = []
        self._node_event_queue = None  # lazily-started ordered worker
        # observability (reference per-phase wall-clock trace,
        # JobScheduler.cpp:1444-1447,1723-1903)
        self.stats = {
            "cycles": 0, "skipped_cycles": 0, "jobs_started_total": 0,
            "jobs_submitted_total": 0, "jobs_finished_total": 0,
            "last_cycle": {}, "last_cycle_walltime": 0.0,
        }
        # structured per-cycle traces (obs/trace.py); _cur_trace is the
        # in-flight cycle's mutable accumulator — cycles are serialized
        # by the server lock, so one slot suffices
        self.cycle_trace = CycleTraceRing(config.cycle_trace_ring)
        self._cur_trace: dict = {}
        # the cycle thread's ledger (obs/trace.py CycleClock): the
        # server's loop and this module mark phase boundaries on it,
        # and cycle_phases' last act writes the period's parts into the
        # row this cycle ringed (_ledger_row; None when it ringed none).
        # Beside it the lock ledger (LockLedger): the classed takes of
        # the server lock by handlers and the snapshotter, drained into
        # the same row
        self.lock_ledger = LockLedger()
        self.cycle_clock = CycleClock(self.lock_ledger)
        self._ledger_row: dict | None = None
        # per-job lifecycle tracing + SLO plane (obs/jobtrace.py,
        # obs/slo.py): None when JobTrace is off — every stamp site
        # guards on it, so "off" removes the whole layer from the hot
        # path, not just the output
        self.slo_engine = SloEngine.from_config(config.slo)
        self.jobtrace = (JobTraceRecorder(
            capacity=config.job_trace_capacity, slo=self.slo_engine)
            if config.job_trace else None)
        # structured cluster event log (obs/events.py): this ctld emits
        # locally; a follower additionally ingests the leader's events
        # via the HaFetchWal piggyback, so cevents works on standbys
        self.events = EventLog()
        if self.slo_engine is not None:
            self.slo_engine.event_sink = self._slo_event
        # introspection plane (obs/introspect.py): per-cycle recompile
        # attribution is delta-based off the process-wide counter; the
        # profiler window is armed by the CaptureProfile RPC and ticked
        # at cycle boundaries
        self._cycle_compile_base = introspect.total_compiles()
        # cycle_compiling(): the fresh-call count at the running cycle's
        # opening (None between cycles), and what its end notifies
        self._cycle_fresh_base = None
        self._cycle_thread = None
        self._cycle_end = threading.Condition()
        self.profiler_window = introspect.ProfilerWindow(
            event_sink=lambda type, sev, detail="": self.events.emit(
                type, sev, detail=detail),
            namespace=lambda: self.shard_name)
        # stall forensics (obs/flight.py): always-on phase ring the
        # cycle stamps (~6 appends/cycle), plus the stall sentry the
        # server's cycle loop arms around every cycle — a wedged cycle
        # lands with all-thread stacks in flight.last_stall instead of
        # a silent hang
        self.flight = FlightRecorder(
            event_sink=lambda type, sev, detail="": self.events.emit(
                type, sev, detail=detail),
            lock_ledger=self.lock_ledger)
        # the in-flight cycle's ``now``: the dispatch-ring drain runs
        # lock-released and stamps committed_durable/dispatched on the
        # same clock the cycle used (virtual in sims, wall in daemons)
        self._cycle_now = 0.0
        # timed preemption's deferred evictions: victim job_id ->
        # (due time, preemptor job_id).  Victims of a future-start
        # preemption survive until the preemptor's start bucket
        # (reference JobScheduler.cpp:6378-6505); the prelude drains
        # entries whose due time passed, next_wake_time() wakes the
        # event loop for the earliest one.  Deliberately NOT
        # WAL-persisted: after a failover the preemption solve
        # re-derives any eviction still worth making.
        self._deferred_evictions: dict[int, tuple[float, int]] = {}
        # federated control plane (fed/): this controller's shard name
        # ("" outside a federation) and the lease plane grafted on by
        # fed.shard.FedShardPlane.attach — None for single-controller
        # clusters, so every fed hook is a cheap attribute check
        self.shard_name = ""
        self.fed = None
        if archive is not None:
            self.attach_archive(archive)

    def emit_node_event(self, event: str, node_name: str,
                        detail: str = "", now: float = 0.0) -> None:
        """Record + fan out one node lifecycle event.  The hook runs on
        ONE worker thread draining a queue — operator code never blocks
        a cycle, and back-to-back transitions (drain then undrain)
        reach the hook in ORDER, never concurrently (a per-event thread
        would let the undrain overtake the drain and leave the
        operator's external system with the wrong final state)."""
        record = {"event": event, "node": node_name, "detail": detail,
                  "time": now}
        self.node_events.append(record)
        if len(self.node_events) > 200:
            del self.node_events[: len(self.node_events) - 200]
        # mirror into the typed event ring (flap detection included)
        self.events.emit_node_transition(event, node_name, detail=detail,
                                         now=now)
        if self.node_event_hook is None:
            return
        if self._node_event_queue is None:
            import queue
            import threading
            self._node_event_queue = queue.Queue()

            def worker():
                while True:
                    rec = self._node_event_queue.get()
                    hook = self.node_event_hook
                    if hook is None:
                        continue
                    try:
                        hook(rec)
                    except Exception:
                        import logging
                        import traceback
                        logging.getLogger("cranesched.ctld").error(
                            "node event hook raised:\n%s",
                            traceback.format_exc())

            threading.Thread(target=worker, daemon=True).start()
        self._node_event_queue.put(record)

    def _slo_event(self, name: str, window: float, burn: float,
                   breaching: bool) -> None:
        """SloEngine breach-edge sink -> typed event ring."""
        if breaching:
            self.events.emit(
                "slo_breach", "error",
                detail="%s window=%ds burn=%.2f" % (name, window, burn))
        else:
            self.events.emit(
                "slo_clear",
                detail="%s window=%ds recovered" % (name, window))

    def explain_pending(self, job_id: int, now: float) -> dict:
        """First-failing-gate decomposition for one job (``cexplain``).
        Caller holds the server lock."""
        from cranesched_tpu.ctld.explain import explain_pending
        return explain_pending(self, job_id, now)

    # history the RAM dict may hold with an archive attached (the
    # durable store serves the rest; without an archive RAM is the only
    # record and must not be evicted)
    HISTORY_CACHE_MAX = 10_000

    # cycles before a fresh jit compile counts as a steady-state
    # violation (the first cycles after boot/failover legitimately
    # populate the cache for each padded-shape bucket)
    WARMUP_CYCLES = 3

    def attach_archive(self, archive) -> None:
        """Wire the durable history store (also used by ctld_main after
        construction).  Seeds the job-id counter past every archived id:
        a restart whose WAL was auto-compacted would otherwise reuse ids
        and INSERT OR REPLACE over history."""
        self.archive = archive
        self._next_job_id = max(getattr(self, "_next_job_id", 1),
                                archive.max_job_id() + 1)

    # ------------------------------------------------------------------
    # incremental cycle state (ARCHITECTURE.md "Incremental cycle
    # state"): membership hooks, the PendingTable row derivation, the
    # no-op-cycle fingerprint, and the event-driven loop's sleep seam
    # ------------------------------------------------------------------

    def _kick(self) -> None:
        """Wake the server's event-driven cycle loop (no-op standalone)."""
        kick = self.cycle_kick
        if kick is not None:
            kick()

    def _user_jobs_drop(self, job_id: int, job: Job) -> None:
        """``job_id`` left pending or running and is in neither now.  A
        move is a del THEN a set (a start, a requeue), so a user's only
        job drops the entry here and the set hook opens it again within
        the same lock hold: nobody reads the index in between."""
        ids = self._user_jobs.get(job.spec.user)
        if ids is not None:
            ids.discard(job_id)
            if not ids:
                del self._user_jobs[job.spec.user]

    def user_jobs(self, user: str) -> Iterable[int]:
        """Ids of ``user``'s pending and running jobs, in no order."""
        return self._user_jobs.get(user, ())

    def _on_pending_set(self, job_id: int, job: Job) -> None:
        self._table_upsert(job)
        if job.spec.array is not None:
            self._array_templates.add(job_id)
        self._user_jobs[job.spec.user].add(job_id)
        _MET_PENDING.set(len(self.pending))
        self._kick()

    def _on_pending_del(self, job_id: int, job: Job) -> None:
        priority = self._ptable.remove(job_id)
        if priority is not None:
            # the table held it while the job had a row (job_priority)
            job.priority = priority
        self._array_templates.discard(job_id)
        if job_id not in self.running:
            self._user_jobs_drop(job_id, job)
        _MET_PENDING.set(len(self.pending))
        self._kick()

    def _on_running_set(self, job_id: int, job: Job) -> None:
        if job.spec.alloc_only:
            self._alloc_only.add(job_id)
        self._user_jobs[job.spec.user].add(job_id)
        if self._rtable is not None:
            self._running_put(job)
        _MET_RUNNING.set(len(self.running))
        if self.global_usage is not None:
            self.global_usage.note_run(job.spec.user, job.spec.account, 1)
            if job.global_run_reserved:
                # admission's held slot becomes the real running count
                self.global_usage.unreserve_run(job.spec.user,
                                                job.spec.account)
                job.global_run_reserved = False

    def _on_running_del(self, job_id: int, job: Job) -> None:
        self._alloc_only.discard(job_id)
        if job_id not in self.pending:
            self._user_jobs_drop(job_id, job)
        if self._rtable is not None:
            self._rtable.remove(job_id)
        _MET_RUNNING.set(len(self.running))
        if self.global_usage is not None:
            self.global_usage.note_run(job.spec.user, job.spec.account, -1)

    def _dep_cols(self, job: Job) -> tuple[float, bool]:
        """``(dep_ready_time, never)`` table columns mirroring
        ``_deps_runnable`` exactly: the row is dep-blocked while
        ``dep_ready_time > now``; ``never`` selects the
        DEPENDENCY_NEVER_SATISFIED reason.  Edges still waiting on an
        event map to +inf with never=False — only ``_trigger_dep_event``
        (which refreshes the row) can unblock them."""
        if not job.dep_state:
            return float("-inf"), False
        states = list(job.dep_state.values())
        if job.spec.deps_is_or:
            finite = [v for v in states
                      if v is not None and v != DEP_NEVER]
            if finite:
                return min(finite), False
            if all(v == DEP_NEVER for v in states):
                return float("inf"), True
            return float("inf"), False
        if any(v == DEP_NEVER for v in states):
            return float("inf"), True
        if any(v is None for v in states):
            return float("inf"), False
        return max(states), False

    def _table_upsert(self, job: Job) -> None:
        """Derive one PendingTable row from the Job (the table owns
        storage; the scheduler owns JobSpec semantics).  Every value the
        cycle's vectorized passes gather must be re-derived here on any
        event that can change it."""
        spec = job.spec
        dep, dep_never = self._dep_cols(job)
        req, _, time_limit = self._job_row(job)
        qos, part, node_num, cpus, mem, acct = self._priority_row(job)
        packed = bool(spec.exclusive or spec.task_res is not None
                      or (spec.ntasks is not None
                          and spec.ntasks != spec.node_num)
                      or spec.ntasks_per_node_max > 1)
        self._ptable.upsert(
            job.job_id,
            template=spec.array is not None,
            held=job.held,
            begin=(spec.begin_time if spec.begin_time is not None
                   else float("-inf")),
            dep=dep, dep_never=dep_never,
            lic=self._ptable.lic_key(spec.licenses),
            submit=job.submit_time,
            qos=qos, part=part, nnum=node_num, cpus=cpus, mem=mem,
            acct=acct,
            tlimit=time_limit,
            packed=packed,
            req=req,
            priority=job.priority)

    def _table_refresh(self, job: Job) -> None:
        """Re-derive a pending job's row after an IN-PLACE mutation
        (hold / modify / dep trigger — paths that don't re-insert into
        the dict) and wake the loop."""
        if job.job_id in self.pending:
            self._table_upsert(job)
            self._kick()

    def job_priority(self, job: Job) -> float:
        """The priority a reply shows for ``job``.  A pending job's
        lives in its PendingTable row, where ``_priority_sort`` scatters
        a whole cycle's in one write; ``Job.priority`` is brought up to
        date when the job leaves the table."""
        priority = self._ptable.priority_of(job.job_id)
        return job.priority if priority is None else priority

    def _set_reason(self, job: Job, reason: PendingReason) -> None:
        """Write a PENDING job's reason from outside ``_commit``'s
        stamped pass, and forget the row's stamp so that the next commit
        visits it (PendingTable ``stamped``).  The other writers need no
        such call: the gate reasons ride ``candidates()``' own reset,
        the batch cut resets its rows in one slice, and hold / modify /
        requeue / eviction re-upsert the row they wrote."""
        job.pending_reason = reason
        self._ptable.forget(job.job_id)

    def _cycle_fingerprint(self) -> tuple:
        """Everything a zero-placement solve's outcome depends on, as
        epochs: queue content (table), node availability/liveness
        (meta), license seats, reservation set.  Time-dependent gates
        (begin/dep/reservation windows) are handled by ``_noop_edge``,
        not the fingerprint."""
        return (self._ptable.epoch, self.meta.meta_epoch,
                self.licenses.epoch, self.meta.resv_epoch)

    def _arm_noop(self, now: float) -> None:
        """Arm the no-op short-circuit after a cycle that placed
        nothing, preempted nothing, and queued no dispatch: until an
        epoch moves or the nearest time edge passes, an identical cycle
        would place nothing again (every candidate failed against the
        same snapshot, and aging alone cannot create a placement when
        zero jobs placed — order among non-placing jobs is moot).
        Never armed with preemption enabled: a preemptor's eligibility
        depends on running-set age, which no epoch tracks."""
        if not self.config.incremental:
            return
        if self.config.preempt_mode != "off" and self.accounts is not None:
            return
        if (self.global_usage is not None
                and self.global_usage.denied
                != self._cycle_usage_denied0):
            # a candidate was refused by the cluster-wide usage gate
            # this cycle; that gate's answer depends on gossip state
            # (publish throttle, peer summaries) no epoch tracks —
            # the next cycle may well place it
            return
        fp = self._cycle_fp0
        if fp is None or self._cycle_fingerprint() != fp:
            return   # something moved mid-cycle; next cycle must look
        edge = self._ptable.next_edge(now)
        for resv in self.meta.reservations.values():
            # activity flips don't bump resv_epoch — cover them by edge
            if resv.start_time > now:
                edge = min(edge, resv.start_time)
            if resv.end_time > now:
                edge = min(edge, resv.end_time)
        self._noop_fp = fp
        self._noop_edge = edge

    def _skip_cycle(self, t0, now: float, reason: str) -> list[int]:
        """The short-circuited cycle: count it, refresh watchdog
        liveness, and coalesce consecutive skips into ONE trace-ring
        row (an idle night must not flush real cycles out of the
        ring).  The queue drains already ran — only the snapshot /
        sort / solve / commit machinery is skipped."""
        import time as _time
        self._in_cycle = False
        self.stats["cycles"] += 1
        _MET_CYCLES.inc()
        self.stats["skipped_cycles"] = (
            self.stats.get("skipped_cycles", 0) + 1)
        _MET_SKIPS.inc(reason=reason)
        self.flight.stamp("skip", detail=reason)
        ms = round((_time.perf_counter() - t0) * 1e3, 3)
        self.stats["last_cycle_walltime"] = _time.time()
        self.stats["last_cycle"] = {
            "solver": "skip", "prelude_ms": ms, "total_ms": ms,
            "pending": 0, "started": 0, "running": len(self.running)}
        st = self._skip_trace
        if st is not None:
            st["skips"] = st.get("skips", 0) + 1
            st["now"] = now
            st["total_ms"] = ms
        else:
            trace = {
                "now": now, "queue_depth": len(self.pending),
                "solver": "skip", "skip_reason": reason, "skips": 1,
                "prelude_ms": ms, "solve_ms": 0.0, "commit_ms": 0.0,
                "dispatch_ms": 0.0, "total_ms": ms, "lock_held_ms": ms,
                "candidates": 0, "placed": 0, "preempted": 0,
                "backfilled": 0, "running": len(self.running)}
            self.cycle_trace.push(trace)
            self._skip_trace = trace
        self._ledger_row = self._skip_trace
        return []

    def can_idle(self) -> bool:
        """True when the event-driven loop may sleep up to
        ``cycle_idle_sleep``: the no-op fingerprint is armed and still
        matches, and no queued work (dispatch ring, status/step
        reports, unconfirmed kill / time-limit intents) needs the next
        cycle.  Call under the server lock."""
        return (self.config.incremental
                and self._noop_fp is not None
                and self._cycle_fingerprint() == self._noop_fp
                and not self._dispatch_ring
                and not self._status_queue
                and not self._step_report_queue
                and not self._cancel_kill_sent
                and not self._step_cancel_sent
                and not self._limit_intents
                and not self._deferred_evictions)

    def next_wake_time(self, now: float) -> float:
        """Earliest future moment a sleeping loop must cycle even
        without an event: a begin/dep/reservation edge (_noop_edge),
        an alloc_only job's time limit (ctld enforces those itself),
        or the next craned ping-timeout sweep.  +inf when nothing is
        time-gated."""
        wake = self._noop_edge
        for job_id in self._alloc_only:
            job = self.running.get(job_id)
            if job is not None and job.status == JobStatus.RUNNING:
                wake = min(wake, self._effective_end(job, now))
        if any(node.alive and node.expect_pings
               for node in self.meta.nodes.values()):
            wake = min(wake, now + self.config.craned_timeout / 2)
        for due, _preemptor in self._deferred_evictions.values():
            wake = min(wake, due)
        return wake

    # ------------------------------------------------------------------
    # submit / cancel / hold (reference SubmitJobToScheduler :3405,
    # cancel/hold queues JobScheduler.h:1239-1320)
    # ------------------------------------------------------------------

    def submit(self, spec: JobSpec, now: float) -> int:
        """Validate and enqueue; returns job_id (0 = rejected)."""
        if self.submit_hook is not None:
            # operator code: a crashing or misbehaving hook rejects the
            # job, never the control plane (the reference's Lua seam
            # treats hook failure as reject-with-message) — but the
            # failure must stay diagnosable: log it and count it
            try:
                spec = self.submit_hook(spec)
            except Exception:
                import logging
                import traceback
                logging.getLogger("cranesched.ctld").error(
                    "submit hook raised:\n%s", traceback.format_exc())
                self.stats["submit_hook_failures"] = (
                    self.stats.get("submit_hook_failures", 0) + 1)
                return 0
            if spec is None:
                return 0
            if not isinstance(spec, JobSpec):
                import logging
                logging.getLogger("cranesched.ctld").error(
                    "submit hook returned %r (expected JobSpec or None)",
                    type(spec).__name__)
                self.stats["submit_hook_failures"] = (
                    self.stats.get("submit_hook_failures", 0) + 1)
                return 0
        if len(self.pending) >= self.config.pending_queue_max_size:
            return 0
        part = self.meta.partitions.get(spec.partition)
        if part is None or not part.account_allowed(spec.account):
            return 0
        if spec.partition in self.sealed_partitions:
            return 0  # mid-migration: the successor map owns it now
        # gangs beyond the configured bound (or the partition size) can
        # never be placed — reject at submit rather than leaving the job
        # pending forever with a transient-looking reason
        if not (1 <= spec.node_num
                <= min(self.config.max_nodes_per_job, len(part.node_ids))):
            return 0
        # unknown GRES pairs can never be satisfied (the layout is the
        # cluster's configured inventory) — clean rejection, not a crash
        known_gres = set(self.meta.layout.gres_dims)
        for res in (spec.res, spec.task_res):
            if res is not None and res.gres:
                if not set(res.gres) <= known_gres:
                    return 0
        # CheckJobValidity analog: the per-node minimum request (base +
        # task_res * min tasks, reference min_res_view cpp:6152) must fit
        # at least one node's *total* in the partition.
        req = spec.res.encode(self.meta.layout)
        if spec.task_res is not None:
            req = req + (spec.task_res.encode(self.meta.layout)
                         * spec.ntasks_per_node_min)
        if not (req <= self.meta.partition_max_total(spec.partition)).all():
            return 0
        if spec.ntasks is not None:
            nt_max = max(spec.ntasks_per_node_max,
                         spec.ntasks_per_node_min)
            nt_min = spec.ntasks_per_node_min
            if not (max(spec.node_num, spec.node_num * nt_min)
                    <= spec.ntasks <= spec.node_num * nt_max):
                return 0  # every chosen node must host at least
                          # ntasks_per_node_min tasks (>= 1) and the
                          # gang's combined per-node cap must cover ntasks

        if spec.reservation:
            resv = self.meta.reservations.get(spec.reservation)
            if resv is None or not resv.account_allowed(spec.account):
                return 0
        if spec.licenses and self.licenses.legal(spec.licenses):
            return 0  # unknown license or count beyond the total
        if spec.array is not None and not spec.array.task_ids():
            return 0

        qos_name, qos_priority = "", spec.qos_priority
        if self.accounts is not None:
            qos, err = self.accounts.resolve_submit(
                spec.user, spec.account, spec.partition, spec.qos or None)
            if err:
                return 0
            if qos is not None:
                err = self.account_meta.try_malloc_submit(
                    spec.user, spec.account, qos, spec)
                if err:
                    return 0
                qos_name, qos_priority = qos.name, qos.priority
        if self.global_usage is not None:
            # federation-wide MaxSubmitJobs (fed/usage.py): conservative
            # under bounded staleness — deny-early, never overshoot
            if self.global_usage.check_submit(spec.user, spec.account):
                if self.account_meta is not None and qos_name:
                    self.account_meta.free_submit(
                        spec.user, spec.account, qos_name)
                return 0
            self.global_usage.note_submit(spec.user, spec.account)

        job_id = self._next_job_id
        self._next_job_id += 1
        self.stats["jobs_submitted_total"] += 1
        job = Job(job_id=job_id, spec=spec, submit_time=now,
                  qos_name=qos_name, qos_priority=qos_priority,
                  held=spec.held)
        if spec.held:
            job.pending_reason = PendingReason.HELD
        if spec.array is not None:
            job.array_remaining = spec.array.task_ids()
        self._register_dependencies(job)
        self.pending[job_id] = job
        if self.wal is not None:
            self.wal.job_submitted(job)
        if self.jobtrace is not None:
            self.jobtrace.stamp(job_id, 0, "submit", now)
        return job_id

    # ------------------------------------------------------------------
    # dependencies (reference: event-driven, AddDependent
    # CtldPublicDefs.cpp:1750, start triggers AFTER JobScheduler.cpp:1873,
    # terminal triggers ANY/OK/NOT_OK with InfiniteFuture for the failed
    # branch :1768-1775)
    # ------------------------------------------------------------------

    def _register_dependencies(self, job: Job) -> None:
        for dep in job.spec.dependencies:
            target = self.job_info(dep.job_id)
            if target is None:
                job.dep_state[dep.job_id] = DEP_NEVER
                continue
            sat = self._dep_satisfied_time(dep, target)
            job.dep_state[dep.job_id] = sat
            if sat is None:   # still waiting on an event
                self._dependents.setdefault(dep.job_id, set()).add(
                    job.job_id)

    @staticmethod
    def _dep_satisfied_time(dep, target: Job) -> float | None:
        """Edge state from the dependee's CURRENT state: a timestamp
        (satisfiable from then + delay), DEP_NEVER, or None (waiting)."""
        if dep.type == DepType.AFTER:
            if target.start_time is not None:
                return target.start_time + dep.delay_seconds
            if target.status.is_terminal:   # never started and never will
                return (target.end_time or 0.0) + dep.delay_seconds \
                    if target.status == JobStatus.COMPLETED else DEP_NEVER
            return None
        if not target.status.is_terminal:
            return None
        end = target.end_time or 0.0
        if dep.type == DepType.AFTER_ANY:
            return end + dep.delay_seconds
        if dep.type == DepType.AFTER_OK:
            return (end + dep.delay_seconds
                    if target.status == JobStatus.COMPLETED else DEP_NEVER)
        # AFTER_NOT_OK
        return (end + dep.delay_seconds
                if target.status.is_failed_kind else DEP_NEVER)

    def _trigger_dep_event(self, target: Job) -> None:
        """Re-evaluate waiting edges of this job's dependents."""
        waiting = self._dependents.get(target.job_id)
        if not waiting:
            return
        done = set()
        for jid in waiting:
            dep_job = self.pending.get(jid)
            if dep_job is None:
                done.add(jid)
                continue
            changed = False
            for dep in dep_job.spec.dependencies:
                if dep.job_id != target.job_id:
                    continue
                sat = self._dep_satisfied_time(dep, target)
                if sat is not None:
                    dep_job.dep_state[dep.job_id] = sat
                    changed = True
            if changed:
                # dep_state mutated in place: the table row must see
                # the new dep-ready time / NEVER verdict
                self._table_refresh(dep_job)
            if all(v is not None
                   for v in dep_job.dep_state.values()):
                done.add(jid)
        if target.status.is_terminal:
            self._dependents.pop(target.job_id, None)
        else:
            waiting -= done

    def _deps_runnable(self, job: Job, now: float) -> PendingReason | None:
        """None = runnable; else the pending reason to surface."""
        if not job.dep_state:
            return None
        states = list(job.dep_state.values())
        if job.spec.deps_is_or:
            if any(v is not None and v != DEP_NEVER and v <= now
                   for v in states):
                return None
            if all(v == DEP_NEVER for v in states):
                return PendingReason.DEPENDENCY_NEVER_SATISFIED
            return PendingReason.DEPENDENCY
        # AND combination
        if any(v == DEP_NEVER for v in states):
            return PendingReason.DEPENDENCY_NEVER_SATISFIED
        if all(v is not None and v <= now for v in states):
            return None
        return PendingReason.DEPENDENCY

    def cancel(self, job_id: int, now: float) -> bool:
        if job_id in self.pending:
            job = self.pending.pop(job_id)
            job.status = JobStatus.CANCELLED
            job.end_time = now
            if job.spec.array is not None:
                # cancel the template: drop unmaterialized tasks and
                # cancel live children
                job.array_remaining = []
                for c in list(job.array_children):
                    self.cancel(c, now)
            self._finalize_terminal(job)
            return True
        if job_id in self.running:
            job = self.running[job_id]
            job.cancel_requested = True
            if job.spec.alloc_only:
                # no batch step will ever report: ctld owns the
                # allocation's lifecycle, so finalize synchronously and
                # free the allocation on the craneds (best-effort;
                # re-registration reconciles a missed FreeJob)
                self._teardown_alloc_job(job, now, JobStatus.CANCELLED,
                                         130)
                return True
            # real system: TerminateSteps RPC → craned kills → status
            # change flows back.  The dispatch seam owns the kill; the
            # status change arrives via step_status_change.  The intent is
            # recorded on the job AND WAL-logged so neither a node death
            # racing the kill nor a ctld crash can resurrect the job.
            for step in job.steps.values():
                if not step.status.is_terminal:
                    step.cancel_requested = True
            if self.wal is not None:
                self.wal.job_updated(job)
            self._cancel_kill_sent[job_id] = now
            self.dispatch_terminate(job_id, now)
            self._kick()   # kill-intent renewal runs on the cycle thread
            return True
        return False

    def dispatch_terminate(self, job_id: int, now: float,
                           incarnation: int | None = None,
                           skip_node: int | None = None) -> None:
        """Overridden/patched by the transport layer; simulated clusters
        hook this to deliver a Cancelled status change.

        ``incarnation`` guards the kill to exactly that requeue_count
        (system-initiated kills that are followed by a same-cycle requeue
        must never touch the re-placed incarnation); None = user intent,
        kill whatever runs.  ``skip_node`` omits a node already declared
        dead (its steps died with the daemon; an RPC to it only blocks a
        pool worker for the full timeout)."""

    def hold(self, job_id: int, held: bool, now: float) -> bool:
        job = self.pending.get(job_id)
        if job is None:
            return False
        job.held = held
        job.pending_reason = (PendingReason.HELD if held
                              else PendingReason.NONE)
        if self.wal is not None:
            self.wal.job_updated(job)
        self._table_refresh(job)
        return True

    def requeue(self, job_id: int, now: float) -> str:
        """Operator-requested requeue (reference RequeueJob,
        Crane.proto:1407): kill the running incarnation and return the
        job to pending.  Returns "" on success, else the refusal reason.

        Held/pending jobs are refused (nothing to requeue); the kill is
        incarnation-guarded exactly like the node-death path so a late
        terminate can never touch the re-placed incarnation."""
        if job_id in self.pending:
            return "job is pending; nothing to requeue"
        job = self.running.get(job_id)
        if job is None:
            return "no such running job"
        if job.cancel_requested:
            return "cancel already requested"
        if job.status == JobStatus.SUSPENDED:
            return "job is suspended; resume it first"
        self.dispatch_terminate(job_id, now,
                                incarnation=job.requeue_count)
        self._release_job_resources(job)
        del self.running[job_id]
        self._cancel_kill_sent.pop(job_id, None)
        if self.jobtrace is not None:
            self.jobtrace.stamp(job_id, job.requeue_count, "requeue",
                                now)
        job.reset_for_requeue()
        if job.requeue_count > self.config.max_requeue_count:
            job.held = True
            job.pending_reason = PendingReason.HELD
        self.pending[job_id] = job
        self.events.emit("requeue", job_id=job_id, detail="operator",
                         time=now)
        if self.wal is not None:
            self.wal.job_requeued(job)
        return ""

    def job_summary(self, user: str = "", partition: str = ""
                    ) -> dict[str, int]:
        """Per-status job counts (reference QueryJobSummary,
        Crane.proto:1588) over pending + running + in-RAM history."""
        counts: dict[str, int] = {}
        for col in (self.pending, self.running, self.history):
            for job in col.values():
                if user and job.spec.user != user:
                    continue
                if partition and job.spec.partition != partition:
                    continue
                key = job.status.name
                counts[key] = counts.get(key, 0) + 1
        return counts

    def modify_job(self, job_id: int, now: float, *,
                   time_limit: float | None = None,
                   priority: int | None = None,
                   partition: str | None = None) -> str:
        """Modify a job in place (reference ModifyJob, Crane.proto:1447).
        Returns "" on success, else the refusal reason.

        time_limit applies to pending AND running jobs — for running
        jobs the new deadline propagates to the supervisors through
        ``dispatch_change_time_limit`` (the ChangeJobTimeConstraint
        path, Crane.proto:1654), so a job about to hit its old limit is
        NOT killed at it.  priority and partition change pending jobs
        only (the reference likewise refuses to migrate a running job)."""
        job = self.pending.get(job_id) or self.running.get(job_id)
        if job is None:
            return f"job {job_id} not found or already terminal"
        running = job_id in self.running
        if running and (priority is not None or partition is not None):
            return "only the time limit of a running job can change"
        if time_limit is not None:
            if time_limit <= 0:
                return "time limit must be positive"
            if self.accounts is not None and job.qos_name:
                qos = self.accounts.qos.get(job.qos_name)
                if qos is not None and (
                        time_limit > qos.max_time_limit_per_job
                        or time_limit > qos.max_wall):
                    return ("time limit exceeds qos "
                            f"{job.qos_name} bound")
        if partition is not None:
            # full submit-time validation against the NEW partition
            # (skipping it would let an owner bypass account ACLs or
            # strand a gang in a partition that can never host it)
            part = self.meta.partitions.get(partition)
            if part is None:
                return f"partition {partition} not found"
            if not part.node_ids:
                return f"partition {partition} has no nodes"
            if not part.account_allowed(job.spec.account):
                return (f"account {job.spec.account} not allowed in "
                        f"partition {partition}")
            if job.spec.node_num > len(part.node_ids):
                return (f"gang of {job.spec.node_num} exceeds "
                        f"partition {partition} size")
            req = job.spec.res.encode(self.meta.layout)
            if job.spec.task_res is not None:
                req = req + (job.spec.task_res.encode(self.meta.layout)
                             * job.spec.ntasks_per_node_min)
            if not (req <= self.meta.partition_max_total(partition)
                    ).all():
                return (f"request exceeds every node in partition "
                        f"{partition}")
            if self.accounts is not None:
                _qos, err = self.accounts.resolve_submit(
                    job.spec.user, job.spec.account, partition,
                    job.spec.qos or None)
                if err:
                    return err
        import dataclasses as _dc
        if time_limit is not None:
            job.spec = _dc.replace(job.spec,
                                   time_limit=float(time_limit))
            if running:
                # the incremental ledger's release row must follow the
                # new deadline, or every later time map would reserve
                # against a bucket the job will still occupy
                self._ledger.set_end_time(
                    job_id, self._effective_end(job, now))
                self._limit_intents[job_id] = (float(time_limit), now)
                self.dispatch_change_time_limit(job_id, float(time_limit),
                                                now)
                self._kick()   # intent re-sends run on the cycle thread
        if priority is not None:
            job.qos_priority = int(priority)
        if partition is not None:
            job.spec = _dc.replace(job.spec, partition=partition)
            job.pending_reason = PendingReason.NONE
        if self.wal is not None:
            self.wal.job_updated(job)
        if not running:
            self._table_refresh(job)
        return ""

    def dispatch_change_time_limit(self, job_id: int, time_limit: float,
                                   now: float) -> None:
        """Transport seam: push the new deadline to the job's craneds.
        The sim plane has no supervisors to update (deadlines re-read
        spec.time_limit), so the base seam just confirms the intent."""
        self._limit_intents.pop(job_id, None)

    # ------------------------------------------------------------------
    # status changes (reference StepStatusChangeAsync :5294 + batched
    # drain :5318)
    # ------------------------------------------------------------------

    def step_status_change(self, job_id: int, status: JobStatus,
                           exit_code: int, now: float,
                           node_id: int = -1,
                           incarnation: int | None = None) -> None:
        """node_id >= 0 is a per-node report from a real craned; the job
        is terminal only when every allocated node reported (or on the
        first failure, which kills the rest).  node_id == -1 is a
        whole-job report (simulated plane / dispatch failures)."""
        queue_incarnation = incarnation
        if node_id >= 0:
            job = self.running.get(job_id)
            if job is None:
                return
            if node_id not in job.node_ids:
                # stale report from a previous incarnation's node
                # (e.g. a preemption kill confirmed after the victim was
                # requeued and re-placed elsewhere)
                return
            if (incarnation is not None
                    and incarnation != job.requeue_count):
                # stale report from a pre-requeue step, even if the new
                # incarnation landed on the same node
                return
            is_failure = status not in (JobStatus.COMPLETED,
                                        JobStatus.CANCELLED)
            had_failure = any(
                st not in (JobStatus.COMPLETED, JobStatus.CANCELLED)
                for st, _ in job.node_reports.values())
            job.node_reports[node_id] = (status, exit_code)
            if is_failure and not had_failure:
                # first failure: kill the remaining steps; their
                # Cancelled reports complete the set.  Guarded by this
                # incarnation — if the job requeues before the async kill
                # lands, the new run must survive it.
                self.dispatch_terminate(job_id, now,
                                        incarnation=job.requeue_count)
            if not all(n in job.node_reports for n in job.node_ids):
                return
            # aggregate: worst status wins (any non-complete -> that)
            agg_status, agg_code = JobStatus.COMPLETED, 0
            for st, code in job.node_reports.values():
                if st != JobStatus.COMPLETED and st != JobStatus.CANCELLED:
                    agg_status, agg_code = st, code
                    break
            else:
                if any(st == JobStatus.CANCELLED
                       for st, _ in job.node_reports.values()) and \
                        not all(st == JobStatus.CANCELLED
                                for st, _ in job.node_reports.values()):
                    # mixed Cancelled (our kill) + Completed: the kill
                    # was collateral of another node's failure... or a
                    # user cancel; cancel_requested disambiguates
                    agg_status = (JobStatus.CANCELLED
                                  if job.cancel_requested
                                  else JobStatus.COMPLETED)
                elif all(st == JobStatus.CANCELLED
                         for st, _ in job.node_reports.values()):
                    agg_status, agg_code = JobStatus.CANCELLED, 130
            status, exit_code = agg_status, agg_code
            queue_incarnation = job.requeue_count
        self._status_queue.append(
            StatusChange(job_id, status, exit_code, now,
                         incarnation=queue_incarnation))
        self._kick()   # Event.set is thread-safe (transport threads)

    def record_remote_spans(self, job_id: int, incarnation: int,
                            spans) -> int:
        """Merge craned-side spans (craned_received / cgroup_ready /
        step_start) shipped back inside StepStatusChange into the job's
        timeline.  Each span keeps its original seq from the propagated
        trace context, so the merged timeline stays monotone; stamp-once
        drops duplicates from retried RPCs.  Thread-safe (recorder lock);
        returns the number of spans newly recorded."""
        if self.jobtrace is None:
            return 0
        n = 0
        for s in spans:
            edge = s["edge"] if isinstance(s, dict) else s.edge
            if isinstance(s, dict):
                t, seq = s["t"], s.get("seq")
                node_id = s.get("node_id", -1)
                skew = s.get("skew", 0.0)
            else:
                t, seq, node_id, skew = s.time, s.seq, s.node_id, s.skew
            if self.jobtrace.stamp(job_id, incarnation, edge, float(t),
                                   node_id=int(node_id),
                                   skew=float(skew), seq=int(seq)):
                n += 1
        return n

    def trace_seq(self, job_id: int, incarnation: int) -> int:
        """Next span seq for (job_id, incarnation) — the base the
        dispatcher embeds in the crane-trace gRPC metadata so craned
        numbers its local spans after the ctld-side ones."""
        if self.jobtrace is None:
            return 0
        return self.jobtrace.next_seq(job_id, incarnation)

    def step_report_async(self, job_id: int, step_id: int,
                          status: "StepStatus", exit_code: int,
                          now: float,
                          incarnation: int | None = None) -> None:
        """Thread-safe step report enqueue for transport pool threads
        (drained at the next process_status_changes)."""
        self._step_report_queue.append(
            (job_id, step_id, status, exit_code, now, incarnation))
        self._kick()

    def process_status_changes(self) -> int:
        """Drain the queue (cycle step 1).  Returns #processed.

        All WAL events from one drain (requeues, finalize tombstones)
        commit as one group — inside a cycle this nests into the
        cycle's group; called standalone (Tick RPC, tests) it opens its
        own, so a big drain still pays one fsync, not one per job."""
        self._wal_begin()
        try:
            return self._process_status_changes()
        finally:
            self._wal_flush()

    def _process_status_changes(self) -> int:
        while self._step_report_queue:
            args = self._step_report_queue.popleft()
            job_id, step_id, status, exit_code, now, incarnation = args
            self.step_report(job_id, step_id, status, exit_code, now,
                             incarnation=incarnation)
        n = 0
        while self._status_queue:
            ch = self._status_queue.popleft()
            job = self.running.get(ch.job_id)
            if job is None:
                continue
            if (ch.incarnation is not None
                    and ch.incarnation != job.requeue_count):
                continue  # stale report for a pre-requeue incarnation
            n += 1
            self._release_job_resources(job)
            del self.running[ch.job_id]
            self._cancel_kill_sent.pop(ch.job_id, None)
            job.end_time = ch.time
            job.exit_code = ch.exit_code
            job.status = ch.status
            if self._should_requeue(job, ch):
                if self.jobtrace is not None:
                    self.jobtrace.stamp(job.job_id, job.requeue_count,
                                        "requeue", ch.time)
                job.reset_for_requeue()
                if job.requeue_count > self.config.max_requeue_count:
                    # over the cap: requeued but held (reference keeps the
                    # job, operator must release)
                    job.held = True
                    job.pending_reason = PendingReason.HELD
                self.pending[job.job_id] = job
                if self.wal is not None:
                    self.wal.job_requeued(job)
            else:
                self._finalize_terminal(job)
        return n

    def _should_requeue(self, job: Job, ch: StatusChange) -> bool:
        """Reference ShouldRequeue (CtldPublicDefs tests :397-457):
        user-requested requeue-if-failed, or system failure (craned
        death), bounded by MaxRequeueCount."""
        if job.cancel_requested:
            return False
        if ch.status == JobStatus.FAILED and job.spec.requeue_if_failed:
            return True
        return False

    def _job_alloc(self, job: Job) -> list[np.ndarray]:
        """Per-node allocation vectors (exclusive jobs own whole nodes;
        packed jobs scale with their task layout).  Cached per incarnation
        — this is on the per-cycle hot path via _initial_cost."""
        if (job.alloc_cache is not None
                and len(job.alloc_cache) == len(job.node_ids)):
            return job.alloc_cache
        spec = job.spec
        if spec.exclusive:
            alloc = [self.meta.nodes[n].total.copy()
                     for n in job.node_ids]
        else:
            base = spec.res.encode(self.meta.layout)
            if spec.task_res is None:
                alloc = [base] * len(job.node_ids)
            else:
                task = spec.task_res.encode(self.meta.layout)
                layout = (job.task_layout
                          or [spec.ntasks_per_node_min]
                          * len(job.node_ids))
                alloc = [base + task * t for t in layout]
        job.alloc_cache = alloc
        return alloc

    def _release_job_resources(self, job: Job) -> None:
        self.meta.free_resource(job.job_id, job.node_ids,
                                self._job_alloc(job))
        self._ledger.remove(job.job_id)
        self.licenses.free(job.spec.licenses or {})
        self._free_run_limits(job)
        self._kick()   # freed capacity: pending jobs may now place

    def _ledger_add(self, job: Job, now: float) -> None:
        """Register a just-started (or re-adopted) job's allocation rows
        in the incremental ledger."""
        self._ledger.add(
            job.job_id, job.node_ids, self._job_alloc(job),
            self._effective_end(job, now),
            [self.meta.nodes[n].total[DIM_CPU] for n in job.node_ids])
        if job.status == JobStatus.SUSPENDED:
            self._ledger.suspend(job.job_id, now)

    def _ledger_add_batch(self, jobs: list[Job], now: float) -> None:
        """Batch form of _ledger_add for the commit hot path: the whole
        just-started set registers its rows in one ledger call (started
        jobs are RUNNING, so no suspend bookkeeping here)."""
        if not jobs:
            return
        nodes = self.meta.nodes
        self._ledger.add_batch(
            [(job.job_id, job.node_ids, self._job_alloc(job),
              self._effective_end(job, now),
              [nodes[n].total[DIM_CPU] for n in job.node_ids])
             for job in jobs])

    def _malloc_run_limits(self, job: Job) -> bool:
        """Schedule-time QoS limit check + usage take (reference
        CheckAndMallocMetaResource, AccountMetaContainer.h:113).  The
        take is recorded on the job so the free stays symmetric even if
        the QoS is deleted/re-created while the job runs."""
        job.run_usage_taken = False
        gu = self.global_usage
        if gu is not None and gu.check_run(job.spec.user,
                                           job.spec.account):
            # federation-wide MaxJobs: the job stays pending
            return False
        if self.account_meta is not None and job.qos_name:
            qos = self.accounts.qos.get(job.qos_name)
            if qos is not None:
                err = self.account_meta.check_and_malloc_run(
                    job.spec.user, job.spec.account, qos, job.spec)
                if err:
                    return False
                job.run_usage_taken = True
        if gu is not None:
            # hold the slot NOW: batch commits check every candidate
            # before any lands in the running dict, so later same-cycle
            # checks must see this admission (the dict hook converts
            # the reservation into the real count)
            gu.reserve_run(job.spec.user, job.spec.account)
            job.global_run_reserved = True
        return True

    def _free_run_limits(self, job: Job) -> None:
        if self.account_meta is not None and job.run_usage_taken:
            self.account_meta.free_run(job.spec.user, job.spec.account,
                                       job.qos_name, job.spec)
            job.run_usage_taken = False
        if self.global_usage is not None and job.global_run_reserved:
            self.global_usage.unreserve_run(job.spec.user,
                                            job.spec.account)
            job.global_run_reserved = False

    def _finalize_terminal(self, job: Job) -> None:
        """Full terminal processing: archive + fire dependency events +
        array-parent bookkeeping.  Every path that moves a job to a
        terminal state outside process_status_changes must use this (a
        bare _finalize drops the event hooks and dependents would wait
        forever — dependency edges are event-driven, never polled)."""
        # close the step records with the allocation: the implicit batch
        # step 0 mirrors the job's outcome; any other live step died
        # with the allocation
        for step in job.steps.values():
            if step.status.is_terminal:
                continue
            if step.step_id == 0 and not job.spec.alloc_only:
                step.status = StepStatus(job.status.value)
                step.exit_code = (job.exit_code
                                  if job.exit_code is not None else 0)
            else:
                step.status = StepStatus.CANCELLED
                step.exit_code = 130
            step.end_time = job.end_time
        if self.jobtrace is not None:
            t = (job.end_time if job.end_time is not None
                 else (job.start_time or job.submit_time))
            self.jobtrace.stamp(job.job_id, job.requeue_count, "end", t,
                                epoch=self.fencing_epoch)
        self._finalize(job)
        self._trigger_dep_event(job)
        if job.array_parent_id is not None:
            self._on_array_child_terminal(job)

    def _finalize(self, job: Job) -> None:
        self.stats["jobs_finished_total"] += 1
        # array children never took a submit slot (the template owns it)
        if (self.account_meta is not None and job.qos_name
                and job.array_parent_id is None):
            self.account_meta.free_submit(job.spec.user, job.spec.account,
                                          job.qos_name)
        if self.global_usage is not None and job.array_parent_id is None:
            self.global_usage.note_release_submit(job.spec.user,
                                                  job.spec.account)
        self.history[job.job_id] = job
        if self.archive is not None:
            # archive BEFORE the WAL tombstone: once both exist the job
            # survives compaction and restart in the durable store
            self.archive.append(job)
            # with the durable store in place, RAM history is a bounded
            # recency cache — evict oldest-inserted beyond the cap
            # (without an archive the dict is the ONLY record: no evict)
            while len(self.history) > self.HISTORY_CACHE_MAX:
                self.history.pop(next(iter(self.history)))
        if self.wal is not None:
            self.wal.job_finalized(job)
            # periodic purge of finalized rows (the reference compacts
            # the embedded DB only after the Mongo transfer): safe to
            # automate ONLY with a durable archive — without one the
            # tombstones are the entire history
            if self.archive is not None:
                self._finalized_since_compact += 1
                if self._finalized_since_compact >= 1000:
                    self._finalized_since_compact = 0
                    self.wal.compact()

    # ------------------------------------------------------------------
    # suspend / resume (reference SuspendJobByCgroup/ResumeJobByCgroup,
    # JobManager.h:150-152; suspended time credited back to the limit,
    # JobScheduler.cpp:118-126)
    # ------------------------------------------------------------------

    def suspend(self, job_id: int, now: float) -> bool:
        job = self.running.get(job_id)
        if job is None or job.status != JobStatus.RUNNING:
            return False
        job.status = JobStatus.SUSPENDED
        job.suspend_time = now
        self._ledger.suspend(job_id, now)
        if self.wal is not None:
            self.wal.job_updated(job)
        self.dispatch_suspend(job_id, now)
        return True

    def resume(self, job_id: int, now: float) -> bool:
        job = self.running.get(job_id)
        if job is None or job.status != JobStatus.SUSPENDED:
            return False
        job.suspended_total += max(now - (job.suspend_time or now), 0.0)
        job.suspend_time = None
        job.status = JobStatus.RUNNING
        self._ledger.resume(job_id, now)
        if self.wal is not None:
            self.wal.job_updated(job)
        self.dispatch_resume(job_id, now)
        return True

    def dispatch_suspend(self, job_id: int, now: float) -> None:
        """Transport seam: freeze the job's cgroups on its nodes."""

    def dispatch_resume(self, job_id: int, now: float) -> None:
        """Transport seam: thaw the job's cgroups."""

    # ------------------------------------------------------------------
    # steps within a job allocation (reference StepInCtld +
    # StepScheduleThread_, CtldPublicDefs.h:521-782, JobScheduler.cpp:
    # 1985; AllocJobs = the allocation, AllocSteps/ExecuteStep = per-step
    # dispatch :1732-1839).  Batch jobs carry an implicit step 0; a
    # calloc-style ``alloc_only`` job holds the allocation while crun
    # steps are submitted, scheduled against the allocation's internal
    # capacity, and complete independently.
    # ------------------------------------------------------------------

    def _init_steps(self, job: Job, now: float) -> None:
        """Called when the allocation starts: batch jobs materialize
        their implicit step 0 (the batch script); alloc_only jobs start
        empty."""
        job.steps = {}
        if job.spec.alloc_only:
            job.next_step_id = 0
            return
        spec = job.spec
        job.steps[0] = Step(
            step_id=0,
            spec=StepSpec(name="batch", script=spec.script,
                          res=None, node_num=0,
                          time_limit=spec.time_limit,
                          output_path=spec.output_path,
                          interactive_address=spec.interactive_address,
                          pty=spec.pty,
                          interactive_token=spec.interactive_token,
                          sim_runtime=spec.sim_runtime,
                          sim_exit_code=spec.sim_exit_code),
            submit_time=now, status=StepStatus.RUNNING,
            start_time=now, node_ids=list(job.node_ids))
        job.next_step_id = 1

    def submit_step(self, job_id: int, spec: StepSpec,
                    now: float) -> int:
        """Add a step to a running allocation; returns step_id (-1 =
        rejected).  The step starts immediately if its per-node share
        fits in the allocation's remaining internal capacity, else waits
        PENDING until an earlier step finishes (the reference's step
        scheduling over the allocation)."""
        job = self.running.get(job_id)
        if job is None or job.status != JobStatus.RUNNING:
            return -1
        if job.cancel_requested:
            return -1
        if spec.node_num > len(job.node_ids):
            return -1
        if spec.res is not None:
            req = spec.res.encode(self.meta.layout)
            # must fit the allocation's per-node share at all (ignoring
            # other steps) or it can never start
            if not all((req <= alloc).all()
                       for alloc in self._job_alloc(job)):
                return -1
        step_id = job.next_step_id
        job.next_step_id += 1
        job.steps[step_id] = Step(step_id=step_id, spec=spec,
                                  submit_time=now)
        self._try_start_steps(job, now)
        if self.wal is not None:
            self.wal.job_updated(job)
        return step_id

    def _step_req(self, job: Job, step: Step) -> np.ndarray | None:
        """Per-node vector the step occupies, or None = whole allocation."""
        if step.spec.res is None:
            return None
        return step.spec.res.encode(self.meta.layout)

    def _try_start_steps(self, job: Job, now: float) -> list[int]:
        """Start pending steps (id order) that fit the allocation's free
        internal capacity.  A step with res=None takes whole nodes, so
        such steps serialize; sized steps pack."""
        started = []
        allocs = self._job_alloc(job)
        # free capacity per allocation node = alloc - sum(running steps)
        free = [a.astype(np.int64).copy() for a in allocs]
        whole_busy = [False] * len(job.node_ids)
        for st in job.steps.values():
            if st.status != StepStatus.RUNNING or st.spec.overlap:
                continue
            req = self._step_req(job, st)
            for n in st.node_ids:
                i = job.node_ids.index(n)
                if req is None:
                    whole_busy[i] = True
                else:
                    free[i] -= req
        for step_id in sorted(job.steps):
            step = job.steps[step_id]
            if step.status != StepStatus.PENDING:
                continue
            if step.spec.overlap:
                # observation channels (cattach): start immediately on
                # the step's span without holding any share (the Slurm
                # --overlap analog) — they neither block nor are
                # blocked by the allocation's internal packing.  A
                # follow_step targets the OBSERVED step's nodes (the
                # container lives there, not on the prefix).
                want = step.spec.node_num or len(job.node_ids)
                nodes = None
                if step.spec.follow_step is not None:
                    tgt = job.steps.get(step.spec.follow_step)
                    if tgt is not None and not tgt.status.is_terminal:
                        if tgt.status != StepStatus.RUNNING:
                            continue   # wait for the target to place
                        nodes = list(tgt.node_ids)[:want] \
                            if want < len(tgt.node_ids) \
                            else list(tgt.node_ids)
                step.status = StepStatus.RUNNING
                step.start_time = now
                step.node_ids = (nodes if nodes
                                 else job.node_ids[:want])
                started.append(step_id)
                self.dispatch_step(job, step)
                continue
            want = step.spec.node_num or len(job.node_ids)
            req = self._step_req(job, step)
            picked = []
            for i, n in enumerate(job.node_ids):
                if len(picked) == want:
                    break
                if whole_busy[i]:
                    continue
                if req is None:
                    if (free[i] == allocs[i]).all():
                        picked.append(i)
                elif (req <= free[i]).all():
                    picked.append(i)
            if len(picked) < want:
                continue
            step.status = StepStatus.RUNNING
            step.start_time = now
            step.node_ids = [job.node_ids[i] for i in picked]
            for i in picked:
                if req is None:
                    whole_busy[i] = True
                else:
                    free[i] -= req
            started.append(step_id)
            self.dispatch_step(job, step)
        return started

    def dispatch_step(self, job: Job, step: Step) -> None:
        """Transport seam: push the step to the allocation's craneds."""

    def dispatch_terminate_step(self, job_id: int, step_id: int,
                                now: float) -> None:
        """Transport seam: kill exactly one step."""

    def dispatch_free_alloc(self, job_id: int, now: float,
                            incarnation: int | None = None,
                            skip_node: int | None = None) -> None:
        """Transport seam: release the job's ALLOCATION on its craneds
        (kill remaining steps, drop cgroup + GRES).  Defaults to a plain
        terminate — the sim plane has no allocation state to free."""
        self.dispatch_terminate(job_id, now, incarnation=incarnation,
                                skip_node=skip_node)

    def cancel_step(self, job_id: int, step_id: int, now: float) -> bool:
        job = self.running.get(job_id)
        if job is None:
            return False
        step = job.steps.get(step_id)
        if step is None or step.status.is_terminal:
            return False
        step.cancel_requested = True
        if step.status == StepStatus.PENDING:
            step.status = StepStatus.CANCELLED
            step.end_time = now
            step.exit_code = 130
            if self.wal is not None:
                self.wal.job_updated(job)
            return True
        self.dispatch_terminate_step(job_id, step_id, now)
        self._step_cancel_sent[(job_id, step_id)] = now
        if self.wal is not None:
            self.wal.job_updated(job)
        self._kick()   # kill-intent renewal runs on the cycle thread
        return True

    def _teardown_alloc_job(self, job: Job, now: float,
                            status: JobStatus, exit_code: int) -> None:
        """Shared end-of-allocation path (cancel / cfree / time limit):
        free the allocation on the craneds, return the resources, and
        finalize with the given outcome.  Live steps are closed
        uniformly by _finalize_terminal (CANCELLED, 130) — callers must
        NOT pre-mark them, or the shared closer skips them and the
        exit code diverges between the paths."""
        self.dispatch_free_alloc(job.job_id, now,
                                 incarnation=job.requeue_count)
        self._release_job_resources(job)
        del self.running[job.job_id]
        self._cancel_kill_sent.pop(job.job_id, None)
        job.status = status
        job.end_time = now
        job.exit_code = exit_code
        self._finalize_terminal(job)

    def free_allocation(self, job_id: int, now: float) -> bool:
        """End an alloc_only job: kill running steps, release resources,
        finalize COMPLETED (the calloc exit path)."""
        job = self.running.get(job_id)
        if job is None or not job.spec.alloc_only:
            return False
        self._teardown_alloc_job(job, now, JobStatus.COMPLETED, 0)
        return True

    def step_report(self, job_id: int, step_id: int, status: StepStatus,
                    exit_code: int, now: float, node_id: int = -1,
                    incarnation: int | None = None,
                    cpu_seconds: float = 0.0,
                    max_rss_bytes: int = 0) -> None:
        """Per-step status report from a craned (or whole-step from the
        sim).  Steps aggregate per-node exactly like jobs; a terminal
        step frees its internal share and pulls the next pending step
        in.  Step 0 of a batch job closes the whole job (via the
        job-level status-change queue, preserving requeue semantics)."""
        job = self.running.get(job_id)
        if job is None:
            return
        if incarnation is not None and incarnation != job.requeue_count:
            return
        step = job.steps.get(step_id)
        if step is None or step.status.is_terminal:
            return

        def fold_usage():
            # efficiency accounting (ceff): cpu-seconds sum across
            # node reports, RSS keeps the peak; the job aggregates its
            # steps.  Folded only for ACCEPTED first-time reports —
            # a re-delivered or rejected report must not inflate ceff
            if cpu_seconds or max_rss_bytes:
                step.cpu_seconds += cpu_seconds
                step.max_rss_bytes = max(step.max_rss_bytes,
                                         max_rss_bytes)
                job.cpu_seconds += cpu_seconds
                job.max_rss_bytes = max(job.max_rss_bytes,
                                        max_rss_bytes)

        if node_id >= 0:
            if node_id not in step.node_ids:
                return
            if node_id not in step.node_reports:
                fold_usage()
            is_failure = status not in (StepStatus.COMPLETED,
                                        StepStatus.CANCELLED)
            had_failure = any(
                st not in (StepStatus.COMPLETED, StepStatus.CANCELLED)
                for st, _ in step.node_reports.values())
            step.node_reports[node_id] = (status, exit_code)
            if is_failure and not had_failure:
                self.dispatch_terminate_step(job_id, step_id, now)
            if not all(n in step.node_reports for n in step.node_ids):
                return
            status, exit_code = self._aggregate_step(step)
        else:
            fold_usage()   # whole-step (sim) form: accepted exactly
                           # once — the step turns terminal below
        step.status = status
        step.end_time = now
        step.exit_code = exit_code
        self._step_cancel_sent.pop((job_id, step_id), None)
        if self.wal is not None:
            self.wal.job_updated(job)
        if step_id == 0 and not job.spec.alloc_only:
            # the batch step IS the job: feed the job-level machine —
            # and wake the loop: the close runs on the cycle thread,
            # which may be deep in an idle sleep
            self._status_queue.append(StatusChange(
                job_id, JobStatus(status.value), exit_code, now,
                incarnation=job.requeue_count))
            self._kick()
            return
        self._try_start_steps(job, now)

    @staticmethod
    def _aggregate_step(step: Step) -> tuple[StepStatus, int]:
        """Worst-status-wins aggregation over the step's node reports
        (same rule as the job-level path)."""
        agg_status, agg_code = StepStatus.COMPLETED, 0
        for st, code in step.node_reports.values():
            if st not in (StepStatus.COMPLETED, StepStatus.CANCELLED):
                return st, code
        reports = list(step.node_reports.values())
        if any(st == StepStatus.CANCELLED for st, _ in reports):
            if (all(st == StepStatus.CANCELLED for st, _ in reports)
                    or step.cancel_requested):
                return StepStatus.CANCELLED, 130
        return agg_status, agg_code

    def _check_alloc_timeouts(self, now: float) -> None:
        """alloc_only jobs have no batch supervisor enforcing the time
        limit — the ctld cycle enforces it (reference: ctld-side
        termination timers for allocations).  Iterates the _alloc_only
        index, not the running map (the scan is per-cycle)."""
        for job_id in sorted(self._alloc_only):
            job = self.running.get(job_id)
            if job is None or not job.spec.alloc_only:
                continue
            if job.status != JobStatus.RUNNING:
                continue
            if now >= self._effective_end(job, now):
                self._teardown_alloc_job(job, now,
                                         JobStatus.EXCEED_TIME_LIMIT,
                                         124)

    def _effective_end(self, job: Job, now: float) -> float:
        """Expected end with suspended time credited back."""
        start = job.start_time if job.start_time is not None else now
        suspended = job.suspended_total
        if job.suspend_time is not None:   # currently frozen
            suspended += max(now - job.suspend_time, 0.0)
        return start + job.spec.time_limit + suspended

    # ------------------------------------------------------------------
    # node failure (reference CranedDown → TerminateJobsOnCraned,
    # JobScheduler.h:1076; EC_CRANED_DOWN requeue)
    # ------------------------------------------------------------------

    def on_craned_down(self, node_id: int, now: float) -> list[int]:
        """Node died: terminate its jobs; system-failure auto-requeue up
        to MaxRequeueCount, then held (CtldPublicDefs.h:101-102)."""
        node = self.meta.nodes.get(node_id)
        self.emit_node_event("node_down",
                             node.name if node else str(node_id),
                             now=now)
        victim_ids = self.meta.craned_down(node_id)
        for job_id in victim_ids:
            job = self.running.get(job_id)
            if job is None:
                continue
            # Kill the gang's steps on SURVIVING nodes before freeing the
            # resources (reference TerminateJobsOnCraned): without this a
            # multi-node job's live steps keep running while ctld re-places
            # work onto those nodes — orphaned workload + physical
            # oversubscription.  The node list is captured synchronously by
            # the dispatcher, so this must precede the running-map removal.
            # Incarnation-guarded (the requeue below bumps requeue_count;
            # an async kill racing the re-dispatch must miss the new run)
            # and skipping the dead node (RPCs to it only burn a worker).
            if len(job.node_ids) > 1:
                if job.spec.alloc_only:
                    # surviving nodes must also drop the explicit
                    # allocation (cgroup + GRES), not just kill steps —
                    # a lingering alloc would refuse the re-dispatch
                    self.dispatch_free_alloc(
                        job_id, now, incarnation=job.requeue_count,
                        skip_node=node_id)
                else:
                    self.dispatch_terminate(
                        job_id, now, incarnation=job.requeue_count,
                        skip_node=node_id)
            self._release_job_resources(job)
            del self.running[job_id]
            self._cancel_kill_sent.pop(job_id, None)
            if job.cancel_requested:
                # the kill we sent can no longer be confirmed; honor the
                # user's cancel instead of resurrecting the job
                job.status = JobStatus.CANCELLED
                job.end_time = now
                job.exit_code = 130
                self._finalize_terminal(job)
                continue
            job.reset_for_requeue()
            if job.requeue_count > self.config.max_requeue_count:
                # same terminal behavior as the status-change path:
                # requeued but held, operator must release
                job.held = True
                job.pending_reason = PendingReason.HELD
            self.pending[job_id] = job
            self.events.emit("requeue", job_id=job_id,
                             detail="node down", time=now)
            if self.wal is not None:
                self.wal.job_requeued(job)
        return victim_ids

    # minimum seconds between kill re-sends for one unconfirmed cancel:
    # each renewal is a full terminate fan-out whose RPCs can block up to
    # their timeout on an unresponsive craned, so renewing every 1 Hz
    # cycle would pile tasks onto the dispatcher pool faster than they
    # drain and starve healthy dispatches behind terminate retries
    CANCEL_RENEW_INTERVAL = 5.0

    def _renew_cancel_intents(self, now: float) -> None:
        """Re-send the kill for running jobs whose cancel intent is still
        unconfirmed.  A TerminateStep that reaches a craned before its
        ExecuteStep (both async on separate workers) is a no-op there, so
        a single kill can be lost and the cancelled job would run to
        completion; the intent is durable on the job, so re-dispatching
        (with backoff) until the Cancelled status change arrives closes
        the race (idempotent on the craned side)."""
        # keyed on the outstanding-cancel map (sized by cancels in
        # flight), NOT the running map — the latter would add an
        # O(running) scan to every cycle's prelude
        for job_id, last in list(self._cancel_kill_sent.items()):
            job = self.running.get(job_id)
            if job is None or not job.cancel_requested:
                self._cancel_kill_sent.pop(job_id, None)
                continue
            if now - last < self.CANCEL_RENEW_INTERVAL:
                continue
            self._cancel_kill_sent[job_id] = now
            self.dispatch_terminate(job_id, now)
        # step-level cancel intents renew identically (ADVICE r3: a lost
        # TerminateStep left a cancelled step running forever)
        for key, last in list(self._step_cancel_sent.items()):
            job_id, step_id = key
            job = self.running.get(job_id)
            step = job.steps.get(step_id) if job is not None else None
            if (step is None or step.status.is_terminal
                    or not step.cancel_requested):
                self._step_cancel_sent.pop(key, None)
                continue
            if now - last < self.CANCEL_RENEW_INTERVAL:
                continue
            self._step_cancel_sent[key] = now
            self.dispatch_terminate_step(job_id, step_id, now)
        # unconfirmed time-limit pushes renew every cycle (idempotent;
        # the dispatcher pops the intent once every node accepted) —
        # the update must land before the OLD deadline fires, so no
        # backoff: a modify is rare and the fan-out is tiny
        for job_id, (limit, _last) in list(self._limit_intents.items()):
            job = self.running.get(job_id)
            if job is None or job.spec.time_limit != limit:
                self._limit_intents.pop(job_id, None)
                continue
            self.dispatch_change_time_limit(job_id, limit, now)

    # ------------------------------------------------------------------
    # THE scheduling cycle (reference ScheduleThread_ :1321-1981)
    # ------------------------------------------------------------------

    def schedule_cycle(self, now: float) -> list[int]:
        """One cycle: drain status changes, snapshot, device solve, commit,
        dispatch.  Returns the job_ids started this cycle.  Per-phase
        wall-clock timings land in ``stats['last_cycle']`` (reference
        phase trace, JobScheduler.cpp:1444-1447).

        This driver runs every phase inline (single-threaded callers,
        tick mode, tests).  Concurrent servers use ``cycle_phases``
        directly and drop their lock around each yielded solve closure
        — see CtldServer._cycle_loop."""
        gen = self.cycle_phases(now)
        try:
            fn = next(gen)
            while True:
                fn = gen.send(fn())
        except StopIteration as stop:
            return stop.value or []

    def cycle_phases(self, now: float):
        """The cycle as a generator: code between yields mutates
        scheduler state and MUST run under the caller's lock; each
        yielded closure is pure compute over snapshot arrays (the
        device/native solve — the expensive 99%) and is safe to run
        with the lock released.  Mid-solve mutations are caught at
        commit: the meta event window (start_logging →
        ResReduceEvents, the reference's NodeSelect revalidation
        pattern, JobScheduler.cpp:1437-1540) flags touched nodes, and
        _commit re-checks pending membership, licenses, QoS and the
        authoritative ledger per job.

        WAL group commit: every lock-held segment of the cycle runs
        inside one WAL group (one write + one fsync for all its
        events), flushed BEFORE each yield — a group must never stay
        open across a lock release or RPC-path appends (submit acks)
        would buffer without their durability barrier.  The last
        yielded closure drains the post-commit dispatch ring, so no
        dispatch is issued until the group holding its job's ``start``
        record is durable."""
        wal = self.wal
        self._wal_cycle_base = ((wal.fsync_total, wal.groups_total)
                                if wal is not None else (0, 0))
        # introspection: per-cycle recompile attribution + the armed
        # profiler capture window tick (cheap no-ops when idle)
        self._cycle_compile_base = introspect.total_compiles()
        clock = self.cycle_clock
        clock.mark("record")
        self._ledger_row = None
        self.profiler_window.tick()
        clock.annotate = self.lock_ledger.annotate = \
            self.profiler_window.capturing
        self.flight.stamp("cycle_begin")
        clock.mark("drain")
        self._wal_begin()
        self._cycle_thread = threading.get_ident()
        self._cycle_fresh_base = introspect.fresh_calls()
        try:
            started = yield from self._cycle_body(now)
            return started
        finally:
            # safety net for the watchdog's gen.close() and crashed
            # phases: no WAL event may sit buffered across cycles, and
            # a job committed to RUNNING must still get its dispatch
            # (drained inline here; the normal path drained lock-free)
            try:
                clock.mark("wal")
                self._wal_flush()
                self._drain_dispatch_ring()
                self.flight.stamp("cycle_end")
            finally:
                with self._cycle_end:
                    self._cycle_fresh_base = None
                    self._cycle_end.notify_all()
            self._close_cycle_ledger()

    def cycle_compiling(self) -> bool:
        """True from the first jit call of the running cycle that meets
        a signature new to the process (it is about to compile, or to
        load from the persistent cache) until that cycle has ended."""
        base = self._cycle_fresh_base
        return base is not None and introspect.fresh_calls() > base

    def wait_out_compiling_cycle(self) -> None:
        """Batch ingest's back-pressure; call it with NO lock held.

        A cycle that compiles lasts seconds to a minute, most of it with
        the server lock released (the solve).  Whatever a batch caller
        pushes meanwhile is the NEXT cycle's candidates: a larger J
        bucket, so another compile, behind which still more piles up:
        a cold daemon under a flood walked the ladder to 131,072 and
        down again, minutes of compiles for shapes no later cycle
        meets.  Waiting here bounds what a compiling cycle leaves
        behind to about one RPC's specs.  A cycle that compiles nothing
        costs two reads; the wait ends with the cycle, however it ends
        (``cycle_phases``' ``finally``).  The thread that drives the
        cycle never waits for it (a single-threaded driver ingests
        between the phases)."""
        if (not self.cycle_compiling()
                or self._cycle_thread == threading.get_ident()):
            return
        with self._cycle_end:
            while self.cycle_compiling():
                self._cycle_end.wait(1.0)

    def _close_cycle_ledger(self) -> None:
        """The period ends here, under the lock: its parts, and what
        the lock ledger's classes booked since the last close, go into
        the row this cycle ringed, in place (QueryStats serialises the
        ring under the same lock).  dispatch_ms stays _note_dispatch's.
        A cycle that ringed no row (no candidate) still closes its
        period and drains the ledger: its classes' seconds go to
        crane_server_lock_seconds_total alone."""
        fields = self.cycle_clock.close()
        fields.update(self.lock_ledger.drain(fields))
        row = self._ledger_row
        if row is not None:
            if row is self._skip_trace:
                # the coalesced skip row carries its LATEST period: a
                # class that took the lock in an earlier one goes
                for key in [k for k in row if k.startswith("rpc_")]:
                    del row[key]
            row.update(fields)
            if self.cycle_clock.annotate:
                row["profiled"] = True

    def _wal_begin(self) -> None:
        if self.wal is not None:
            self.wal.begin_batch()

    def _wal_flush(self) -> None:
        if self.wal is not None:
            self.wal.commit_batch()

    def _queue_dispatch(self, job: Job, node_ids: list[int]) -> None:
        """Ring entries capture incarnation + fencing epoch NOW, under
        the ctld lock at commit time: the ring drains lock-RELEASED, so
        a requeue or lease loss between queue and drain must not let a
        push go out stamped with the job's newer identity (the
        dispatcher's staleness guard and craned-side fencing both key
        off the values as of the commit).  The current WAL seq rides
        along as the durability watermark — the job's start record has
        seq <= it, so the drain can enforce durable-before-dispatch
        even on a failed barrier."""
        self._dispatch_ring.append((job, list(node_ids),
                                    job.requeue_count,
                                    self.fencing_epoch,
                                    self.wal.seq
                                    if self.wal is not None else 0))

    def _drain_dispatch_ring(self) -> int:
        """Issue every queued dispatch in commit order.  With a batched
        seam wired (GrpcDispatcher.dispatch_batch) the whole ring goes
        out in one call so the dispatcher can coalesce per craned.

        Entries whose WAL watermark is not yet durable are DROPPED, not
        dispatched: that only happens when the group's fsync failed
        (the daemon is about to die) — pushing work whose start record
        never hit disk would resurrect as a ghost allocation after the
        recovery replay requeues the job."""
        ring = self._dispatch_ring
        if not ring:
            return 0
        items: list[tuple] = []
        while ring:
            items.append(ring.popleft())
        if self.wal is not None:
            durable = self.wal.durable_seq
            items = [it for it in items if it[4] <= durable]
            if not items:
                return 0
        trace = self.jobtrace
        if trace is not None:
            # past the durability filter == the WAL group-commit
            # watermark covers each job's start record.  "dispatched"
            # is stamped as the push is ISSUED (the grpc dispatcher
            # pushes from pool threads; the sim plane runs inline and
            # stamps its craned-side spans during the call below, which
            # must sequence after these two).
            t = self._cycle_now
            for job, _nodes, inc, epoch, _seq in items:
                if job is None:  # dropped entry (cancelled at commit)
                    continue
                trace.stamp(job.job_id, inc, "committed_durable", t,
                            epoch=epoch)
                trace.stamp(job.job_id, inc, "dispatched", t,
                            epoch=epoch)
        self.flight.stamp("dispatch", detail=str(len(items)))
        if self.dispatch_batch is not None:
            self.dispatch_batch(items)
        else:
            for job, node_ids, *_ in items:
                self.dispatch(job, node_ids)
        return len(items)

    def _dispatch_phase(self):
        """The cycle's final yielded closure: drain the dispatch ring
        with the lock RELEASED.  Only built after _wal_flush — the
        durable-before-dispatch boundary."""
        import time as _time

        def run():
            self.cycle_clock.mark("dispatch")
            t0 = _time.perf_counter()
            n = self._drain_dispatch_ring()
            return n, (_time.perf_counter() - t0) * 1e3

        return run

    def _note_dispatch(self, result) -> None:
        n, ms = result
        self._cur_trace["dispatch_ms"] = round(ms, 3)
        lc = self.stats.get("last_cycle")
        if isinstance(lc, dict):
            lc["dispatch_ms"] = round(ms, 3)
        _MET_PHASE.observe(ms / 1e3, phase="dispatch")

    def _cycle_body(self, now: float):
        import time as _time
        t0 = _time.perf_counter()
        # guards _initial_cost_reference (reference-only oracle) from
        # ever running inside a cycle; cleared by _record_cycle_stats /
        # _skip_cycle / the empty-candidates return
        self._in_cycle = True
        self._cur_trace = {
            "now": now, "queue_depth": len(self.pending),
            "solver": "", "solve_ms": 0.0,
            "preempted": 0, "backfilled": 0, "num_streams": 1,
            "prelude_jobs_touched": 0,
            "run_walked": 0, "run_cols_ms": 0.0,
        }
        _MET_PENDING.set(len(self.pending))
        self._cycle_now = now
        self.process_status_changes()
        self._check_craned_timeouts(now)
        self._check_alloc_timeouts(now)
        self._drain_deferred_evictions(now)
        self._renew_cancel_intents(now)
        self.meta.purge_expired_reservations(now)
        self._materialize_array_children(now)
        t_prelude = _time.perf_counter()
        self.flight.stamp("prelude")

        # no-op short-circuit: the drains above already ran (they are
        # the event sinks), so if no epoch moved since the last armed
        # zero-placement solve and no time edge passed, this cycle
        # would rebuild the identical inputs and place nothing — skip
        # before building anything
        fp = self._cycle_fingerprint()
        if (self.config.incremental and self._noop_fp is not None
                and fp == self._noop_fp and now < self._noop_edge
                and not self._dispatch_ring):
            return self._skip_cycle(t0, now, "fingerprint")
        self._cycle_fp0 = fp
        self._noop_fp = None
        self._cycle_usage_denied0 = (self.global_usage.denied
                                     if self.global_usage is not None
                                     else 0)

        self.stats["cycles"] += 1
        _MET_CYCLES.inc()
        clock = self.cycle_clock
        clock.mark("candidates")
        candidates = self._pending_candidates(now)
        if self.jobtrace is not None and candidates:
            self._stamp_eligible(candidates, now)
        if not candidates:
            # empty cycles still refresh the liveness timestamp (the
            # watchdog's stall detection keys off it) but don't enter
            # the trace ring — an idle cluster would otherwise flush
            # every interesting trace out of the ring
            self.stats["last_cycle_walltime"] = _time.time()
            self.stats["last_cycle"] = {
                "prelude_ms": round((t_prelude - t0) * 1e3, 3),
                "pending": 0, "started": 0,
                "running": len(self.running)}
            self._skip_trace = None
            self._arm_noop(now)
            self._in_cycle = False
            return []

        # snapshot + event capture window (cpp:1437)
        clock.mark("snapshot")
        self.meta.start_logging()
        avail, total, alive = self.meta.snapshot()

        # rank EVERY candidate (factor bounds over the whole queue), then
        # cut: the first schedule_batch_size of the order are the cycle's
        # batch, the rest wait on "Priority" with their priority written
        # (GetOrderedJobPtrVec(limit), cpp:6734, :7606-7629).  A queue
        # that fits is its own batch: the slice is the whole order
        clock.mark("priority")
        ranked = self._priority_sort(candidates, now)
        clock.mark("cut")
        limit = self.config.schedule_batch_size
        ordered = ranked[:limit]
        if len(ranked) > limit:
            self._cut_batch(ranked[limit:])
        self._cur_trace.update(ranked=len(ranked),
                               cut=len(ranked) - len(ordered))
        clock.mark("build")
        # the table epoch of the lock-free solve window: every writer of
        # a pending job's spec, hold flag or dependencies re-upserts its
        # row (modify_job REPLACES job.spec, then _table_refresh), so
        # _commit voids the placement of a row written since (e.g. a
        # partition move validated against the NEW partition while the
        # solve placed it in the OLD one)
        self._plan_epoch = self._ptable.epoch
        jobs_batch, max_nodes = self._build_batch(ordered, avail.shape[0],
                                                  now)
        cost0 = self._ledger.cost0(now, total.shape[0])

        # cycles containing packed/exclusive jobs route to the
        # full-fidelity packed solver (immediate-fit; such jobs don't get
        # backfill reservations this round)
        orows = ordered.rows
        if orows is not None:
            packed = bool(self._ptable.packed[orows].any())
        else:
            packed = any(j.spec.exclusive or j.spec.task_res is not None
                         or (j.spec.ntasks is not None
                             and j.spec.ntasks != j.spec.node_num)
                         or j.spec.ntasks_per_node_max > 1
                         for j in ordered.jobs)
        if packed:
            state = make_cluster_state(avail, total, alive, cost0)
            pbatch = self._packed_batch(jobs_batch.dense,
                                        self._materialise(ordered))
            placements = yield from self._solve_phase(
                "packed", lambda: solve_packed(
                    state, pbatch, max_nodes=max_nodes)[0])
            started = self._commit(ordered, placements, now,
                                   tasks=np.asarray(placements.tasks))
            started += self._try_preemption(ordered, now)
            clock.mark("wal")
            self._wal_flush()
            self._record_cycle_stats(t0, t_prelude, ordered, started,
                                     _time.perf_counter(), "packed")
            if self._dispatch_ring:
                self._note_dispatch((yield self._dispatch_phase()))
            return started

        topo = self._active_topology()
        if topo is not None:
            self._update_topo_fragmentation(topo, avail, total, alive)
        if topo is not None and (
                bool((self._ptable.nnum[orows] > 1).any())
                if orows is not None
                else any(j.spec.node_num > 1 for j in ordered.jobs)):
            # gang cycle with a topology configured: route through the
            # best-fit-block solve (topo/place.py).  Backfill is skipped
            # for this cycle — locality dominates reservation lookahead
            # for gangs, and single-node cycles keep the full backfill
            # path (plus the block-major permutation, see
            # _immediate_solve).
            state = make_cluster_state(avail, total, alive, cost0)
            dense = (jobs_batch.dense
                     if isinstance(jobs_batch, FactoredJobBatch)
                     else jobs_batch)
            levels = topo.jnp_levels
            # _note_topo writes a verdict on every job: look them up
            # here, while no cancel can have taken one away
            self._materialise(ordered)
            placements, _, topo_info = yield from self._solve_phase(
                "topo", lambda: solve_greedy_topo(
                    state, dense, levels, max_nodes=max_nodes))
            self._note_topo(topo, ordered.jobs, topo_info)
            started = self._commit(ordered, placements, now)
            started += self._try_preemption(ordered, now)
            clock.mark("wal")
            self._wal_flush()
            self._record_cycle_stats(t0, t_prelude, ordered, started,
                                     _time.perf_counter(), "topo")
            if self._dispatch_ring:
                self._note_dispatch((yield self._dispatch_phase()))
            return started

        if self.config.backfill:
            bf_max = max(1, self.config.backfill_max_jobs)
            if len(ordered) > bf_max:
                started = yield from self._split_backfill_phases(
                    ordered, jobs_batch, avail, total, alive,
                    cost0, max_nodes, now)
                started += self._try_preemption(ordered, now)
                clock.mark("wal")
                self._wal_flush()
                self._record_cycle_stats(t0, t_prelude, ordered,
                                         started,
                                         _time.perf_counter(),
                                         "backfill-split")
                if self._dispatch_ring:
                    self._note_dispatch((yield self._dispatch_phase()))
                return started
            state = self._timed_state(now, avail, total, alive, cost0)
            tbatch = self._timed_batch(jobs_batch.dense)
            placements = yield from self._solve_phase(
                "backfill", lambda: solve_backfill(
                    state, tbatch, edges=self._grid.jnp_edges,
                    max_nodes=max_nodes)[0])
            start_buckets = np.asarray(placements.start_bucket)
            self._cur_trace["backfilled"] = int(np.sum(
                np.asarray(placements.placed) & (start_buckets > 0)))
        else:
            placements, solver_name = yield from self._solve_phase(
                None, lambda: self._immediate_solve(
                    avail, total, alive, cost0, jobs_batch, max_nodes,
                    resident_ok=True))
            start_buckets = None

        started = self._commit(ordered, placements, now, start_buckets)
        started += self._try_preemption(ordered, now)
        clock.mark("wal")
        self._wal_flush()
        # double buffer: pre-upload the rows this commit dirtied so the
        # next cycle's resident patch finds them already on device
        clock.mark("commit_apply")
        self._resident.stage()
        self._record_cycle_stats(
            t0, t_prelude, ordered, started, _time.perf_counter(),
            "backfill" if self.config.backfill else solver_name)
        if self._dispatch_ring:
            self._note_dispatch((yield self._dispatch_phase()))
        return started

    def _immediate_solve(self, avail, total, alive, cost0, jobs_batch,
                         max_nodes, resident_ok=False):
        """Route one immediate-fit solve through the configured backend
        (auto/native/device/pallas/sharded — bit-identical on one
        backend, see SchedulerConfig.solver).

        When a topology is configured, the node axis is presented to the
        backend in block-major order (Topology.perm): the backends'
        ascending-cost / first-fit walks then cluster picks inside
        blocks — locality with zero kernel changes — and the chosen
        indices are mapped back to real node ids before commit.

        ``resident_ok=True`` (only the plain immediate cycle passes it —
        never the backfill-split tail solve, whose ``avail`` is the
        min-over-horizon array, and never under a topology permutation)
        lets the device/pallas/sharded backends use the cross-cycle
        resident ClusterState instead of rebuilding from host arrays."""
        topo = self._active_topology()
        perm = None
        if topo is not None:
            perm = topo.perm
            avail = np.asarray(avail)[perm]
            total = np.asarray(total)[perm]
            alive = np.asarray(alive)[perm]
            cost0 = np.asarray(cost0)[perm]
            jobs_batch = self._permute_batch(jobs_batch, topo)
            # permuted rows don't line up with meta node ids — the
            # resident dirty feed would patch the wrong rows
            self._resident.invalidate()
            resident_ok = False
        placements = None
        solver_name = "immediate"
        import jax as _jax
        solver = self.config.solver
        if solver == "auto" and _jax.default_backend() == "tpu":
            # the chip is there: the immediate solve belongs on it
            solver = "pallas"
        if solver in ("auto", "native"):
            placements = self._solve_native(avail, total, alive, cost0,
                                            jobs_batch, max_nodes)
            if placements is not None:
                solver_name = "native"
            elif solver == "native":
                raise RuntimeError("native solver unavailable")
        if placements is None and solver == "sharded":
            placements = self._solve_sharded(avail, total, alive, cost0,
                                             jobs_batch, max_nodes,
                                             resident_ok=resident_ok)
            solver_name = "sharded"
        if placements is None and solver == "pallas":
            placements, solver_name = self._solve_pallas(
                avail, total, alive, cost0, jobs_batch, max_nodes,
                resident_ok=resident_ok)
        if placements is None:
            dense = (jobs_batch.dense
                     if isinstance(jobs_batch, FactoredJobBatch)
                     else jobs_batch)
            if resident_ok and self._resident.enabled:
                state, _mode = self._resident.acquire(
                    avail, total, alive, cost0,
                    key=("device", int(np.asarray(avail).shape[0]),
                         int(np.asarray(avail).shape[1]),
                         self._mask_table.generation))
                fn = (solve_greedy_donating
                      if _jax.default_backend() == "tpu" else solve_greedy)
                placements, new_state = fn(state, dense,
                                           max_nodes=max_nodes)
                self._resident.adopt(new_state)
            else:
                state = make_cluster_state(avail, total, alive, cost0)
                placements, _ = solve_greedy(state, dense,
                                             max_nodes=max_nodes)
        if perm is not None:
            nodes = np.asarray(placements.nodes)
            real = np.where(nodes >= 0, perm[np.maximum(nodes, 0)],
                            np.int32(-1)).astype(np.int32)
            placements = Placements(placed=np.asarray(placements.placed),
                                    nodes=real,
                                    reason=np.asarray(placements.reason),
                                    passes=getattr(placements,
                                                   "passes", None))
        return placements, solver_name

    # ---- topology-aware placement (topo/) ----

    def _active_topology(self):
        """The attached Topology, or None when absent/stale (nodes
        registered after it was built — size mismatch means its arrays
        no longer line up with the snapshot)."""
        topo = getattr(self.meta, "topology", None)
        if topo is not None and topo.num_nodes != len(self.meta.nodes):
            return None
        return topo

    def _permute_batch(self, jobs_batch, topo):
        """Job batch with the node axis in block-major order."""
        jperm = topo.jnp_perm
        if isinstance(jobs_batch, FactoredJobBatch):
            node_class = jobs_batch.node_class_np
            return FactoredJobBatch(
                req=jobs_batch.req, node_num=jobs_batch.node_num,
                time_limit=jobs_batch.time_limit, valid=jobs_batch.valid,
                job_class=jobs_batch.job_class,
                class_masks=jobs_batch.class_masks[:, jperm],
                job_class_np=jobs_batch.job_class_np,
                class_rows_np=np.asarray(
                    jobs_batch.class_rows_np)[:, topo.perm],
                node_class_np=(np.asarray(node_class)[topo.perm]
                               if node_class is not None else None))
        return jobs_batch.replace(part_mask=jobs_batch.part_mask[:, jperm])

    def _update_topo_fragmentation(self, topo, avail, total, alive):
        """Per-level free-capacity fragmentation gauge + trace field,
        computed from the cycle snapshot (a free node is alive with its
        full capacity available)."""
        free = alive & (avail == total).all(axis=1)
        frags = topo.fragmentation(free)
        for name, frag in frags:
            _MET_TOPO_FRAG.set(frag, level=name)
        self._cur_trace["topo_frag"] = frags[0][1]

    def _note_topo(self, topo, ordered, info) -> None:
        """Record per-gang locality verdicts: trace fields, the
        cross-block counter, and each job's topo_block/cross_block."""
        import jax as _jax
        info = _jax.device_get(info)  # one transfer for all three
        in_b = info.in_block.tolist()
        crs = info.cross.tolist()
        blocks = info.block.tolist()
        n_in = sum(in_b)
        n_cross = sum(crs)
        self._cur_trace["topo_in_block"] = n_in
        self._cur_trace["topo_cross"] = n_cross
        self.stats["topo_in_block_total"] = (
            self.stats.get("topo_in_block_total", 0) + n_in)
        self.stats["topo_cross_block_total"] = (
            self.stats.get("topo_cross_block_total", 0) + n_cross)
        if n_cross:
            _MET_TOPO_CROSS.inc(n_cross)
        for i, job in enumerate(ordered):
            job.cross_block = bool(crs[i])
            job.topo_block = (
                topo.block_names[int(blocks[i])]
                if in_b[i] and blocks[i] >= 0
                else ("spanning" if crs[i] else ""))

    def _split_backfill_phases(self, ordered, jobs_batch, avail,
                               total, alive, cost0, max_nodes, now):
        """Bounded backfill lookahead (Slurm's sched/bf split): the
        timed solve with full reservation semantics covers only the top
        ``backfill_max_jobs`` priority jobs; the tail is placed by the
        fast immediate solver against the MIN-over-horizon availability
        of the post-reservation time map, so no tail placement can ever
        violate a head reservation (it fits even the tightest bucket —
        strictly conservative, like the rest of the grid design)."""
        bf_max = max(1, self.config.backfill_max_jobs)
        head, tail = ordered[:bf_max], ordered[bf_max:]

        # slice the already-built batch — rebuilding it would pay the
        # prelude twice per cycle in exactly the regime this split
        # exists to keep fast.  The head needs dense rows anyway (the
        # timed solver gathers per-job masks), so slice the device-side
        # gather; the tail STAYS factored — the immediate solve it feeds
        # is exactly the path the [C, N] table exists for.
        import jax

        hb = self._job_bucket(len(head))
        head_batch = jax.tree.map(lambda x: x[:hb], jobs_batch.dense)
        # rows past len(head) in the bucketed slice are REAL tail jobs —
        # invalidate them or they would place in both passes
        head_batch = head_batch.replace(valid=head_batch.valid & (
            jnp.arange(hb) < len(head)))
        tail_valid = jobs_batch.valid & (
            jnp.arange(jobs_batch.valid.shape[0]) >= bf_max)
        tail_batch = jobs_batch.with_valid(tail_valid)

        state = self._timed_state(now, avail, total, alive, cost0)
        tb = self._timed_batch(head_batch)
        placements, tstate = yield from self._solve_phase(
            "backfill", lambda: solve_backfill(
                state, tb, edges=self._grid.jnp_edges,
                max_nodes=max_nodes))
        head_start = np.asarray(placements.start_bucket)
        self._cur_trace["backfilled"] = int(np.sum(
            np.asarray(placements.placed) & (head_start > 0)))
        started = self._commit(head, placements, now, head_start)

        # pass 2: the tail against the tightest bucket of the horizon
        clock = self.cycle_clock
        clock.mark("snapshot")
        self.meta.start_logging()   # fresh event window for this commit

        def _tail_solve():
            clock.mark("solve_host")
            min_avail = np.asarray(jnp.min(tstate.time_avail, axis=1))
            cost1 = np.asarray(tstate.cost)
            clock.mark("solve_enqueue")
            return self._immediate_solve(
                min_avail, total, alive, cost1, tail_batch, max_nodes)

        placements2, _ = yield from self._solve_phase(None, _tail_solve)
        tail_placements = Placements(
            placed=placements2.placed[bf_max:],
            nodes=placements2.nodes[bf_max:],
            reason=placements2.reason[bf_max:])
        started += self._commit(tail, tail_placements, now)
        return started

    def _solve_phase(self, backend, fn):
        """Yield one solve closure with the cycle's WAL group closed
        across it (a group must never stay open over a lock release)
        and a fresh one begun once the lock is held again.  What
        follows, up to the next mark, is the commit's."""
        clock = self.cycle_clock
        clock.mark("wal")
        self._wal_flush()
        out = yield self._traced_solve(backend, fn)
        clock.mark("wal")
        self._wal_begin()
        clock.mark("commit_apply")
        return out

    def _traced_solve(self, backend, fn):
        """Wrap a yielded solve closure: time it (this is the
        lock-RELEASED span), tag it with a jax.profiler span so device
        traces line up with cycle phases, and record backend + latency
        into the in-flight cycle trace.  ``backend=None`` derives the
        label from an ``(placements, solver_name)`` result tuple
        (the _immediate_solve contract)."""
        import time as _time
        trace = self._cur_trace
        clock = self.cycle_clock

        def run():
            label = backend or "immediate"
            clock.mark("solve_enqueue")
            t0 = _time.perf_counter()
            # the cycle's PRELUDE ends when the first solve starts:
            # priority sort + batch build + stream planning all count
            # toward it (that is the span the device-resident tables
            # exist to shrink)
            trace.setdefault("_prelude_end", t0)
            with solve_span(f"crane:solve:{label}"):
                out = fn()
            # settle async device work before stopping the clock —
            # otherwise jax's deferred execution charges the whole
            # solve to the commit phase (the np.asarray sync there)
            first = out[0] if isinstance(out, tuple) else out
            sync = getattr(first, "placed", None)
            clock.mark("solve_device_wait")
            if hasattr(sync, "block_until_ready"):
                sync.block_until_ready()
            clock.mark("solve_host")
            # the Pallas kernels' pass counter came with the placements
            # just waited for: eight bytes, here where the lock is free
            passes = getattr(first, "passes", None)
            if passes is not None:
                ran, bound = np.asarray(passes).tolist()
                trace["_tail_passes"] = trace.get("_tail_passes", 0) + ran
                trace["_tail_bound"] = trace.get("_tail_bound", 0) + bound
            dt = _time.perf_counter() - t0
            if (backend is None and isinstance(out, tuple)
                    and len(out) == 2 and isinstance(out[1], str)):
                label = out[1]
            trace["solve_ms"] = trace.get("solve_ms", 0.0) + dt * 1e3
            if not trace.get("solver"):
                trace["solver"] = label
            _MET_SOLVE.observe(dt, backend=label)
            return out

        return run

    def _record_cycle_stats(self, t0, t_prelude, candidates, started,
                            t_end, solver: str) -> None:
        import time as _time
        self.cycle_clock.mark("record")
        self.stats["jobs_started_total"] += len(started)
        _MET_STARTED.inc(len(started))
        self.flight.stamp("commit", detail=str(len(started)))
        total_ms = (t_end - t0) * 1e3
        drain_ms = (t_prelude - t0) * 1e3
        # prelude = everything before the FIRST solve closure started
        # (status drains + sort + batch build); cycles that never solved
        # fall back to the drain span
        prelude_end = self._cur_trace.pop("_prelude_end", None)
        prelude_ms = (drain_ms if prelude_end is None
                      else (prelude_end - t0) * 1e3)
        solve_ms = float(self._cur_trace.get("solve_ms", 0.0))
        tail_passes = self._cur_trace.pop("_tail_passes", 0)
        tail_bound = self._cur_trace.pop("_tail_bound", 0)
        commit_visited = self._cur_trace.pop("_commit_visited", 0)
        commit_scan_s = self._cur_trace.pop("_commit_scan_s", 0.0)
        nodes_selected = self._cur_trace.pop("_nodes_selected", 0)
        # commit = everything after the prelude that ran under the
        # lock, i.e. total minus prelude minus the lock-released solves.
        # Dispatch is NOT in here: the ring drains post-lock and its
        # span lands separately in dispatch_ms (_note_dispatch).
        commit_ms = max(total_ms - prelude_ms - solve_ms, 0.0)
        base_fsync, base_groups = getattr(self, "_wal_cycle_base",
                                          (0, 0))
        wal = self.wal
        wal_fsyncs = (wal.fsync_total - base_fsync
                      if wal is not None else 0)
        wal_groups = (wal.groups_total - base_groups
                      if wal is not None else 0)
        self.stats["last_cycle"] = {
            "solver": solver,
            "prelude_ms": round(prelude_ms, 3),
            "total_ms": round(total_ms, 3),
            "dispatch_ms": 0.0,
            "pending": len(candidates),
            "started": len(started),
            "running": len(self.running),
        }
        self.stats["last_cycle_walltime"] = _time.time()
        trace = self._cur_trace
        trace.update(
            solver=solver,
            prelude_ms=round(prelude_ms, 3),
            solve_ms=round(solve_ms, 3),
            commit_ms=round(commit_ms, 3),
            # placeholder: the dispatch ring drains AFTER this push (the
            # cycle's last, lock-released phase) and _note_dispatch
            # updates the ringed dict in place
            dispatch_ms=0.0,
            total_ms=round(total_ms, 3),
            lock_held_ms=round(prelude_ms + commit_ms, 3),
            wal_fsyncs=wal_fsyncs,
            wal_groups=wal_groups,
            candidates=len(candidates),
            # BASELINE's yardstick as the served path pays it
            decisions_per_s=round(len(candidates) * 1e3 / solve_ms, 1)
            if solve_ms > 0 else 0.0,
            # the share of its slots x K selection passes the cycle's
            # Pallas kernel ran (100.0: no such kernel ran, so none was
            # left out; never 0, which a reader of a share that is better
            # lower would take for the best value)
            tail_pass_pct=round(100.0 * tail_passes / tail_bound, 3)
            if tail_bound else 100.0,
            # the rows the cycle's commits visited in Python (placed, or
            # told another reason than the one they carried) over its
            # candidates, and the part of commit_apply_ms spent on the
            # array pulls and that visit
            commit_visited_pct=round(
                100.0 * commit_visited / len(candidates), 3),
            commit_scan_ms=round(commit_scan_s * 1e3, 3),
            # the nodes of the jobs the cycle started and of the head's
            # reservations: what the selection passes were run FOR
            nodes_selected=nodes_selected,
            placed=len(started),
            dirty_jobs=self._ptable.last_dirty,
            dirty_nodes=self.meta.last_snapshot_dirty,
        )
        res = self._resident
        res_mode = res.pop_cycle_mode()
        if res_mode is not None:
            trace.update(
                resident=res_mode,
                h2d_rows=res.last_h2d_rows,
                h2d_bytes=res.last_h2d_bytes,
                patch_overlap=bool(res.last_overlap),
            )
            _MET_H2D.inc(res.last_h2d_bytes, mode=res_mode)
            _MET_RESIDENT.inc(mode=res_mode)
            _MET_OVERLAP.set(res.overlap_share())
        # introspection plane: recompiles paid by THIS cycle (delta off
        # the process-wide observer) + device-memory gauges.  A warm
        # cycle paying a fresh compile breaks the bucketed-padding
        # contract — surface it as an event, not just a counter.
        recompiles = (introspect.total_compiles()
                      - getattr(self, "_cycle_compile_base", 0))
        mem = introspect.sample_device_memory()
        trace.update(
            recompiles=recompiles,
            device_bytes=mem["bytes"],
            device_peak_bytes=mem["peak_bytes"],
            device_buffers=mem["buffers"],
        )
        if recompiles > 0 and self.stats["cycles"] >= self.WARMUP_CYCLES:
            self.events.emit(
                "recompile_steady", "warning",
                detail="cycle %d paid %d recompile(s)" % (
                    self.stats["cycles"], recompiles))
        self._in_cycle = False
        self.cycle_trace.push(trace)
        self._ledger_row = trace
        self._skip_trace = None
        _MET_PHASE.observe(prelude_ms / 1e3, phase="prelude")
        _MET_PHASE.observe(solve_ms / 1e3, phase="solve")
        _MET_PHASE.observe(commit_ms / 1e3, phase="commit")
        _MET_LOCK.observe((prelude_ms + commit_ms) / 1e3)
        # a zero-placement solve with nothing preempted or in flight can
        # arm the no-op fingerprint: the next cycle seeing the same
        # epochs would rebuild identical inputs and place nothing
        if (not started and trace.get("preempted", 0) == 0
                and not self._dispatch_ring):
            self._arm_noop(trace.get("now", 0.0))

    def _solve_native(self, avail, total, alive, cost0, jobs_batch,
                      max_nodes):
        """The C++ treap solver for immediate-fit cycles (bit-identical
        to solve_greedy; tests/test_native_solver.py).  Returns None when
        the library or shape is unsupported — caller falls back."""
        from cranesched_tpu.utils import native

        class _Shim:
            pass

        common = (avail, total, alive.astype(np.uint8), cost0,
                  np.asarray(jobs_batch.req),
                  np.asarray(jobs_batch.node_num),
                  np.asarray(jobs_batch.time_limit),
                  np.asarray(jobs_batch.valid).astype(np.uint8))
        if isinstance(jobs_batch, FactoredJobBatch):
            node_class = jobs_batch.node_class_np
            if node_class is not None:
                # factored fast path: class ids in, no [J, N] mask
                # materialized anywhere (partition-id mode)
                out = native.solve_greedy_native(
                    *common, max_nodes=max_nodes,
                    job_part=jobs_batch.job_class_np,
                    node_part=node_class)
            else:
                # overlapping classes: host gather of the C rows —
                # still no per-job _mask_for rebuild
                out = native.solve_greedy_native(
                    *common, max_nodes=max_nodes,
                    mask=jobs_batch.dense_mask_np())
        else:
            out = native.solve_greedy_native(
                *common, max_nodes=max_nodes,
                mask=np.asarray(jobs_batch.part_mask))
        if out is None:
            return None
        shim = _Shim()
        shim.placed, shim.nodes, shim.reason = out[0], out[1], out[2]
        return shim

    def _solve_sharded(self, avail, total, alive, cost0, jobs_batch,
                       max_nodes, resident_ok=False):
        """Node-axis-sharded multi-chip solve (parallel/sharded.py):
        cluster tensors are sharded over every visible device, the
        per-job candidate merge rides ICI all_gathers.  Bit-identical
        placements to solve_greedy (tests/test_sharded_parity.py);
        the multichip dryrun asserts the same through this exact path.

        With ``resident_ok`` the cluster state comes from the
        cross-cycle resident store: the dirty-row patch scatters into
        the node-sharded buffers (each row lands on its owning shard)
        instead of re-uploading the full [N, R] state.  The resident
        key carries the mesh descriptor (procs x local devices) so any
        mesh reshape — device count change, future multi-process
        attach — invalidates the state rather than patching buffers
        laid out for a different shard map."""
        from cranesched_tpu.parallel.sharded import (
            make_node_mesh,
            shard_cluster_state,
            solve_greedy_sharded,
            solve_greedy_sharded_classes,
        )

        if self._mesh is None:
            self._mesh = make_node_mesh()
        mesh = self._mesh
        d = mesh.devices.size
        # single-process scheduler: 1 process x d local devices (the
        # multi-process ProcessMesh path reports its own via describe())
        mesh_desc = f"1x{d}"
        self._cur_trace["mesh"] = mesh_desc
        n = avail.shape[0]
        pad = (-n) % d
        factored = isinstance(jobs_batch, FactoredJobBatch)
        class_masks = jobs_batch.class_masks if factored else None
        if pad:
            # pad with permanently-dead nodes so the node axis divides
            # the mesh; they are never eligible, so placements and the
            # trailing ledger rows are unaffected
            zrow = np.zeros((pad, avail.shape[1]), avail.dtype)
            avail = np.concatenate([avail, zrow])
            total = np.concatenate([total, zrow])
            alive = np.concatenate([alive, np.zeros(pad, bool)])
            cost0 = np.concatenate(
                [cost0, np.zeros(pad, cost0.dtype)])
            if factored:
                class_masks = jnp.pad(class_masks, ((0, 0), (0, pad)),
                                      constant_values=False)
            else:
                jobs_batch = jobs_batch.replace(part_mask=jnp.pad(
                    jobs_batch.part_mask, ((0, 0), (0, pad)),
                    constant_values=False))
        use_resident = resident_ok and self._resident.enabled
        if use_resident:
            # padded shape + mesh descriptor in the key: a node-count
            # change (different pad) or mesh reshape drops the state
            state, _mode = self._resident.acquire(
                avail, total, alive, cost0,
                key=("sharded", int(avail.shape[0]),
                     int(avail.shape[1]),
                     self._mask_table.generation, mesh_desc))
        else:
            state = make_cluster_state(avail, total, alive, cost0)
        # re-assert the node-axis sharding every cycle: a no-op when
        # the resident buffers already live on their shards (rebuild /
        # first cycle is the only real transfer)
        state = shard_cluster_state(state, mesh)
        if factored:
            # class-factored path: the [C, N] table is the only mask
            # that crosses the host→device boundary, and class-disjoint
            # batches decode S jobs per collective round (streamed)
            placements, new_state = solve_greedy_sharded_classes(
                state, jobs_batch.req, jobs_batch.node_num,
                jobs_batch.time_limit, jobs_batch.valid,
                jobs_batch.job_class, class_masks, mesh,
                max_nodes=max_nodes)
        else:
            placements, new_state = solve_greedy_sharded(
                state, jobs_batch, mesh, max_nodes=max_nodes)
        if use_resident:
            self._resident.adopt(new_state)
        return placements

    def _solve_pallas(self, avail, total, alive, cost0, jobs_batch,
                      max_nodes, resident_ok=False):
        """Single-kernel TPU solve (models/pallas_solver.py), returning
        ``(placements, label)``.  A factored batch feeds the kernel its
        class table directly (no dense mask anywhere); class-disjoint
        batches run the S-stream decomposition, labeled
        ``pallas-stream`` with ``num_streams`` in the cycle trace —
        both derived from the plan the auto dispatch ACTUALLY ran with,
        including the planner's internal decision when no cached plan
        exists.  The cluster-state buffers are donated; with
        ``resident_ok`` they come from the cross-cycle resident state
        (dirty-row scatter patch) instead of a fresh host upload.
        The kernel is compiled for the backend JAX runs on — a backend
        that cannot run it raises (``pallas_interpret`` is the tests'
        opt-out, never derived from the platform)."""
        from cranesched_tpu.models.pallas_solver import (
            plan_streams,
            solve_greedy_pallas_auto,
            solve_greedy_pallas_from_batch,
        )

        interpret = self.pallas_interpret
        donate = not interpret   # the interpreter's CPU ignores donation
        if resident_ok and self._resident.enabled:
            state, _mode = self._resident.acquire(
                avail, total, alive, cost0,
                key=("pallas", int(np.asarray(avail).shape[0]),
                     int(np.asarray(avail).shape[1]),
                     self._mask_table.generation))
        else:
            state = make_cluster_state(avail, total, alive, cost0)
        if not isinstance(jobs_batch, FactoredJobBatch):
            placements, new_state, used_plan = (
                solve_greedy_pallas_from_batch(
                    state, jobs_batch, max_nodes=max_nodes,
                    interpret=interpret, donate=donate,
                    return_plan=True))
        else:
            plan = None
            if self._mask_table.disjoint:
                # the table already proved its rows disjoint (cached
                # per epoch) — the planner skips its [C, N] host
                # reduction
                plan = plan_streams(jobs_batch.job_class_np,
                                    jobs_batch.class_rows_np,
                                    known_disjoint=True)
            placements, new_state, used_plan = solve_greedy_pallas_auto(
                state, jobs_batch.req, jobs_batch.node_num,
                jobs_batch.time_limit, jobs_batch.valid,
                jobs_batch.job_class, jobs_batch.class_masks,
                max_nodes=max_nodes, interpret=interpret,
                donate=donate, plan=plan, return_plan=True)
        if resident_ok and self._resident.enabled:
            self._resident.adopt(new_state)
        num_streams = used_plan[1] if used_plan is not None else 1
        self._cur_trace["num_streams"] = num_streams
        return placements, ("pallas-stream" if num_streams > 1
                            else "pallas")

    def _initial_cost_reference(self, now: float,
                                total: np.ndarray) -> np.ndarray:
        """REFERENCE-ONLY implementation of the cost seed: the
        O(running × nodes) per-job Python loop the RunLedger replaced,
        kept solely so parity tests can assert the incremental ledger
        is bit-identical (reference NodeRater, JobScheduler.h:499-516:
        cost = Σ (end - now) * cpu / cpu_total).  Never called from the
        scheduling cycle — cycles seed costs from ``_ledger.cost0`` —
        and the assert below keeps it that way."""
        assert not getattr(self, "_in_cycle", False), (
            "_initial_cost_reference is a test-only oracle; the cycle "
            "seeds costs from RunLedger.cost0")
        cost = np.zeros(total.shape[0], np.int64)
        for job in self.running.values():
            end = self._effective_end(job, now)
            remaining = max(end - now, 0.0)
            for n, alloc in zip(job.node_ids, self._job_alloc(job)):
                cpus = float(alloc[DIM_CPU]) / CPU_SCALE
                cpu_total = max(float(total[n, DIM_CPU]) / CPU_SCALE, 1e-9)
                # int32 fixed-point ledger units (models/solver.py
                # COST_SCALE) so the seeded base keeps cost accumulation
                # associative across all solver implementations
                cost[n] += int(np.round(
                    np.float32(remaining) * np.float32(cpus)
                    * np.float32(COST_SCALE) / np.float32(cpu_total)))
        return cost.astype(np.int32)

    def _timed_state(self, now, avail, total, alive, cost0):
        res = self.config.time_resolution
        T = self.config.time_buckets
        # one release row per (job, node) straight from the incremental
        # ledger — O(rows) numpy, no Python loop over running jobs
        run_nodes, run_req, run_end = self._ledger.timed_rows(
            now, res, T, grid=self._grid)
        # bucket the row count: the running set changes by a few rows
        # every cycle, and each fresh shape recompiles the release
        # scatter (measured ~300 ms/cycle of prelude).  Padding rows use
        # node -1, which the scatter drops as out-of-bounds
        m = run_nodes.shape[0]
        mp = self._bucket(m)
        if mp != m:
            run_nodes = np.concatenate([run_nodes, np.full(
                (mp - m, run_nodes.shape[1]), -1, np.int32)])
            run_req = np.concatenate([run_req, np.zeros(
                (mp - m, run_req.shape[1]), np.int32)])
            run_end = np.concatenate([run_end, np.full(
                mp - m, T, np.int32)])
        return make_timed_state(avail, total, alive, run_nodes, run_req,
                                run_end, T, cost0)

    def _packed_batch(self, batch: JobBatch, ordered: list[Job]
                      ) -> PackedJobBatch:
        lay = self.meta.layout
        J = batch.req.shape[0]
        node_req = np.zeros((J, lay.num_dims), np.int32)
        task_req = np.zeros((J, lay.num_dims), np.int32)
        ntasks = np.ones(J, np.int32)
        nt_min = np.ones(J, np.int32)
        nt_max = np.ones(J, np.int32)
        exclusive = np.zeros(J, bool)
        for i, job in enumerate(ordered):
            spec = job.spec
            node_req[i] = spec.res.encode(lay)
            if spec.task_res is not None:
                task_req[i] = spec.task_res.encode(lay)
            ntasks[i] = (spec.ntasks if spec.ntasks is not None
                         else spec.node_num)
            nt_min[i] = spec.ntasks_per_node_min
            nt_max[i] = max(spec.ntasks_per_node_max,
                            spec.ntasks_per_node_min)
            exclusive[i] = spec.exclusive
        return PackedJobBatch(
            node_req=jnp.asarray(node_req), task_req=jnp.asarray(task_req),
            ntasks=jnp.asarray(ntasks), ntasks_min=jnp.asarray(nt_min),
            ntasks_max=jnp.asarray(nt_max), node_num=batch.node_num,
            time_limit=batch.time_limit, part_mask=batch.part_mask,
            exclusive=jnp.asarray(exclusive), valid=batch.valid)

    def _timed_batch(self, batch: JobBatch) -> TimedJobBatch:
        # time_limit stays in seconds; the solver derives occupancy
        # windows from the grid edges passed alongside the batch
        return TimedJobBatch(req=batch.req, node_num=batch.node_num,
                             time_limit=batch.time_limit,
                             part_mask=batch.part_mask, valid=batch.valid)

    # ------------------------------------------------------------------
    # job arrays (reference ArrayManager, Array.h:51-177: the parent is a
    # pending template; the scheduler materializes at most ONE child per
    # parent per cycle, bounded by the %N run limit)
    # ------------------------------------------------------------------

    def _materialize_array_children(self, now: float) -> None:
        # the _array_templates index replaces an O(pending) scan; id
        # order == the old dict-iteration order (ids are monotonic)
        for parent_id in sorted(self._array_templates):
            parent = self.pending.get(parent_id)
            if parent is None:
                continue
            if parent.spec.array is None or not parent.array_remaining:
                continue
            if parent.held:
                continue
            if self._deps_runnable(parent, now) is not None:
                continue
            limit = parent.spec.array.max_concurrent
            live = sum(1 for c in parent.array_children
                       if not (self.job_info(c) or parent).status
                       .is_terminal)
            if limit and live >= limit:
                continue
            task_id = parent.array_remaining.pop(0)
            child_spec = dataclasses.replace(
                parent.spec, array=None,
                name=f"{parent.spec.name}_{task_id}")
            child_id = self._next_job_id
            self._next_job_id += 1
            child = Job(job_id=child_id, spec=child_spec,
                        submit_time=parent.submit_time,
                        qos_name=parent.qos_name,
                        qos_priority=parent.qos_priority,
                        array_parent_id=parent.job_id,
                        array_task_id=task_id)
            parent.array_children.append(child_id)
            self.pending[child_id] = child
            if self.wal is not None:
                self.wal.job_submitted(child)
                self.wal.job_updated(parent)

    def _on_array_child_terminal(self, child: Job) -> None:
        """Reference OnChildTerminal: parent finishes when every task id
        has materialized and reached a terminal state."""
        parent = self.pending.get(child.array_parent_id)
        if parent is None:
            return
        if not parent.array_remaining and all(
                (self.job_info(c) is not None
                 and self.job_info(c).status.is_terminal)
                for c in parent.array_children):
            del self.pending[parent.job_id]
            statuses = [self.job_info(c).status
                        for c in parent.array_children]
            parent.status = (
                JobStatus.COMPLETED
                if all(st == JobStatus.COMPLETED for st in statuses)
                else JobStatus.FAILED)
            parent.end_time = child.end_time
            self._finalize_terminal(parent)

    # ------------------------------------------------------------------
    # QoS preemption (reference TryPreempt_, JobScheduler.cpp:6378-6505:
    # a blocked job whose QoS lists lower QoS as preemptable evicts their
    # running jobs; victims ordered lowest-qos-first then youngest-first)
    # ------------------------------------------------------------------

    def _preemptor_req(self, job: Job) -> tuple[np.ndarray, list[int]]:
        """Per-node requirement a preemptor needs freed, plus its task
        layout.  Packed jobs use the balanced layout's MAX per-node
        requirement in the what-if (the commit distributes floor tasks
        to later nodes, which can only use less)."""
        spec = job.spec
        base = spec.res.encode(self.meta.layout).astype(np.int64)
        ntasks = spec.ntasks if spec.ntasks is not None else \
            spec.node_num
        # balanced layout ALWAYS (for ntasks == node_num it is all
        # ones): an empty layout would make the dispatcher fall back to
        # one task per node and launch half the gang
        hi = int(np.ceil(ntasks / spec.node_num))
        lo = ntasks // spec.node_num
        n_hi = ntasks - lo * spec.node_num
        layout = [hi] * n_hi + [lo] * (spec.node_num - n_hi)
        if spec.task_res is None:
            return base, layout
        task = spec.task_res.encode(self.meta.layout).astype(np.int64)
        return base + task * hi, layout

    def _try_preemption(self, ordered: _CycleJobs, now: float
                        ) -> list[int]:
        """Device-side what-if (models/preempt.solve_preempt — the
        prefix-sum formulation of the reference's PreemptSegTree) +
        host-authoritative commit.  Runs after the normal solve, so a
        job that got only a future-start backfill reservation can still
        preempt its way to an immediate start (the reference's ordering:
        TryPreempt_ before Backfill_, cpp:6369-6378)."""
        self.cycle_clock.mark("preempt")
        if self.config.preempt_mode == "off" or self.accounts is None:
            return []
        # blocked preemptor candidates, in priority order
        cands = []
        prey_sets = []
        for job in ordered.still_pending():  # the rest placed normally
            if job.pending_reason not in (PendingReason.RESOURCE,
                                          PendingReason.PRIORITY):
                continue
            qos = self.accounts.qos.get(job.qos_name)
            if qos is None or not qos.preempt:
                continue
            cands.append(job)
            prey_sets.append(qos.preempt)
        if not cands:
            return []
        # victim pool: only jobs SOME candidate may actually prey on —
        # the kernel builds [M, N, R] tensors per scan step, so the
        # pool must be bounded by preemptable jobs, not the whole
        # running set.  Sorted ONCE by the reference order (lowest qos
        # first, youngest first); the global sort induces the same
        # per-node prefix order the segment-tree walk used.
        prey_union = set().union(*prey_sets)
        victims = sorted(
            (j for j in self.running.values()
             if j.qos_name in prey_union),
            key=lambda v: (v.qos_priority, -(v.start_time or 0.0)))
        if not victims:
            return []

        from cranesched_tpu.models.preempt import (
            PreemptorBatch, VictimRows, solve_preempt)

        lay = self.meta.layout
        avail, total, alive = self.meta.snapshot()
        N = total.shape[0]
        # flat (victim, node) rows, padded to a bucketed size
        rows = [(vi, n, alloc) for vi, v in enumerate(victims)
                for n, alloc in zip(v.node_ids, self._job_alloc(v))]
        M = self._bucket(len(rows))
        V = self._bucket(len(victims))
        r_vid = np.zeros(M, np.int32)
        r_node = np.full(M, -1, np.int32)
        r_alloc = np.zeros((M, lay.num_dims), np.int32)
        r_valid = np.zeros(M, bool)
        for i, (vi, n, alloc) in enumerate(rows):
            r_vid[i], r_node[i], r_alloc[i] = vi, n, alloc
            r_valid[i] = True

        J = self._bucket(len(cands))
        req = np.zeros((J, lay.num_dims), np.int64)
        node_num = np.zeros(J, np.int32)
        time_limit = np.zeros(J, np.int32)
        part_mask = np.zeros((J, N), bool)
        exclusive = np.zeros(J, bool)
        can_prey = np.zeros((J, V), bool)
        valid = np.zeros(J, bool)
        layouts = []
        for i, (job, prey) in enumerate(zip(cands, prey_sets)):
            jr, layout = self._preemptor_req(job)
            layouts.append(layout)
            req[i] = jr
            node_num[i] = job.spec.node_num
            time_limit[i] = job.spec.time_limit
            part_mask[i] = self._mask_for(job, now)
            exclusive[i] = job.spec.exclusive
            valid[i] = True
            for vi, v in enumerate(victims):
                can_prey[i, vi] = v.qos_name in prey
        max_nodes = self._bucket(
            max(1, min(int(node_num.max(initial=1)),
                       self.config.max_nodes_per_job)), floor=1)

        batch = PreemptorBatch(
            req=jnp.asarray(req, jnp.int32),
            node_num=jnp.asarray(node_num),
            time_limit=jnp.asarray(time_limit),
            part_mask=jnp.asarray(part_mask),
            exclusive=jnp.asarray(exclusive),
            can_prey=jnp.asarray(can_prey),
            valid=jnp.asarray(valid))
        vrows = VictimRows(vid=jnp.asarray(r_vid),
                           node=jnp.asarray(r_node),
                           alloc=jnp.asarray(r_alloc),
                           valid=jnp.asarray(r_valid))
        start_buckets = None
        if self.config.backfill:
            # time-axis what-if (models/preempt_time — the reference's
            # PreemptSegTree capability): a preemptor may combine
            # eviction with waiting for natural releases.  Victim rows
            # carry their release bucket; decisions carry a start
            # bucket: s == 0 starts now, s > 0 kills the victims now
            # and leaves the preemptor pending (the next cycles' solve
            # re-reserves its window against the freed resources).
            from cranesched_tpu.models.preempt_time import (
                TimedPreemptorBatch, TimedVictimRows,
                solve_preempt_timed)

            T = self.config.time_buckets
            r_end = np.full(M, T + 1, np.int32)
            for i, (vi, _n, _a) in enumerate(rows):
                v = victims[vi]
                remain = max((v.start_time or now)
                             + v.spec.time_limit - now, 0.0)
                r_end[i] = min(int(self._grid.release_bucket(remain)),
                               T + 1)
            tstate = self._timed_state(now, avail, total, alive,
                                       self._ledger.cost0(now, N))
            tbatch = TimedPreemptorBatch(
                req=batch.req, node_num=batch.node_num,
                time_limit=batch.time_limit,
                part_mask=batch.part_mask, exclusive=batch.exclusive,
                can_prey=batch.can_prey, valid=batch.valid)
            decisions, _ = solve_preempt_timed(
                tstate.time_avail, total, alive, tstate.cost,
                TimedVictimRows(rows=vrows,
                                end_bucket=jnp.asarray(r_end)),
                tbatch, num_victims=V, max_nodes=max_nodes,
                edges=self._grid.jnp_edges)
            start_buckets = np.asarray(decisions.start_bucket)
        else:
            decisions, _ = solve_preempt(
                avail, total, alive, self._ledger.cost0(now, N),
                vrows, batch, num_victims=V, max_nodes=max_nodes)

        placed = np.asarray(decisions.placed)
        nodes_mat = np.asarray(decisions.nodes)
        evict_mat = np.asarray(decisions.evict)
        started: list[int] = []
        for i, job in enumerate(cands):
            if not placed[i]:
                continue
            chosen = [int(n) for n in nodes_mat[i] if n >= 0]
            evict_ids = [victims[vi].job_id
                         for vi in np.nonzero(evict_mat[i])[0]
                         if vi < len(victims)]
            if start_buckets is not None and start_buckets[i] > 0:
                # Future-start preemption: the preemptor cannot start
                # until its start bucket, so killing the victims NOW
                # would strand their resources idle for the whole gap
                # (the documented divergence in models/preempt_time.py;
                # reference JobScheduler.cpp:6378-6505 keeps victims
                # running).  Defer the eviction to the start-bucket
                # edge instead: the event-driven loop wakes via
                # next_wake_time and the cycle prelude drains due
                # entries.  Re-solving each cycle refreshes the due
                # time, and a preemptor that gets placed (or cancelled)
                # before then releases its victims unharmed.
                if evict_ids:
                    due = now + float(self._grid.edges[
                        min(int(start_buckets[i]), T)])
                    for victim_id in evict_ids:
                        self._deferred_evictions[victim_id] = (
                            due, job.job_id)
                    self._set_reason(job, PendingReason.PRIORITY)
                continue
            if self._commit_preemption(job, chosen, evict_ids,
                                       layouts[i], now):
                started.append(job.job_id)
            else:
                # the device sequenced later candidates assuming this
                # one placed; their decisions are now stale — stop here
                # (they retry next cycle against fresh state) rather
                # than kill victims for placements that cannot commit
                break
        return started

    def _commit_preemption(self, job: Job, chosen: list[int],
                           evict_ids: list[int], layout: list[int],
                           now: float) -> bool:
        """Host-authoritative commit of one device preemption decision:
        admission checks BEFORE any eviction (victims must never die for
        a preemptor that cannot start), then evict, then malloc with
        mid-cycle revalidation."""
        if len(chosen) < job.spec.node_num:
            return False
        if job.spec.licenses and not self.licenses.malloc(
                job.spec.licenses):
            self._set_reason(job, PendingReason.LICENSE)
            return False
        if not self._malloc_run_limits(job):
            self.licenses.free(job.spec.licenses or {})
            self._set_reason(job, PendingReason.QOS_LIMIT)
            return False

        for victim_id in evict_ids:
            self._evict(victim_id, now)
        job.node_ids = chosen
        job.task_layout = list(layout)
        job.alloc_cache = None
        if not self.meta.malloc_resource(job.job_id, chosen,
                                         self._job_alloc(job)):
            # only a mid-cycle reduce event can get here; undo admission
            self.licenses.free(job.spec.licenses or {})
            self._free_run_limits(job)
            job.node_ids = []
            job.task_layout = []
            job.alloc_cache = None
            self._set_reason(job, PendingReason.RESOURCE)
            return False
        del self.pending[job.job_id]
        job.status = JobStatus.RUNNING
        job.start_time = now
        job.pending_reason = PendingReason.NONE
        self._init_steps(job, now)
        self.running[job.job_id] = job
        self._ledger_add(job, now)
        if self.wal is not None:
            self.wal.job_started(job)
        if self.jobtrace is not None:
            self.jobtrace.stamp(job.job_id, job.requeue_count, "placed",
                                now, epoch=self.fencing_epoch)
        self._trigger_dep_event(job)
        # onto the ring: the push goes out post-lock, after the cycle's
        # WAL group (holding this start record) is durable
        self._queue_dispatch(job, chosen)
        return True

    def _drain_deferred_evictions(self, now: float) -> None:
        """Fire timed-preemption evictions whose start bucket arrived.

        Entries are claims, not commitments: each cycle's solve rewrites
        the due time, and a claim is void the moment its preemptor left
        the pending queue (placed, cancelled, held) or the victim ended
        on its own — void entries are dropped without killing anything.
        Not WAL-persisted: after a failover the promoted leader's first
        solve re-derives the same claims from the same pending state."""
        if not self._deferred_evictions:
            return
        for victim_id in list(self._deferred_evictions):
            due, preemptor_id = self._deferred_evictions[victim_id]
            preemptor = self.pending.get(preemptor_id)
            if (preemptor is None or preemptor.held
                    or victim_id not in self.running):
                del self._deferred_evictions[victim_id]
                continue
            if due <= now:
                del self._deferred_evictions[victim_id]
                self._evict(victim_id, now)

    def _evict(self, victim_id: int, now: float) -> None:
        """Evict a running job for a preemptor: kill its steps, free its
        resources, then requeue or cancel per PreemptMode."""
        victim = self.running.get(victim_id)
        if victim is None:
            return
        _MET_PREEMPTED.inc()
        self._cur_trace["preempted"] = (
            self._cur_trace.get("preempted", 0) + 1)
        self.events.emit("preemption", "warning", job_id=victim_id,
                         detail="mode=%s" % self.config.preempt_mode,
                         time=now)
        if victim.spec.alloc_only:
            self.dispatch_free_alloc(victim_id, now,
                                     incarnation=victim.requeue_count)
        else:
            self.dispatch_terminate(victim_id, now,
                                    incarnation=victim.requeue_count)
        self._release_job_resources(victim)
        del self.running[victim_id]
        self._cancel_kill_sent.pop(victim_id, None)
        if victim.cancel_requested:
            # the user already cancelled this job (kill in flight); honor
            # the cancel instead of resurrecting it as PREEMPTED — same
            # contract as the on_craned_down path
            victim.status = JobStatus.CANCELLED
            victim.end_time = now
            victim.exit_code = 130
            self._finalize_terminal(victim)
            return
        if self.config.preempt_mode == "requeue":
            if self.jobtrace is not None:
                self.jobtrace.stamp(victim_id, victim.requeue_count,
                                    "requeue", now)
            victim.reset_for_requeue()
            victim.pending_reason = PendingReason.PREEMPTED
            if victim.requeue_count > self.config.max_requeue_count:
                # same cap as every other requeue path: held, operator
                # must release
                victim.held = True
                victim.pending_reason = PendingReason.HELD
            self.pending[victim_id] = victim
            if self.wal is not None:
                self.wal.job_requeued(victim)
        else:  # cancel
            victim.status = JobStatus.CANCELLED
            victim.end_time = now
            victim.exit_code = 143
            self._finalize_terminal(victim)

    def _check_craned_timeouts(self, now: float) -> None:
        """Ping-miss failure detection (reference ping FSM + CranedDown,
        SURVEY §3.5): real craneds that stopped pinging are declared dead
        and their jobs requeued."""
        for node in self.meta.nodes.values():
            if (node.alive and node.expect_pings
                    and now - node.last_ping > self.config.craned_timeout):
                self.on_craned_down(node.node_id, now)

    def _pending_candidates(self, now: float) -> _CycleJobs:
        """Candidate scan: one vectorized pass over the PendingTable
        (incremental mode), which hands on ROWS, or the legacy per-job
        Python walk, which hands on the jobs.  Both produce the
        identical candidates and pending_reason writes (oracle:
        tests/test_delta_cycle.py, tests/test_prelude_rows.py)."""
        pending = self.pending
        self._rows_gen = self._ptable.generation
        if not self.config.incremental:
            jobs = self._pending_candidates_rebuild(now)
            self._cur_trace["prelude_jobs_touched"] += len(jobs)
            return _CycleJobs(pending, jobs=jobs)
        pt = self._ptable
        lic_ok = pt.license_mask(self.licenses.sufficient)
        cand_rows, changed, gates = pt.candidates(now, lic_ok)
        # candidates never get a reason write here — the old loop left
        # stale reasons on runnable jobs too, and the solve/batch-cut
        # paths overwrite them downstream
        blocked = gates != GATE_CANDIDATE
        for jid, gate in zip(pt.job_id[changed[blocked]].tolist(),
                             gates[blocked].tolist()):
            job = pending.get(jid)
            if job is not None:
                job.pending_reason = _GATE_REASON[gate]
        self._cur_trace["prelude_jobs_touched"] += int(blocked.sum())
        return _CycleJobs(pending, cand_rows, pt.job_id[cand_rows])

    def _materialise(self, cycle_jobs: _CycleJobs) -> list[Job]:
        """The Job of every row, for a route that walks them all: paid
        once a cycle, counted in ``prelude_jobs_touched``.  Only while
        the prelude holds the lock (every id is pending then)."""
        if cycle_jobs.jobs is None:
            pending = self.pending
            cycle_jobs.jobs = [pending[j] for j in cycle_jobs.ids.tolist()]
            self._cur_trace["prelude_jobs_touched"] += len(cycle_jobs)
        return cycle_jobs.jobs

    def _stamp_eligible(self, candidates: _CycleJobs, now: float) -> None:
        """First-sight jobtrace "eligible" stamp, once an incarnation:
        the table remembers which rows had theirs (``eligible``), so a
        repeat cycle pays one vectorized compare, not a probe a job."""
        pt = self._ptable
        rows = candidates.rows
        if rows is None:
            rows = pt.rows_of(job.job_id for job in candidates.jobs)
        fresh = pt.job_id[pt.first_sight(rows)].tolist()
        if fresh:
            pending = self.pending
            self._cur_trace["prelude_jobs_touched"] += len(fresh)
            self.jobtrace.stamp_many(
                "eligible",
                [(jid, pending[jid].requeue_count) for jid in fresh], now)

    def _cut_batch(self, cut: _CycleJobs) -> None:
        """The ranked candidates past ``schedule_batch_size``, the
        lowest of the order, wait on "Priority".  With rows, the reason
        is written only where the stamp says the job carries another
        (as ``_commit`` does)."""
        if cut.rows is None:
            for job in cut.jobs:
                job.pending_reason = PendingReason.PRIORITY
            return
        pt = self._ptable
        told = pt.job_id[
            cut.rows[pt.stamped[cut.rows] != REASON_PRIORITY]].tolist()
        pending = self.pending
        for jid in told:
            pending[jid].pending_reason = PendingReason.PRIORITY
        pt.stamped[cut.rows] = REASON_PRIORITY
        self._cur_trace["prelude_jobs_touched"] += len(told)

    def _pending_candidates_rebuild(self, now: float) -> list[Job]:
        """Skip held / future-begin-time jobs (cpp:1374-1413); dependency
        gating joins here once dependencies land."""
        out = []
        for job in self.pending.values():  # id order == insertion order
            if job.spec.array is not None:
                continue  # templates never run; children materialize
            if job.held:
                job.pending_reason = PendingReason.HELD
                continue
            if job.spec.begin_time is not None and (
                    job.spec.begin_time > now):
                job.pending_reason = PendingReason.BEGIN_TIME
                continue
            dep_reason = self._deps_runnable(job, now)
            if dep_reason is not None:
                job.pending_reason = dep_reason
                continue
            if job.spec.licenses and not self.licenses.sufficient(
                    job.spec.licenses):
                # reference pre-checks licenses before NodeSelect
                # (CheckLicenseCountSufficient, cpp:6739) so a blocked
                # job never idles nodes the solver reserved for it
                job.pending_reason = PendingReason.LICENSE
                continue
            out.append(job)
        return out

    def _account_id(self, account: str) -> int:
        if account not in self._account_index:
            self._account_index[account] = len(self._account_index)
        return self._account_index[account]

    def _priority_row(self, job: Job) -> tuple:
        """``(qos, partition priority, node_num, total cpus, total mem,
        account index)``: what the priority model reads of one job,
        derived from the Job as ``_table_upsert`` derives a pending
        row's columns."""
        spec = job.spec
        req = self._job_row(job)[0]   # spec-cached encode
        part = self.meta.partitions.get(spec.partition)
        return (job.qos_priority,
                part.priority if part is not None else 0,
                spec.node_num,
                float(req[DIM_CPU]) / 256.0 * spec.node_num,
                float(req[DIM_MEM]) * spec.node_num,
                self._account_id(spec.account))

    def _running_put(self, job: Job) -> None:
        """Write a running job's RunningTable row: once a start (the
        ``running`` dict's set hook), since nothing in it changes while
        the job runs."""
        self._rtable.put(
            job.job_id, *self._priority_row(job),
            job.start_time if job.start_time is not None else np.inf)

    def _priority_sort(self, candidates: _CycleJobs, now: float
                       ) -> _CycleJobs:
        if self.config.priority_type == "basic" or not candidates:
            return candidates  # FIFO: id order (JobScheduler.h:183-201)
        import time as _time

        # vectorized path: gather priority attrs straight from the
        # PendingTable columns (O(1) numpy gathers) instead of touching
        # every Job object; priority output is invariant to the account
        # index permutation so upsert-time registration is parity-safe
        pt = self._ptable
        prows = candidates.rows
        vec = prows is not None
        if not vec:
            for job in candidates.jobs:
                self._account_id(job.spec.account)

        # running-set attrs: none of them change while a job RUNS (qos,
        # partition, shape and account are modify-refused for running
        # jobs; only run_time ages), so the cycle reads the columns the
        # running dict's hooks keep (RunningTable) and never walks the
        # jobs: Python derives rows here only to MAKE the table, on the
        # first cycle and on the first after rebuild_device_state
        # dropped it (run_walked).  Both routes read the same table.
        # The padded device copies stand until the membership moves.
        # A row's account registered as the row was written, which is
        # why this block precedes num_accounts
        t_run = _time.perf_counter()
        walked = 0
        rt = self._rtable
        if rt is None:
            rt = self._rtable = RunningTable(len(self.running))
            self._run_dev = None
            for job in self.running.values():
                self._running_put(job)
            walked = len(rt)
        nR = len(rt)
        RP = self._bucket(nR)
        if self._run_dev is None or self._run_dev[0] != rt.epoch:

            def rcol(name, dt):
                arr = np.zeros(RP, dt)
                arr[:nR] = rt.column(name)
                return jnp.asarray(arr)

            self._run_dev = (rt.epoch, dict(
                qos_prio=rcol("qos", np.int32),
                part_prio=rcol("part", np.int32),
                node_num=rcol("nnum", np.int32),
                cpus=rcol("cpus", np.float32),
                mem=rcol("mem", np.float32),
                account=rcol("acct", np.int32),
                valid=jnp.asarray(np.arange(RP) < nR)))
        # start == +inf encodes "not started yet": it clamps to 0, as
        # the per-job `now - (start or now)` did
        run_time = np.zeros(RP, np.int32)
        run_time[:nR] = np.maximum(now - rt.column("start"), 0.0)
        running = RunningPriorityAttrs(
            run_time=jnp.asarray(run_time), **self._run_dev[1])
        self._cur_trace.update(
            run_walked=walked,
            run_cols_ms=round((_time.perf_counter() - t_run) * 1e3, 3))
        # bucketed: num_accounts is a jit static arg, and the dense index
        # grows monotonically — pad so new accounts rarely recompile
        num_accounts = self._bucket(len(self._account_index))

        # pad both batches to bucketed shapes (same rationale as
        # _build_batch: keep the jit cache small)
        JP = self._job_bucket(len(candidates))

        p_valid = np.zeros(JP, bool)
        p_valid[: len(candidates)] = True
        if vec:
            kN = len(candidates)

            def pcol(src, dt):
                arr = np.zeros(JP, dt)
                arr[:kN] = src[prows]
                return jnp.asarray(arr)

            age = np.zeros(JP, np.int32)
            age[:kN] = np.maximum(now - pt.submit[prows], 0.0)
            pending = PendingPriorityAttrs(
                age=jnp.asarray(age),
                qos_prio=pcol(pt.qos, np.int32),
                part_prio=pcol(pt.part, np.int32),
                node_num=pcol(pt.nnum, np.int32),
                cpus=pcol(pt.cpus, np.float32),
                mem=pcol(pt.mem, np.float32),
                account=pcol(pt.acct, np.int32),
                valid=jnp.asarray(p_valid))
        else:
            def col(rows, k, dt, size):
                arr = np.zeros(size, dt)
                arr[: len(rows)] = [r[k] for r in rows]
                return jnp.asarray(arr)

            p_rows = [self._priority_row(j) for j in candidates.jobs]
            age = np.zeros(JP, np.int32)
            age[: len(candidates)] = [max(now - j.submit_time, 0.0)
                                      for j in candidates.jobs]
            pending = PendingPriorityAttrs(
                age=jnp.asarray(age),
                qos_prio=col(p_rows, 0, np.int32, JP),
                part_prio=col(p_rows, 1, np.int32, JP),
                node_num=col(p_rows, 2, np.int32, JP),
                cpus=col(p_rows, 3, np.float32, JP),
                mem=col(p_rows, 4, np.float32, JP),
                account=col(p_rows, 5, np.int32, JP),
                valid=jnp.asarray(p_valid))

        extra_service = None
        if self.global_usage is not None:
            remote = self.global_usage.remote_account_jobs()
            if remote:
                # cluster-wide fair-share: remote running-job counts per
                # account feed the service sum (fed/usage.py); accounts
                # the gossip names but this shard has never seen get no
                # dense index yet — they have no local jobs to sort, so
                # their remote burn cannot change this shard's order
                es = np.zeros(num_accounts, np.float32)
                for acct, jobs in remote.items():
                    idx = self._account_index.get(acct)
                    if idx is not None and idx < num_accounts:
                        es[idx] = float(jobs)
                if es.any():
                    extra_service = jnp.asarray(es)

        pri = np.asarray(multifactor_priority(
            pending, running, self.config.priority_weights, num_accounts,
            extra_service=extra_service))
        order = np.asarray(priority_order(jnp.asarray(pri)))
        order = order[order < len(candidates)]  # drop -inf padding rows
        if not vec:
            # the oracle writes it on every Job too: what the rows
            # route's scatter is compared with (test_prelude_rows)
            for job, p in zip(candidates.jobs, pri):
                job.priority = float(p)
            prows = pt.rows_of(job.job_id for job in candidates.jobs)
        # a pending job's priority lives in its row (job_priority): one
        # scatter, not a write on every Job
        pt.priority[prows] = pri[:len(candidates)]
        return candidates[order]

    @staticmethod
    def _bucket(n: int, floor: int = 16) -> int:
        """Pad counts to the next power of two so the jitted solve sees a
        small set of static shapes (a fresh XLA compile per distinct J
        would dominate every cycle)."""
        b = floor
        while b < n:
            b *= 2
        return b

    @staticmethod
    def _job_bucket(n: int) -> int:
        """The row count a cycle's candidates are padded to: 256, then
        1,024, then by twos.  A step of this ladder is a compile of the
        priority model and of the solve on the cycle thread, with batch
        ingest held at the door: 15 s cold at 5,000 nodes, 3 s from a
        warm cache, and nothing drains or starts meanwhile.  A closed
        loop of 250-spec batches stands at 500 candidates; a period
        half as long again (the snapshot's hold, once a minute) leaves
        750, and the cycle that runs between two 32-spec chunks of the
        stream's LAST batch leaves 26 to 218 behind.  By twos from 16
        each of those was a step first met minutes into a run or at
        its very end, and met cold it outlasted the 5 s a job that
        fits may wait.  So every cycle under 257 candidates is one
        program and every cycle under 1,025 another, both compiled
        with the first two batches; the price is the scan's 1,024
        steps where 500 jobs wait (ARCHITECTURE.md, the ladder of J)."""
        b = 256
        while b < n:
            b *= 4 if b < 1024 else 2
        return b

    def _mask_for(self, job: Job, now: float = 0.0) -> np.ndarray:
        if self._mask_cache_epoch != self.meta.resv_epoch:
            # reservation churn invalidates reservation-derived masks;
            # drop everything so stale epochs can't accumulate
            self._mask_cache.clear()
            self._mask_cache_epoch = self.meta.resv_epoch
        key = (job.spec.partition, tuple(job.spec.include_nodes),
               tuple(job.spec.exclude_nodes), len(self.meta.nodes),
               job.spec.reservation)
        mask = self._mask_cache.get(key)
        if mask is None:
            mask = self.meta.partition_mask(
                job.spec.partition, job.spec.include_nodes,
                job.spec.exclude_nodes)
            if job.spec.reservation:
                # reservation jobs run ONLY inside their carve-out
                # (reference: reservations are their own LocalScheduler
                # domain, JobScheduler.cpp:6624-6732)
                resv = self.meta.reservations.get(job.spec.reservation)
                rmask = np.zeros(len(self.meta.nodes), bool)
                if resv is not None:
                    for n in resv.node_ids:
                        rmask[n] = True
                mask = mask & rmask
            self._mask_cache[key] = mask
        if job.spec.reservation:
            resv = self.meta.reservations.get(job.spec.reservation)
            if resv is None or not resv.active(now):
                return np.zeros(len(self.meta.nodes), bool)
            return mask
        # non-reservation jobs must stay clear of any reservation whose
        # window overlaps this job's would-be runtime [now, now+limit]
        # (reference "Resource Reserved" check, cpp:6797-6810)
        if self.meta.reservations:
            mask = mask.copy()
            end = now + job.spec.time_limit
            for resv in self.meta.reservations.values():
                if now < resv.end_time and resv.start_time < end:
                    for n in resv.node_ids:
                        mask[n] = False
        return mask

    def _job_row(self, job: Job) -> tuple:
        """``(encoded req, node_num, time_limit)`` cached on the Job:
        modify_job REPLACES job.spec, so an ``is`` check on the cached
        spec invalidates exactly when the row could change.  Saves the
        per-cycle re-encode for every job that sits in the queue across
        many cycles (the common case at depth)."""
        cached = job.row_cache
        if cached is not None and cached[0] is job.spec:
            return cached[1]
        row = (job.spec.res.encode(self.meta.layout),
               int(job.spec.node_num), int(job.spec.time_limit))
        job.row_cache = (job.spec, row)
        return row

    def _class_key(self, job: Job, now: float) -> tuple:
        """Eligibility-class key: equal keys provably produce identical
        ``_mask_for`` rows within one resv_epoch, so the row is cacheable
        for the whole epoch.  The post-cache dynamic parts of _mask_for
        depend only on (a) the job's reservation being active at ``now``
        and (b) the set of reservations overlapping [now, now+limit] —
        both are folded into the key."""
        spec = job.spec
        base = (spec.partition, tuple(spec.include_nodes),
                tuple(spec.exclude_nodes), spec.reservation)
        if spec.reservation:
            resv = self.meta.reservations.get(spec.reservation)
            return base + (resv is not None and resv.active(now),)
        if not self.meta.reservations:
            return base
        end = now + spec.time_limit
        return base + (frozenset(
            name for name, r in self.meta.reservations.items()
            if now < r.end_time and r.start_time < end),)

    def _refresh_mask_table(self) -> None:
        """Same invalidation rule as ``_mask_cache`` (resv_epoch), plus a
        node-count guard (rows are [N]) and a size backstop: within one
        epoch the moving ``now`` can mint fresh overlap sets every cycle,
        and the table must not grow without bound.  Called ONCE per cycle
        (before the batch loop) — resetting mid-batch would orphan class
        ids already assigned to earlier jobs in the same batch."""
        table = self._mask_table
        if (table.epoch != self.meta.resv_epoch
                or table.num_nodes != len(self.meta.nodes)
                or len(table.rows) > 512):
            table.reset(self.meta.resv_epoch, len(self.meta.nodes))

    def _class_for(self, job: Job, now: float) -> int:
        return self._mask_table.class_for(
            self._class_key(job, now), lambda: self._mask_for(job, now))

    def _build_batch(self, ordered: _CycleJobs | list[Job],
                     num_nodes: int, now: float = 0.0
                     ) -> tuple[FactoredJobBatch, int]:
        if isinstance(ordered, list):
            ordered = _CycleJobs(self.pending, jobs=ordered)
        lay = self.meta.layout
        J = self._job_bucket(len(ordered))
        req = np.zeros((J, lay.num_dims), np.int32)
        node_num = np.zeros(J, np.int32)
        time_limit = np.zeros(J, np.int32)
        # padding rows keep class 0 — the table's permanent all-False
        # row — so a dense gather reproduces the old zero-padded mask
        job_class = np.zeros(J, np.int32)
        valid = np.zeros(J, bool)
        self._refresh_mask_table()
        orows = ordered.rows
        if orows is not None:
            pt = self._ptable
            kN = len(ordered)
            req[:kN] = pt.req[orows]
            node_num[:kN] = pt.nnum[orows]
            time_limit[:kN] = pt.tlimit[orows]
            valid[:kN] = True
            if self.meta.reservations:
                # reservation-scoped class keys depend on now — can't
                # cache per mask-table generation
                for i, job in enumerate(self._materialise(ordered)):
                    job_class[i] = self._class_for(job, now)
            else:
                gen = self._mask_table.generation
                stale = np.nonzero(pt.cls_gen[orows] != gen)[0]
                for i in stale.tolist():
                    r = int(orows[i])
                    pt.cls[r] = self._class_for(ordered.job(i), now)
                    pt.cls_gen[r] = gen
                job_class[:kN] = pt.cls[orows]
                self._cur_trace["prelude_jobs_touched"] += len(stale)
        else:
            for i, job in enumerate(ordered.jobs):
                req[i], node_num[i], time_limit[i] = self._job_row(job)
                job_class[i] = self._class_for(job, now)
                valid[i] = True
        max_nodes = max(1, min(int(node_num.max(initial=1)),
                               self.config.max_nodes_per_job))
        # bucket the static gang bound too (it is a jit static arg)
        max_nodes = self._bucket(max_nodes, floor=1)
        # max_nodes is the static bound of the cycle's solves: the width
        # of their [J, K] node lists, and the share of them the
        # candidates can fill (the head picks by one sort, the Pallas
        # tail runs only the passes a slot can use: tail_pass_pct)
        if len(ordered):
            self._cur_trace.update(
                gang_bound=max_nodes,
                gang_fill_pct=round(100.0 * int(node_num.sum())
                                    / (len(ordered) * max_nodes), 3))
        rows_np, table = self._mask_table.tables()
        batch = FactoredJobBatch(
            req=jnp.asarray(req), node_num=jnp.asarray(node_num),
            time_limit=jnp.asarray(time_limit),
            valid=jnp.asarray(valid), job_class=jnp.asarray(job_class),
            class_masks=table, job_class_np=job_class,
            class_rows_np=rows_np,
            node_class_np=self._mask_table.node_class())
        return batch, max_nodes

    def _commit(self, ordered: _CycleJobs, placements: Placements,
                now: float, start_buckets=None,
                tasks=None) -> list[int]:
        """Host authoritative commit + dispatch (cpp:1557-1839): re-check
        against the live ledger and the cycle's reduce events; jobs whose
        nodes died mid-cycle simply stay pending for the next cycle.

        With the time axis, ``start_buckets`` marks future-start jobs:
        they hold in-cycle reservations and surface the "Priority" reason
        (the reference's flow at cpp:6795-6835) — only bucket-0 starts
        dispatch.

        The commit scales with the rows a cycle PLACED or whose reason
        CHANGED, not with its candidates.  ``ordered.rows`` are the
        PendingTable rows of the solve's batch: an unplaced row whose
        job already carries the reason the solve returns for it
        (``stamped``) is not visited in Python at all, since a visit
        would rewrite the value already there or skip the row as void,
        and a row not visited has no Job looked up for it.  Every row
        that is visited goes through the whole body below.  Without a
        row map that can be trusted (the rebuild route has none; a
        compaction since the rows were taken moved them) every row is
        visited, by its id, and every stamp forgotten.  The stamps are
        written BEFORE the started jobs leave ``pending``: each removal
        can compact the table and move the rows.  ``commit_visited_pct``
        and ``commit_scan_ms`` (cycle trace) say what the pass cost.

        Admission checks that are pure array functions (placed/reason
        rows, the mid-cycle dirty-node flag) run as one vectorized
        pre-pass; the per-job loop keeps only what must stay per-job
        (pending membership, spec-epoch void, license/QoS takes with
        their undo ordering); the ledger commit goes through
        meta.malloc_resource_batch + _ledger_add_batch over the whole
        placed set; WAL ``start`` records land in the cycle's open
        group (one fsync for all); dispatch is QUEUED on the ring and
        issued post-lock, after the group's durability barrier."""
        import time as _time
        t_scan = _time.perf_counter()
        events = self.meta.stop_logging()
        dirty_nodes = {ev.node_id for ev in events}

        n = len(ordered)
        placed = np.asarray(placements.placed)
        reasons = np.asarray(placements.reason)
        # the node lists of the rows the solve placed (the head's
        # reservations among them), and of no other: an unplaced row's
        # list is all -1.  ``at[i]`` is row i's place in ``nodes_mat``
        placed_idx = np.flatnonzero(placed[:n])
        nodes_mat = _pull_rows(placements.nodes, placed_idx)
        at = {i: k for k, i in enumerate(placed_idx.tolist())}

        def nodes_of(i: int) -> list[int]:
            row = nodes_mat[at[i]]
            return row[row >= 0].tolist()

        # vectorized pre-pass: one gather flags every placement row
        # touching a node some mid-cycle event dirtied, replacing a
        # per-job set intersection
        dirty_row = None
        if dirty_nodes:
            size = max(len(self.meta.nodes), max(dirty_nodes) + 1)
            dirty_vec = np.zeros(size, dtype=bool)
            dirty_vec[list(dirty_nodes)] = True
            dirty_row = (dirty_vec[np.clip(nodes_mat, 0, size - 1)]
                         & (nodes_mat >= 0)).any(axis=1)
        started: list[int] = []
        admitted: list[Job] = []
        admitted_rows: list[int] = []
        # placement rows the SOLVER took on device but the host rejects
        # below: the device state subtracted resources the ledger never
        # allocated, and no host mutation will dirty those rows — feed
        # them to the resident state so it force-patches them next cycle
        rejected_rows: list[int] = []
        future_start: list[tuple[Job, list[int]]] = []
        pt = self._ptable
        rows = ordered.rows
        if rows is not None and self._rows_gen == pt.generation:
            took, why = placed[:n], reasons[:n]
            visit = np.flatnonzero(
                took | (why != pt.stamped[rows])).tolist()
            # an unplaced row is told ``why`` below (a row not visited
            # carries it already); a placed one starts, or is refused
            # with a reason of the host's
            pt.stamped[rows] = np.where(took, STAMP_NONE, why)
        else:
            rows = None
            visit = range(n)
            pt.stamped.fill(STAMP_NONE)
        since = self._plan_epoch
        for i in visit:
            job = ordered.job(i)
            if (job is None or job.job_id not in self.pending or job.held
                    or pt.written_since(job.job_id, since)):
                # canceled / finalized / held / modified (its row
                # written since the prelude) while the solve ran
                # outside the lock (cycle_phases): its placement is
                # void; resources were never committed so nothing to
                # undo.  The job stays pending for the next cycle,
                # which sees the new spec.
                if placed[i]:
                    rejected_rows.append(i)
                elif rows is not None:
                    # not told: the hold's or the modify's own reason
                    # stands on the job
                    pt.stamped[rows[i]] = STAMP_NONE
                continue
            if not placed[i]:
                job.pending_reason = _REASON_MAP.get(
                    int(reasons[i]), PendingReason.RESOURCE)
                continue
            if start_buckets is not None and start_buckets[i] > 0:
                # reference cpp:6797-6835: a future-start job reports
                # "Resource" when its chosen nodes lack free resources
                # right now, and "Priority" only when resources are free
                # but running would delay a higher-priority reservation.
                # The avail read must see this cycle's commits (the old
                # per-job loop interleaved it with earlier jobs'
                # mallocs), so it is DEFERRED until after the batch
                # malloc below.
                future_start.append((job, nodes_of(i)))
                continue
            if dirty_row is not None and dirty_row[at[i]]:
                job.pending_reason = PendingReason.RESOURCE
                rejected_rows.append(i)
                continue
            if job.spec.licenses and not self.licenses.malloc(
                    job.spec.licenses):
                job.pending_reason = PendingReason.LICENSE
                rejected_rows.append(i)
                continue
            if not self._malloc_run_limits(job):
                self.licenses.free(job.spec.licenses or {})
                job.pending_reason = PendingReason.QOS_LIMIT
                rejected_rows.append(i)
                continue
            job.node_ids = nodes_of(i)
            job.task_layout = ([int(t) for t, n in
                                zip(tasks[i], nodes_mat[at[i]]) if n >= 0]
                               if tasks is not None else [])
            admitted.append(job)
            admitted_rows.append(i)
        cur = self._cur_trace
        cur["_commit_visited"] = cur.get("_commit_visited", 0) + len(visit)
        cur["_commit_scan_s"] = (cur.get("_commit_scan_s", 0.0)
                                 + _time.perf_counter() - t_scan)
        # batched ledger commit: ONE meta call checks and subtracts the
        # whole placed set in admission order (each entry sees earlier
        # subtractions exactly as per-job malloc_resource calls would)
        oks = self.meta.malloc_resource_batch(
            [(job.job_id, job.node_ids, self._job_alloc(job))
             for job in admitted])
        started_jobs: list[Job] = []
        for job, row, ok in zip(admitted, admitted_rows, oks):
            if not ok:
                self.licenses.free(job.spec.licenses or {})
                self._free_run_limits(job)
                job.node_ids = []
                job.task_layout = []
                job.alloc_cache = None  # never reuse a failed
                                        # placement's per-node amounts
                job.pending_reason = PendingReason.RESOURCE
                rejected_rows.append(row)
                continue
            del self.pending[job.job_id]
            job.status = JobStatus.RUNNING
            job.start_time = now
            job.pending_reason = PendingReason.NONE
            self._init_steps(job, now)
            self.running[job.job_id] = job
            started_jobs.append(job)
            started.append(job.job_id)
        cur["_nodes_selected"] = (
            cur.get("_nodes_selected", 0)
            + sum(len(job.node_ids) for job in started_jobs)
            + sum(len(node_ids) for _, node_ids in future_start))
        for job, node_ids in future_start:
            req = job.spec.res.encode(self.meta.layout)
            fits_now = all(
                (req <= self.meta.nodes[n].avail).all()
                for n in node_ids) if node_ids else False
            job.pending_reason = (PendingReason.PRIORITY if fits_now
                                  else PendingReason.RESOURCE)
        if rejected_rows:
            bad = nodes_mat[[at[i] for i in rejected_rows]]
            self._resident.mark_diverged(np.unique(bad[bad >= 0]))
        self._ledger_add_batch(started_jobs, now)
        _MET_COMMIT_BATCH.observe(len(started_jobs))
        wal = self.wal
        trace = self.jobtrace
        for job in started_jobs:
            if wal is not None:
                wal.job_started(job)  # buffered into the cycle's group
            if trace is not None:
                trace.stamp(job.job_id, job.requeue_count, "placed",
                            now, epoch=self.fencing_epoch)
            self._trigger_dep_event(job)   # AFTER edges fire on start
            self._queue_dispatch(job, job.node_ids)
        return started

    # ------------------------------------------------------------------
    # recovery (reference JobScheduler::Init, JobScheduler.cpp:191-1091:
    # re-queue pending via RequeueRecoveredJobIntoPendingQueueLock_ :1120,
    # re-adopt running via PutRecoveredJobIntoRunningQueueLock_ :1139)
    # ------------------------------------------------------------------

    def recover(self, replayed: dict, now: float = 0.0) -> None:
        """Rebuild queues from a WAL replay (``WriteAheadLog.replay``).

        Classification is by the job's recorded *status*, not the event
        name, so any durable mutation (cancel intent, hold) recovers too:
        terminal → history; RUNNING → re-adopted WITH resources re-applied
        to the ledger (the craneds still run them — the reference
        reconciles with each craned at re-registration; the simulated
        plane re-dispatches); anything else → pending.
        """
        for job_id, (event, job) in sorted(replayed.items()):
            self._next_job_id = max(self._next_job_id, job_id + 1)
            if not job.status.is_terminal and (
                    self.account_meta is not None and job.qos_name
                    and job.array_parent_id is None):
                self.account_meta.restore_submit(
                    job.spec.user, job.spec.account, job.qos_name)
            if not job.status.is_terminal and (
                    self.global_usage is not None
                    and job.array_parent_id is None):
                # restore without re-checking: the slot was legitimately
                # admitted before the crash (fed/usage.py note_submit)
                self.global_usage.note_submit(job.spec.user,
                                              job.spec.account)
            if job.status.is_terminal:
                self.history[job_id] = job
                if self.archive is not None and job_id not in \
                        self.archive:
                    # a crash between finalize and the archive write:
                    # the WAL tombstone still has the record
                    self.archive.append(job)
            elif job.status == JobStatus.RUNNING:
                if self.meta.malloc_resource(job_id, job.node_ids,
                                             self._job_alloc(job)):
                    if not job.spec.alloc_only and 0 not in job.steps:
                        # WAL record predates the step model: re-create
                        # the implicit batch step so step-level reports
                        # from the still-running supervisors land
                        self._init_steps(job, job.start_time or now)
                    self.licenses.restore(job.spec.licenses or {})
                    if (self.account_meta is not None and job.qos_name):
                        self.account_meta.restore_run(
                            job.spec.user, job.spec.account, job.qos_name,
                            job.spec)
                        job.run_usage_taken = True
                    self.running[job_id] = job
                    self._ledger_add(job, now)
                    if job.cancel_requested:
                        # the kill may have been lost with the crash;
                        # re-send it (seeding the renewal map so the
                        # cycle keeps retrying until confirmed)
                        self._cancel_kill_sent[job_id] = now
                        self.dispatch_terminate(job_id, now)
                else:
                    # node vanished while we were down -> requeue, unless
                    # the user had already cancelled
                    if job.cancel_requested:
                        job.status = JobStatus.CANCELLED
                        job.end_time = now
                        self._finalize(job)  # frees the submit slot too
                        continue
                    job.reset_for_requeue()
                    self.pending[job_id] = job
            elif job.status == JobStatus.SUSPENDED:
                # suspended jobs hold their allocation across the crash
                if self.meta.malloc_resource(job_id, job.node_ids,
                                             self._job_alloc(job)):
                    self.licenses.restore(job.spec.licenses or {})
                    if (self.account_meta is not None and job.qos_name):
                        self.account_meta.restore_run(
                            job.spec.user, job.spec.account, job.qos_name,
                            job.spec)
                        job.run_usage_taken = True
                    self.running[job_id] = job
                    self._ledger_add(job, now)
                else:
                    job.reset_for_requeue()
                    self.pending[job_id] = job
            else:
                job.status = JobStatus.PENDING
                self.pending[job_id] = job
        if self.jobtrace is not None:
            # Seed timelines for every replayed job: synthetic spans
            # back-date the edges the WAL proves were passed, so the
            # lost/doubled ledger and cstats --job stay meaningful
            # across a failover.  Stamp-once makes this a no-op for
            # spans a promoted standby already holds.
            for job_id, (_event, job) in sorted(replayed.items()):
                self.jobtrace.seed_recovered(job, now)
        # re-derive waiting edges against the CURRENT state of each
        # dependee (events that fired between the WAL snapshot and the
        # crash would otherwise be lost forever), then rebuild the
        # dependents map for edges still waiting
        for job in self.pending.values():
            for dep in job.spec.dependencies:
                if job.dep_state.get(dep.job_id) is not None:
                    continue
                target = self.job_info(dep.job_id)
                if target is None:
                    job.dep_state[dep.job_id] = DEP_NEVER
                    continue
                sat = self._dep_satisfied_time(dep, target)
                job.dep_state[dep.job_id] = sat
                if sat is None:
                    self._dependents.setdefault(dep.job_id, set()).add(
                        job.job_id)
        # the table rows written as jobs were inserted above predate the
        # dep re-derivation; re-upsert so dep columns match dep_state
        for job in self.pending.values():
            self._table_upsert(job)

    def rebuild_device_state(self) -> None:
        """Promotion-time rebuild of device-resident scheduler state.

        A standby's shadow apply only touches the job dicts; after
        ``recover()`` re-adopts the replicated state, the accelerator-
        side caches must be rebuilt from scratch before the first cycle:
        the ``_MaskTable`` [C, N] class-row table (its rows were computed
        against the OLD leader's device buffers), every per-job row/alloc
        cache, and the dense mask cache.  The run-ledger rows were
        re-added by ``recover``; timed-state buckets and the grid
        re-derive on the first cycle from the refreshed caches."""
        self._mask_table = _MaskTable()
        self._mask_cache.clear()
        self._mask_cache_epoch = -1
        self._mesh = None
        for col in (self.pending, self.running):
            for job in col.values():
                job.row_cache = None
                job.alloc_cache = None
        # caches are cleared FIRST so _table_upsert re-encodes rows
        # against the fresh layout; the incremental caches themselves
        # restart cold (the old leader's epochs mean nothing here)
        for job in self.pending.values():
            job.priority = self.job_priority(job)   # seeds the new row
        self._ptable = PendingTable(self.meta.layout.num_dims)
        for job in self.pending.values():
            self._table_upsert(job)
        self.meta._snap = None
        self._noop_fp = None
        self._rows_gen = -1
        # rows encoded against the old layout: the next cycle's
        # _priority_sort makes the table again from self.running
        self._rtable = None
        # the resident ClusterState mirrors the OLD leader's ledger —
        # drop it; the first cycle pays one full rebuild
        self._resident.invalidate()

    def job_info(self, job_id: int) -> Job | None:
        return (self.pending.get(job_id) or self.running.get(job_id)
                or self.history.get(job_id))

    def queue(self) -> list[Job]:
        return list(self.pending.values()) + list(self.running.values())
