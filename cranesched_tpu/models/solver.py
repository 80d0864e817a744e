"""The per-cycle scheduling solve as a jit-compiled JAX function.

This is the TPU-native replacement for the reference's C++ NodeSelect loop
(reference: src/CraneCtld/JobScheduler.cpp:6507-6836 and
LocalScheduler::GetNodesAndTrySchedule_ at :6147-6369): for each pending job
in priority order, find the ``node_num`` cheapest alive nodes (by the
MinCpuTimeRatioFirst cost policy, JobScheduler.h:40-54) on which the job's
per-node requirement fits *right now*, allocate, and update node costs.

Design (TPU-first, not a translation):

* Cluster state is a dense SoA: ``avail[N, R]`` int32 resource vectors,
  ``total[N, R]``, boolean masks, and an int32 ``cost[N]`` ledger.  The
  reference's cost-ordered ``std::set`` + per-node object scan becomes a
  masked top-k over the cost vector — one vectorized op instead of an
  O(nodes) pointer walk.
* The inherently sequential greedy loop (each placement mutates
  availability) is a ``lax.scan`` over the priority-ordered job batch.  Each
  scan step is O(N*R) vector work that XLA fuses; there is no data-dependent
  control flow.  This scan is the semantics-defining reference path: the
  Pallas kernels (models/pallas_solver.py), the native solver and the
  sharded solves are each asserted bit-identical to it.
* Selection semantics match the reference: nodes are considered in ascending
  cost order and the first ``node_num`` nodes whose *current* availability
  fits the per-node requirement are taken (GetNodesAndTrySchedule_ iterates
  GetOrderedNodesSet and breaks once node_num feasible nodes are found).
  Ties in cost resolve to the lowest node index (the reference's tie order —
  pointer value in a std::set — is unspecified; we pin it down).
* A job that cannot be placed leaves state untouched and is reported
  unplaced with a pending-reason code (resource vs partition/constraint),
  mirroring the reason strings of NodeSelect.

Not yet in this v0 model (tracked for later rounds, see SURVEY.md §7 build
order): the time axis (backfill / earliest-start), preemption, reservations,
multi-task-per-node packing (ntasks_per_node > 1), exclusive nodes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import struct

from cranesched_tpu.obs.introspect import instrument_jit
from cranesched_tpu.ops.resources import DIM_CPU

# The node-cost ledger is int32 fixed point: unit = 1/COST_SCALE
# cpu-seconds.  Integer addition is associative, so ANY grouping of cost
# updates — sequential scan, blocked prefix sums, sharded scatters —
# yields bit-identical ledgers (the property solve_blocked's parallel
# reconstruction relies on), with none of float32's 2^24 exactness cliff.
# Resolution: a 60 s / 1-cpu job on a 128-cpu node still contributes
# round(60*16/128) = 8 units, so small placements keep moving nodes off
# the cost frontier (load spreading preserved).  Headroom: a max job
# (86400 s, full node) is 1.4M units; int32 holds >1500 of those per
# node per cycle — beyond the reference's own per-node job cap (1000,
# JobScheduler.h:269).
COST_SCALE = 16
COST_INF = 2**31 - 1  # "infeasible" sentinel cost (int32 max; a plain
                      # Python int so importing this module never
                      # initializes a JAX backend)


def quantized_dcost(time_limit, req_cpu, cpu_total_f32):
    """int32 MinCpuTimeRatioFirst increment:
    round(seconds * cpu/cpu_total * COST_SCALE)
    (reference JobScheduler.h:40-54 uses double; we pin fixed point)."""
    return jnp.round(time_limit.astype(jnp.float32)
                     * req_cpu.astype(jnp.float32) * COST_SCALE
                     / cpu_total_f32).astype(jnp.int32)


def normalize_cost_ledger(cost, n: int):
    """Coerce a cost seed into the int32 ledger.  Float inputs (ledger
    units) are rounded; integer inputs must NOT round-trip through float32
    (that would reintroduce the 2^24 exactness cliff for large seeds)."""
    if cost is None:
        return jnp.zeros(n, jnp.int32)
    cost = jnp.asarray(cost)
    if jnp.issubdtype(cost.dtype, jnp.floating):
        cost = jnp.round(cost.astype(jnp.float32))
    return cost.astype(jnp.int32)


def cheapest_k(masked_cost, k: int):
    """The k smallest entries of an int32 cost vector, ascending, ties to
    the lowest index.  Returns (values, indices).

    ONE stable sort of (cost, index), whatever k is, so that a
    placement step's cost does not follow the static gang bound.  k
    argmin passes, each masking its winner, did, and unrolled k deep:
    on a v5e at 10k nodes the backfill head's 1,024 steps took 160.3 /
    185.2 ms at k = 2 / 8 and take 159.3 / 159.3 / 162.8 ms at k = 2 /
    8 / 64 (PERF.md, PR 35).  Stable over an ascending index is the tie
    order argmin's first occurrence gave."""
    n = masked_cost.shape[0]
    vals, idxs = jax.lax.sort(
        (masked_cost, jnp.arange(n, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    return vals[:k], idxs[:k]


# Pending-reason codes (subset of the reference's pending reasons,
# docs/en/reference/pending_reason.md).
REASON_NONE = 0  # placed
REASON_RESOURCE = 1  # feasible nodes exist but not enough free now
REASON_CONSTRAINT = 2  # partition/include/exclude/alive masks rule nodes out
REASON_PRIORITY = 3  # cut off by batch limit (set host-side)
REASON_HELD = 4  # held / dependency / begin-time (set host-side)


@struct.dataclass
class ClusterState:
    """Device-resident cluster snapshot for one scheduling cycle.

    avail:  int32[N, R]  free resources per node (resource-vector encoding)
    total:  int32[N, R]  total resources per node
    alive:  bool[N]      node is up and not drained
    cost:   int32[N]     MinCpuTimeRatioFirst running cost per node in
                         1/COST_SCALE cpu-second units (sum over
                         allocations of duration * cpu/cpu_total;
                         reference JobScheduler.h:40-54, NodeRater
                         h:499-516)
    """

    avail: jax.Array
    total: jax.Array
    alive: jax.Array
    cost: jax.Array

    @property
    def num_nodes(self) -> int:
        return self.avail.shape[0]

    @property
    def num_dims(self) -> int:
        return self.avail.shape[1]


@struct.dataclass
class JobBatch:
    """Priority-ordered pending jobs for one cycle (SoA, padded to J).

    req:        int32[J, R] per-node resource requirement
                (node_res + task_res * ntasks_per_node; reference
                ``min_res_view`` at JobScheduler.cpp:6153)
    node_num:   int32[J]    gang size (nodes required simultaneously)
    time_limit: int32[J]    seconds; drives the cost update
    part_mask:  bool[J, N]  per-job node eligibility (partition membership
                            AND include/exclude nodelists, precomputed
                            host-side as bitmasks)
    valid:      bool[J]     padding mask (False rows are no-ops)
    """

    req: jax.Array
    node_num: jax.Array
    time_limit: jax.Array
    part_mask: jax.Array
    valid: jax.Array

    @property
    def num_jobs(self) -> int:
        return self.req.shape[0]


class FactoredJobBatch:
    """A job batch whose eligibility is FACTORED: a per-job class id into
    a small device-resident ``class_masks[C, N]`` table instead of the
    dense ``part_mask[J, N]`` matrix.

    At the north-star shape (100k jobs x 10k nodes) the dense matrix is a
    1 GB bool rebuilt row-by-row on the host and re-transferred every
    cycle; the factored form ships ``job_class[J]`` (400 KB) per cycle
    plus the [C, N] table only when a row actually changed
    (reservation/partition churn — see JobScheduler._mask_table).  Dense
    consumers (the scan/backfill solvers) gather ``class_masks[job_class]``
    ON DEVICE via :meth:`dense`, so the host never materializes [J, N].

    Not a pytree on purpose: the host-side mirrors (``job_class_np``,
    ``class_rows_np``, ``node_class_np``) ride along for the native C++
    solver and the stream planner, and must not be traced.
    """

    def __init__(self, req, node_num, time_limit, valid, job_class,
                 class_masks, job_class_np, class_rows_np,
                 node_class_np=None):
        self.req = req                    # int32[J, R] (device)
        self.node_num = node_num          # int32[J]
        self.time_limit = time_limit      # int32[J]
        self.valid = valid                # bool[J]
        self.job_class = job_class        # int32[J] (device)
        self.class_masks = class_masks    # bool[C, N] (device table)
        self.job_class_np = job_class_np  # int32[J] host mirror
        self.class_rows_np = class_rows_np  # bool[C0, N] host rows
        self.node_class_np = node_class_np  # int32[N] iff rows disjoint
        self._dense: JobBatch | None = None

    @property
    def num_jobs(self) -> int:
        return self.req.shape[0]

    @property
    def dense(self) -> "JobBatch":
        """Dense JobBatch with ``part_mask`` gathered on device (cached)."""
        if self._dense is None:
            self._dense = JobBatch(
                req=self.req, node_num=self.node_num,
                time_limit=self.time_limit,
                part_mask=self.class_masks[self.job_class],
                valid=self.valid)
        return self._dense

    def dense_mask_np(self):
        """Host-side dense mask (numpy gather) for host solvers that
        need rows but can't use the factored form."""
        import numpy as np
        return np.asarray(self.class_rows_np)[self.job_class_np]

    def with_valid(self, valid) -> "FactoredJobBatch":
        """Same batch with a replaced validity mask (shares the tables)."""
        return FactoredJobBatch(
            req=self.req, node_num=self.node_num,
            time_limit=self.time_limit, valid=valid,
            job_class=self.job_class, class_masks=self.class_masks,
            job_class_np=self.job_class_np,
            class_rows_np=self.class_rows_np,
            node_class_np=self.node_class_np)


@struct.dataclass
class Placements:
    """Solve output, aligned with the input job order.

    placed: bool[J]
    nodes:  int32[J, K] chosen node indices, -1 padded (K = max gang size)
    reason: int32[J]    REASON_* for unplaced jobs
    passes: int32[2]    the Pallas kernels only: the selection passes
                        the solve ran, and the slots x K that the static
                        bound allows (models/pallas_solver.py)
    """

    placed: jax.Array
    nodes: jax.Array
    reason: jax.Array
    passes: jax.Array | None = None


def make_cluster_state(avail, total, alive, cost=None) -> ClusterState:
    avail = jnp.asarray(avail, jnp.int32)
    total = jnp.asarray(total, jnp.int32)
    alive = jnp.asarray(alive, bool)
    cost = normalize_cost_ledger(cost, avail.shape[0])
    return ClusterState(avail=avail, total=total, alive=alive, cost=cost)


@functools.partial(jax.jit, donate_argnums=(0,))
def _patch_cluster_state(state: ClusterState, dirty_idx, avail_rows,
                         total_rows, alive_rows, cost) -> ClusterState:
    return state.replace(
        avail=state.avail.at[dirty_idx].set(avail_rows, mode="drop"),
        total=state.total.at[dirty_idx].set(total_rows, mode="drop"),
        alive=state.alive.at[dirty_idx].set(alive_rows, mode="drop"),
        cost=cost)


_patch_cluster_state = instrument_jit("patch_cluster_state",
                                      _patch_cluster_state)


def patch_cluster_state(state: ClusterState, dirty_idx, avail_rows,
                        total_rows, alive_rows, cost) -> ClusterState:
    """Scatter-patch a device-resident ClusterState in place: overwrite
    rows ``dirty_idx`` of avail/total/alive with the host's current
    values and replace the whole cost ledger (the cost seed is
    time-dependent — it changes for EVERY node every cycle — so it
    ships full as [N] int32; the [N, R] tensors ship only dirty rows).

    The input state's buffers are DONATED: on TPU the scatter rewrites
    them in place and the caller must never touch ``state`` again
    (ctld/resident.py owns that discipline).  ``dirty_idx`` may be
    padded with out-of-range indices (>= N) — ``mode="drop"`` discards
    them — so callers can bucket the dirty-row count to a small set of
    static shapes without a mask argument."""
    cost = normalize_cost_ledger(cost, state.num_nodes)
    return _patch_cluster_state(
        state, jnp.asarray(dirty_idx, jnp.int32),
        jnp.asarray(avail_rows, jnp.int32),
        jnp.asarray(total_rows, jnp.int32),
        jnp.asarray(alive_rows, bool), cost)


@functools.partial(jax.jit, donate_argnums=(0,))
def _refresh_cost(state: ClusterState, cost) -> ClusterState:
    return state.replace(cost=cost)


_refresh_cost = instrument_jit("refresh_cost", _refresh_cost)


def refresh_cost_ledger(state: ClusterState, cost) -> ClusterState:
    """The empty-delta fast path of patch_cluster_state: no rows moved,
    so only the time-dependent [N] cost ledger ships.  Same donation
    contract — never touch the input ``state`` again."""
    return _refresh_cost(state, normalize_cost_ledger(cost, state.num_nodes))


def job_feasibility(avail, alive, part_mask, req):
    """eligible/feasible node masks for one job against one (shard of the)
    cluster — the per-job predicate both solver paths share."""
    eligible = alive & part_mask
    fits_now = jnp.all(req[None, :] <= avail, axis=-1)
    return eligible, eligible & fits_now


def decide_job(valid, node_num, max_nodes, num_feasible, num_eligible):
    """Admission decision + pending reason from the (global) counts.

    node_num > max_nodes violates the static gang bound; refuse rather than
    silently allocating a partial gang.  Reason: constraint for invalid
    jobs or when eligibility alone rules the job out; resource when enough
    eligible nodes exist but are busy (mirrors the reason strings of
    NodeSelect).
    """
    ok = (valid & (node_num > 0) & (node_num <= max_nodes)
          & (num_feasible >= node_num))
    bad = (~valid) | (node_num <= 0)
    any_could_ever = num_eligible >= node_num
    reason = jnp.where(
        ok, REASON_NONE,
        jnp.where(bad | ~any_could_ever, REASON_CONSTRAINT, REASON_RESOURCE))
    return ok, reason


def apply_placement(avail, cost, total, req, time_limit, scatter_idx,
                    apply_mask):
    """Subtract ``req`` from rows ``scatter_idx`` where ``apply_mask`` and
    apply the MinCpuTimeRatioFirst cost update
    (cost += seconds * cpu_alloc / cpu_total; reference JobScheduler.h:40-54).

    Rows with apply_mask False must carry an out-of-range ``scatter_idx``
    OR a zero delta; both paths pass mode="drop"-safe indices.
    """
    local_n = avail.shape[0]
    delta = jnp.where(apply_mask[:, None], req[None, :], 0)
    avail = avail.at[scatter_idx].add(-delta, mode="drop")

    cpu_total = jnp.maximum(total[:, DIM_CPU], 1).astype(jnp.float32)
    safe = jnp.clip(scatter_idx, 0, local_n - 1)
    dcost = quantized_dcost(time_limit, req[DIM_CPU], cpu_total[safe])
    cost = cost.at[scatter_idx].add(
        jnp.where(apply_mask, dcost, 0), mode="drop")
    return avail, cost


def _place_one(avail, cost, state_total, state_alive, req, node_num,
               time_limit, part_mask, valid, max_nodes: int):
    """Try to place one job; returns updated (avail, cost) and the decision."""
    eligible, feasible = job_feasibility(avail, state_alive, part_mask, req)
    ok, reason = decide_job(valid, node_num, max_nodes,
                            jnp.sum(feasible, dtype=jnp.int32),
                            jnp.sum(eligible, dtype=jnp.int32))

    # "First node_num feasible nodes in ascending cost order": mask
    # infeasible nodes to the sentinel and take the k smallest; ties go
    # to the lowest index.
    masked_cost = jnp.where(feasible, cost, COST_INF)
    sel_cost, idx = cheapest_k(masked_cost, max_nodes)
    k_mask = jnp.arange(max_nodes) < node_num
    sel = ok & k_mask & (sel_cost < COST_INF)

    avail, cost = apply_placement(avail, cost, state_total, req, time_limit,
                                  idx, sel)
    chosen = jnp.where(sel, idx, -1)
    return avail, cost, ok, chosen, reason


@functools.partial(jax.jit, static_argnames=("max_nodes",))
def solve_greedy(state: ClusterState, jobs: JobBatch,
                 max_nodes: int = 1) -> tuple[Placements, ClusterState]:
    """Greedy in-priority-order placement via lax.scan (reference path).

    jobs must already be in descending priority order (see models/priority.py
    for the multifactor sort).  ``max_nodes`` is the static bound on gang
    size for this batch; jobs with node_num > max_nodes are refused — with
    REASON_RESOURCE when enough eligible nodes exist (the gang merely exceeds
    this batch's static bound) and REASON_CONSTRAINT when eligibility alone
    rules the job out.
    """
    max_nodes = min(max_nodes, state.num_nodes)

    def step(carry, job):
        avail, cost = carry
        req, node_num, time_limit, part_mask, valid = job
        avail, cost, ok, chosen, reason = _place_one(
            avail, cost, state.total, state.alive, req, node_num,
            time_limit, part_mask, valid, max_nodes)
        return (avail, cost), (ok, chosen, reason)

    (avail, cost), (placed, nodes, reason) = jax.lax.scan(
        step, (state.avail, state.cost),
        (jobs.req, jobs.node_num, jobs.time_limit, jobs.part_mask,
         jobs.valid))

    new_state = state.replace(avail=avail, cost=cost)
    return Placements(placed=placed, nodes=nodes, reason=reason), new_state


solve_greedy = instrument_jit("solve_greedy", solve_greedy)


# Donating twin of solve_greedy for the device-resident cycle pipeline:
# the input ClusterState's buffers are donated so XLA writes avail/cost
# updates into them in place (zero-copy across cycle iterations on TPU;
# CPU ignores donation).  After calling this the input state is dead —
# ctld/resident.py enforces that by surrendering ownership on acquire()
# and re-adopting only the returned state.
_solve_greedy_donating = instrument_jit(
    "solve_greedy_donating",
    functools.partial(
        jax.jit, static_argnames=("max_nodes",),
        donate_argnums=(0,))(solve_greedy.__wrapped__))


def solve_greedy_donating(state: ClusterState, jobs: JobBatch,
                          max_nodes: int = 1
                          ) -> tuple[Placements, ClusterState]:
    """solve_greedy with ``state`` donated; never reuse the input state."""
    return _solve_greedy_donating(state, jobs, max_nodes=max_nodes)
