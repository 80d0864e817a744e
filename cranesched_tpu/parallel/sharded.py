"""Node-axis-sharded greedy solve: the multi-chip scheduling path.

The reference scales by throwing one big C++ process at the problem (the
cost-ordered node set walk in LocalScheduler::GetNodesAndTrySchedule_,
src/CraneCtld/JobScheduler.cpp:6147-6369, is strictly single-threaded per
scheduling domain).  The TPU-native design instead shards the *node axis*
of every cluster tensor across the device mesh (SURVEY.md §7), so a
100k-node cluster's state lives in D chips' HBM and each placement step is:

1. each shard computes feasibility + masked cost for its own nodes
   (pure local vector work, no communication);
2. each shard proposes its k cheapest feasible nodes (``lax.top_k``);
3. one ``all_gather`` over ICI merges the D*k candidates; every shard
   deterministically selects the same global k winners (ascending cost,
   ties to the lowest global node index — candidates arrive shard-major
   and within-shard ascending, so a stable argsort preserves that order);
4. each shard applies the resource subtraction for the winners it owns
   (scatter with OOB-drop — no communication).

Feasible/eligible *counts* (for the "can this gang ever fit" decision and
the pending-reason) are global ``psum`` reductions.

This mirrors how the per-cycle solve distributes: jobs stay replicated
(the greedy order is inherently sequential), nodes are the long axis.
The collectives per job are O(D * max_nodes) bytes — tiny — so the ICI
cost is latency-bound and amortized by XLA pipelining across scan steps.

Parity contract: bit-identical placements to ``models.solver.solve_greedy``
(asserted in tests/test_sharded_parity.py on an 8-device CPU mesh).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cranesched_tpu.models.solver import (
    COST_INF,
    ClusterState,
    JobBatch,
    Placements,
    apply_placement,
    cheapest_k,
    decide_job,
    job_feasibility,
)
from cranesched_tpu.obs.introspect import instrument_jit as _instrument_jit

NODE_AXIS = "nodes"


def make_node_mesh(devices=None) -> Mesh:
    """1-D device mesh over which the node axis is sharded."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (NODE_AXIS,))


def shard_cluster_state(state: ClusterState, mesh: Mesh) -> ClusterState:
    """Place the cluster tensors with the node axis sharded over the mesh."""
    row = NamedSharding(mesh, P(NODE_AXIS))
    mat = NamedSharding(mesh, P(NODE_AXIS, None))
    return ClusterState(
        avail=jax.device_put(state.avail, mat),
        total=jax.device_put(state.total, mat),
        alive=jax.device_put(state.alive, row),
        cost=jax.device_put(state.cost, row),
    )


def _place_one_shard(avail, cost, total, alive, req, node_num, time_limit,
                     part_mask, valid, max_nodes: int):
    """One placement step on one node shard (runs under shard_map).

    The per-job math (feasibility, admission decision, resource/cost
    update) is shared with the single-device solver — only the counts
    (psum) and the candidate merge (all_gather) are collective here.
    """
    local_n = avail.shape[0]
    shard = jax.lax.axis_index(NODE_AXIS)
    offset = shard * local_n

    eligible, feasible = job_feasibility(avail, alive, part_mask, req)
    num_feasible = jax.lax.psum(
        jnp.sum(feasible, dtype=jnp.int32), NODE_AXIS)
    num_eligible = jax.lax.psum(
        jnp.sum(eligible, dtype=jnp.int32), NODE_AXIS)
    ok, reason = decide_job(valid, node_num, max_nodes, num_feasible,
                            num_eligible)

    # Local k cheapest feasible nodes.  top_k ties resolve to the lowest
    # local index, matching the single-device solver's tie order.
    k = min(max_nodes, local_n)
    masked_cost = jnp.where(feasible, cost, COST_INF)
    cand_cost, lidx = cheapest_k(masked_cost, k)
    cand_gidx = lidx + offset

    # Merge candidates across shards (ICI all_gather), then select the
    # global k winners.  tiled=False -> [D, k] in shard order; flattening
    # keeps shard-major order so the stable argsort resolves cost ties to
    # the lowest global node index.
    all_cost = jax.lax.all_gather(cand_cost, NODE_AXIS).reshape(-1)
    all_gidx = jax.lax.all_gather(cand_gidx, NODE_AXIS).reshape(-1)
    order = jnp.argsort(all_cost, stable=True)[:max_nodes]
    sel_cost = all_cost[order]
    sel_gidx = all_gidx[order]

    k_mask = jnp.arange(max_nodes) < node_num
    sel = ok & k_mask & (sel_cost < COST_INF)
    chosen = jnp.where(sel, sel_gidx, -1)

    # Apply updates for winners this shard owns.  OOB sentinel + drop mode
    # (negative indices would wrap, so clamp explicitly).
    local = sel_gidx - offset
    owned = sel & (local >= 0) & (local < local_n)
    scatter_idx = jnp.where(owned, local, local_n)  # local_n == OOB
    avail, cost = apply_placement(avail, cost, total, req, time_limit,
                                  scatter_idx, owned)
    return avail, cost, ok, chosen, reason


@functools.partial(jax.jit, static_argnames=("max_nodes", "mesh"))
def solve_greedy_sharded(state: ClusterState, jobs: JobBatch, mesh: Mesh,
                         max_nodes: int = 1
                         ) -> tuple[Placements, ClusterState]:
    """Greedy in-priority-order placement with the node axis sharded.

    Same contract as ``models.solver.solve_greedy``; requires the node count
    to be divisible by the mesh size (callers pad dead nodes, which never
    match).  The returned state keeps its node-sharded layout so successive
    cycles never regather the cluster to one device.
    """
    max_nodes = min(max_nodes, state.num_nodes)

    def shard_fn(avail, total, alive, cost, req, node_num, time_limit,
                 part_mask, valid):
        def step(carry, job):
            a, c = carry
            jreq, jnn, jtl, jpm, jv = job
            a, c, ok, chosen, reason = _place_one_shard(
                a, c, total, alive, jreq, jnn, jtl, jpm, jv, max_nodes)
            return (a, c), (ok, chosen, reason)

        (avail, cost), (placed, nodes, reason) = jax.lax.scan(
            step, (avail, cost),
            (req, node_num, time_limit, part_mask, valid))
        return avail, cost, placed, nodes, reason

    node_row = P(NODE_AXIS)
    node_mat = P(NODE_AXIS, None)
    avail, cost, placed, nodes, reason = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(node_mat, node_mat, node_row, node_row,
                  P(None, None), P(None), P(None), P(None, NODE_AXIS),
                  P(None)),
        out_specs=(node_mat, node_row, P(None), P(None, None), P(None)),
        check_vma=False,
    )(state.avail, state.total, state.alive, state.cost,
      jobs.req, jobs.node_num, jobs.time_limit, jobs.part_mask, jobs.valid)

    new_state = state.replace(avail=avail, cost=cost)
    return Placements(placed=placed, nodes=nodes, reason=reason), new_state


solve_greedy_sharded = _instrument_jit("solve_greedy_sharded",
                                       solve_greedy_sharded)


@functools.partial(jax.jit, static_argnames=("max_nodes", "mesh",
                                             "num_streams", "stream_len"))
def _solve_sharded_streamed(state: ClusterState, req, node_num,
                            time_limit, valid, job_class, class_masks,
                            stream_of_class, mesh: Mesh, max_nodes: int,
                            num_streams: int, stream_len: int
                            ) -> tuple[Placements, ClusterState]:
    """Factored-eligibility sharded solve with S independent job
    streams per scan step.

    Eligibility arrives as ``job_class[J]`` + ``class_masks[C, N]``
    (the class table is node-sharded alongside the cluster tensors, so
    no [J, N] mask ever exists on any device).  Jobs are regrouped
    stream-major exactly like the Pallas streamed kernel; each scan
    step then places one job from each of the S streams.  Because
    streams own pairwise-disjoint class masks (verified by
    ``plan_streams``), the S selections read pre-step state and their
    updates touch disjoint node sets — bit-identical to the serial
    order.  The payoff is collective BATCHING: one psum of 2*S counts
    and one all_gather of the S*k candidate block per step, instead of
    2 psums + 2 gathers per job — J*4 collectives become (J/S)*2.
    """
    J = req.shape[0]
    R = req.shape[1]
    S = num_streams
    L = stream_len
    C = class_masks.shape[0]
    K = min(max_nodes, state.num_nodes)

    cls = jnp.clip(job_class.astype(jnp.int32), 0, C - 1)
    stream = stream_of_class[cls]                       # [J]
    order = jnp.argsort(stream, stable=True)
    sorted_stream = stream[order]
    slot = (jnp.arange(J, dtype=jnp.int32)
            - jnp.searchsorted(sorted_stream,
                               sorted_stream).astype(jnp.int32))
    lin = sorted_stream * L + slot                      # [J] flat slots

    def scat(x, fill, dtype):
        flat = jnp.full((S * L,) + x.shape[1:], fill, dtype)
        return flat.at[lin].set(x[order].astype(dtype), mode="drop")

    # [S*L, ..] -> [S, L, ..] -> scan-major [L, S, ..]
    req_sl = scat(req, 0, jnp.int32).reshape(S, L, R).transpose(1, 0, 2)
    nn_sl = scat(node_num, 0, jnp.int32).reshape(S, L).T
    tl_sl = scat(time_limit, 0, jnp.int32).reshape(S, L).T
    v_sl = scat(valid, False, jnp.bool_).reshape(S, L).T
    cls_sl = scat(cls, 0, jnp.int32).reshape(S, L).T

    def shard_fn(avail, total, alive, cost, cm, req_x, nn_x, tl_x, cls_x,
                 v_x):
        local_n = avail.shape[0]
        shard = jax.lax.axis_index(NODE_AXIS)
        offset = shard * local_n
        k = min(max_nodes, local_n)

        def step(carry, xs):
            a, c = carry
            jreq, jnn, jtl, jcls, jv = xs

            # --- selection phase: all S streams against PRE-step state
            # (exact: no stream can touch another stream's nodes) ---
            feas_cnt, elig_cnt, cand_cost, cand_gidx = [], [], [], []
            for s in range(S):
                pm = cm[jcls[s]]
                eligible, feasible = job_feasibility(a, alive, pm,
                                                     jreq[s])
                feas_cnt.append(jnp.sum(feasible, dtype=jnp.int32))
                elig_cnt.append(jnp.sum(eligible, dtype=jnp.int32))
                masked_cost = jnp.where(feasible, c, COST_INF)
                cc, lidx = cheapest_k(masked_cost, k)
                cand_cost.append(cc)
                cand_gidx.append(lidx + offset)

            # --- batched collectives: ONE psum, ONE all_gather ---
            counts = jax.lax.psum(
                jnp.stack(feas_cnt + elig_cnt), NODE_AXIS)      # [2S]
            packed = jnp.stack(
                [jnp.stack(cand_cost), jnp.stack(cand_gidx)])   # [2, S, k]
            allp = jax.lax.all_gather(packed, NODE_AXIS)        # [D, 2, S, k]

            # --- decide + apply per stream (disjoint updates) ---
            oks, chosens, reasons = [], [], []
            for s in range(S):
                ok, reason = decide_job(jv[s], jnn[s], max_nodes,
                                        counts[s], counts[S + s])
                ac = allp[:, 0, s, :].reshape(-1)
                ag = allp[:, 1, s, :].reshape(-1)
                sel_order = jnp.argsort(ac, stable=True)[:max_nodes]
                sel_cost = ac[sel_order]
                sel_gidx = ag[sel_order]
                k_mask = jnp.arange(max_nodes) < jnn[s]
                sel = ok & k_mask & (sel_cost < COST_INF)
                chosen = jnp.where(sel, sel_gidx, -1)
                local = sel_gidx - offset
                owned = sel & (local >= 0) & (local < local_n)
                scatter_idx = jnp.where(owned, local, local_n)
                a, c = apply_placement(a, c, total, jreq[s], jtl[s],
                                       scatter_idx, owned)
                oks.append(ok)
                chosens.append(chosen)
                reasons.append(reason)
            return (a, c), (jnp.stack(oks), jnp.stack(chosens),
                            jnp.stack(reasons))

        (avail, cost), (placed, nodes, reason) = jax.lax.scan(
            step, (avail, cost), (req_x, nn_x, tl_x, cls_x, v_x))
        return avail, cost, placed, nodes, reason

    node_row = P(NODE_AXIS)
    node_mat = P(NODE_AXIS, None)
    avail, cost, placed, nodes, reason = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(node_mat, node_mat, node_row, node_row,
                  P(None, NODE_AXIS), P(None, None, None), P(None, None),
                  P(None, None), P(None, None), P(None, None)),
        out_specs=(node_mat, node_row, P(None, None),
                   P(None, None, None), P(None, None)),
        check_vma=False,
    )(state.avail, state.total, state.alive, state.cost,
      class_masks, req_sl, nn_sl, tl_sl, cls_sl, v_sl)

    # [L, S, ..] -> [S, L, ..] -> flat, then gather each original job
    inv = jnp.zeros(J, jnp.int32).at[order].set(lin, mode="drop")
    placed_j = placed.transpose(1, 0).reshape(-1)[inv].astype(bool)
    nodes_j = nodes.transpose(1, 0, 2).reshape(S * L, K)[inv]
    reason_j = reason.transpose(1, 0).reshape(-1)[inv]

    new_state = state.replace(avail=avail, cost=cost)
    return (Placements(placed=placed_j, nodes=nodes_j, reason=reason_j),
            new_state)


_solve_sharded_streamed = _instrument_jit("solve_sharded_streamed",
                                          _solve_sharded_streamed)


def solve_greedy_sharded_classes(state: ClusterState, req, node_num,
                                 time_limit, valid, job_class,
                                 class_masks, mesh: Mesh,
                                 max_nodes: int = 1, max_streams: int = 4,
                                 plan=None
                                 ) -> tuple[Placements, ClusterState]:
    """Factored-eligibility sharded solve with auto stream dispatch.

    Accepts eligibility as (job_class, class_masks) — the sharded twin
    of ``solve_greedy_pallas_auto``.  When ``plan_streams`` finds a
    worthwhile class-disjoint packing the S-stream scan runs (batched
    collectives); otherwise the same scan runs with S=1, which is the
    plain serial order.  ``plan`` overrides the planner (the scheduler
    caches it per mask-table epoch).  Parity:
    tests/test_sharded_parity.py."""
    from cranesched_tpu.models.pallas_solver import plan_streams

    J = int(req.shape[0])
    if plan is None:
        # block_jobs=1: stream_len quantizes to ceil(longest/8)*8 —
        # scan steps, not kernel blocks, so no 256-job padding quantum
        plan = plan_streams(job_class, class_masks,
                            max_streams=max_streams, block_jobs=1)
    if plan is None:
        C = int(class_masks.shape[0])
        plan = (jnp.zeros(C, jnp.int32), 1,
                -(-max(J, 1) // 8) * 8)
    stream_of_class, S, L = plan
    return _solve_sharded_streamed(
        state, req, node_num, time_limit, valid, job_class, class_masks,
        stream_of_class, mesh=mesh, max_nodes=max_nodes, num_streams=S,
        stream_len=L)
