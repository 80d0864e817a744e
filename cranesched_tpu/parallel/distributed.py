"""Multi-process pod-slice solve: node slabs across processes.

One process per host is the TPU pod-slice reality (a v4-32 is 4 hosts
x 4 chips; no single PJRT client sees all 16).  The node axis of the
cluster tensors therefore shards twice:

    process p owns the contiguous node slab [sum(n_0..n_{p-1}),
    sum(n_0..n_p)); inside the slab the existing ``shard_map`` solve
    (parallel.sharded) spreads rows over the process's LOCAL devices.

Cross-process merging is hierarchical.  Each scan step of the greedy
solve splits into a *select* and an *apply* half:

1. ``select``: every process computes, per job stream, its slab-level
   feasible/eligible counts and its k cheapest candidates (one local
   psum + one local all_gather over ICI — exactly the single-process
   solver's collectives, confined to the slab);
2. one host-level rendezvous ``Fence`` (rpc.rendezvous, epoch-tagged)
   all-gathers the packed counts + candidate blocks in rank order;
3. ``apply``: every process deterministically merges the P candidate
   lists (stable sort: cost ascending, ties to the lowest global node
   id — rank-major concatenation of per-slab sorted lists makes the
   stable sort resolve ties exactly like the single-process oracle),
   re-derives the same admission decision from the summed counts, and
   scatters the resource subtraction into whichever winner rows its
   slab owns.

Why a host fence and not ``jax.lax.psum`` over a global mesh: the CPU
backend (CI, and any host-only bring-up) cannot run cross-process XLA
computations at all ("Multiprocess computations aren't implemented on
the CPU backend", jaxlib 0.4.x), and on real pods the per-step payload
is O(P * S * max_nodes) bytes — latency-bound either way.  On silicon
with ``jax.distributed`` initialized, ``native_global_mesh()`` returns
a true global mesh instead and callers run ``solve_greedy_sharded*``
over it directly, skipping this module's host loop entirely.

Parity contract: ``solve_greedy_sharded_classes_mp`` is bit-identical
to single-process ``solve_greedy_sharded_classes`` on the concatenated
slabs (tests/test_multihost.py, overlapping and disjoint class
tables).

Metrics: ``crane_mesh_fence_seconds`` (host-barrier latency, by kind)
and ``crane_mesh_solve_seconds`` (wall time of one distributed solve,
by process count).
"""

from __future__ import annotations

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cranesched_tpu.models.solver import (
    COST_INF,
    ClusterState,
    Placements,
    apply_placement,
    cheapest_k,
    decide_job,
    job_feasibility,
)
from cranesched_tpu.obs.metrics import REGISTRY as _OBS
from cranesched_tpu.parallel.sharded import (
    NODE_AXIS,
    make_node_mesh,
)
from cranesched_tpu.rpc.rendezvous import RendezvousClient

_MET_FENCE = _OBS.histogram(
    "crane_mesh_fence_seconds",
    "Host-level rendezvous fence latency in multi-process solves")
_MET_SOLVE = _OBS.histogram(
    "crane_mesh_solve_seconds",
    "Wall time of one multi-process sharded solve")

DEFAULT_FENCE_TIMEOUT_S = 120.0

# XLA's CPU collective rendezvous deadlocks when two THREADS of one
# process execute multi-device collective programs concurrently (the
# 8 per-device threads of both runs interleave at the same
# participant barrier).  A real deployment has one solver thread per
# process, so this lock is uncontended; it only serializes the
# in-process multi-rank harnesses (tests, bench's thread stand-in).
# Conversions to numpy happen INSIDE the lock so the program has
# fully retired before the next rank's program launches.
_EXEC_LOCK = threading.Lock()


def native_global_mesh():
    """The fast path for real pod slices: a single global mesh over
    every device of every process, valid only where the runtime can
    execute cross-process XLA computations (TPU/GPU under an
    initialized ``jax.distributed``; the CPU backend cannot).  Callers
    holding one run ``solve_greedy_sharded_classes`` on it directly —
    psum/all_gather ride ICI/DCN and no host fence exists.  Returns
    None when the hierarchical path is required."""
    if jax.process_count() <= 1:
        return None
    if jax.devices()[0].platform == "cpu":
        return None
    return make_node_mesh(jax.devices())


class ProcessMesh:
    """One process's membership in the gang of solver processes.

    Holds the local device mesh (this process's slab is device-sharded
    over it), the slab geometry agreed at bootstrap, and the
    epoch-tagged rendezvous client used for the per-step host fences.
    """

    def __init__(self, rank: int, nprocs: int, client: RendezvousClient,
                 epoch: int, mesh, node_offset: int, slab_nodes: int,
                 total_nodes: int, peers: list[dict],
                 fence_timeout: float = DEFAULT_FENCE_TIMEOUT_S):
        self.rank = rank
        self.nprocs = nprocs
        self.client = client
        self.epoch = epoch
        self.mesh = mesh
        self.node_offset = node_offset
        self.slab_nodes = slab_nodes
        self.total_nodes = total_nodes
        self.peers = peers
        self.fence_timeout = fence_timeout
        self._solve_seq = 0

    @property
    def local_device_count(self) -> int:
        return self.mesh.devices.size

    def describe(self) -> str:
        """``procs x local-devices`` — the MESH column of cstats."""
        return f"{self.nprocs}x{self.local_device_count}"

    def fence(self, name: str, payload: bytes = b"",
              timeout: float | None = None, kind: str = "solve"
              ) -> list[bytes]:
        t0 = time.monotonic()
        try:
            return self.client.fence(
                name, self.rank, self.nprocs, data=payload,
                timeout=self.fence_timeout if timeout is None
                else timeout)
        finally:
            _MET_FENCE.observe(time.monotonic() - t0, kind=kind)

    def next_solve_id(self) -> int:
        self._solve_seq += 1
        return self._solve_seq

    def close(self) -> None:
        self.client.close()


def bootstrap_process_mesh(rank: int, nprocs: int, slab_nodes: int, *,
                           address: str | None = None,
                           token: str | None = None, epoch: int = 1,
                           timeout: float = 60.0, tls=None
                           ) -> ProcessMesh:
    """The jax.distributed-shaped bootstrap over our own rendezvous.

    Every process dials the coordinator (``address`` or
    ``CRANE_RENDEZVOUS``), contributes its slab size and device
    inventory to an epoch-tagged boot fence, and derives the agreed
    slab offsets from the rank-ordered contributions.  A missing rank
    surfaces as the fence's structured ``x/y arrived`` timeout — never
    a silent hang (the whole point of ISSUE 17)."""
    address = address or os.environ.get("CRANE_RENDEZVOUS", "")
    if not address:
        raise ValueError("no coordinator: pass address= or set "
                         "CRANE_RENDEZVOUS")
    if token is None:
        token = os.environ.get("CRANE_RENDEZVOUS_TOKEN", "")
    client = RendezvousClient(address, token=token, tls=tls,
                              epoch=epoch)
    mesh = make_node_mesh()
    info = {"slab": int(slab_nodes),
            "devices": int(mesh.devices.size),
            "platform": jax.devices()[0].platform}
    t0 = time.monotonic()
    try:
        datas = client.fence(f"mesh/boot/{epoch}", rank, nprocs,
                             data=json.dumps(info).encode(),
                             timeout=timeout)
    finally:
        _MET_FENCE.observe(time.monotonic() - t0, kind="boot")
    peers = [json.loads(d.decode()) for d in datas]
    slabs = [int(p["slab"]) for p in peers]
    return ProcessMesh(
        rank=rank, nprocs=nprocs, client=client, epoch=epoch, mesh=mesh,
        node_offset=int(sum(slabs[:rank])), slab_nodes=int(slabs[rank]),
        total_nodes=int(sum(slabs)), peers=peers)


# ---- the select/apply split of one scan step ----
#
# Both halves compile ONCE per solve (every step has identical [S,...]
# shapes); the host loop between them is the fence.

def _select_step(avail, alive, cost, cm, jreq, jcls, *, mesh, k_slab):
    S = jreq.shape[0]

    def shard_fn(a, al, c, cm_l, jreq_x, jcls_x):
        local_n = a.shape[0]
        offset = jax.lax.axis_index(NODE_AXIS) * local_n
        k = min(k_slab, local_n)
        f_cnt, e_cnt, cc_l, cg_l = [], [], [], []
        for s in range(S):
            pm = cm_l[jcls_x[s]]
            eligible, feasible = job_feasibility(a, al, pm, jreq_x[s])
            f_cnt.append(jnp.sum(feasible, dtype=jnp.int32))
            e_cnt.append(jnp.sum(eligible, dtype=jnp.int32))
            masked = jnp.where(feasible, c, COST_INF)
            cc, lidx = cheapest_k(masked, k)
            cc_l.append(cc)
            cg_l.append(lidx + offset)
        # ONE local psum + ONE local all_gather per step, same
        # batching as the single-process streamed solver
        counts = jax.lax.psum(jnp.stack(f_cnt + e_cnt), NODE_AXIS)
        packed = jnp.stack([jnp.stack(cc_l), jnp.stack(cg_l)])
        allp = jax.lax.all_gather(packed, NODE_AXIS)     # [D, 2, S, k]
        sl_cost, sl_gidx = [], []
        for s in range(S):
            flat_c = allp[:, 0, s, :].reshape(-1)
            flat_g = allp[:, 1, s, :].reshape(-1)
            o = jnp.argsort(flat_c, stable=True)[:k_slab]
            sl_cost.append(flat_c[o])
            sl_gidx.append(flat_g[o])
        return counts, jnp.stack(sl_cost), jnp.stack(sl_gidx)

    node_row = P(NODE_AXIS)
    node_mat = P(NODE_AXIS, None)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(node_mat, node_row, node_row, P(None, NODE_AXIS),
                  P(None, None), P(None)),
        out_specs=(P(None), P(None, None), P(None, None)),
        check_vma=False,
    )(avail, alive, cost, cm, jreq, jcls)


_select_step = jax.jit(_select_step,
                       static_argnames=("mesh", "k_slab"))


def _apply_step(avail, cost, total, jreq, jnn, jtl, jv, counts,
                sel_cost, sel_gidx, slab_offset, *, mesh, max_nodes):
    S = jreq.shape[0]

    def shard_fn(a, c, t, jreq_x, jnn_x, jtl_x, jv_x, counts_x,
                 sc_x, sg_x, off_x):
        local_n = a.shape[0]
        offset = off_x + jax.lax.axis_index(NODE_AXIS) * local_n
        oks, chosens, reasons = [], [], []
        for s in range(S):
            ok, reason = decide_job(jv_x[s], jnn_x[s], max_nodes,
                                    counts_x[s], counts_x[S + s])
            k_mask = jnp.arange(max_nodes) < jnn_x[s]
            sel = ok & k_mask & (sc_x[s] < COST_INF)
            chosen = jnp.where(sel, sg_x[s], -1)
            local = sg_x[s] - offset
            owned = sel & (local >= 0) & (local < local_n)
            scatter_idx = jnp.where(owned, local, local_n)
            a, c = apply_placement(a, c, t, jreq_x[s], jtl_x[s],
                                   scatter_idx, owned)
            oks.append(ok)
            chosens.append(chosen)
            reasons.append(reason)
        return (a, c, jnp.stack(oks), jnp.stack(chosens),
                jnp.stack(reasons))

    node_row = P(NODE_AXIS)
    node_mat = P(NODE_AXIS, None)
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(node_mat, node_row, node_mat, P(None, None), P(None),
                  P(None), P(None), P(None), P(None, None),
                  P(None, None), P()),
        out_specs=(node_mat, node_row, P(None), P(None, None),
                   P(None)),
        check_vma=False,
    )(avail, cost, total, jreq, jnn, jtl, jv, counts, sel_cost,
      sel_gidx, slab_offset)


_apply_step = jax.jit(_apply_step,
                      static_argnames=("mesh", "max_nodes"))


def _pack(counts, cc, cg) -> bytes:
    hdr = np.asarray([cc.shape[0], cc.shape[1]], np.int32)
    return b"".join(np.ascontiguousarray(x, "<i4").tobytes()
                    for x in (hdr, counts, cc.reshape(-1),
                              cg.reshape(-1)))


def _unpack(buf: bytes):
    a = np.frombuffer(buf, "<i4")
    s, k = int(a[0]), int(a[1])
    counts = a[2:2 + 2 * s]
    cc = a[2 + 2 * s:2 + 2 * s + s * k].reshape(s, k)
    cg = a[2 + 2 * s + s * k:2 + 2 * (s + s * k)].reshape(s, k)
    return counts, cc, cg


def solve_greedy_sharded_classes_mp(pmesh: ProcessMesh,
                                    state: ClusterState, req, node_num,
                                    time_limit, valid, job_class,
                                    class_masks, max_nodes: int = 1,
                                    plan=None
                                    ) -> tuple[Placements, ClusterState]:
    """Greedy class-table solve across the process mesh.

    ``state``/``class_masks`` hold only THIS process's node slab (the
    job tensors stay replicated, as in the single-process solver).
    Same contract and bit-identical results as running
    ``solve_greedy_sharded_classes`` over the concatenated slabs.

    ``plan`` must be identical on every rank when given (it fixes the
    fence count and payload shapes); the default is the serial S=1
    plan, which depends only on replicated job data and therefore
    always agrees.  Multi-stream plans from ``plan_streams`` are legal
    only when computed from the GLOBAL class table — a slab-local plan
    can disagree across ranks about class disjointness.
    """
    if int(state.num_nodes) != pmesh.slab_nodes:
        raise ValueError(
            f"state has {int(state.num_nodes)} nodes but this rank's "
            f"slab is {pmesh.slab_nodes}")
    if max_nodes > pmesh.total_nodes:
        raise ValueError(f"max_nodes {max_nodes} exceeds the "
                         f"{pmesh.total_nodes}-node cluster")
    J = int(req.shape[0])
    R = int(req.shape[1])
    C = int(class_masks.shape[0])
    if J == 0:
        return (Placements(
            placed=jnp.zeros((0,), bool),
            nodes=jnp.zeros((0, max_nodes), jnp.int32),
            reason=jnp.zeros((0,), jnp.int32)), state)
    if plan is None:
        plan = (np.zeros(C, np.int32), 1, -(-J // 8) * 8)
    stream_of_class, S, L = plan

    # stream-major regrouping, the host-side twin of the jnp version in
    # _solve_sharded_streamed (replicated inputs -> identical on every
    # rank)
    cls = np.clip(np.asarray(job_class, np.int32), 0, C - 1)
    stream = np.asarray(stream_of_class, np.int32)[cls]
    order = np.argsort(stream, kind="stable")
    sorted_stream = stream[order]
    slot = (np.arange(J, dtype=np.int32)
            - np.searchsorted(sorted_stream,
                              sorted_stream).astype(np.int32))
    lin = sorted_stream * L + slot

    def scat(x, fill, dtype):
        flat = np.full((S * L,) + np.asarray(x).shape[1:], fill, dtype)
        flat[lin] = np.asarray(x)[order]
        return flat

    req_sl = scat(req, 0, np.int32).reshape(S, L, R).transpose(1, 0, 2)
    nn_sl = scat(node_num, 0, np.int32).reshape(S, L).T
    tl_sl = scat(time_limit, 0, np.int32).reshape(S, L).T
    v_sl = scat(valid, False, np.bool_).reshape(S, L).T
    cls_sl = scat(cls, 0, np.int32).reshape(S, L).T

    k_slab = min(max_nodes, pmesh.slab_nodes)
    sid = pmesh.next_solve_id()
    avail, cost = state.avail, state.cost
    placed_sl = np.zeros((L, S), bool)
    nodes_sl = np.zeros((L, S, max_nodes), np.int32)
    reason_sl = np.zeros((L, S), np.int32)
    t0 = time.monotonic()
    for step in range(L):
        with _EXEC_LOCK:
            counts, cc, cg = _select_step(
                avail, state.alive, cost, class_masks,
                jnp.asarray(req_sl[step]), jnp.asarray(cls_sl[step]),
                mesh=pmesh.mesh, k_slab=k_slab)
            counts, cc, cg = (np.asarray(counts), np.asarray(cc),
                              np.asarray(cg))
        payload = _pack(counts, cc, cg + pmesh.node_offset)

        datas = pmesh.fence(f"solve/{pmesh.epoch}/{sid}/{step}",
                            payload)

        parts = [_unpack(d) for d in datas]   # rank order
        counts_g = np.sum([p[0] for p in parts], axis=0,
                          dtype=np.int64).astype(np.int32)
        sel_cost = np.full((S, max_nodes), COST_INF, np.int32)
        sel_gidx = np.full((S, max_nodes), -1, np.int32)
        for s in range(S):
            all_c = np.concatenate([p[1][s] for p in parts])
            all_g = np.concatenate([p[2][s] for p in parts])
            o = np.argsort(all_c, kind="stable")[:max_nodes]
            sel_cost[s, :o.size] = all_c[o]
            sel_gidx[s, :o.size] = all_g[o]

        with _EXEC_LOCK:
            avail, cost, placed, chosen, reason = _apply_step(
                avail, cost, state.total, jnp.asarray(req_sl[step]),
                jnp.asarray(nn_sl[step]), jnp.asarray(tl_sl[step]),
                jnp.asarray(v_sl[step]), jnp.asarray(counts_g),
                jnp.asarray(sel_cost), jnp.asarray(sel_gidx),
                jnp.int32(pmesh.node_offset), mesh=pmesh.mesh,
                max_nodes=max_nodes)
            placed_sl[step] = np.asarray(placed)
            nodes_sl[step] = np.asarray(chosen)
            reason_sl[step] = np.asarray(reason)
    _MET_SOLVE.observe(time.monotonic() - t0, procs=str(pmesh.nprocs))

    inv = np.zeros(J, np.int64)
    inv[order] = lin
    placed_j = placed_sl.transpose(1, 0).reshape(-1)[inv]
    nodes_j = nodes_sl.transpose(1, 0, 2).reshape(S * L, max_nodes)[inv]
    reason_j = reason_sl.transpose(1, 0).reshape(-1)[inv]

    new_state = state.replace(avail=avail, cost=cost)
    return (Placements(placed=jnp.asarray(placed_j),
                       nodes=jnp.asarray(nodes_j),
                       reason=jnp.asarray(reason_j)),
            new_state)
