"""The greedy placement cycle as ONE Pallas TPU kernel.

Why: the lax.scan solver (models/solver.py) is semantically exact but
latency-bound on TPU — 100k scan steps of ~15 tiny kernels each measured
2.7 s/cycle at the north-star shape on a v5e (PERF.md, PR 21), entirely
dispatch/latency overhead: the actual arithmetic is ~10 GOP.  The
TPU-native fix is to run the WHOLE job loop inside a single kernel:

* cluster state (``avail`` transposed and folded to [R, 8, N/8], the
  int32 cost ledger, per-eligibility-class node masks) lives in VMEM
  scratch for the whole solve — at 10k nodes that is ~0.5 MB, far under
  the ~16 MB/core budget, read/updated at VPU speed with zero HBM
  traffic;
* per-job scalars (req, node_num, time_limit, class id, valid) stream
  through SMEM in blocks of ``BJ`` jobs per grid step;
* a slot of the job loop (one job of each of the S streams) costs what
  its jobs can use, inside the static gang bound K that stays a
  compile-time argument: one feasibility compare per resource dim, then
  selection passes (a masked min over the node axis, a second for the
  lowest tied node id, a mask of the winner) that end at the widest
  ``node_num`` among the slot's valid streams and at the first infinite
  minimum: the minima come out ascending, so a job whose first one is
  infinite (no feasible node: nearly every job of a standing backlog) is
  decided after ONE pass, as is a padded or invalidated slot.  Pass 0
  is straight-line code; the passes after it are ONE loop whose trip
  count the device computes, so neither the depth nor the size of the
  traced kernel follows K (a branch nested a pass overflowed the stack
  of Mosaic's layout inference at K = 16), and K is only the width of
  the ``chosen`` output, which leaves VMEM a grid step at a time.
  Only a job that is placed pays the masked subtract/add of the
  resource/cost update.  The kernel counts the
  passes it ran (``Placements.passes``; the cycle trace's
  ``tail_pass_pct``).  No dynamic-index gathers or scatters at all:
  selection and update are both expressed as elementwise ops against a
  node-index iota, which is exactly what the VPU wants.  The node axis
  is folded to (8 sublanes, N/8 lanes) so every op fills the full 8x128
  VPU instead of one sublane.

Semantics are bit-identical to ``solver.solve_greedy`` on the same
backend (same fixed-point cost ledger, same (cost, lowest-index) tie
order, same decide_job admission + pending reasons — asserted in
interpret mode by tests/test_pallas_parity.py and on the chip at
100k x 10k by chip_smoke.py).
The one interface difference: per-job node eligibility arrives as
``job_class[J]`` + ``class_masks[C, N]`` instead of a dense
``part_mask[J, N]`` — the [J, N] matrix at 100k x 10k is a 1 GB bool
that neither HBM nor the control plane wants, while real clusters have a
handful of distinct (partition x include/exclude) masks (reference:
partition membership drives eligibility,
src/CraneCtld/JobScheduler.cpp:6516-6607).

Reference for the loop semantics: LocalScheduler::GetNodesAndTrySchedule_
walks nodes in ascending cost order and takes the first node_num that fit
(src/CraneCtld/JobScheduler.cpp:6147-6369); the cost policy is
MinCpuTimeRatioFirst (JobScheduler.h:40-54).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cranesched_tpu.models.solver import (
    COST_INF,
    COST_SCALE,
    ClusterState,
    JobBatch,
    Placements,
    REASON_CONSTRAINT,
    REASON_NONE,
    REASON_RESOURCE,
)
from cranesched_tpu.obs.introspect import instrument_jit as _instrument_jit
from cranesched_tpu.ops.resources import DIM_CPU

# node axis is folded to (SUB, N/SUB) so every vector op fills all 8
# sublanes x 128 lanes of the VPU instead of 1/8th of it
SUB = 8
LANES = 128
NODE_TILE = SUB * LANES  # node padding quantum (1024)


def _pad_to(x, size, axis, value):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def classes_from_part_mask(part_mask) -> tuple[np.ndarray, np.ndarray]:
    """Host-side helper (tests / adapters): compress a dense [J, N]
    eligibility matrix into (job_class[J], class_masks[C, N])."""
    pm = np.asarray(part_mask, bool)
    classes, inverse = np.unique(pm, axis=0, return_inverse=True)
    return inverse.astype(np.int32), classes


def _make_kernel(BJ: int, K: int, R: int, W: int, S: int = 1):
    # all per-job scalars ride in ONE SMEM window (layout [S, R+4, BJ]:
    # req dims, node_num, time_limit, valid, class as ROWS, jobs as
    # columns) — SMEM windows are padded to 1 KiB/row and
    # double-buffered, so the fields-as-rows orientation costs
    # S*(R+4) padded rows instead of S*BJ (1024 rows = a full MiB of
    # SMEM, measured OOM at S=4, BJ=256).
    #
    # S is the number of INDEPENDENT job streams processed per loop
    # iteration.  Streams own pairwise-disjoint eligibility classes
    # (verified host-side), so their greedy decisions never interact:
    # selections of all S streams are mutually independent and their
    # latency chains overlap (the kernel is latency-bound on each
    # job's compare→min-reduce→update dependency chain, NOT on vector
    # width).  This is the TPU analog of the
    # reference's per-partition LocalScheduler split
    # (src/CraneCtld/JobScheduler.cpp:6516-6530).
    def kernel(job_s, nelig_s,                           # SMEM scalars
               avail_in, cost_in, elig_in, cputot_in,    # VMEM cluster in
               placed_o, chosen_o, reason_o, avail_o, cost_o,  # outputs
               passes_o,                                 # SMEM counter out
               avail_s, cost_s, placed_s, reason_s, mcost_s):  # scratch
        nb = pl.num_programs(0)
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            avail_s[...] = avail_in[...]
            cost_s[...] = cost_in[...]

        # global node index at each (sublane, lane) position; masked mins
        # over it resolve cost ties to the LOWEST node id, matching the
        # scan solver's argmin-first-occurrence order
        nid = (jax.lax.broadcasted_iota(jnp.int32, (SUB, W), 0) * W
               + jax.lax.broadcasted_iota(jnp.int32, (SUB, W), 1))
        jlane = jax.lax.broadcasted_iota(jnp.int32, (1, BJ), 1)
        inf = jnp.int32(COST_INF)
        npad = jnp.int32(SUB * W)

        placed_s[...] = jnp.zeros((S, BJ), jnp.int32)
        reason_s[...] = jnp.zeros((S, BJ), jnp.int32)
        # this grid step's block of the chosen ids; it leaves VMEM when
        # the step ends (at K = 64 the whole output would not fit there)
        chosen_o[...] = jnp.full((1, S, K, BJ), -1, jnp.int32)

        def lowest(mcosts):
            """One selection pass for all S streams side by side: the
            minimum of each masked cost, then the lowest node id that
            holds it.  All S minima are asked for before the first id:
            the reduce chains of one pass are mutually independent, and
            in the order stream by stream they ran one behind the other
            (the streamed kernel a third slower, PERF.md, PR 35)."""
            ms = [jnp.min(mcost) for mcost in mcosts]
            return ms, [jnp.min(jnp.where(mcost == m, nid, npad))
                        for mcost, m in zip(mcosts, ms)]

        def job_body(j, passes):
            nns = [job_s[c, R, j] for c in range(S)]
            valids = [job_s[c, R + 2, j] != 0 for c in range(S)]
            clss = [job_s[c, R + 3, j] for c in range(S)]
            # the selection passes a stream's job can use: its own
            # width; none where it is refused whatever the minima read
            # (padding, an invalidated row, a gang wider than K)
            wants = [jnp.where(valids[c] & (nns[c] <= K), nns[c], 0)
                     for c in range(S)]

            def put(c, k, idx, take):
                """Gang member k of stream c's job: its row of the
                block, this job's lane."""
                row = chosen_o.at[0, c, pl.ds(k, 1), :]
                row[...] = jnp.where((jlane == j) & take, idx, row[...])

            def more_after(k, ms):
                """Does any stream that still wants a node after pass k
                have a chance of one?  The minima come out ascending,
                so after an infinite one every later one is infinite."""
                return functools.reduce(jnp.logical_or, [
                    (wants[c] > k + 1) & (ms[c] < inf) for c in range(S)])

            # --- selection, pass 0: reads pre-update state for all S
            # streams side by side (the latency-heavy reduce chains of
            # one pass are mutually independent), which is exact because
            # no other stream can touch the nodes a stream sees.  It
            # runs for every slot: a branch round it for the few slots
            # with no valid job (padding, the head's rows) cost a sixth
            # of the kernel on the chip (PERF.md, PR 32). ---
            mcosts = []
            for c in range(S):
                feas = elig_in[clss[c]] != 0             # [SUB, W]
                for r in range(R):
                    feas = feas & (avail_s[r] >= job_s[c, r, j])
                mcosts.append(jnp.where(feas, cost_s[0], inf))
            ms0, idxs0 = lowest(mcosts)
            takes0 = [(wants[c] > 0) & (ms0[c] < inf) for c in range(S)]
            # a winner is masked only for the stream that takes it, so
            # that what the passes masked IS the set of nodes won
            wins0 = [jnp.where(takes0[c], idxs0[c], -1) for c in range(S)]
            got0 = tuple(t.astype(jnp.int32) for t in takes0)

            # --- the passes after it: ONE loop whose trip count the
            # device computes.  It runs while some stream that still
            # wants a node found one, so it ends at the slot's widest
            # min(node_num, K) and at the first infinite minimum, and
            # its depth and its size do not follow K.  A job whose first
            # minimum is infinite (no feasible node: nearly every job of
            # a standing backlog) is decided after pass 0, as is a
            # padded or invalidated slot.  The ids go to their rows as
            # the loop runs (a gang that then falls short takes them
            # back below), the finite minima a stream took are counted
            # as it runs. ---
            def later():
                for c in range(S):
                    mcost_s[c] = mcosts[c]

                def body(carry):
                    k, _, wins, got = carry
                    # mask the last winners for the next gang members
                    masked = [jnp.where(nid == wins[c], inf, mcost_s[c])
                              for c in range(S)]
                    for c in range(S):
                        mcost_s[c] = masked[c]
                    ms, idxs = lowest(masked)
                    takes = [(k < wants[c]) & (ms[c] < inf)
                             for c in range(S)]
                    for c in range(S):
                        put(c, k, idxs[c], takes[c])
                    return (k + 1, more_after(k, ms),
                            tuple(jnp.where(takes[c], idxs[c], -1)
                                  for c in range(S)),
                            tuple(got[c] + takes[c].astype(jnp.int32)
                                  for c in range(S)))

                k, _, wins, got = jax.lax.while_loop(
                    lambda carry: carry[1], body,
                    (jnp.int32(1), jnp.bool_(True), tuple(wins0), got0))
                for c in range(S):
                    mcost_s[c] = jnp.where(nid == wins[c], inf, mcost_s[c])
                return k, got

            if K > 1:
                more = more_after(0, ms0)
                ran, got = jax.lax.cond(
                    more, later, lambda: (jnp.int32(1), got0))
            else:
                more, ran, got = False, jnp.int32(1), got0

            # --- decide + update phase.  Updates touch only the
            # stream's own (disjoint) nodes, so stream order here is
            # immaterial. ---
            for c in range(S):
                nn, valid, cls = nns[c], valids[c], clss[c]
                tl = job_s[c, R + 1, j]

                # admission (decide_job): the masked minima are sorted
                # ascending, so "at least nn feasible nodes" is
                # exactly "nn finite minima" — no O(N) popcount.  The
                # eligible count is solve-invariant and precomputed per
                # class host-side.
                ok = (wants[c] > 0) & (got[c] >= nn)
                bad = jnp.logical_not(valid) | (nn <= 0)
                never = bad | (nelig_s[cls, 0] < nn)
                reason = jnp.where(ok, REASON_NONE,
                                   jnp.where(never, REASON_CONSTRAINT,
                                             REASON_RESOURCE))

                placed_s[c:c + 1, :] = jnp.where(
                    jlane == j, ok.astype(jnp.int32),
                    placed_s[c:c + 1, :])
                reason_s[c:c + 1, :] = jnp.where(
                    jlane == j, reason, reason_s[c:c + 1, :])

                if K > 1:
                    # a gang that found some nodes and not enough: its
                    # rows go back to -1 (never partly placed)
                    @pl.when(jnp.logical_not(ok) & (got[c] > 1))
                    def _(c=c):
                        chosen_o[0, c] = jnp.where(
                            jlane == j, -1, chosen_o[0, c])

                # row 0 of the block and one combined state update for
                # all gang members, both only for a job that is placed:
                # the jobs that fail skip the row write and the whole
                # masked-subtract/cost pass
                @pl.when(ok)
                def _(c=c, tl=tl):
                    put(c, 0, idxs0[c], True)
                    win = nid == idxs0[c]
                    if K > 1:
                        # and, where later passes ran, the nodes they
                        # masked: feasible before, infinite after
                        # (nothing is below ``lim`` where none ran)
                        lim = jnp.where(more, inf, -inf)
                        win = win | ((mcost_s[c] == inf)
                                     & (mcosts[c] < lim))
                    # MinCpuTimeRatioFirst increment, elementwise over
                    # nodes with this job's scalars — identical f32
                    # expression (and associativity) to
                    # solver.quantized_dcost
                    dcost = jnp.round(
                        tl.astype(jnp.float32)
                        * job_s[c, DIM_CPU, j].astype(jnp.float32)
                        * jnp.float32(COST_SCALE)
                        / cputot_in[0]).astype(jnp.int32)
                    for r in range(R):
                        avail_s[r] = avail_s[r] - jnp.where(
                            win, job_s[c, r, j], 0)
                    cost_s[0] = cost_s[0] + jnp.where(win, dcost, 0)
            return passes + ran

        # no partial unroll: the Mosaic lowering of the installed JAX
        # accepts only unroll=1 or a full unroll of the BJ steps
        # (tests/test_pallas_lowering.py lowers every entry point)
        passes_o[0, step] = jax.lax.fori_loop(0, BJ, job_body,
                                              jnp.int32(0))

        # placed / reason live whole in VMEM (tiny); write this block's
        # row at a dynamic offset — blocked specs would need a
        # sublane-divisible leading block dim the (NB, S, BJ) shape lacks
        placed_o[pl.ds(step, 1)] = placed_s[...][None]
        reason_o[pl.ds(step, 1)] = reason_s[...][None]

        @pl.when(step == nb - 1)
        def _():
            avail_o[...] = avail_s[...]
            cost_o[...] = cost_s[...]

    return kernel


def _fold_cluster(state: ClusterState, class_masks):
    """Node-axis tensors folded to [.., SUB, W] + per-class eligible
    counts; shared by the serial and streamed entry points."""
    N = state.num_nodes
    R = state.num_dims
    C = class_masks.shape[0]
    n_pad = -(-N // NODE_TILE) * NODE_TILE
    W = n_pad // SUB
    availT = _pad_to(state.avail.T.astype(jnp.int32), n_pad, 1, 0)
    avail3 = availT.reshape(R, SUB, W)
    cost2 = _pad_to(state.cost.astype(jnp.int32)[None, :], n_pad, 1,
                    COST_INF).reshape(1, SUB, W)
    elig = class_masks.astype(jnp.int32) * state.alive.astype(jnp.int32)
    elig3 = _pad_to(elig, n_pad, 1, 0).reshape(C, SUB, W)
    nelig = jnp.sum(elig, axis=1, dtype=jnp.int32)[:, None]  # [C, 1]
    cputot = jnp.maximum(state.total[:, DIM_CPU], 1).astype(jnp.float32)
    cputot3 = _pad_to(cputot[None, :], n_pad, 1, 1.0).reshape(1, SUB, W)
    return n_pad, W, avail3, cost2, elig3, nelig, cputot3


def _job_scalars(req, node_num, time_limit, valid, job_class, C):
    return jnp.concatenate([
        req.astype(jnp.int32),
        node_num.astype(jnp.int32)[:, None],
        time_limit.astype(jnp.int32)[:, None],
        valid.astype(jnp.int32)[:, None],
        jnp.clip(job_class.astype(jnp.int32), 0, C - 1)[:, None],
    ], axis=1)                                         # [J, R + 4]


#: the kernels' names as a device trace shows them (the custom call's
#: HLO name, plus XLA's ".N"): what a per-kernel metric keys on, so a
#: refactor of the functions around them does not rename them
KERNEL_SERIAL = "crane_greedy_serial"
KERNEL_STREAMED = "crane_greedy_streamed"


def _launch(job_p, nelig, avail3, cost2, elig3, cputot3,
            S, NB, BJ, K, R, W, C, interpret, name):
    """pallas_call plumbing shared by both entry points.  job_p is
    [S, R+4, NB*BJ] (scalar axis innermost so the SMEM BlockSpec
    (S, R+4, BJ) slices the job axis per grid step); returns raw
    blocked outputs, final ledgers and ``Placements.passes``."""
    def vmem_full():
        return pl.BlockSpec(memory_space=pltpu.VMEM)

    out_shapes = (
        jax.ShapeDtypeStruct((NB, S, BJ), jnp.int32),     # placed
        jax.ShapeDtypeStruct((NB, S, K, BJ), jnp.int32),  # chosen
        jax.ShapeDtypeStruct((NB, S, BJ), jnp.int32),     # reason
        jax.ShapeDtypeStruct((R, SUB, W), jnp.int32),     # avail out
        jax.ShapeDtypeStruct((1, SUB, W), jnp.int32),     # cost out
        jax.ShapeDtypeStruct((1, NB), jnp.int32),         # passes run
    )
    *outs, passes = pl.pallas_call(
        _make_kernel(BJ, K, R, W, S),
        grid=(NB,),
        in_specs=[pl.BlockSpec((S, R + 4, BJ), lambda i: (0, 0, i),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((C, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
                  vmem_full(), vmem_full(), vmem_full(), vmem_full()],
        out_shape=out_shapes,
        out_specs=(vmem_full(),
                   # the chosen ids leave VMEM block by block: whole,
                   # [NB, S, K, BJ] is 35.7 MB at the north-star shape
                   # and K = 64
                   pl.BlockSpec((1, S, K, BJ), lambda i: (i, 0, 0, 0)),
                   vmem_full(), vmem_full(), vmem_full(),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[
            pltpu.VMEM((R, SUB, W), jnp.int32),
            pltpu.VMEM((1, SUB, W), jnp.int32),
            pltpu.VMEM((S, BJ), jnp.int32),
            pltpu.VMEM((S, BJ), jnp.int32),
            pltpu.VMEM((S, SUB, W), jnp.int32),     # masked costs
        ],
        interpret=interpret,
        name=name,
    )(job_p, nelig, avail3, cost2, elig3, cputot3)
    return (*outs, jnp.stack([jnp.sum(passes), jnp.int32(NB * BJ * K)]))


def _solve_serial_impl(state: ClusterState, req, node_num, time_limit,
                       valid, job_class, class_masks,
                       max_nodes: int = 1, block_jobs: int = 256,
                       interpret: bool = False
                       ) -> tuple[Placements, ClusterState]:
    J = req.shape[0]
    N = state.num_nodes
    R = state.num_dims
    K = min(max_nodes, N)
    BJ = block_jobs

    j_pad = -(-max(J, 1) // BJ) * BJ
    NB = j_pad // BJ
    C = class_masks.shape[0]
    n_pad, W, avail3, cost2, elig3, nelig, cputot3 = _fold_cluster(
        state, class_masks)

    job_p = _pad_to(_job_scalars(req, node_num, time_limit, valid,
                                 job_class, C), j_pad, 0, 0).T[None]

    placed, chosen, reason, avail_f, cost_f, passes = _launch(
        job_p, nelig, avail3, cost2, elig3, cputot3,
        1, NB, BJ, K, R, W, C, interpret, KERNEL_SERIAL)

    placed = placed.reshape(-1)[:J].astype(bool)
    nodes = chosen.reshape(NB, K, BJ).transpose(0, 2, 1).reshape(-1, K)[:J]
    reason = reason.reshape(-1)[:J]
    avail_new = avail_f.reshape(R, n_pad)[:, :N].T
    cost_new = cost_f.reshape(n_pad)[:N]
    new_state = state.replace(avail=avail_new, cost=cost_new)
    return Placements(placed=placed, nodes=nodes, reason=reason,
                      passes=passes), new_state


# jit twins: the donating variant hands the ClusterState's device
# buffers to XLA for reuse (avail/cost are rewritten in place on TPU;
# total/alive alias straight through).  Callers opt in per call via
# ``donate=`` — a donated state must not be touched again, so only the
# scheduler's cycle loop (which always adopts the returned state) asks
# for it; parity tests re-solve from the same state and must keep the
# non-donating twin.
_SERIAL_STATICS = ("max_nodes", "block_jobs", "interpret")
_solve_serial_jit = _instrument_jit(
    "solve_pallas_serial", functools.partial(
        jax.jit, static_argnames=_SERIAL_STATICS)(_solve_serial_impl))
_solve_serial_donate = _instrument_jit(
    "solve_pallas_serial_donating", functools.partial(
        jax.jit, static_argnames=_SERIAL_STATICS,
        donate_argnums=(0,))(_solve_serial_impl))


def solve_greedy_pallas(state: ClusterState, req, node_num, time_limit,
                        valid, job_class, class_masks,
                        max_nodes: int = 1, block_jobs: int = 256,
                        interpret: bool = False, donate: bool = False
                        ) -> tuple[Placements, ClusterState]:
    """Single-kernel greedy solve (one serial job stream).  Same
    contract as ``solve_greedy`` with eligibility given as
    (job_class, class_masks); returns (Placements, new ClusterState).
    ``donate=True`` donates the input state's buffers (see twins)."""
    fn = _solve_serial_donate if donate else _solve_serial_jit
    return fn(state, req, node_num, time_limit, valid, job_class,
              class_masks, max_nodes=max_nodes, block_jobs=block_jobs,
              interpret=interpret)


def _solve_streamed_impl(state: ClusterState, req, node_num, time_limit,
                         valid, job_class, class_masks, stream_of_class,
                         max_nodes: int, block_jobs: int, num_streams: int,
                         stream_len: int, interpret: bool
                         ) -> tuple[Placements, ClusterState]:
    """S-stream greedy solve: jobs are regrouped per stream (classes
    were packed into streams host-side; disjointness verified there),
    solved with the streamed kernel, and scattered back to the
    original order.  Bit-identical to the serial path whenever the
    streams' class masks are pairwise disjoint."""
    J = req.shape[0]
    N = state.num_nodes
    R = state.num_dims
    K = min(max_nodes, N)
    BJ = block_jobs
    S = num_streams
    L = stream_len                    # padded per-stream length
    NB = L // BJ
    C = class_masks.shape[0]
    n_pad, W, avail3, cost2, elig3, nelig, cputot3 = _fold_cluster(
        state, class_masks)

    cls = jnp.clip(job_class.astype(jnp.int32), 0, C - 1)
    stream = stream_of_class[cls]                       # [J]
    order = jnp.argsort(stream, stable=True)            # orig ids, stream-major
    sorted_stream = stream[order]
    # slot within stream = position among same-stream jobs (original
    # relative order preserved — the within-class greedy order)
    slot = (jnp.arange(J, dtype=jnp.int32)
            - jnp.searchsorted(sorted_stream,
                               sorted_stream).astype(jnp.int32))
    lin = sorted_stream * L + slot                      # [J] flat slots

    scal = _job_scalars(req, node_num, time_limit, valid, cls, C)
    job_p = jnp.zeros((S * L, R + 4), jnp.int32).at[lin].set(
        scal[order], mode="drop")
    job_p = job_p.reshape(S, L, R + 4).transpose(0, 2, 1)

    placed, chosen, reason, avail_f, cost_f, passes = _launch(
        job_p, nelig, avail3, cost2, elig3, cputot3,
        S, NB, BJ, K, R, W, C, interpret, KERNEL_STREAMED)

    # [NB, S, ..] -> [S, NB, ..] -> flat [S * L, ..], then gather each
    # original job's slot
    placed_f = placed.transpose(1, 0, 2).reshape(-1)
    reason_f = reason.transpose(1, 0, 2).reshape(-1)
    chosen_f = chosen.transpose(1, 0, 3, 2).reshape(-1, K)
    inv = jnp.zeros(J, jnp.int32).at[order].set(lin, mode="drop")
    placed_j = placed_f[inv].astype(bool)
    reason_j = reason_f[inv]
    nodes_j = chosen_f[inv]

    avail_new = avail_f.reshape(R, n_pad)[:, :N].T
    cost_new = cost_f.reshape(n_pad)[:N]
    new_state = state.replace(avail=avail_new, cost=cost_new)
    return (Placements(placed=placed_j, nodes=nodes_j, reason=reason_j,
                       passes=passes), new_state)


_STREAM_STATICS = ("max_nodes", "block_jobs", "num_streams",
                   "stream_len", "interpret")
_solve_streamed_jit = _instrument_jit(
    "solve_pallas_streamed", functools.partial(
        jax.jit, static_argnames=_STREAM_STATICS)(_solve_streamed_impl))
_solve_streamed_donate = _instrument_jit(
    "solve_pallas_streamed_donating", functools.partial(
        jax.jit, static_argnames=_STREAM_STATICS,
        donate_argnums=(0,))(_solve_streamed_impl))


def _solve_streamed(state, req, node_num, time_limit, valid, job_class,
                    class_masks, stream_of_class, max_nodes: int,
                    block_jobs: int, num_streams: int, stream_len: int,
                    interpret: bool, donate: bool = False):
    fn = _solve_streamed_donate if donate else _solve_streamed_jit
    return fn(state, req, node_num, time_limit, valid, job_class,
              class_masks, stream_of_class, max_nodes=max_nodes,
              block_jobs=block_jobs, num_streams=num_streams,
              stream_len=stream_len, interpret=interpret)


def plan_streams(job_class, class_masks, max_streams: int = 4,
                 block_jobs: int = 256, known_disjoint: bool = False):
    """Host-side stream planner.  Returns (stream_of_class[C],
    num_streams, stream_len) when the class masks are pairwise
    disjoint and the packing is worthwhile, else None (caller uses the
    serial kernel).  Classes are LPT-packed into at most
    ``max_streams`` streams balanced by job count; stream_len is the
    max stream job count rounded up to a block multiple (and to a
    power-of-two-ish quantum to bound recompiles across cycles).

    ``known_disjoint=True`` skips the [C, N] overlap reduction — the
    scheduler's mask table proves disjointness once per epoch, so
    steady-state cycles pay only the O(C) LPT pack here."""
    cm = np.asarray(class_masks).astype(bool)
    C = cm.shape[0]
    if C < 2 or max_streams < 2:
        return None
    if not known_disjoint and (cm.sum(axis=0) > 1).any():
        return None                 # overlapping eligibility: serial
    counts = np.bincount(np.asarray(job_class), minlength=C)[:C]
    S = min(max_streams, int((counts > 0).sum()))
    if S < 2:
        return None
    # LPT: biggest class first onto the lightest stream
    load = np.zeros(S, np.int64)
    stream_of_class = np.zeros(C, np.int32)
    for c in np.argsort(-counts):
        s = int(np.argmin(load))
        stream_of_class[c] = s
        load[s] += int(counts[c])
    longest = int(load.max())
    total = int(counts.sum())
    if longest * 2 > total:
        return None                 # too skewed: streams mostly padding
    # quantize the padded stream length to 8-block steps: padding
    # stays under 8 * block_jobs slots while shifting workloads still
    # reuse a bounded set of compiled kernels
    nb = -(-max(longest, 1) // block_jobs)
    stream_len = (-(-nb // 8) * 8) * block_jobs
    return jnp.asarray(stream_of_class), S, stream_len


def solve_greedy_pallas_auto(state: ClusterState, req, node_num,
                             time_limit, valid, job_class, class_masks,
                             max_nodes: int = 1, block_jobs: int = 256,
                             max_streams: int = 4,
                             interpret: bool = False,
                             donate: bool = False, plan=None,
                             return_plan: bool = False):
    """Dispatch: streamed kernel when eligibility classes are disjoint
    and balanced enough to profit, serial single-kernel otherwise.
    Semantics are identical either way (tests/test_pallas_parity.py).

    ``plan`` short-circuits the host-side planner with a precomputed
    ``plan_streams`` result (the scheduler caches it per mask-table
    epoch so steady-state cycles skip the [C, N] host reduction).

    ``return_plan=True`` appends the plan this call *actually ran with*
    (None for the serial kernel) to the result tuple, so callers that
    pass ``plan=None`` — letting the internal planner decide — can
    still record the true stream count instead of guessing."""
    if plan is None:
        plan = plan_streams(job_class, class_masks,
                            max_streams=max_streams,
                            block_jobs=block_jobs)
    if plan is None:
        out = solve_greedy_pallas(
            state, req, node_num, time_limit, valid, job_class,
            class_masks, max_nodes=max_nodes, block_jobs=block_jobs,
            interpret=interpret, donate=donate)
        return (*out, None) if return_plan else out
    stream_of_class, S, L = plan
    out = _solve_streamed(
        state, req, node_num, time_limit, valid, job_class, class_masks,
        stream_of_class, max_nodes=max_nodes, block_jobs=block_jobs,
        num_streams=S, stream_len=L, interpret=interpret, donate=donate)
    return (*out, plan) if return_plan else out


def solve_greedy_pallas_from_batch(state: ClusterState, jobs: JobBatch,
                                   max_nodes: int = 1,
                                   interpret: bool = False,
                                   donate: bool = False,
                                   block_jobs: int = 256,
                                   max_streams: int = 4,
                                   return_plan: bool = False):
    """Adapter for callers holding a dense part_mask (tests, small
    cycles): compress to eligibility classes host-side, then run the
    auto dispatch — real scheduler cycles get the S-stream kernel
    whenever the compressed classes are disjoint, not the serial one.
    Not for the 100k x 10k hot path — pass classes directly."""
    job_class, class_masks = classes_from_part_mask(jobs.part_mask)
    return solve_greedy_pallas_auto(
        state, jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
        jnp.asarray(job_class), jnp.asarray(class_masks),
        max_nodes=max_nodes, block_jobs=block_jobs,
        max_streams=max_streams, interpret=interpret, donate=donate,
        return_plan=return_plan)
