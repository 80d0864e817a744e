"""Bit-parity of the single-kernel Pallas solve vs the scan solver.

The Pallas kernel (models/pallas_solver.py) is the TPU hot path for the
greedy cycle; ``solve_greedy`` (models/solver.py) is the
semantics-defining reference.  Placements, reasons, chosen nodes, and
the post-solve (avail, cost) ledgers must all be bit-identical —
including cost-tie pileups (ties break to the lowest node index),
gangs, dead nodes, infeasible and invalid jobs, and multi-class
eligibility.  Runs in Pallas interpret mode on the CPU test platform.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from cranesched_tpu.models.pallas_solver import (
    _solve_streamed,
    classes_from_part_mask,
    plan_streams,
    solve_greedy_pallas,
    solve_greedy_pallas_auto,
    solve_greedy_pallas_from_batch,
)
from cranesched_tpu.models.solver import (
    REASON_CONSTRAINT,
    REASON_NONE,
    REASON_RESOURCE,
    JobBatch,
    make_cluster_state,
    solve_greedy,
)
from cranesched_tpu.ops.resources import ResourceLayout
from cranesched_tpu.testing.oracle import solve_greedy_oracle


def _random_problem(rng, num_jobs, num_nodes, num_classes=3,
                    tie_costs=False, dead_frac=0.1, big_frac=0.1,
                    max_nodes=3):
    lay = ResourceLayout()
    total = np.stack([
        lay.encode(cpu=int(rng.integers(4, 33)),
                   mem_bytes=int(rng.integers(8, 65)) << 30,
                   is_capacity=True)
        for _ in range(num_nodes)])
    alive = rng.random(num_nodes) > dead_frac
    cost = (np.zeros(num_nodes, np.float32) if tie_costs
            else rng.integers(0, 50, num_nodes).astype(np.float32))
    state = make_cluster_state(total.copy(), total, alive, cost)

    req = np.stack([
        lay.encode(cpu=float(rng.integers(1, 9)),
                   mem_bytes=int(rng.integers(1, 9)) << 30)
        for _ in range(num_jobs)])
    big = rng.random(num_jobs) < big_frac
    req[big] = lay.encode(cpu=1000.0, mem_bytes=1 << 40)  # never fits
    node_part = rng.integers(0, num_classes, num_nodes)
    job_part = rng.integers(0, num_classes, num_jobs)
    part_mask = job_part[:, None] == node_part[None, :]
    node_num = rng.integers(1, max_nodes + 2, num_jobs)  # some > max
    valid = rng.random(num_jobs) > 0.05
    jobs = JobBatch(
        req=jnp.asarray(req),
        node_num=jnp.asarray(node_num, jnp.int32),
        time_limit=jnp.asarray(rng.integers(60, 86400, num_jobs),
                               jnp.int32),
        part_mask=jnp.asarray(part_mask),
        valid=jnp.asarray(valid))
    return state, jobs


def _assert_same_as_scan(got, new_state, state, jobs, max_nodes):
    """Placements and ledgers of a kernel against ``solve_greedy``'s."""
    ref, ref_state = solve_greedy(state, jobs, max_nodes=max_nodes)
    for a, b in ((got.placed, ref.placed), (got.nodes, ref.nodes),
                 (got.reason, ref.reason),
                 (new_state.avail, ref_state.avail),
                 (new_state.cost, ref_state.cost)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_bit_identical(state, jobs, max_nodes):
    p_pl, s_pl = solve_greedy_pallas_from_batch(
        state, jobs, max_nodes=max_nodes, interpret=True)
    _assert_same_as_scan(p_pl, s_pl, state, jobs, max_nodes)


@pytest.mark.parametrize("seed", range(6))
def test_random_parity(seed):
    rng = np.random.default_rng(seed)
    state, jobs = _random_problem(rng, num_jobs=70, num_nodes=50)
    _assert_bit_identical(state, jobs, max_nodes=3)


def test_tie_pileup_parity():
    """All costs equal: every selection is a pure lowest-index
    tie-break, maximizing disagreement if tie order diverges."""
    rng = np.random.default_rng(7)
    state, jobs = _random_problem(rng, num_jobs=60, num_nodes=40,
                                  tie_costs=True, num_classes=1,
                                  dead_frac=0.0)
    _assert_bit_identical(state, jobs, max_nodes=2)


def test_oversubscribed_cluster_parity():
    """More demand than capacity: exercises the infeasible tail where
    REASON_RESOURCE/REASON_CONSTRAINT decisions dominate."""
    rng = np.random.default_rng(11)
    state, jobs = _random_problem(rng, num_jobs=200, num_nodes=10,
                                  big_frac=0.3)
    _assert_bit_identical(state, jobs, max_nodes=3)


def test_non_multiple_block_and_node_padding():
    """Job count not a multiple of the block, node count far from the
    1024 padding quantum."""
    rng = np.random.default_rng(13)
    state, jobs = _random_problem(rng, num_jobs=33, num_nodes=17)
    _assert_bit_identical(state, jobs, max_nodes=2)


def _assert_auto_bit_identical(state, jobs, max_nodes, max_streams=4):
    """The auto dispatcher (streamed kernel when classes are disjoint)
    must match the scan solver bit-for-bit as well."""
    job_class, masks = classes_from_part_mask(np.asarray(jobs.part_mask))
    p_st, s_st = solve_greedy_pallas_auto(
        state, jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
        jnp.asarray(job_class), jnp.asarray(masks),
        max_nodes=max_nodes, max_streams=max_streams, interpret=True)
    _assert_same_as_scan(p_st, s_st, state, jobs, max_nodes)


@pytest.mark.parametrize("seed", range(6))
def test_streamed_parity_disjoint_classes(seed):
    """Deployment-like shape: disjoint partitions -> the auto path takes the
    S-stream kernel; placements must still be bit-identical to the
    scan solver."""
    rng = np.random.default_rng(seed)
    state, jobs = _random_problem(rng, num_jobs=90, num_nodes=60,
                                  num_classes=4)
    job_class, masks = classes_from_part_mask(np.asarray(jobs.part_mask))
    assert plan_streams(job_class, masks) is not None, \
        "expected the streamed plan for disjoint balanced classes"
    _assert_auto_bit_identical(state, jobs, max_nodes=2)


def test_streamed_parity_tie_pileup():
    """All costs tied inside each class: lowest-index tie-breaks must
    survive the stream regroup/scatter round-trip."""
    rng = np.random.default_rng(5)
    state, jobs = _random_problem(rng, num_jobs=64, num_nodes=48,
                                  tie_costs=True, num_classes=4,
                                  dead_frac=0.0)
    _assert_auto_bit_identical(state, jobs, max_nodes=2)


def test_streamed_parity_skewed_classes_falls_back():
    """One dominant class: plan_streams refuses (padding would defeat
    the point) and auto must give the serial kernel's exact result."""
    rng = np.random.default_rng(9)
    state, jobs = _random_problem(rng, num_jobs=80, num_nodes=40,
                                  num_classes=3)
    job_class = np.zeros(80, np.int32)
    job_class[:5] = 1
    node_part = np.asarray(rng.integers(0, 2, 40))
    part_mask = job_class[:, None] == node_part[None, :]
    jobs = jobs.replace(part_mask=jnp.asarray(part_mask))
    jc, masks = classes_from_part_mask(part_mask)
    assert plan_streams(jc, masks) is None
    _assert_auto_bit_identical(state, jobs, max_nodes=2)


def test_streamed_parity_overlapping_classes_falls_back():
    """Overlapping eligibility (include-lists spanning partitions):
    the planner must detect the overlap and auto must fall back."""
    rng = np.random.default_rng(21)
    state, jobs = _random_problem(rng, num_jobs=50, num_nodes=30)
    pm = np.asarray(rng.random((50, 30)) > 0.35)
    jobs = jobs.replace(part_mask=jnp.asarray(pm))
    jc, masks = classes_from_part_mask(pm)
    assert plan_streams(jc, masks) is None
    _assert_auto_bit_identical(state, jobs, max_nodes=3)


def test_streamed_parity_gangs_and_dead_nodes():
    """Gang jobs (node_num up to K) on the streamed path, with dead
    nodes thinning each class."""
    rng = np.random.default_rng(17)
    state, jobs = _random_problem(rng, num_jobs=70, num_nodes=80,
                                  num_classes=4, dead_frac=0.2,
                                  max_nodes=3)
    _assert_auto_bit_identical(state, jobs, max_nodes=3)


# ---------------------------------------------------------------------------
# each kernel by name (the auto dispatch above picks one from the classes):
# inputs the random problem of this file does not draw: nodes partly in use,
# fractional costs, gangs up to K = 4, a cluster that saturates, a spread
# regime in which every placement reorders the costs
# ---------------------------------------------------------------------------

KERNEL_BLOCK = 8        # jobs a block: the interpreter pays a slot, not a job


def _assert_kernel_bit_identical(state, jobs, max_nodes, kernel):
    """``serial``: the one-job-a-slot kernel whatever the classes are.
    ``streamed``: one stream a class, which needs the classes disjoint
    (the caller's business) and takes no advice from ``plan_streams``."""
    job_class, masks = classes_from_part_mask(np.asarray(jobs.part_mask))
    args = (state, jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
            jnp.asarray(job_class), jnp.asarray(masks))
    if kernel == "serial":
        got, new_state = solve_greedy_pallas(
            *args, max_nodes=max_nodes, block_jobs=KERNEL_BLOCK,
            interpret=True)
    else:
        C = masks.shape[0]
        assert 2 <= C <= 4 and not (masks.sum(axis=0) > 1).any()
        longest = int(np.bincount(job_class, minlength=C).max())
        got, new_state = _solve_streamed(
            *args, jnp.arange(C, dtype=jnp.int32), max_nodes=max_nodes,
            block_jobs=KERNEL_BLOCK, num_streams=C,
            stream_len=-(-longest // KERNEL_BLOCK) * KERNEL_BLOCK,
            interpret=True)
    _assert_same_as_scan(got, new_state, state, jobs, max_nodes)
    return got


def _partitioned(jobs, num_nodes, parts, rng):
    """The batch with eligibility redrawn as ``parts`` disjoint partitions."""
    node_part = rng.integers(0, parts, num_nodes)
    job_part = rng.integers(0, parts, jobs.valid.shape[0])
    return jobs.replace(part_mask=jnp.asarray(
        job_part[:, None] == node_part[None, :]))


@pytest.mark.parametrize("max_nodes,seed", [
    (4, 0), (4, 1), (4, 2), (4, 3), (16, 0), (32, 0), (64, 0)])
@pytest.mark.parametrize("kernel", ["serial", "streamed"])
def test_used_nodes_fractional_costs(kernel, seed, max_nodes):
    """Widths 1 to the bound mixed in one batch, on nodes that are
    partly used; ten nodes a partition at K = 4, 2.5 K beyond it, so
    that the widest gangs are placed as well as refused."""
    from test_sharded_parity import _random_problem as used_problem
    rng = np.random.default_rng(seed)
    num_nodes = max(40, 10 * max_nodes)
    state, jobs = used_problem(rng, num_jobs=100, num_nodes=num_nodes,
                               max_nodes=max_nodes)
    if kernel == "streamed":
        jobs = _partitioned(jobs, num_nodes, 4, rng)
    got = _assert_kernel_bit_identical(state, jobs, max_nodes, kernel)
    wide = np.asarray(jobs.node_num) > max_nodes // 2
    assert np.asarray(got.placed)[wide].any()
    assert not np.asarray(got.placed)[wide].all()


@pytest.mark.parametrize("kernel", ["serial", "streamed"])
def test_gangs_saturate_the_cluster(kernel):
    """Whole-node gangs of 2, 1, 3, ... on six nodes a partition: the
    first three take all six, every later one reads a cluster with
    nothing left."""
    lay = ResourceLayout()
    parts = 1 if kernel == "serial" else 2
    N, J = 6 * parts, 12 * parts
    total = np.tile(lay.encode(cpu=8, is_capacity=True), (N, 1))
    state = make_cluster_state(total.copy(), total, np.ones(N, bool),
                               np.arange(N, dtype=np.float32))
    jobs = JobBatch(
        req=jnp.asarray(np.tile(lay.encode(cpu=8), (J, 1))),
        node_num=jnp.asarray([2, 1, 3, 1, 2, 1] * (J // 6), jnp.int32),
        time_limit=jnp.full(J, 3600, jnp.int32),
        part_mask=jnp.asarray((np.arange(J) // 12)[:, None]
                              == (np.arange(N) // 6)[None, :]),
        valid=jnp.ones(J, bool))
    got = _assert_kernel_bit_identical(state, jobs, 4, kernel)
    placed = np.asarray(got.placed).reshape(parts, 12)
    assert placed[:, :3].all() and not placed[:, 3:].any()


@pytest.mark.parametrize("kernel", ["serial", "streamed"])
def test_spread_regime_over_two_partitions(kernel):
    """Distinct costs and a large increment a placement: the cheapest
    node is another one after nearly every job."""
    lay = ResourceLayout()
    rng = np.random.default_rng(3)
    N, J = 32, 64
    total = np.tile(lay.encode(cpu=64, is_capacity=True), (N, 1))
    state = make_cluster_state(total.copy(), total, np.ones(N, bool),
                               rng.random(N).astype(np.float32))
    jpart = rng.integers(0, 2, J)
    jobs = JobBatch(
        req=jnp.asarray(np.tile(lay.encode(cpu=4), (J, 1))),
        node_num=jnp.asarray(rng.integers(1, 3, J), jnp.int32),
        time_limit=jnp.full(J, 36000, jnp.int32),
        part_mask=jnp.asarray(jpart[:, None] == (np.arange(N) % 2)[None, :]),
        valid=jnp.ones(J, bool))
    got = _assert_kernel_bit_identical(state, jobs, 2, kernel)
    assert np.asarray(got.placed).all()
    # spread: no node of a partition is taken twice before all were once
    first = np.asarray(got.nodes)[:, 0]
    for part in (0, 1):
        firsts = first[jpart == part][:8]
        assert len(set(firsts.tolist())) == len(firsts)


def test_classes_from_part_mask_roundtrip():
    rng = np.random.default_rng(3)
    pm = rng.random((20, 9)) > 0.4
    job_class, masks = classes_from_part_mask(pm)
    np.testing.assert_array_equal(masks[job_class], pm)


# ---------------------------------------------------------------------------
# the selection passes a slot runs (ISSUE 32): pass 0 always, then a slot
# stops at the widest node_num among its valid streams and at the first
# infinite minimum.  Every edge against the numpy oracle and solve_greedy bit
# for bit, and the kernel's pass counter against a count made by hand.
# ---------------------------------------------------------------------------

EDGE_PARTS = 4          # one stream a partition at S = 4
EDGE_BLOCK = 8          # jobs a block


def _edge_sizes(K):
    """(nodes a partition: node i of it has i + 1 cpus free, so at least
    K + 2 of them; a stream's length in jobs: whole blocks that hold the
    K - 1 jobs of the longest case).  10 and 16 up to K = 8."""
    return max(10, K + 2), max(16, -(-K // EDGE_BLOCK) * EDGE_BLOCK)


def _edge_case(name, K):
    """(jobs, passes run AFTER pass 0, (placed, reason) a job).  A job is
    (partition, cpus, node_num, valid); a job of c cpus has
    per - c + 1 feasible nodes in an untouched partition, one of `big`
    cpus none, and
    partition 3 has ONE node alive.  The streamed kernel puts the n-th
    job of every partition into slot n, the serial kernel one job a
    slot; in every case here the jobs that share a slot run as many
    further passes together as they would alone, so the count is one
    number.  Every slot, padding too, runs pass 0: the test adds one a
    slot."""
    per, _ = _edge_sizes(K)
    big = per + 6
    yes, no = (True, REASON_NONE), (False, REASON_RESOURCE)
    never = (False, REASON_CONSTRAINT)
    if name == "one_to_nn_minus_1_feasible":
        # nn = K, f = 1 .. K-1 feasible nodes: passes 0 .. f run, pass f
        # reads the first infinite minimum.  One partition, so a slot
        # holds one job in either kernel
        jobs = [(0, per - f + 1, K, True) for f in range(1, K)]
        count = sum(f for f in range(1, K))
        return jobs, count, [no] * (K - 1)
    if name == "nn_equals_K_all_feasible":
        return [(1, 1, K, True)], K - 1, [yes]
    if name == "no_job_of_the_block_feasible":
        # 8 jobs, two a partition, widths mixed, `big` cpus each: pass 0
        # says so; the last asks partition 3 for K of its one live node
        jobs = [(j % 3, big, 1 + j % K, True) for j in range(6)]
        jobs += [(3, big, 1, True), (3, big, K, True)]
        return jobs, 0, [no] * 7 + [never]
    if name == "wide_feasible_beside_narrow_infeasible":
        return [(0, 1, K, True), (1, big, 1, True)], K - 1, [yes, no]
    if name == "narrow_feasible_beside_wide_infeasible":
        return [(0, 1, 1, True), (1, big, K, True)], 0, [yes, no]
    if name == "invalid_slot_and_padded_stream":
        # slot 0: three invalidated rows (two of them K wide, one of
        # those feasible had it been valid) and a stream with no job;
        # then a gang of 2 that fits, in slot 1 of its stream
        jobs = [(0, 1, K, False), (1, 1, 1, False), (2, big, K, False),
                (0, 1, 2, True)]
        return jobs, 1, [never] * 3 + [yes]
    if name == "fewer_eligible_nodes_than_nn":
        # partition 3 has one node alive: pass 0 finds it, pass 1 nothing
        return [(3, 1, 2, True)], 1, [never]
    if name == "wider_than_the_bound":
        # refused whatever the minima read: pass 0 alone, though every
        # node fits; the gang of 2 behind it in the stream pays its own
        return [(0, 1, K + 1, True), (0, 1, 2, True)], 1, [no, yes]
    raise KeyError(name)


EDGE_CASES = [
    "one_to_nn_minus_1_feasible", "nn_equals_K_all_feasible",
    "no_job_of_the_block_feasible", "wide_feasible_beside_narrow_infeasible",
    "narrow_feasible_beside_wide_infeasible",
    "invalid_slot_and_padded_stream", "fewer_eligible_nodes_than_nn",
    "wider_than_the_bound"]


@pytest.mark.parametrize("K", [2, 4, 8, 16, 32, 64])
@pytest.mark.parametrize("kernel", ["serial", "streamed"])
@pytest.mark.parametrize("case", EDGE_CASES)
def test_selection_pass_edges(case, kernel, K):
    jobs, want_passes, want_jobs = _edge_case(case, K)
    per, stream_len = _edge_sizes(K)
    lay = ResourceLayout()
    N = EDGE_PARTS * per
    node_part = np.arange(N) // per
    total = np.tile(lay.encode(cpu=per + 6, mem_bytes=64 << 30,
                               is_capacity=True), (N, 1))
    avail = np.stack([lay.encode(cpu=int(i % per) + 1,
                                 mem_bytes=64 << 30, is_capacity=True)
                      for i in range(N)])
    alive = np.ones(N, bool)
    alive[3 * per + 1:] = False
    cost = (np.arange(N) % 3).astype(np.float32)   # ties inside a partition
    part, cpus, node_num, valid = (np.asarray(x) for x in zip(*jobs))
    req = np.stack([lay.encode(cpu=float(c), mem_bytes=1 << 30)
                    for c in cpus])
    time_limit = np.full(len(jobs), 3600, np.int32)
    part_mask = part[:, None] == node_part[None, :]
    class_masks = np.arange(EDGE_PARTS)[:, None] == node_part[None, :]

    state = make_cluster_state(avail.copy(), total, alive, cost)
    args = (state, jnp.asarray(req), jnp.asarray(node_num, jnp.int32),
            jnp.asarray(time_limit), jnp.asarray(valid),
            jnp.asarray(part, jnp.int32), jnp.asarray(class_masks))
    if kernel == "serial":
        got, new_state = solve_greedy_pallas(
            *args, max_nodes=K, block_jobs=EDGE_BLOCK, interpret=True)
        slots = -(-len(jobs) // EDGE_BLOCK) * EDGE_BLOCK
    else:
        got, new_state = _solve_streamed(
            *args, jnp.arange(EDGE_PARTS, dtype=jnp.int32), max_nodes=K,
            block_jobs=EDGE_BLOCK, num_streams=EDGE_PARTS,
            stream_len=stream_len, interpret=True)
        slots = stream_len

    o_placed, o_nodes, o_reason, o_avail, o_cost = solve_greedy_oracle(
        avail.copy(), total, alive, cost, req, node_num, time_limit,
        part_mask, valid, K)
    ref, ref_state = solve_greedy(state, JobBatch(
        req=args[1], node_num=args[2], time_limit=args[3],
        part_mask=jnp.asarray(part_mask), valid=args[4]), max_nodes=K)
    for want in ((o_placed, o_nodes, o_reason, o_avail, o_cost),
                 (ref.placed, ref.nodes, ref.reason, ref_state.avail,
                  ref_state.cost)):
        for a, b in zip((got.placed, got.nodes, got.reason,
                         new_state.avail, new_state.cost), want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert list(zip(o_placed.tolist(), o_reason.tolist())) == want_jobs
    if not o_placed.any():      # nothing placed: the ledgers are untouched
        np.testing.assert_array_equal(np.asarray(new_state.avail), avail)
    assert np.asarray(got.passes).tolist() == [slots + want_passes,
                                                slots * K]
