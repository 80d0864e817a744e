"""Native C++ greedy solver ≡ JAX solver, bit for bit — placements,
reasons, availability, and the fixed-point cost ledger."""

import numpy as np
import pytest

from cranesched_tpu.models.solver import solve_greedy
from cranesched_tpu.utils import native

from test_sharded_parity import _random_problem


@pytest.mark.parametrize("seed", range(6))
def test_native_matches_jax_random(seed):
    rng = np.random.default_rng(seed)
    state, jobs = _random_problem(rng, num_jobs=120, num_nodes=50,
                                  max_nodes=4)
    p_ref, s_ref = solve_greedy(state, jobs, max_nodes=4)
    out = native.solve_greedy_native(
        np.asarray(state.avail), np.asarray(state.total),
        np.asarray(state.alive), np.asarray(state.cost),
        np.asarray(jobs.req), np.asarray(jobs.node_num),
        np.asarray(jobs.time_limit), np.asarray(jobs.valid),
        max_nodes=4, mask=np.asarray(jobs.part_mask))
    assert out is not None
    placed, nodes, reason, avail, cost = out
    np.testing.assert_array_equal(placed, np.asarray(p_ref.placed))
    np.testing.assert_array_equal(nodes, np.asarray(p_ref.nodes))
    np.testing.assert_array_equal(reason, np.asarray(p_ref.reason))
    np.testing.assert_array_equal(avail, np.asarray(s_ref.avail))
    np.testing.assert_array_equal(cost, np.asarray(s_ref.cost))


def test_native_reason_for_oversized_gang_matches_jax():
    # node_num > max_nodes with enough eligible nodes: the JAX solver
    # reports RESOURCE (gang merely beyond the static bound), not
    # CONSTRAINT — the native path must agree in both modes
    import jax.numpy as jnp
    from cranesched_tpu.models.solver import (
        JobBatch, make_cluster_state)
    from cranesched_tpu.ops.resources import ResourceLayout
    lay = ResourceLayout()
    N = 6
    total = np.tile(lay.encode(cpu=8, is_capacity=True), (N, 1))
    state = make_cluster_state(total.copy(), total, np.ones(N, bool),
                               np.zeros(N, np.int32))
    req = np.tile(lay.encode(cpu=1.0), (2, 1)).astype(np.int32)
    nn = np.array([4, 1], np.int32)   # 4 > max_nodes=2
    tl = np.full(2, 60, np.int32)
    jobs = JobBatch(req=jnp.asarray(req), node_num=jnp.asarray(nn),
                    time_limit=jnp.asarray(tl),
                    part_mask=jnp.ones((2, N), bool),
                    valid=jnp.ones(2, bool))
    p_ref, s_ref = solve_greedy(state, jobs, max_nodes=2)
    for kwargs in (dict(mask=np.ones((2, N), np.uint8)),
                   dict(job_part=np.zeros(2, np.int32),
                        node_part=np.zeros(N, np.int32))):
        out = native.solve_greedy_native(
            total.copy(), total, np.ones(N, np.uint8),
            np.zeros(N, np.int32), req, nn, tl,
            np.ones(2, np.uint8), max_nodes=2, **kwargs)
        np.testing.assert_array_equal(out[2], np.asarray(p_ref.reason))
        np.testing.assert_array_equal(out[0], np.asarray(p_ref.placed))


def test_native_degenerate_inputs_fall_back_to_none():
    # unsupported shapes return None (caller falls back to JAX) instead
    # of raising
    lay_args = (np.zeros((4, 3), np.int32), np.zeros((4, 3), np.int32),
                np.ones(4, np.uint8), np.zeros(4, np.int32),
                np.zeros((2, 3), np.int32), np.ones(2, np.int32),
                np.ones(2, np.int32), np.ones(2, np.uint8))
    assert native.solve_greedy_native(
        *lay_args, max_nodes=1,
        job_part=np.array([-1, 0], np.int32),
        node_part=np.zeros(4, np.int32)) is None
    big = np.zeros((4, 17), np.int32)
    assert native.solve_greedy_native(
        big, big, np.ones(4, np.uint8), np.zeros(4, np.int32),
        np.zeros((2, 17), np.int32), np.ones(2, np.int32),
        np.ones(2, np.int32), np.ones(2, np.uint8), max_nodes=1,
        job_part=np.zeros(2, np.int32),
        node_part=np.zeros(4, np.int32)) is None


def test_native_sparse_partition_labels_remapped():
    # partition ids are labels, not indices: large sparse labels must
    # work (densely remapped), matching the dense-mask result
    rng = np.random.default_rng(9)
    state, jobs = _random_problem(rng, num_jobs=40, num_nodes=16,
                                  max_nodes=2, dead_frac=0.0)
    node_part = rng.choice([7, 500, 3999], 16).astype(np.int32)
    job_part = rng.choice([7, 500, 3999], 40).astype(np.int32)
    mask = (job_part[:, None] == node_part[None, :])
    args = (np.asarray(state.avail), np.asarray(state.total),
            np.asarray(state.alive), np.asarray(state.cost),
            np.asarray(jobs.req), np.asarray(jobs.node_num),
            np.asarray(jobs.time_limit), np.asarray(jobs.valid))
    a = native.solve_greedy_native(*args, max_nodes=2, mask=mask)
    b = native.solve_greedy_native(*args, max_nodes=2,
                                   job_part=job_part,
                                   node_part=node_part)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_native_partition_ids_equal_dense_mask():
    rng = np.random.default_rng(42)
    state, jobs = _random_problem(rng, num_jobs=60, num_nodes=32,
                                  max_nodes=2, dead_frac=0.0)
    # derive a partition structure and the equivalent dense mask
    node_part = rng.integers(0, 3, 32).astype(np.int32)
    job_part = rng.integers(0, 3, 60).astype(np.int32)
    mask = (job_part[:, None] == node_part[None, :])
    args = (np.asarray(state.avail), np.asarray(state.total),
            np.asarray(state.alive), np.asarray(state.cost),
            np.asarray(jobs.req), np.asarray(jobs.node_num),
            np.asarray(jobs.time_limit), np.asarray(jobs.valid))
    a = native.solve_greedy_native(*args, max_nodes=2, mask=mask)
    b = native.solve_greedy_native(*args, max_nodes=2,
                                   job_part=job_part,
                                   node_part=node_part)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_native_throughput_smoke():
    """The ordered-frontier walk must stay fast at a mid-size shape."""
    import time
    rng = np.random.default_rng(0)
    N, J = 2000, 20000
    from cranesched_tpu.ops.resources import ResourceLayout
    lay = ResourceLayout()
    total = np.stack([lay.encode(cpu=int(rng.integers(32, 129)),
                                 mem_bytes=int(rng.integers(64, 513)) << 30,
                                 is_capacity=True) for _ in range(N)])
    req = np.stack([lay.encode(cpu=float(rng.integers(1, 17)),
                               mem_bytes=int(rng.integers(1, 33)) << 30)
                    for _ in range(J)])
    node_part = rng.integers(0, 4, N).astype(np.int32)
    job_part = rng.integers(0, 4, J).astype(np.int32)
    t0 = time.perf_counter()
    out = native.solve_greedy_native(
        total.copy(), total, np.ones(N, np.uint8),
        rng.integers(0, 100, N).astype(np.int32),
        req, rng.integers(1, 3, J).astype(np.int32),
        rng.integers(60, 86400, J).astype(np.int32),
        np.ones(J, np.uint8), max_nodes=2,
        job_part=job_part, node_part=node_part)
    dt = time.perf_counter() - t0
    placed = out[0]
    assert placed.sum() > 0
    assert (out[3] >= 0).all()          # no oversubscription
    assert dt < 5.0                     # sanity bound, not a benchmark
