"""Sharded solver ≡ single-device solver, on the 8-device CPU mesh.

The conftest forces 8 virtual CPU devices; the node axis is sharded over
all of them and every placement decision must be bit-identical to
models.solver.solve_greedy (which itself is parity-tested against the
NumPy oracle)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from cranesched_tpu.models.pallas_solver import plan_streams
from cranesched_tpu.models.solver import (
    JobBatch,
    make_cluster_state,
    solve_greedy,
)
from cranesched_tpu.ops.resources import ResourceLayout
from cranesched_tpu.parallel import (
    make_node_mesh,
    shard_cluster_state,
    solve_greedy_sharded,
    solve_greedy_sharded_classes,
)


def _random_problem(rng, num_jobs, num_nodes, max_nodes, lay=None,
                    dead_frac=0.1):
    lay = lay or ResourceLayout()
    total = np.stack([
        lay.encode(cpu=int(rng.integers(8, 65)),
                   mem_bytes=int(rng.integers(16, 257)) << 30,
                   is_capacity=True)
        for _ in range(num_nodes)
    ])
    used = np.stack([
        lay.encode(cpu=float(rng.integers(0, 8)),
                   mem_bytes=int(rng.integers(0, 8)) << 30)
        for _ in range(num_nodes)
    ])
    avail = total - np.minimum(used, total)
    alive = rng.random(num_nodes) >= dead_frac
    cost = rng.random(num_nodes).astype(np.float32) * 10

    req = np.stack([
        lay.encode(cpu=float(rng.integers(1, 17)),
                   mem_bytes=int(rng.integers(1, 33)) << 30)
        for _ in range(num_jobs)
    ])
    node_num = rng.integers(1, max_nodes + 1,
                            size=num_jobs).astype(np.int32)
    time_limit = rng.integers(60, 86400, size=num_jobs).astype(np.int32)
    part_mask = rng.random((num_jobs, num_nodes)) > 0.2
    valid = rng.random(num_jobs) > 0.05

    state = make_cluster_state(avail, total, alive, cost)
    jobs = JobBatch(req=jnp.asarray(req), node_num=jnp.asarray(node_num),
                    time_limit=jnp.asarray(time_limit),
                    part_mask=jnp.asarray(part_mask),
                    valid=jnp.asarray(valid))
    return state, jobs


def _assert_same(p1, s1, p2, s2):
    np.testing.assert_array_equal(np.asarray(p1.placed),
                                  np.asarray(p2.placed))
    np.testing.assert_array_equal(np.asarray(p1.nodes), np.asarray(p2.nodes))
    np.testing.assert_array_equal(np.asarray(p1.reason),
                                  np.asarray(p2.reason))
    np.testing.assert_array_equal(np.asarray(s1.avail), np.asarray(s2.avail))
    np.testing.assert_allclose(np.asarray(s1.cost), np.asarray(s2.cost),
                               rtol=1e-6, atol=1e-6)


def test_mesh_has_8_devices():
    mesh = make_node_mesh()
    assert mesh.devices.size == 8


@pytest.mark.parametrize("seed", range(4))
def test_sharded_matches_single_device(seed):
    rng = np.random.default_rng(seed)
    state, jobs = _random_problem(rng, num_jobs=64, num_nodes=64,
                                  max_nodes=4)
    mesh = make_node_mesh()
    p_ref, s_ref = solve_greedy(state, jobs, max_nodes=4)
    p_sh, s_sh = solve_greedy_sharded(shard_cluster_state(state, mesh),
                                      jobs, mesh, max_nodes=4)
    _assert_same(p_ref, s_ref, p_sh, s_sh)


def test_sharded_gang_spanning_shards():
    # A gang of 8 on a 16-node cluster sharded 8 ways: winners must span
    # multiple shards and all shards must agree on the same selection.
    lay = ResourceLayout()
    total = np.tile(lay.encode(cpu=16, mem_bytes=32 << 30,
                               is_capacity=True), (16, 1))
    state = make_cluster_state(total.copy(), total, np.ones(16, bool),
                               np.arange(16, dtype=np.float32)[::-1].copy())
    jobs = JobBatch(
        req=jnp.asarray(np.tile(lay.encode(cpu=16, mem_bytes=32 << 30),
                                (1, 1))),
        node_num=jnp.asarray([8], jnp.int32),
        time_limit=jnp.asarray([3600], jnp.int32),
        part_mask=jnp.ones((1, 16), bool),
        valid=jnp.ones(1, bool))
    mesh = make_node_mesh()
    p_ref, s_ref = solve_greedy(state, jobs, max_nodes=8)
    p_sh, s_sh = solve_greedy_sharded(shard_cluster_state(state, mesh),
                                      jobs, mesh, max_nodes=8)
    _assert_same(p_ref, s_ref, p_sh, s_sh)
    # cost is descending by index, so the cheapest 8 are nodes 8..15
    assert sorted(np.asarray(p_sh.nodes)[0].tolist()) == list(range(8, 16))


def test_sharded_cost_tie_breaks_to_lowest_global_index():
    lay = ResourceLayout()
    total = np.tile(lay.encode(cpu=8, is_capacity=True), (16, 1))
    # all costs equal -> winners must be the lowest global indices
    state = make_cluster_state(total.copy(), total, np.ones(16, bool),
                               np.zeros(16, np.float32))
    jobs = JobBatch(
        req=jnp.asarray(np.tile(lay.encode(cpu=1), (3, 1))),
        node_num=jnp.asarray([3, 1, 2], jnp.int32),
        time_limit=jnp.asarray([60, 60, 60], jnp.int32),
        part_mask=jnp.ones((3, 16), bool),
        valid=jnp.ones(3, bool))
    mesh = make_node_mesh()
    p_ref, s_ref = solve_greedy(state, jobs, max_nodes=3)
    p_sh, s_sh = solve_greedy_sharded(shard_cluster_state(state, mesh),
                                      jobs, mesh, max_nodes=3)
    _assert_same(p_ref, s_ref, p_sh, s_sh)
    assert list(np.asarray(p_sh.nodes)[0]) == [0, 1, 2]


def test_sharded_second_cycle_reuses_sharded_state():
    # The state returned by a sharded solve feeds the next cycle directly.
    rng = np.random.default_rng(99)
    state, jobs = _random_problem(rng, num_jobs=32, num_nodes=32,
                                  max_nodes=2)
    mesh = make_node_mesh()
    p_ref1, s_ref = solve_greedy(state, jobs, max_nodes=2)
    p_sh1, s_sh = solve_greedy_sharded(shard_cluster_state(state, mesh),
                                       jobs, mesh, max_nodes=2)
    _, jobs2 = _random_problem(rng, num_jobs=32, num_nodes=32, max_nodes=2)
    p_ref2, s_ref2 = solve_greedy(s_ref, jobs2, max_nodes=2)
    p_sh2, s_sh2 = solve_greedy_sharded(s_sh, jobs2, mesh, max_nodes=2)
    _assert_same(p_ref2, s_ref2, p_sh2, s_sh2)


def _class_problem(seed, num_jobs, num_nodes, num_classes, max_nodes,
                   disjoint):
    """A scheduling problem with eligibility FACTORED into a class table
    (the form ``_solve_sharded`` serves a FactoredJobBatch with):
    (state, the factored arguments, the same batch with dense rows)."""
    rng = np.random.default_rng(seed)
    state, jobs = _random_problem(rng, num_jobs, num_nodes, max_nodes)
    job_class = rng.integers(0, num_classes,
                             size=num_jobs).astype(np.int32)
    if disjoint:
        owner = rng.integers(0, num_classes, size=num_nodes)
        class_masks = np.stack([owner == c for c in range(num_classes)])
    else:
        class_masks = rng.random((num_classes, num_nodes)) > 0.25
    factored = (jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
                jnp.asarray(job_class), jnp.asarray(class_masks))
    return state, factored, jobs.replace(
        part_mask=jnp.asarray(class_masks[job_class]))


@pytest.mark.parametrize("disjoint", [False, True],
                         ids=["overlapping", "disjoint"])
@pytest.mark.parametrize("seed", [0, 1])
def test_sharded_classes_matches_single_device(seed, disjoint):
    """Overlapping tables run the scan in serial order over the mesh,
    disjoint ones the S-stream scan: either way solve_greedy's answer."""
    state, factored, jobs = _class_problem(
        seed, num_jobs=48, num_nodes=32, num_classes=3, max_nodes=4,
        disjoint=disjoint)
    mesh = make_node_mesh()
    assert (plan_streams(factored[4], factored[5], block_jobs=1)
            is not None) == disjoint
    p_ref, s_ref = solve_greedy(state, jobs, max_nodes=4)
    p_sh, s_sh = solve_greedy_sharded_classes(
        shard_cluster_state(state, mesh), *factored, mesh, max_nodes=4)
    _assert_same(p_ref, s_ref, p_sh, s_sh)
