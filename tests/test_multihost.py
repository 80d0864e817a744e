"""Multi-process pod-slice solve (ISSUE 17): hierarchical shard_map.

The CPU backend cannot run cross-process XLA computations, so CI
validates the multi-host design at two levels:

* in-process "ranks": P ProcessMesh members in threads, each owning a
  contiguous node slab sharded over the (shared) 8-device CPU mesh,
  fencing through a real RendezvousServer — bit-exact parity against
  the single-process ``solve_greedy_sharded_classes`` oracle on
  overlapping AND disjoint class tables (the acceptance bar);
* real processes: two subprocesses with their own jax runtimes (4
  forced host devices each) bootstrap over the rendezvous and must
  emit identical placements, matching the parent's oracle.

Lane: ``make tier1-multihost`` (-m multihost); all fast enough for
tier-1 as well.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from cranesched_tpu.models.solver import make_cluster_state  # noqa: E402
from cranesched_tpu.ops.resources import ResourceLayout  # noqa: E402
from cranesched_tpu.parallel.distributed import (  # noqa: E402
    bootstrap_process_mesh,
    solve_greedy_sharded_classes_mp,
)
from cranesched_tpu.parallel.sharded import (  # noqa: E402
    make_node_mesh,
    shard_cluster_state,
    solve_greedy_sharded_classes,
)
from cranesched_tpu.rpc.rendezvous import RendezvousServer  # noqa: E402

pytestmark = pytest.mark.multihost

NPROCS = 2


def _problem(seed, num_jobs, num_nodes, num_classes, max_nodes,
             disjoint):
    """A class-table scheduling problem (the factored-eligibility
    form both solvers accept)."""
    rng = np.random.default_rng(seed)
    lay = ResourceLayout()
    total = np.stack([
        lay.encode(cpu=int(rng.integers(8, 65)),
                   mem_bytes=int(rng.integers(16, 257)) << 30,
                   is_capacity=True)
        for _ in range(num_nodes)])
    used = np.stack([
        lay.encode(cpu=float(rng.integers(0, 8)),
                   mem_bytes=int(rng.integers(0, 8)) << 30)
        for _ in range(num_nodes)])
    avail = total - np.minimum(used, total)
    alive = rng.random(num_nodes) >= 0.1
    cost = rng.random(num_nodes).astype(np.float32) * 10

    req = np.stack([
        lay.encode(cpu=float(rng.integers(1, 17)),
                   mem_bytes=int(rng.integers(1, 33)) << 30)
        for _ in range(num_jobs)])
    node_num = rng.integers(1, max_nodes + 1,
                            size=num_jobs).astype(np.int32)
    time_limit = rng.integers(60, 86400,
                              size=num_jobs).astype(np.int32)
    valid = rng.random(num_jobs) > 0.05
    job_class = rng.integers(0, num_classes,
                             size=num_jobs).astype(np.int32)
    if disjoint:
        owner = rng.integers(0, num_classes, size=num_nodes)
        class_masks = np.stack([owner == c
                                for c in range(num_classes)])
    else:
        class_masks = rng.random((num_classes, num_nodes)) > 0.25
    return dict(avail=avail, total=total, alive=alive, cost=cost,
                req=req, node_num=node_num, time_limit=time_limit,
                valid=valid, job_class=job_class,
                class_masks=class_masks)


def _oracle(pb, max_nodes):
    state = make_cluster_state(pb["avail"], pb["total"], pb["alive"],
                               pb["cost"])
    mesh = make_node_mesh()
    return solve_greedy_sharded_classes(
        shard_cluster_state(state, mesh),
        jnp.asarray(pb["req"]), jnp.asarray(pb["node_num"]),
        jnp.asarray(pb["time_limit"]), jnp.asarray(pb["valid"]),
        jnp.asarray(pb["job_class"]), jnp.asarray(pb["class_masks"]),
        mesh, max_nodes=max_nodes)


def _run_ranks(pb, max_nodes, nprocs=NPROCS):
    """P in-process ranks, each with a node slab, through a real
    rendezvous.  Returns per-rank (placements, slab_state)."""
    n = pb["avail"].shape[0]
    assert n % nprocs == 0
    slab = n // nprocs
    server = RendezvousServer(token="mp", nranks=nprocs, epoch=1)
    port = server.start("127.0.0.1:0")
    results: list = [None] * nprocs
    errors: list = []

    def worker(rank):
        try:
            lo, hi = rank * slab, (rank + 1) * slab
            state = make_cluster_state(
                pb["avail"][lo:hi], pb["total"][lo:hi],
                pb["alive"][lo:hi], pb["cost"][lo:hi])
            pmesh = bootstrap_process_mesh(
                rank, nprocs, slab, address=f"127.0.0.1:{port}",
                token="mp", epoch=1)
            try:
                results[rank] = solve_greedy_sharded_classes_mp(
                    pmesh, state, jnp.asarray(pb["req"]),
                    jnp.asarray(pb["node_num"]),
                    jnp.asarray(pb["time_limit"]),
                    jnp.asarray(pb["valid"]),
                    jnp.asarray(pb["job_class"]),
                    jnp.asarray(pb["class_masks"][:, lo:hi]),
                    max_nodes=max_nodes)
            finally:
                pmesh.close()
        except BaseException as e:  # surfaced by the main thread
            errors.append((rank, e))

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(nprocs)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        server.stop()
    if errors:
        raise errors[0][1]
    assert all(r is not None for r in results)
    return results


@pytest.mark.parametrize("disjoint", [False, True],
                         ids=["overlapping", "disjoint"])
@pytest.mark.parametrize("seed", [0, 1])
def test_mp_solve_matches_single_process_oracle(seed, disjoint):
    """The acceptance bar: ≥2 processes' hierarchical solve is
    bit-exact against the single-process sharded-classes oracle, on
    overlapping and disjoint class tables."""
    max_nodes = 4
    pb = _problem(seed, num_jobs=48, num_nodes=32, num_classes=3,
                  max_nodes=max_nodes, disjoint=disjoint)
    p_ref, s_ref = _oracle(pb, max_nodes)
    results = _run_ranks(pb, max_nodes)

    for rank, (p_mp, _) in enumerate(results):
        np.testing.assert_array_equal(
            np.asarray(p_mp.placed), np.asarray(p_ref.placed),
            err_msg=f"rank {rank} placed")
        np.testing.assert_array_equal(
            np.asarray(p_mp.nodes), np.asarray(p_ref.nodes),
            err_msg=f"rank {rank} nodes")
        np.testing.assert_array_equal(
            np.asarray(p_mp.reason), np.asarray(p_ref.reason),
            err_msg=f"rank {rank} reason")
    # the slab states concatenate to the oracle's post-solve state
    avail_mp = np.concatenate(
        [np.asarray(s.avail) for _, s in results])
    cost_mp = np.concatenate([np.asarray(s.cost) for _, s in results])
    np.testing.assert_array_equal(avail_mp, np.asarray(s_ref.avail))
    np.testing.assert_array_equal(cost_mp, np.asarray(s_ref.cost))


def test_mp_second_cycle_reuses_slab_state():
    """The returned slab state feeds the next cycle without any
    regather, exactly like the single-process contract."""
    max_nodes = 2
    pb = _problem(7, num_jobs=24, num_nodes=16, num_classes=2,
                  max_nodes=max_nodes, disjoint=False)
    pb2 = _problem(8, num_jobs=24, num_nodes=16, num_classes=2,
                   max_nodes=max_nodes, disjoint=False)
    # oracle: two cycles
    p_ref1, s_ref = _oracle(pb, max_nodes)
    mesh = make_node_mesh()
    p_ref2, s_ref2 = solve_greedy_sharded_classes(
        s_ref, jnp.asarray(pb2["req"]), jnp.asarray(pb2["node_num"]),
        jnp.asarray(pb2["time_limit"]), jnp.asarray(pb2["valid"]),
        jnp.asarray(pb2["job_class"]), jnp.asarray(pb2["class_masks"]),
        mesh, max_nodes=max_nodes)

    n = pb["avail"].shape[0]
    slab = n // NPROCS
    server = RendezvousServer(token="mp", nranks=NPROCS, epoch=1)
    port = server.start("127.0.0.1:0")
    results: list = [None] * NPROCS
    errors: list = []

    def worker(rank):
        try:
            lo, hi = rank * slab, (rank + 1) * slab
            state = make_cluster_state(
                pb["avail"][lo:hi], pb["total"][lo:hi],
                pb["alive"][lo:hi], pb["cost"][lo:hi])
            pmesh = bootstrap_process_mesh(
                rank, NPROCS, slab, address=f"127.0.0.1:{port}",
                token="mp", epoch=1)
            try:
                args1 = [jnp.asarray(pb[k]) for k in
                         ("req", "node_num", "time_limit", "valid",
                          "job_class")]
                _, state = solve_greedy_sharded_classes_mp(
                    pmesh, state, *args1,
                    jnp.asarray(pb["class_masks"][:, lo:hi]),
                    max_nodes=max_nodes)
                args2 = [jnp.asarray(pb2[k]) for k in
                         ("req", "node_num", "time_limit", "valid",
                          "job_class")]
                results[rank] = solve_greedy_sharded_classes_mp(
                    pmesh, state, *args2,
                    jnp.asarray(pb2["class_masks"][:, lo:hi]),
                    max_nodes=max_nodes)
            finally:
                pmesh.close()
        except BaseException as e:
            errors.append((rank, e))

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(NPROCS)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        server.stop()
    if errors:
        raise errors[0][1]
    p_mp, _ = results[0]
    np.testing.assert_array_equal(np.asarray(p_mp.placed),
                                  np.asarray(p_ref2.placed))
    np.testing.assert_array_equal(np.asarray(p_mp.nodes),
                                  np.asarray(p_ref2.nodes))
    avail_mp = np.concatenate(
        [np.asarray(s.avail) for _, s in results])
    np.testing.assert_array_equal(avail_mp, np.asarray(s_ref2.avail))


def test_bootstrap_missing_rank_is_structured():
    """A member that never arrives must surface as the fence's typed
    x/y-arrived timeout, not a bare deadline."""
    server = RendezvousServer(token="mp", nranks=2, epoch=1)
    port = server.start("127.0.0.1:0")
    try:
        with pytest.raises(RuntimeError,
                           match=r"fence timeout \(1/2 arrived\)"):
            bootstrap_process_mesh(0, 2, 8,
                                   address=f"127.0.0.1:{port}",
                                   token="mp", epoch=1, timeout=1.0)
    finally:
        server.stop()


def test_process_mesh_describe():
    server = RendezvousServer(token="mp", nranks=1, epoch=1)
    port = server.start("127.0.0.1:0")
    try:
        pmesh = bootstrap_process_mesh(0, 1, 8,
                                       address=f"127.0.0.1:{port}",
                                       token="mp", epoch=1)
        assert pmesh.describe() == f"1x{len(jax.devices())}"
        assert pmesh.total_nodes == 8 and pmesh.node_offset == 0
        pmesh.close()
    finally:
        server.stop()


_CHILD_SRC = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
from cranesched_tpu.models.solver import make_cluster_state
from cranesched_tpu.parallel.distributed import (
    bootstrap_process_mesh, solve_greedy_sharded_classes_mp)

rank = int(os.environ["CRANE_MP_RANK"])
nprocs = int(os.environ["CRANE_MP_NPROCS"])
pb = dict(np.load(sys.argv[1]))
max_nodes = int(pb.pop("max_nodes"))
n = pb["avail"].shape[0]
slab = n // nprocs
lo, hi = rank * slab, (rank + 1) * slab
state = make_cluster_state(pb["avail"][lo:hi], pb["total"][lo:hi],
                           pb["alive"][lo:hi], pb["cost"][lo:hi])
pmesh = bootstrap_process_mesh(rank, nprocs, slab)
p, s = solve_greedy_sharded_classes_mp(
    pmesh, state, jnp.asarray(pb["req"]), jnp.asarray(pb["node_num"]),
    jnp.asarray(pb["time_limit"]), jnp.asarray(pb["valid"]),
    jnp.asarray(pb["job_class"]),
    jnp.asarray(pb["class_masks"][:, lo:hi]), max_nodes=max_nodes)
print(json.dumps({
    "rank": rank, "mesh": pmesh.describe(),
    "devices": len(jax.devices()),
    "placed": np.asarray(p.placed).tolist(),
    "nodes": np.asarray(p.nodes).tolist(),
    "reason": np.asarray(p.reason).tolist(),
    "avail": np.asarray(s.avail).tolist()}))
pmesh.close()
"""


def test_two_real_processes_agree_with_oracle(tmp_path):
    """Two actual OS processes — separate jax runtimes, 4 forced host
    devices each — bootstrap over the rendezvous and solve; their
    placements must be identical and match the parent's oracle."""
    max_nodes = 2
    pb = _problem(3, num_jobs=16, num_nodes=16, num_classes=2,
                  max_nodes=max_nodes, disjoint=False)
    p_ref, s_ref = _oracle(pb, max_nodes)
    npz = tmp_path / "problem.npz"
    np.savez(npz, max_nodes=max_nodes, **pb)

    server = RendezvousServer(token="mp2", nranks=2, epoch=1)
    port = server.start("127.0.0.1:0")
    procs = []
    try:
        for rank in range(2):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "CRANE_RENDEZVOUS": f"127.0.0.1:{port}",
                "CRANE_RENDEZVOUS_TOKEN": "mp2",
                "CRANE_MP_RANK": str(rank),
                "CRANE_MP_NPROCS": "2",
            })
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _CHILD_SRC, str(npz)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))))
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, err[-3000:]
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    assert [o["rank"] for o in outs] == [0, 1]
    assert all(o["mesh"] == "2x4" and o["devices"] == 4 for o in outs)
    # both processes computed the SAME global placements...
    assert outs[0]["placed"] == outs[1]["placed"]
    assert outs[0]["nodes"] == outs[1]["nodes"]
    assert outs[0]["reason"] == outs[1]["reason"]
    # ...identical to the single-process oracle (device-count and
    # process-count invariant)
    assert outs[0]["placed"] == np.asarray(p_ref.placed).tolist()
    assert outs[0]["nodes"] == np.asarray(p_ref.nodes).tolist()
    assert outs[0]["reason"] == np.asarray(p_ref.reason).tolist()
    avail_mp = np.concatenate([np.asarray(o["avail"]) for o in outs])
    np.testing.assert_array_equal(avail_mp, np.asarray(s_ref.avail))
