"""Device-resident ClusterState (ISSUE 11, `make tier1-resident`).

The resident path must be invisible to scheduling semantics: over a
randomized event script the device solver with
``SchedulerConfig.resident_state=True`` must produce bit-exact
placements, pending reasons, and ledger state against the per-cycle
rebuild (``resident_state=False``), on both the device and pallas
backends.

Plus the residency contract itself: steady-state churn cycles run the
dirty-row scatter patch and never a silent full ``[N, R]`` rebuild;
acquire() transfers buffer ownership (donation safety); and every
invalidation epoch — mask-table generation (reservation), node
re-registration with changed hardware, topology permutation, solver
backend switch — falls back to exactly one full rebuild.
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
from cranesched_tpu.ctld.resident import (
    ResidentClusterState,
    full_state_bytes,
    padded_rows,
    patch_row_bytes,
)

pytestmark = pytest.mark.resident


def _cluster(num_nodes: int = 4, solver: str = "device",
             resident: bool = True, **cfg):
    meta = MetaContainer()
    for i in range(num_nodes):
        meta.add_node(f"n{i:02d}", meta.layout.encode(
            cpu=8, mem_bytes=16 << 30, memsw_bytes=16 << 30,
            is_capacity=True))
        meta.craned_up(i)
    cfg.setdefault("backfill", False)
    sched = JobScheduler(meta, SchedulerConfig(
        solver=solver, resident_state=resident, **cfg))
    # no TPU under pytest: the Pallas kernel runs in the interpreter
    sched.pallas_interpret = solver == "pallas"
    sched.licenses.configure("lic", total=2)
    sim = SimCluster(sched)
    sim.wire(sched)
    return sched, sim


def spec(**kw):
    kw.setdefault("res", ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                      memsw_bytes=1 << 30))
    return JobSpec(**kw)


def _state(sched):
    avail, total, alive = sched.meta.snapshot()
    return {
        "pending": {jid: (str(job.pending_reason), job.held)
                    for jid, job in sched.pending.items()},
        "running": sorted(sched.running),
        "history": sorted(sched.history),
        "avail": np.asarray(avail).copy(),
        "alive": np.asarray(alive).copy(),
        "licenses": {n: (lic.in_use, lic.total) for n, lic in
                     sched.licenses.licenses.items()},
    }


def _trace(sched):
    return sched.cycle_trace.snapshot()[-1]


# ---- steady-state patch: no silent full rebuild ----


def test_steady_state_patch_no_full_rebuild():
    """After the cycle-0 rebuild, every churn cycle must ship only the
    dirty rows — and the per-cycle h2d bytes must respect the bound."""
    sched, sim = _cluster(num_nodes=64)
    n, r = 64, sched.meta.layout.num_dims
    modes = []
    for t in range(1, 13):
        now = float(t)
        # long-running jobs: the only dirt is this cycle's own commit,
        # which stage() uploads post-commit for the next acquire
        sched.submit(spec(sim_runtime=1000.0), now=now)
        sim.advance_to(now)
        sched.schedule_cycle(now=now)
        row = _trace(sched)
        modes.append(row["resident"])
        if row["resident"] == "patch":
            # bytes bound: padded dirty rows + the always-full cost [N]
            assert row["h2d_bytes"] == (
                padded_rows(row["h2d_rows"], n) * patch_row_bytes(r)
                + 4 * n)
            # a patch must beat re-uploading the full [N, R] state
            assert row["h2d_bytes"] < full_state_bytes(n, r)
            assert row["h2d_rows"] <= n
            assert row["patch_overlap"], f"t={t}: delta not pre-staged"
    assert modes[0] == "rebuild"
    assert all(m == "patch" for m in modes[1:]), modes
    assert sched._resident.full_rebuilds == 1
    assert sched._resident.patch_cycles == len(modes) - 1
    # double buffering: stage() runs post-commit every cycle, so every
    # steady patch finds its delta already uploaded
    assert sched._resident.overlap_share() == 1.0


def test_completions_between_cycles_still_patch():
    """Completions land after the previous cycle staged its delta — the
    staged rowset no longer matches, so acquire() must fall back to a
    fresh synchronous gather (never a full rebuild, never a stale
    patch)."""
    sched, sim = _cluster(num_nodes=8)
    for t in range(1, 9):
        now = float(t)
        sched.submit(spec(sim_runtime=2.0), now=now)
        sim.advance_to(now)       # completions dirty rows post-stage
        sched.schedule_cycle(now=now)
    assert sched._resident.full_rebuilds == 1
    assert _trace(sched)["resident"] == "patch"
    # the live gather kept the device state exact: nothing pending that
    # the cycle did not retire, and placements kept landing
    assert len(sched.running) > 0


def test_trace_omits_resident_fields_when_disabled():
    sched, sim = _cluster(resident=False)
    sched.submit(spec(sim_runtime=1.0), now=1.0)
    sched.schedule_cycle(now=1.0)
    assert "resident" not in _trace(sched)
    assert not sched._resident.enabled
    assert sched._resident._state is None


# ---- randomized event-script parity oracle ----


def _random_spec(rng, now):
    kw = {}
    if rng.random() < 0.15:
        kw["held"] = True
    if rng.random() < 0.15:
        kw["begin_time"] = float(now + rng.integers(1, 8))
    if rng.random() < 0.25:
        kw["licenses"] = {"lic": 1}
    return spec(
        res=ResourceSpec(cpu=float(rng.integers(1, 5)),
                         mem_bytes=int(rng.integers(1, 5)) << 30,
                         memsw_bytes=int(rng.integers(1, 5)) << 30),
        node_num=int(rng.integers(1, 3)),
        time_limit=float(rng.integers(60, 3600)),
        sim_runtime=float(rng.integers(1, 6)), **kw)


def _parity_script(solver: str, ticks: int, seed: int = 7):
    """Identical event script against resident-on and resident-off —
    submits (held/begin_time/licensed), holds, cancels, modifies,
    license churn, drains, node deaths — cycle by cycle."""
    res = _cluster(solver=solver, resident=True)
    ref = _cluster(solver=solver, resident=False)
    rng_script = np.random.default_rng(seed)

    def both(fn):
        fn(*res)
        fn(*ref)

    for t in range(1, ticks + 1):
        now = float(t)
        ops = rng_script
        for _ in range(int(ops.integers(0, 4))):
            s = _random_spec(np.random.default_rng(
                int(ops.integers(0, 2**31))), now)
            both(lambda sched, sim, s=s: sched.submit(s, now=now))
        pend = sorted(res[0].pending)
        if pend and ops.random() < 0.4:
            jid = int(pend[int(ops.integers(0, len(pend)))])
            flip = not res[0].pending[jid].held
            rr = ops.random()
            if rr < 0.3:
                both(lambda sched, sim: sched.hold(
                    jid, held=flip, now=now))
            elif rr < 0.5:
                both(lambda sched, sim: sched.cancel(jid, now=now))
            else:
                tl = float(ops.integers(60, 7200))
                both(lambda sched, sim: sched.modify_job(
                    jid, now=now, time_limit=tl))
        if ops.random() < 0.2:
            k = int(ops.integers(0, 4))
            both(lambda sched, sim: sched.licenses.configure(
                "lic", total=k))
        if ops.random() < 0.15:
            node = int(ops.integers(0, 4))
            flag = bool(ops.integers(0, 2))
            both(lambda sched, sim: sched.meta.drain(node, flag))
        if ops.random() < 0.08:
            node = int(ops.integers(0, 4))
            both(lambda sched, sim: sched.on_craned_down(node, now))
        elif ops.random() < 0.15:
            node = int(ops.integers(0, 4))
            both(lambda sched, sim: sched.meta.craned_up(node))

        started = []
        for sched, sim in (res, ref):
            sim.advance_to(now)
            started.append(sched.schedule_cycle(now=now))
        assert started[0] == started[1], f"t={t}: placements diverged"
        si, sr = _state(res[0]), _state(ref[0])
        for key in si:
            if isinstance(si[key], np.ndarray):
                assert np.array_equal(si[key], sr[key]), f"t={t} {key}"
            else:
                assert si[key] == sr[key], f"t={t} {key}"
    return res[0]


def test_oracle_parity_randomized_device():
    sched = _parity_script("device", ticks=40)
    # the resident side must actually have exercised the patch path
    assert sched._resident.patch_cycles > 0


def test_oracle_parity_randomized_pallas():
    sched = _parity_script("pallas", ticks=12, seed=11)
    assert sched._resident.patch_cycles > 0


def test_oracle_parity_randomized_sharded():
    """ISSUE 17: the node-sharded backend rides the same resident
    store — the dirty-row patch scatters into the node-sharded buffers
    (each row lands on its owning shard) and must stay bit-exact
    against the per-cycle rebuild."""
    sched = _parity_script("sharded", ticks=10, seed=11)
    assert sched._resident.patch_cycles > 0
    # mesh-aware resident key + the trace's mesh descriptor (the
    # conftest pins an 8-device CPU platform -> 1 process x 8 devices)
    assert _trace(sched)["mesh"] == "1x8"
    assert sched._resident._key[0] == "sharded"
    assert sched._resident._key[-1] == "1x8"


def test_commit_rejection_divergence_parity():
    """License-capped jobs: the device solver places them, the host
    commit rejects — the rows it touched must be force-patched back so
    the next cycle's state is bit-exact against the rebuild path."""
    res = _cluster(solver="device", resident=True)
    ref = _cluster(solver="device", resident=False)
    for sched, sim in (res, ref):
        sched.licenses.configure("lic", total=1)
        for _ in range(4):
            sched.submit(spec(licenses={"lic": 1}, sim_runtime=10.0),
                         now=0.0)
        sched.schedule_cycle(now=1.0)
    # only one license seat: one job ran, three were rejected at commit
    assert sorted(res[0].running) == sorted(ref[0].running)
    assert len(res[0].running) == 1
    # the diverged rows must be queued for a force-patch
    assert res[0]._resident._pending | res[0]._resident._diverged
    for t in (2.0, 3.0):
        a = res[0].schedule_cycle(now=t)
        b = ref[0].schedule_cycle(now=t)
        assert a == b, f"t={t}: post-divergence placements differ"
    si, sr = _state(res[0]), _state(ref[0])
    for key in si:
        if isinstance(si[key], np.ndarray):
            assert np.array_equal(si[key], sr[key]), key
        else:
            assert si[key] == sr[key], key


# ---- donation safety / ownership discipline ----


def test_acquire_transfers_ownership():
    """acquire() must forget the resident state (the solve donates its
    buffers) and adopt() must install the returned state."""
    sched, sim = _cluster()
    sched.submit(spec(sim_runtime=5.0), now=1.0)
    sched.schedule_cycle(now=1.0)
    res = sched._resident
    assert res._state is not None
    before = res._state  # keep alive: a freed state's id() can be reused
    issued = res.last_issued_id
    avail, total, alive = sched.meta.snapshot()
    cost0 = np.zeros(len(sched.meta.nodes), np.int32)
    state, mode = res.acquire(avail, total, alive, cost0,
                              key=res._key)
    # ownership transferred: nothing else may reference the donated
    # buffers between acquire() and adopt()
    assert res._state is None
    assert mode == "patch"
    assert res.last_issued_id == id(state)
    # `issued` is the id of a state the last solve donated and freed:
    # the new one may be given it again, so only `before` can say the
    # state is new
    assert issued is not None
    assert state is not before
    res.adopt(state)
    assert res._state is state


def test_donating_solve_is_safe():
    """solve_greedy_donating must return usable results; on TPU the
    donated input's buffers must actually be consumed."""
    import jax

    from cranesched_tpu.models.solver import (
        JobBatch,
        make_cluster_state,
        solve_greedy_donating,
    )

    n, r = 4, 3
    total = np.full((n, r), 8, np.int32)
    state = make_cluster_state(total.copy(), total, np.ones(n, bool),
                               np.zeros(n, np.float32))
    jobs = JobBatch(
        req=np.ones((2, r), np.int32),
        node_num=np.ones(2, np.int32),
        time_limit=np.full(2, 60, np.int32),
        part_mask=np.ones((2, n), bool),
        valid=np.ones(2, bool))
    placements, new_state = solve_greedy_donating(state, jobs)
    placed = np.asarray(placements.placed)
    assert placed.all()
    assert np.asarray(new_state.avail).sum() < total.sum()
    if jax.default_backend() == "tpu":
        # donation is honored on TPU: the input buffers are dead
        assert state.avail.is_deleted()


# ---- invalidation epochs ----


def _warm(sched, sim, upto=3):
    for t in range(1, upto + 1):
        sched.submit(spec(sim_runtime=2.0), now=float(t))
        sim.advance_to(float(t))
        sched.schedule_cycle(now=float(t))
    if sched._resident.enabled:
        assert _trace(sched)["resident"] == "patch"


def test_reservation_bumps_mask_generation_rebuild():
    sched, sim = _cluster()
    _warm(sched, sim)
    gen0 = sched._mask_table.generation
    assert sched.meta.create_reservation(
        "resv", "default", ["n00"], start_time=100.0, end_time=200.0)
    sched.submit(spec(sim_runtime=1.0), now=4.0)
    sched.schedule_cycle(now=4.0)
    # the reservation epoch reset the mask table -> new generation ->
    # resident key mismatch -> exactly one full rebuild
    assert sched._mask_table.generation > gen0
    assert _trace(sched)["resident"] == "rebuild"
    assert sched._resident.full_rebuilds == 2
    sched.submit(spec(sim_runtime=1.0), now=5.0)
    sched.schedule_cycle(now=5.0)
    assert _trace(sched)["resident"] == "patch"


def test_update_node_total_patches_and_stays_correct():
    """A craned re-registering with different hardware dirties its row
    through the normal listener — a patch, not a rebuild — and the
    resident state must track the new capacity exactly."""
    res = _cluster(solver="device", resident=True)
    ref = _cluster(solver="device", resident=False)
    for sched, sim in (res, ref):
        _warm(sched, sim)
        # shrink node 0 to 2 cpus: jobs that fit before must spill
        new_total = sched.meta.layout.encode(
            cpu=2, mem_bytes=16 << 30, memsw_bytes=16 << 30,
            is_capacity=True)
        assert sched.meta.update_node_total(0, new_total)
    assert 0 in (res[0]._resident._pending
                 | res[0]._resident._diverged)
    for sched, sim in (res, ref):
        for _ in range(3):
            sched.submit(spec(res=ResourceSpec(
                cpu=4.0, mem_bytes=1 << 30, memsw_bytes=1 << 30),
                sim_runtime=5.0), now=4.0)
        sched.schedule_cycle(now=4.0)
    assert _trace(res[0])["resident"] == "patch"
    si, sr = _state(res[0]), _state(ref[0])
    assert np.array_equal(si["avail"], sr["avail"])
    assert si["running"] == sr["running"]


def test_topology_permutation_invalidates_resident():
    """Under a topology permutation the node axis the solver sees no
    longer lines up with meta node ids — the resident state must be
    dropped, not patched with misaligned rows."""
    from cranesched_tpu.topo.model import Topology

    sched, sim = _cluster()
    _warm(sched, sim)
    assert sched._resident._state is not None
    sched.meta.set_topology(Topology.uniform_blocks(4, 2))
    sched.submit(spec(sim_runtime=1.0), now=4.0)
    started = sched.schedule_cycle(now=4.0)
    assert started
    # the permuted solve invalidated and bypassed the resident path
    assert sched._resident._state is None
    assert "resident" not in _trace(sched)


def test_backend_switch_key_forces_rebuild():
    meta = MetaContainer()
    for i in range(2):
        meta.add_node(f"n{i}", meta.layout.encode(
            cpu=8, mem_bytes=16 << 30, memsw_bytes=16 << 30,
            is_capacity=True))
        meta.craned_up(i)
    res = ResidentClusterState(meta)
    avail, total, alive = (np.asarray(x).copy()
                           for x in meta.snapshot())
    cost0 = np.zeros(2, np.int32)
    state, mode = res.acquire(avail, total, alive, cost0,
                              key=("device", 2, meta.layout.num_dims, 0))
    assert mode == "rebuild"
    res.adopt(state)
    state, mode = res.acquire(avail, total, alive, cost0,
                              key=("pallas", 2, meta.layout.num_dims, 0))
    assert mode == "rebuild", "backend switch must not reuse buffers"
    assert res.full_rebuilds == 2


def test_rebuild_device_state_invalidates():
    sched, sim = _cluster()
    _warm(sched, sim)
    assert sched._resident._state is not None
    sched.rebuild_device_state()
    assert sched._resident._state is None


def test_mid_solve_dirt_survives_acquire():
    """Rows dirtied after acquire() captured its row set must stay
    pending for the next cycle, not be silently retired."""
    sched, sim = _cluster()
    _warm(sched, sim)
    res = sched._resident
    avail, total, alive = sched.meta.snapshot()
    cost0 = np.zeros(len(sched.meta.nodes), np.int32)
    rows_before = frozenset(res._pending | res._diverged)
    state, mode = res.acquire(np.asarray(avail), np.asarray(total),
                              np.asarray(alive), cost0, key=res._key)
    res._note_dirty(3)          # a concurrent mutation lands mid-solve
    res.adopt(state)
    assert 3 in res._pending
    assert not (rows_before & res._pending - {3})


# ---- _initial_cost_reference guard ----


def test_initial_cost_reference_unreachable_from_cycle():
    sched, sim = _cluster()
    _, total, _ = sched.meta.snapshot()
    total = np.asarray(total)
    # callable as the test-only oracle it is
    sched._initial_cost_reference(0.0, total)
    # but asserts if anything inside the cycle ever reaches it
    sched._in_cycle = True
    with pytest.raises(AssertionError, match="test-only oracle"):
        sched._initial_cost_reference(0.0, total)
    sched._in_cycle = False
