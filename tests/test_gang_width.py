"""Gangs up to the site's bound: 8 nodes (ISSUE 30, `northstar-10k`) and
64 (ISSUE 35, `widegang-10k`).

The older parity tests stop at 4, so the widths 5-8 met the solvers only
in ``chip_smoke.py``, and nothing wider compiled at all before PR 35.
Here, on seeded random clusters and queues at a small size on the CPU,
parametrised over the static gang bound K in {4, 8, 16, 32, 64}:

* ``solve_greedy`` and ``solve_greedy_pallas_auto`` (interpreted) against
  ``solve_greedy_oracle``, ``solve_backfill`` against
  ``solve_backfill_oracle``: placed / nodes / reason / avail / cost, bit
  for bit;
* a gang that needs K nodes and finds K - 1 is refused whole by every
  solver;
* one ``JobScheduler`` cycle of the default block over widths 1-8 and
  1-64: its placements pass the benchmark's own replay
  (``benchmark/lib/check.py``) and its trace row says which K the cycle
  paid and what share of the K selection passes a job needed; a submit
  one node wider than ``MaxNodesPerJob`` is refused;
* the benchmark side of the cell: the loader takes it, the replay counts
  a 7-node answer to an 8-wide job, and the reader of a device op's time
  reads a hand-made reduction.
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.models.pallas_solver import (
    classes_from_part_mask,
    plan_streams,
    solve_greedy_pallas_auto,
)
from cranesched_tpu.models.solver import (
    JobBatch,
    make_cluster_state,
    solve_greedy,
)
from cranesched_tpu.models.solver_time import (
    TimedJobBatch,
    make_timed_state,
    solve_backfill,
)
from cranesched_tpu.ops.resources import ResourceLayout
from cranesched_tpu.testing.oracle import solve_greedy_oracle
from cranesched_tpu.testing.time_oracle import (
    build_time_avail_oracle,
    solve_backfill_oracle,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import check, spec                      # noqa: E402
from lib.traffic import Ack, Job                 # noqa: E402
from readers import device_op                    # noqa: E402

LAY = ResourceLayout()
WIDTHS = [4, 8, 16, 32, 64]
CELL = "northstar10k-gangs"


def _problem(rng, num_jobs, num_nodes, max_nodes, num_parts=3):
    """A cluster of `num_parts` disjoint partitions, a tenth of it dead,
    and a queue whose widths run from 1 to one past the bound (such a job
    is refused, never partly placed)."""
    total = np.stack([
        LAY.encode(cpu=int(rng.integers(4, 33)),
                   mem_bytes=int(rng.integers(8, 65)) << 30,
                   is_capacity=True) for _ in range(num_nodes)])
    alive = rng.random(num_nodes) > 0.1
    cost = rng.integers(0, 50, num_nodes).astype(np.float32)
    req = np.stack([
        LAY.encode(cpu=float(rng.integers(1, 9)),
                   mem_bytes=int(rng.integers(1, 9)) << 30)
        for _ in range(num_jobs)])
    node_part = np.arange(num_nodes) % num_parts
    part_mask = rng.integers(0, num_parts, num_jobs)[:, None] == node_part
    node_num = rng.integers(1, max_nodes + 2, num_jobs).astype(np.int32)
    valid = rng.random(num_jobs) > 0.05
    # the first job uses the bound: as wide as it allows, small enough
    # for every node
    req[0] = LAY.encode(cpu=1.0, mem_bytes=1 << 30)
    node_num[0], valid[0] = max_nodes, True
    return dict(
        total=total, alive=alive, cost=cost, req=req, part_mask=part_mask,
        node_num=node_num, valid=valid,
        time_limit=rng.integers(60, 86400, num_jobs).astype(np.int32))


def _greedy_oracle(p, max_nodes):
    return solve_greedy_oracle(
        p["total"].copy(), p["total"], p["alive"], p["cost"], p["req"],
        p["node_num"], p["time_limit"], p["part_mask"], p["valid"],
        max_nodes)


def _assert_greedy(placements, state, oracle):
    o_placed, o_nodes, o_reason, o_avail, o_cost = oracle
    np.testing.assert_array_equal(np.asarray(placements.placed), o_placed)
    np.testing.assert_array_equal(np.asarray(placements.nodes), o_nodes)
    np.testing.assert_array_equal(np.asarray(placements.reason), o_reason)
    np.testing.assert_array_equal(np.asarray(state.avail), o_avail)
    np.testing.assert_array_equal(np.asarray(state.cost), o_cost)
    # the queue used the bound: a gang of exactly max_nodes was placed
    widest = np.asarray(placements.nodes)[o_placed]
    assert (widest >= 0).all(axis=1).any()


@pytest.mark.parametrize("max_nodes", WIDTHS)
def test_greedy_scan_matches_the_oracle(max_nodes):
    p = _problem(np.random.default_rng(300 + max_nodes), 120,
                 max(72, 8 * max_nodes), max_nodes)
    state = make_cluster_state(p["total"].copy(), p["total"], p["alive"],
                               p["cost"])
    jobs = JobBatch(req=jnp.asarray(p["req"]),
                    node_num=jnp.asarray(p["node_num"]),
                    time_limit=jnp.asarray(p["time_limit"]),
                    part_mask=jnp.asarray(p["part_mask"]),
                    valid=jnp.asarray(p["valid"]))
    placements, new_state = solve_greedy(state, jobs, max_nodes=max_nodes)
    _assert_greedy(placements, new_state, _greedy_oracle(p, max_nodes))


@pytest.mark.parametrize("max_nodes", WIDTHS)
def test_pallas_auto_matches_the_oracle(max_nodes):
    """The streamed kernel (disjoint partitions: the plan is taken), in
    interpret mode."""
    p = _problem(np.random.default_rng(310 + max_nodes), 96,
                 max(72, 8 * max_nodes), max_nodes)
    state = make_cluster_state(p["total"].copy(), p["total"], p["alive"],
                               p["cost"])
    job_class, masks = classes_from_part_mask(p["part_mask"])
    assert plan_streams(job_class, masks) is not None
    placements, new_state = solve_greedy_pallas_auto(
        state, jnp.asarray(p["req"]), jnp.asarray(p["node_num"]),
        jnp.asarray(p["time_limit"]), jnp.asarray(p["valid"]),
        jnp.asarray(job_class), jnp.asarray(masks), max_nodes=max_nodes,
        interpret=True)
    _assert_greedy(placements, new_state, _greedy_oracle(p, max_nodes))


@pytest.mark.parametrize("max_nodes", WIDTHS)
def test_backfill_matches_the_oracle(max_nodes):
    """The timed head on a unit grid, running jobs releasing nodes over
    the horizon, so wide gangs reserve a future start."""
    rng = np.random.default_rng(320 + max_nodes)
    T, M = 16, 14
    N = max(24, 5 * max_nodes)
    p = _problem(rng, 40, N, max_nodes, num_parts=2)
    p["time_limit"] = rng.integers(1, T + 2, 40).astype(np.int32)
    run_nodes = rng.integers(0, N, size=(M, 1)).astype(np.int32)
    run_req = np.stack([
        LAY.encode(cpu=int(rng.integers(1, 5)),
                   mem_bytes=int(rng.integers(1, 9)) << 30)
        for _ in range(M)]).astype(np.int32)
    run_end = rng.integers(1, T + 3, size=M).astype(np.int32)
    avail = p["total"].copy()
    for i in range(M):
        avail[run_nodes[i, 0]] -= run_req[i]
    avail = np.maximum(avail, 0)
    state = make_timed_state(avail, p["total"], p["alive"], run_nodes,
                             run_req, run_end, T, p["cost"])
    oracle_ta = build_time_avail_oracle(avail, run_nodes, run_req, run_end,
                                        T)
    jobs = TimedJobBatch(req=jnp.asarray(p["req"]),
                         node_num=jnp.asarray(p["node_num"]),
                         time_limit=jnp.asarray(p["time_limit"]),
                         part_mask=jnp.asarray(p["part_mask"]),
                         valid=jnp.asarray(p["valid"]))
    placements, new_state = solve_backfill(state, jobs,
                                           max_nodes=max_nodes)
    o_placed, o_start, o_nodes, o_reason, o_ta, o_cost = \
        solve_backfill_oracle(oracle_ta, p["total"], p["alive"], p["cost"],
                              p["req"], p["node_num"], p["time_limit"],
                              p["part_mask"], p["valid"], max_nodes)
    np.testing.assert_array_equal(np.asarray(placements.placed), o_placed)
    np.testing.assert_array_equal(
        np.where(o_placed, np.asarray(placements.start_bucket), 0),
        np.where(o_placed, o_start, 0))
    np.testing.assert_array_equal(np.asarray(placements.nodes), o_nodes)
    np.testing.assert_array_equal(np.asarray(placements.reason), o_reason)
    np.testing.assert_array_equal(np.asarray(new_state.time_avail), o_ta)
    np.testing.assert_array_equal(np.asarray(new_state.cost), o_cost)
    assert (o_nodes[o_placed] >= 0).all(axis=1).any()
    assert (o_start[o_placed] > 0).any()


def _one_short(max_nodes):
    """One partition of exactly max_nodes - 1 nodes that fit a gang
    max_nodes wide (and one node too small for it), then a job one
    narrower that is placed on all of them."""
    n = max_nodes + 3
    total = np.tile(LAY.encode(cpu=8, mem_bytes=16 << 30,
                               is_capacity=True), (n, 1))
    total[max_nodes - 1:] = LAY.encode(cpu=1, mem_bytes=16 << 30,
                                       is_capacity=True)
    req = np.tile(LAY.encode(cpu=2.0, mem_bytes=1 << 30), (2, 1))
    return dict(
        total=total, alive=np.ones(n, bool),
        cost=np.arange(n, dtype=np.float32)[::-1].copy(), req=req,
        part_mask=np.ones((2, n), bool),
        node_num=np.array([max_nodes, max_nodes - 1], np.int32),
        time_limit=np.array([600, 600], np.int32),
        valid=np.ones(2, bool))


@pytest.mark.parametrize("solver", ["scan", "serial", "backfill"])
@pytest.mark.parametrize("max_nodes", [8, 64])
def test_a_gang_one_node_short_is_refused_whole(max_nodes, solver):
    """A gang that needs K and finds K - 1: no node, no subtraction, the
    reason `resource`; the next job takes exactly those K - 1, dearest
    index first (the cost falls with the index here)."""
    p = _one_short(max_nodes)
    cols = dict(req=jnp.asarray(p["req"]),
                node_num=jnp.asarray(p["node_num"]),
                time_limit=jnp.asarray(p["time_limit"]),
                part_mask=jnp.asarray(p["part_mask"]),
                valid=jnp.asarray(p["valid"]))
    state = make_cluster_state(p["total"].copy(), p["total"], p["alive"],
                               p["cost"])
    if solver == "scan":
        got, _ = solve_greedy(state, JobBatch(**cols), max_nodes=max_nodes)
    elif solver == "serial":
        job_class, masks = classes_from_part_mask(p["part_mask"])
        got, _ = solve_greedy_pallas_auto(
            state, cols["req"], cols["node_num"], cols["time_limit"],
            cols["valid"], jnp.asarray(job_class), jnp.asarray(masks),
            max_nodes=max_nodes, interpret=True)
    else:
        timed = make_timed_state(
            p["total"].copy(), p["total"], p["alive"],
            np.zeros((0, 1), np.int32),
            np.zeros((0, p["total"].shape[1]), np.int32),
            np.zeros(0, np.int32), 4, p["cost"])
        got, _ = solve_backfill(timed, TimedJobBatch(**cols),
                                max_nodes=max_nodes)
    placed, nodes = np.asarray(got.placed), np.asarray(got.nodes)
    assert placed.tolist() == [False, True]
    assert (nodes[0] == -1).all() and int(got.reason[0]) == 1
    assert nodes[1].tolist() == list(range(max_nodes - 2, -1, -1)) + [-1]


def test_the_head_scan_carries_its_scope():
    """XLA calls the loop `while.N` whatever it is told; the program's
    scope is in the op's metadata (the lowering's locations here)."""
    from cranesched_tpu.models.solver_time import HEAD_SCOPE
    total = np.tile(LAY.encode(cpu=8, is_capacity=True), (4, 1))
    state = make_timed_state(
        total.copy(), total, np.ones(4, bool), np.zeros((0, 1), np.int32),
        np.zeros((0, total.shape[1]), np.int32), np.zeros(0, np.int32), 8,
        np.zeros(4, np.float32))
    jobs = TimedJobBatch(
        req=jnp.asarray(np.stack([LAY.encode(cpu=1)] * 3)),
        node_num=jnp.ones(3, jnp.int32), time_limit=jnp.ones(3, jnp.int32),
        part_mask=jnp.ones((3, 4), bool), valid=jnp.ones(3, bool))
    text = solve_backfill.lower(state, jobs, max_nodes=2).as_text(
        debug_info=True)
    assert f"jit(solve_backfill)/{HEAD_SCOPE}/scan" in text


# ---------------------------------------------------------------------------
# one cycle of the default block, held to the benchmark's own replay
# ---------------------------------------------------------------------------

def _cycle(widths, nodes=48, parts=2, config=None, interpret=False):
    """`nodes` nodes in `parts` partitions, one job of 2 cpu / 4 GiB a
    node for each width, dealt over the partitions; one cycle.  Returns
    the harness's view (cluster, acks, rows) and the cycle's trace row."""
    meta = MetaContainer()
    cluster = {"names": [], "cpu": [], "mem_gib": [], "part": [],
               "drained": []}
    for i in range(nodes):
        part = f"batch{i * parts // nodes}"
        meta.add_node(f"cn{i:05d}", meta.layout.encode(
            cpu=16, mem_bytes=32 << 30, memsw_bytes=32 << 30,
            is_capacity=True), partitions=(part,))
        meta.craned_up(i)
        cluster["names"].append(f"cn{i:05d}")
        cluster["cpu"].append(16)
        cluster["mem_gib"].append(32)
        cluster["part"].append(part)
    sched = JobScheduler(meta, config or SchedulerConfig())
    sched.pallas_interpret = interpret      # no TPU under pytest
    sim = SimCluster(sched)
    sched.dispatch = sim.dispatch
    sched.dispatch_terminate = sim.terminate
    acks = {}
    for k, width in enumerate(widths):
        part = f"batch{k % parts}"
        job_id = sched.submit(JobSpec(
            partition=part, node_num=width, time_limit=3600,
            res=ResourceSpec(cpu=2.0, mem_bytes=4 << 30,
                             memsw_bytes=4 << 30), sim_runtime=600.0),
            now=0.0)
        assert job_id
        acks[job_id] = Ack(Job(2, 4, width, 3600, 600.0, "user", part),
                           "preload", "setup", 0.0, 0.0)
    started = sched.schedule_cycle(now=1.0)
    rows = []
    for job_id in acks:
        job = sched.job_info(job_id)
        rows.append(check.Row(
            job_id, job.status.name.capitalize(), job.spec.partition,
            job.spec.user,
            tuple(meta.nodes[n].name for n in job.node_ids),
            job.start_time or 0.0, 0.0))
    return cluster, acks, rows, started, sched.cycle_trace.snapshot()[-1]


@pytest.mark.parametrize("bound", [8, 64])
def test_a_cycle_of_widths_1_to_the_bound_passes_the_replay_and_says_it(
        bound):
    widths = list(range(1, bound + 1)) * (3 if bound == 8 else 1)
    cluster, acks, rows, started, trace = _cycle(
        widths, nodes=6 * bound,
        config=SchedulerConfig(max_nodes_per_job=bound))
    placed = [r for r in rows if r.node_names]
    assert len(placed) == len(started) == len(widths)
    assert {len(r.node_names) for r in placed} == set(range(1, bound + 1))
    assert check.misplaced_jobs(cluster, acks, rows, t_drained=0.0) == 0
    assert check.overcommitted_nodes(cluster, acks, rows) == 0
    assert trace["gang_bound"] == bound
    # by hand: (1 + ... + bound) nodes asked for each round, a job x
    # bound passes
    asked = sum(widths)
    assert trace["candidates"] == len(widths)
    assert trace["gang_fill_pct"] == pytest.approx(
        100.0 * asked / (len(widths) * bound), abs=1e-3)
    assert trace["nodes_selected"] == asked
    # both sides are rounded (one decimal; solve_ms three): a cold
    # cycle's few decisions a second need the absolute slack
    assert trace["decisions_per_s"] == pytest.approx(
        len(widths) * 1e3 / trace["solve_ms"], rel=1e-3, abs=0.06)


def test_a_submit_wider_than_the_bound_is_refused():
    meta = MetaContainer()
    for i in range(70):
        meta.add_node(f"cn{i:05d}", meta.layout.encode(
            cpu=16, mem_bytes=32 << 30, memsw_bytes=32 << 30,
            is_capacity=True), partitions=("batch0",))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(max_nodes_per_job=64))

    def submit(width):
        return sched.submit(JobSpec(
            partition="batch0", node_num=width, time_limit=60,
            res=ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                             memsw_bytes=1 << 30)), now=0.0)

    assert submit(64) and not submit(65)


def test_the_tail_says_what_share_of_its_passes_it_ran():
    """The default block's split with the Pallas tail (interpreted): the
    head takes the first four jobs; the tail's serial kernel runs pass 0
    for each of its one block's 256 slots (the head's invalidated rows
    and the padding too) and one more pass for every further node a job
    asks for (every job fits)."""
    widths = [1, 2, 3, 4, 5, 6, 7, 8] * 3
    _, _, rows, started, trace = _cycle(
        widths, parts=1, interpret=True,
        config=SchedulerConfig(solver="pallas", backfill_max_jobs=4))
    assert len(started) == 24 and trace["solver"] == "backfill-split"
    assert trace["gang_bound"] == 8 and trace["num_streams"] == 1
    # by hand: 108 nodes asked for, 1 + 2 + 3 + 4 of them by the head;
    # the tail's 20 jobs take 98, the first of each in pass 0
    assert trace["tail_pass_pct"] == pytest.approx(
        100.0 * (256 + 98 - 20) / (256 * 8), abs=1e-3)
    # a cycle that ran no Pallas kernel left no pass out: 100, and not a
    # 0 for a reader of a "lower is better" share to take for the best
    assert _cycle(widths)[4]["tail_pass_pct"] == 100.0


def test_the_tail_s_pass_count_at_k_64_by_hand():
    """The same split at the bound 64: the loop over the later passes
    runs a slot's own width and no further, so the count is 256 first
    passes and one more for every further node of a tail job; of the
    256 x 64 the static bound allows that is 3.1%."""
    widths = [1, 2, 3, 4] + [64, 33, 17, 9, 5, 64, 1, 48]
    _, _, rows, started, trace = _cycle(
        widths, nodes=256, parts=1, interpret=True,
        config=SchedulerConfig(solver="pallas", backfill_max_jobs=4,
                               max_nodes_per_job=64))
    assert len(started) == 12 and trace["solver"] == "backfill-split"
    assert trace["gang_bound"] == 64
    tail = widths[4:]
    assert trace["tail_pass_pct"] == pytest.approx(
        100.0 * (256 + sum(tail) - len(tail)) / (256 * 64), abs=1e-3)
    assert sorted(len(r.node_names) for r in rows) == sorted(widths)
    assert trace["nodes_selected"] == sum(widths)


def test_the_bound_is_the_bucket_of_the_widest_candidate():
    """Widths 1-3: K is 4, the next power of two, and a narrower queue
    fills a larger share of a smaller bound."""
    _, _, _, _, trace = _cycle([1, 2, 3, 3])
    assert trace["gang_bound"] == 4
    assert trace["gang_fill_pct"] == pytest.approx(100.0 * 9 / (4 * 4))


def test_a_seven_node_answer_to_an_eight_wide_job_is_misplaced():
    cluster, acks, rows, _, _ = _cycle([8])
    assert len(rows[0].node_names) == 8
    assert check.misplaced_jobs(cluster, acks, rows, t_drained=0.0) == 0
    short = [rows[0]._replace(node_names=rows[0].node_names[:7])]
    assert check.misplaced_jobs(cluster, acks, short, t_drained=0.0) >= 1
    twice = [rows[0]._replace(
        node_names=rows[0].node_names[:7] + rows[0].node_names[:1])]
    assert check.misplaced_jobs(cluster, acks, twice, t_drained=0.0) >= 1


# ---------------------------------------------------------------------------
# the benchmark side of the cell
# ---------------------------------------------------------------------------

def test_the_loader_takes_the_cell():
    bench = spec.Benchmark()
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "northstar-10k", "backlog-gangs", 1)
    cfg = bench.config_file(CELL)
    assert cfg["nodes"] == 10_000 == sum(
        p["nodes"] for p in cfg["partitions"])
    assert cfg["scheduler"]["MaxNodesPerJob"] == 8 and "Solver" not in \
        cfg["scheduler"]
    assert cfg["reduced"] == [] == bench.configs["northstar-10k"]["reduced"]
    minload = bench.config_file("minload5k-backlog")
    assert cfg["guarantees"] == minload["guarantees"]
    traffic = bench.traffic_file(CELL)
    assert traffic["mixes"]["gang"]["node_num"] == [
        1, cfg["scheduler"]["MaxNodesPerJob"]]
    assert traffic["setup"]["preload"]["pending_target"] == 101_376
    e2e = {m["name"] for m in bench.metrics_for(CELL, "end_to_end")}
    assert e2e == {"start_p95_ms", "submit_p95_ms", "query_p90_ms",
                   "setup_s"}
    layer = {m["name"] for m in bench.metrics_for(CELL, "per_layer")}
    assert layer == {m["name"] for m in bench.metrics_for(
        "minload5k-backlog", "per_layer")
        if m["name"].endswith(".latency")}
    assert {"solve_gang_bound.latency", "solve_gang_fill_share.latency",
            "solve_decisions_per_s.latency", "solve_head_device_ms.latency",
            "solve_tail_device_ms.latency"} <= layer


TRACE = {"cycles": 6, "device_ops": [
    ["while.102", 0.386], ["crane_greedy_streamed.1", 0.312],
    ["while.10", 0.048], ["crane_greedy_streamed", 0.006],
    ["whileish.3", 9.0], ["fusion.26", 0.047]]}


@pytest.mark.parametrize("args,trace,want", [
    ({"op": "while", "pick": "max"}, TRACE, 1e3 * 0.386 / 6),
    ({"op": "while"}, TRACE, 1e3 * (0.386 + 0.048) / 6),
    ({"op": "crane_greedy_streamed"}, TRACE, 1e3 * (0.312 + 0.006) / 6),
    ({"op": "fusion.26"}, TRACE, 1e3 * 0.047 / 6),
    # a program that has no such op (the parent), or no trace: nothing
    ({"op": "_solve_streamed_impl"}, TRACE, None),
    ({"op": "while"}, {}, None),
    ({"op": "while"}, {"cycles": 0, "device_ops": [["while.1", 1.0]]},
     None),
])
def test_the_device_op_reader(args, trace, want):
    got = device_op.read({"trace": trace}, args)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_device_op_reader_refuses_an_unknown_pick():
    with pytest.raises(ValueError):
        device_op.read({"trace": TRACE}, {"op": "while", "pick": "mean"})


def test_the_metric_files_name_what_the_program_names():
    from cranesched_tpu.models import pallas_solver
    bench = spec.Benchmark()
    tail = bench.metric_file("solve_tail_device_ms.latency")
    assert tail["args"]["op"] == pallas_solver.KERNEL_STREAMED
