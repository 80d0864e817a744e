"""Kernel experiment harness for the Pallas greedy solve (round 5).

Builds the exact BENCH problem (bench.py shapes, seed 0) and times
kernel variants on the real device, optionally capturing a
jax.profiler trace.  Used to decide the round-5 optimization strategy
for the >=1M decisions/s north star; results recorded in
profiles/R05_PROFILE.md.

Usage: python tools/kexp.py [variant ...]   (default: base)
  BENCH_JOBS/BENCH_NODES override shapes; KEXP_TRACE=dir captures a
  profiler trace of the timed region.  Results are appended to
  profiles/$PROFILE_TAG_PROFILE.md (tools/profmd.py).  Needs a TPU: no
  TPU, no profile.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_problem(num_jobs, num_nodes):
    import jax
    import jax.numpy as jnp
    from cranesched_tpu.models.solver import JobBatch, make_cluster_state
    from cranesched_tpu.ops.resources import ResourceLayout

    rng = np.random.default_rng(0)
    lay = ResourceLayout()
    total = np.stack([
        lay.encode(cpu=int(rng.integers(32, 129)),
                   mem_bytes=int(rng.integers(64, 513)) << 30,
                   is_capacity=True)
        for _ in range(num_nodes)])
    state = make_cluster_state(total.copy(), total,
                               rng.random(num_nodes) > 0.02,
                               rng.random(num_nodes).astype(np.float32))
    req = np.stack([
        lay.encode(cpu=float(rng.integers(1, 17)),
                   mem_bytes=int(rng.integers(1, 33)) << 30)
        for _ in range(num_jobs)])
    node_part = jnp.asarray(rng.integers(0, 4, num_nodes), jnp.int32)
    job_part = jnp.asarray(rng.integers(0, 4, num_jobs), jnp.int32)
    jobs = JobBatch(
        req=jnp.asarray(req),
        node_num=jnp.asarray(rng.integers(1, 3, num_jobs), jnp.int32),
        time_limit=jnp.asarray(rng.integers(60, 86400, num_jobs),
                               jnp.int32),
        part_mask=None,
        valid=jnp.ones(num_jobs, bool))
    class_masks = jnp.asarray(
        np.stack([np.asarray(node_part) == c for c in range(4)]))
    return state, jobs, job_part, class_masks


def time_fn(fn, repeats=3):
    p = fn()
    jax.block_until_ready(p)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        p = fn()
        jax.block_until_ready(p)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), p


if __name__ == "__main__":
    num_jobs = int(os.environ.get("BENCH_JOBS", 100_000))
    num_nodes = int(os.environ.get("BENCH_NODES", 10_000))
    variants = sys.argv[1:] or ["base"]

    import jax

    from profmd import append_section, require_tpu
    device = require_tpu()
    print("device:", device, file=sys.stderr)
    state, jobs, job_part, class_masks = build_problem(num_jobs, num_nodes)

    from cranesched_tpu.models.pallas_solver import solve_greedy_pallas

    runs = {}
    if "base" in variants:
        runs["base"] = lambda bj=256: solve_greedy_pallas(
            state, jobs.req, jobs.node_num, jobs.time_limit, jobs.valid,
            job_part, class_masks, max_nodes=2, block_jobs=bj)
    for v in variants:
        if v.startswith("bj"):  # block_jobs sweep, e.g. bj512
            bj = int(v[2:])
            runs[v] = (lambda bj=bj: solve_greedy_pallas(
                state, jobs.req, jobs.node_num, jobs.time_limit,
                jobs.valid, job_part, class_masks, max_nodes=2,
                block_jobs=bj))
    for v in variants:
        if v.startswith("streams"):  # e.g. streams4
            ns = int(v[len("streams"):] or 4)
            from cranesched_tpu.models.pallas_solver import (
                solve_greedy_pallas_auto)
            runs[v] = (lambda ns=ns: solve_greedy_pallas_auto(
                state, jobs.req, jobs.node_num, jobs.time_limit,
                jobs.valid, job_part, class_masks, max_nodes=2,
                max_streams=ns))
    if "small" in variants:
        # simulate the per-partition split: quarter nodes, quarter jobs,
        # x4 sequential solves -> what would class-split buy?
        st4, jb4, jp4, cm4 = build_problem(num_jobs // 4, num_nodes // 4)
        cm1 = (cm4.at[:].set(False)).at[0].set(True)

        def run_small():
            outs = []
            for _ in range(4):
                outs.append(solve_greedy_pallas(
                    st4, jb4.req, jb4.node_num, jb4.time_limit, jb4.valid,
                    jp4 * 0, cm1, max_nodes=2))
            return outs
        runs["small(x4 quarter-size)"] = run_small

    trace_dir = os.environ.get("KEXP_TRACE")
    rows = []
    for name, fn in runs.items():
        sec, _ = time_fn(fn)
        print(f"{name}: {sec:.4f} s  ({num_jobs / sec:,.0f} decisions/s)")
        rows.append((name, f"{sec:.4f}", f"{num_jobs / sec:,.0f}"))
        if trace_dir:
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(fn())

    path = append_section(
        "kexp", str(device),
        {"jobs": num_jobs, "nodes": num_nodes},
        rows, ("variant", "median s", "decisions/s"))
    print("profile:", path, file=sys.stderr)
