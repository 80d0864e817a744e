"""Stream-count autotune harness for the S-stream Pallas solve.

plan_streams was pinned at max_streams=4 / block_jobs=256 with no
device-measured basis.  This sweeps the (max_streams x block_jobs)
grid on the attached device over the bench problem (kexp shapes,
seed 0, 8 disjoint partitions so up to 8 streams can actually form),
appends the results to ``profiles/<device>_STREAMS_PROFILE.md``, and
prints the Scheduler YAML to pin the measured optimum — which
`cranesched_tpu/utils/config.py` feeds into plan_streams via
``SchedulerConfig.max_streams`` / ``block_jobs``.

Usage: python tools/kstream.py
  BENCH_JOBS/BENCH_NODES override shapes; KSTREAM_STREAMS and
  KSTREAM_BLOCKS override the sweep lists (comma-separated).  Needs a
  TPU: no TPU, no profile.
"""

from __future__ import annotations

import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_CLASSES = 8


def build_problem(num_jobs, num_nodes):
    import jax.numpy as jnp
    from cranesched_tpu.models.solver import make_cluster_state
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
    node_part = rng.integers(0, NUM_CLASSES, num_nodes)
    job_part = rng.integers(0, NUM_CLASSES, num_jobs)
    req_j = jnp.asarray(req)
    node_num = jnp.asarray(rng.integers(1, 3, num_jobs), jnp.int32)
    time_limit = jnp.asarray(rng.integers(60, 86400, num_jobs), jnp.int32)
    valid = jnp.ones(num_jobs, bool)
    class_masks_np = np.stack(
        [node_part == c for c in range(NUM_CLASSES)])
    return (state, req_j, node_num, time_limit, valid,
            jnp.asarray(job_part, jnp.int32), job_part,
            jnp.asarray(class_masks_np), class_masks_np)


def time_fn(fn, repeats=3):
    import jax
    jax.block_until_ready(fn())       # warmup / compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        p = fn()
        jax.block_until_ready(p)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _int_list(env, default):
    raw = os.environ.get(env)
    return [int(x) for x in raw.split(",")] if raw else default


if __name__ == "__main__":
    from profmd import append_section, require_tpu
    device = require_tpu()
    num_jobs = int(os.environ.get("BENCH_JOBS", 100_000))
    num_nodes = int(os.environ.get("BENCH_NODES", 10_000))
    streams = _int_list("KSTREAM_STREAMS", [1, 2, 4, 8])
    blocks = _int_list("KSTREAM_BLOCKS", [128, 256, 512])
    print("device:", device, file=sys.stderr)

    from cranesched_tpu.models.pallas_solver import (
        plan_streams,
        solve_greedy_pallas_auto,
    )

    (state, req, node_num, time_limit, valid, job_class, job_class_np,
     class_masks, class_masks_np) = build_problem(num_jobs, num_nodes)

    rows = []
    best = None  # (sec, max_streams, block_jobs, used_streams)
    for ms in streams:
        for bj in blocks:
            plan = plan_streams(job_class_np, class_masks_np,
                                max_streams=ms, block_jobs=bj,
                                known_disjoint=True)
            used = plan[1] if plan is not None else 1

            def run(bj=bj, ms=ms, plan=plan):
                return solve_greedy_pallas_auto(
                    state, req, node_num, time_limit, valid,
                    job_class, class_masks, max_nodes=2,
                    block_jobs=bj, max_streams=ms, plan=plan)

            sec = time_fn(run)
            dps = num_jobs / sec
            print(f"max_streams={ms} block_jobs={bj} -> {used} streams, "
                  f"{sec:.4f} s  ({dps:,.0f} decisions/s)")
            rows.append((ms, bj, used, f"{sec:.4f}", f"{dps:,.0f}"))
            if best is None or sec < best[0]:
                best = (sec, ms, bj, used)

    sec, ms, bj, used = best
    yaml = (f"Scheduler:\n  MaxStreams: {ms}\n  BlockJobs: {bj}")
    print(f"\nbest: max_streams={ms} block_jobs={bj} "
          f"({used} streams, {sec:.4f} s, "
          f"{num_jobs / sec:,.0f} decisions/s)\n\npin it with:\n{yaml}")

    dev_tag = re.sub(r"\W+", "_",
                     getattr(device, "device_kind", None)
                     or device.platform).strip("_").upper()
    path = append_section(
        "kstream", str(device),
        {"jobs": num_jobs, "nodes": num_nodes, "classes": NUM_CLASSES},
        rows, ("max_streams", "block_jobs", "streams used", "median s",
               "decisions/s"),
        tag=f"{dev_tag}_STREAMS",
        notes=f"Recommended pin (fastest cell):\n\n```yaml\n{yaml}\n```")
    print("profile:", path, file=sys.stderr)
