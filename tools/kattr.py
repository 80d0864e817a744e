"""Attribution experiment: where does the ~1.6 us/job of the Pallas
greedy kernel go?  Runs stripped-down kernel variants over the bench
shape (100k jobs x 10k nodes) and prints seconds per variant:

  floor    — fori_loop + SMEM scalar reads only (scalar-core floor)
  bcast    — floor + R scalar->vector broadcasts + compares (no mins)
  onemin   — bcast + ONE full min reduction per job
  select   — bcast + the full K=2 selection (4 reductions)
  full     — the real kernel (reference point)

Findings recorded in profiles/R05_PROFILE.md; each run also appends
its table to profiles/$PROFILE_TAG_PROFILE.md (tools/profmd.py).
Needs a TPU: no TPU, no profile.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB, LANES = 8, 128


def make_variant(kind: str, BJ: int, R: int, W: int, K: int = 2):
    def kernel(job_s, avail_in, cost_in, out_o, acc_s):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            acc_s[...] = jnp.zeros((1, BJ), jnp.int32)

        nid = (jax.lax.broadcasted_iota(jnp.int32, (SUB, W), 0) * W
               + jax.lax.broadcasted_iota(jnp.int32, (SUB, W), 1))
        jlane = jax.lax.broadcasted_iota(jnp.int32, (1, BJ), 1)
        inf = jnp.int32(2**31 - 1)
        npad = jnp.int32(SUB * W)

        def body(j, carry):
            # the scalar reads every variant pays
            s = jnp.int32(0)
            for f in range(R + 4):
                s = s + job_s[0, f, j]
            if kind == "floor":
                acc_s[...] = acc_s[...] + s
                return carry
            feas = avail_in[0] >= job_s[0, 0, j]
            for r in range(1, R):
                feas = feas & (avail_in[r] >= job_s[0, r, j])
            if kind == "bcast":
                acc_s[...] = (acc_s[...]
                              + jnp.sum(feas[0:1, 0:1].astype(jnp.int32)))
                return carry
            mcost = jnp.where(feas, cost_in[0], inf)
            if kind == "onemin":
                m = jnp.min(mcost)
                acc_s[...] = jnp.where(jlane == j, s + m, acc_s[...])
                return carry
            ms, idxs = [], []
            for k in range(K):
                m = jnp.min(mcost)
                idx = jnp.min(jnp.where(mcost == m, nid, npad))
                ms.append(m)
                idxs.append(idx)
                if k + 1 < K:
                    mcost = jnp.where(nid == idx, inf, mcost)
            acc_s[...] = jnp.where(jlane == j, s + ms[-1] + idxs[-1],
                                   acc_s[...])
            return carry

        jax.lax.fori_loop(0, BJ, body, jnp.int32(0))
        out_o[pl.ds(step, 1)] = acc_s[...][None]

    return kernel


def run(kind, J, N, R=3, BJ=256):
    n_pad = -(-N // (SUB * LANES)) * (SUB * LANES)
    W = n_pad // SUB
    j_pad = -(-J // BJ) * BJ
    NB = j_pad // BJ
    rng = np.random.default_rng(0)
    job = jnp.asarray(rng.integers(1, 1000, (1, R + 4, j_pad)), jnp.int32)
    avail = jnp.asarray(rng.integers(0, 10000, (R, SUB, W)), jnp.int32)
    cost = jnp.asarray(rng.integers(0, 100, (1, SUB, W)), jnp.int32)

    fn = pl.pallas_call(
        make_variant(kind, BJ, R, W),
        grid=(NB,),
        in_specs=[pl.BlockSpec((1, R + 4, BJ), lambda i: (0, 0, i),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_shape=jax.ShapeDtypeStruct((NB, 1, BJ), jnp.int32),
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((1, BJ), jnp.int32)],
    )
    out = jax.jit(lambda a, b, c: fn(a, b, c))
    r = out(job, avail, cost)
    r.block_until_ready()
    print(f"  {kind} checksum: {int(np.asarray(r).sum())}", file=sys.stderr)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        out(job, avail, cost).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


if __name__ == "__main__":
    J = int(os.environ.get("BENCH_JOBS", 100_000))
    N = int(os.environ.get("BENCH_NODES", 10_000))
    kinds = sys.argv[1:] or ["floor", "bcast", "onemin", "select"]
    from profmd import append_section, require_tpu
    device = require_tpu()
    print("device:", device, file=sys.stderr)
    rows = []
    for kind in kinds:
        sec = run(kind, J, N)
        print(f"{kind:8s}: {sec:.4f} s   ({sec / J * 1e6:.3f} us/job)")
        rows.append((kind, f"{sec:.4f}", f"{sec / J * 1e6:.3f}"))

    path = append_section(
        "kattr", str(device),
        {"jobs": J, "nodes": N},
        rows, ("variant", "median s", "us/job"))
    print("profile:", path, file=sys.stderr)
