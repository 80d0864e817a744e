"""Vectorized multi-factor job priority (the scheduler's sort key).

TPU-native replacement for the reference's ``MultiFactorPriority`` sorter
(reference: src/CraneCtld/JobScheduler.cpp:7606-7819, config weights
etc/config.yaml:97-112).  The C++ walks pending+running job lists three
times to find per-factor min/max bounds, accumulates a per-account
"service value" from running jobs, then computes

    priority = W_age * age_f + W_partition * part_f + W_jobsize * size_f
             + W_fairshare * fshare_f + W_qos * qos_f

per pending job.  Here the same computation is masked tensor reductions:

* factor bounds        = masked min/max over the pending/running SoA,
* per-account service  = ``segment_sum`` over running jobs into a dense
                         account axis,
* the factors          = elementwise normalizations, one fused kernel.

Semantics pinned to the reference:

* age is clipped to ``max_age`` BEFORE the age bounds are computed.
* age bounds come from pending jobs only; node/mem/cpu/qos/partition bounds
  come from pending AND running jobs.
* a running job's service value is the sum of three normalized size terms
  (cpu, nodes, mem), each term contributing **1.0** (not 0) when the bound
  is degenerate (max == min), multiplied by the job's run time, accumulated
  into its account (cpp:7716-7746).
* accounts present = accounts of pending jobs (initialized to 0) plus
  accounts of running jobs; service-value min/max range over exactly those
  (cpp:7666,7741-7748).
* a factor whose bound is degenerate is 0 (cpp:7777-7807); job_size_factor
  is the mean of its three terms, inverted when ``favor_small``.
* jobs are sorted by descending priority; the reference's std::sort is
  unstable, so ties are unspecified there — we pin ties to the lowest job
  index.  Jobs beyond ``limit`` get pending reason "Priority"
  (cpp:7624-7629).

``BasicPriority`` (FIFO, JobScheduler.h:183-201) is the identity order and
needs no kernel: callers just truncate the id-ordered pending list.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import struct

from cranesched_tpu.obs.introspect import instrument_jit

FLOAT_MAX = 3.4e38  # plain float: keep module import backend-free


@struct.dataclass
class PriorityWeights:
    """Static priority configuration (reference Config::Priority,
    CtldPublicDefs.h:160-175; defaults mirror etc/config.yaml:97-112)."""

    age: float = struct.field(pytree_node=False, default=500.0)
    partition: float = struct.field(pytree_node=False, default=1000.0)
    job_size: float = struct.field(pytree_node=False, default=0.0)
    fair_share: float = struct.field(pytree_node=False, default=10000.0)
    qos: float = struct.field(pytree_node=False, default=1000000.0)
    favor_small: bool = struct.field(pytree_node=False, default=True)
    max_age: int = struct.field(pytree_node=False, default=14 * 24 * 3600)


@struct.dataclass
class PendingPriorityAttrs:
    """Per-pending-job attributes feeding the priority solve (SoA, padded).

    age:       int32[J]  seconds since submit (clipped to max_age on device)
    qos_prio:  int32[J]
    part_prio: int32[J]
    node_num:  int32[J]
    cpus:      f32[J]    requested cpu cores (fractional ok)
    mem:       f32[J]    requested memory (any consistent unit; MiB here)
    account:   int32[J]  dense account index in [0, num_accounts)
    valid:     bool[J]
    """

    age: jax.Array
    qos_prio: jax.Array
    part_prio: jax.Array
    node_num: jax.Array
    cpus: jax.Array
    mem: jax.Array
    account: jax.Array
    valid: jax.Array


@struct.dataclass
class RunningPriorityAttrs:
    """Per-running-job attributes (same fields as pending, plus run_time)."""

    qos_prio: jax.Array
    part_prio: jax.Array
    node_num: jax.Array
    cpus: jax.Array
    mem: jax.Array
    account: jax.Array
    run_time: jax.Array
    valid: jax.Array


def _masked_min(x, mask):
    # initial= handles zero-length inputs (e.g. an empty running batch).
    return jnp.min(x, initial=FLOAT_MAX, where=mask)


def _masked_max(x, mask):
    return jnp.max(x, initial=-FLOAT_MAX, where=mask)


def _norm(value, lo, hi):
    """(value - lo) / (hi - lo), or 0 when the bound is degenerate."""
    return jnp.where(hi > lo, (value - lo) / jnp.maximum(hi - lo, 1e-30), 0.0)


def _norm_or_one(value, lo, hi):
    """Like _norm but 1.0 on a degenerate bound (service-value terms,
    cpp:7723-7746 — 'in case that the final service_val is 0')."""
    return jnp.where(hi > lo, (value - lo) / jnp.maximum(hi - lo, 1e-30), 1.0)


@functools.partial(jax.jit, static_argnames=("num_accounts",))
def multifactor_priority(
    pending: PendingPriorityAttrs,
    running: RunningPriorityAttrs,
    weights: PriorityWeights,
    num_accounts: int,
    extra_service: jax.Array | None = None,
) -> jax.Array:
    """Compute f32[J] priorities for the pending batch.

    Invalid (padding) rows get -inf so any downstream descending sort pushes
    them last.

    ``extra_service`` (f32[num_accounts], optional) adds out-of-band
    service units into the per-account service sum BEFORE the fair-share
    normalization — the federation's cluster-wide fair-share input
    (fed/usage.py): accounts burning capacity on other shards sink in
    this shard's queue too.  None keeps the single-cluster behavior
    bit-identical.
    """
    p_ok = pending.valid
    r_ok = running.valid

    # All attributes are unsigned in the reference (uint32/uint64 fields,
    # FactorBound maxima initialized to 0 — cpp:7639-7660); clamp here so
    # accidental negative inputs can't diverge from those semantics.
    def _u(x):
        return jnp.maximum(x, 0).astype(jnp.float32)

    age = _u(jnp.minimum(pending.age, weights.max_age))
    p_qos = _u(pending.qos_prio)
    p_part = _u(pending.part_prio)
    p_nodes = _u(pending.node_num)
    p_cpus = _u(pending.cpus)
    p_mem = _u(pending.mem)
    r_qos = _u(running.qos_prio)
    r_part = _u(running.part_prio)
    r_nodes = _u(running.node_num)
    r_cpus = _u(running.cpus)
    r_mem = _u(running.mem)

    # --- factor bounds (cpp:7633-7719) ---
    age_min, age_max = _masked_min(age, p_ok), _masked_max(age, p_ok)
    qos_min = jnp.minimum(_masked_min(p_qos, p_ok), _masked_min(r_qos, r_ok))
    qos_max = jnp.maximum(_masked_max(p_qos, p_ok), _masked_max(r_qos, r_ok))
    part_min = jnp.minimum(_masked_min(p_part, p_ok),
                           _masked_min(r_part, r_ok))
    part_max = jnp.maximum(_masked_max(p_part, p_ok),
                           _masked_max(r_part, r_ok))
    nodes_min = jnp.minimum(_masked_min(p_nodes, p_ok),
                            _masked_min(r_nodes, r_ok))
    nodes_max = jnp.maximum(_masked_max(p_nodes, p_ok),
                            _masked_max(r_nodes, r_ok))
    cpus_min = jnp.minimum(_masked_min(p_cpus, p_ok),
                           _masked_min(r_cpus, r_ok))
    cpus_max = jnp.maximum(_masked_max(p_cpus, p_ok),
                           _masked_max(r_cpus, r_ok))
    mem_min = jnp.minimum(_masked_min(p_mem, p_ok), _masked_min(r_mem, r_ok))
    mem_max = jnp.maximum(_masked_max(p_mem, p_ok), _masked_max(r_mem, r_ok))

    # --- per-account service value from running jobs (cpp:7716-7748) ---
    service_val = (_norm_or_one(r_cpus, cpus_min, cpus_max)
                   + _norm_or_one(r_nodes, nodes_min, nodes_max)
                   + _norm_or_one(r_mem, mem_min, mem_max))
    service_val = jnp.where(r_ok, service_val
                            * running.run_time.astype(jnp.float32), 0.0)
    acc_service = jax.ops.segment_sum(
        service_val, jnp.where(r_ok, running.account, num_accounts),
        num_segments=num_accounts + 1)[:num_accounts]
    if extra_service is not None:
        acc_service = acc_service + jnp.maximum(
            extra_service.astype(jnp.float32), 0.0)

    # Accounts present = pending accounts ∪ running accounts.
    acc_present = jnp.zeros(num_accounts + 1, bool)
    acc_present = acc_present.at[
        jnp.where(p_ok, pending.account, num_accounts)].set(True)
    acc_present = acc_present.at[
        jnp.where(r_ok, running.account, num_accounts)].set(True)
    if extra_service is not None:
        # an account with remote service is present even with no local
        # running jobs — its remote burn must widen the bounds
        acc_present = acc_present.at[:num_accounts].set(
            acc_present[:num_accounts] | (extra_service > 0))
    acc_present = acc_present[:num_accounts]
    sv_min = _masked_min(acc_service, acc_present)
    sv_max = _masked_max(acc_service, acc_present)

    # --- per-pending-job factors (cpp:7757-7819) ---
    age_f = _norm(age, age_min, age_max)
    qos_f = _norm(p_qos, qos_min, qos_max)
    part_f = _norm(p_part, part_min, part_max)
    size_f = (_norm(p_cpus, cpus_min, cpus_max)
              + _norm(p_nodes, nodes_min, nodes_max)
              + _norm(p_mem, mem_min, mem_max))
    if weights.favor_small:
        size_f = 1.0 - size_f / 3.0
    else:
        size_f = size_f / 3.0
    job_service = acc_service[pending.account]
    fshare_f = jnp.where(sv_max > sv_min,
                         1.0 - (job_service - sv_min)
                         / jnp.maximum(sv_max - sv_min, 1e-30), 0.0)

    priority = (weights.age * age_f + weights.partition * part_f
                + weights.job_size * size_f + weights.fair_share * fshare_f
                + weights.qos * qos_f)
    return jnp.where(p_ok, priority, -jnp.inf)


multifactor_priority = instrument_jit("multifactor_priority",
                                      multifactor_priority)


def priority_order(priority: jax.Array) -> jax.Array:
    """Descending-priority permutation, ties to the lowest job index.

    The reference sorts with an unstable std::sort (cpp:7621); we pin tie
    order so device and oracle agree bit-for-bit.
    """
    return jnp.argsort(-priority, stable=True)
