"""The batch cut by priority, as upstream's sorter takes it: a plain
reference.

``MultiFactorPriority::GetOrderedJobPtrVec(limit = ScheduledBatchSize)``
(JobScheduler.cpp:6734, :7606-7819) computes the factor bounds over the
WHOLE pending queue (and the running jobs), a priority for every pending
job, sorts them all by descending priority and hands the scheduler the
first ``limit``.  This module does that in numpy from what was
SUBMITTED (sizes, widths, submit times), the weights, ``now`` and
``limit``, and imports nothing of the scheduler or of the device model:
it is what tests/test_batch_cut.py holds ``_cycle_body`` to on the CPU
and what tools/check_batch_cut.py holds a full-size run to on the chip.

Arithmetic is float32, as the device's (upstream's is double; only the
order is contractual).  The sort is stable and descending, so equal
priorities keep their queue order: ties go to the job that stands
first in ``pending`` (the lowest id where nothing was requeued), which
is how ``models/priority.py priority_order`` pins them.

A job is a mapping with ``cpu`` (cores a node), ``mem`` (any one unit,
a node), ``node_num``, ``submit_time`` and, optionally, ``qos``,
``part`` (its partition's priority) and ``account``; a running job also
has ``run_time`` (seconds).  The job-size terms are the job's totals
(a node's request times its nodes), as upstream's ``cpus_alloc`` /
``mem_alloc`` / ``nodes_alloc`` are.
"""

from __future__ import annotations

import numpy as np

F = np.float32

DEFAULT_WEIGHTS = dict(age=500.0, partition=1000.0, job_size=0.0,
                       fair_share=10000.0, qos=1000000.0, favor_small=True,
                       max_age=14 * 24 * 3600)


def _col(jobs, key, default=0):
    return np.array([j.get(key, default) for j in jobs], np.float64)


def _norm(v, lo, hi, degenerate=0.0):
    """(v - lo) / (hi - lo) in float32; ``degenerate`` where hi == lo."""
    if not hi > lo:
        return np.full(len(v), F(degenerate), F)
    return (v.astype(F) - F(lo)) / F(F(hi) - F(lo))


def priorities(pending, running, weights, now):
    """float32 priority of every pending job (cpp:7633-7819)."""
    w = dict(DEFAULT_WEIGHTS, **weights)
    n = len(pending)
    if not n:
        return np.zeros(0, F)
    # the age in whole seconds, clipped to max_age BEFORE its bounds
    age = np.minimum(np.floor(np.maximum(
        now - _col(pending, "submit_time"), 0.0)), w["max_age"])
    both = list(pending) + list(running)
    nodes = _col(both, "node_num", 1)
    cpus = _col(both, "cpu") * nodes
    mem = _col(both, "mem") * nodes
    qos = _col(both, "qos")
    part = _col(both, "part")
    # age bounds from the pending jobs, every other bound from both sets
    b = {name: (max(min(v.min(), np.inf), 0.0), max(v.max(), 0.0))
         for name, v in (("nodes", nodes), ("cpus", cpus), ("mem", mem),
                         ("qos", qos), ("part", part))}
    age_f = _norm(age, age.min(), age.max())
    qos_f = _norm(qos[:n], *b["qos"])
    part_f = _norm(part[:n], *b["part"])
    size_f = (_norm(cpus[:n], *b["cpus"]) + _norm(nodes[:n], *b["nodes"])
              + _norm(mem[:n], *b["mem"]))
    size_f = (F(1.0) - size_f / F(3.0) if w["favor_small"]
              else size_f / F(3.0))
    # fair share: an account's service is the sum over its running jobs
    # of (three size terms, 1.0 each where a bound is degenerate) x the
    # run time; accounts present are the pending jobs' and the running's
    accounts = [j.get("account", "default") for j in both]
    service = {a: F(0.0) for a in accounts}
    if running:
        val = (_norm(cpus[n:], *b["cpus"], degenerate=1.0)
               + _norm(nodes[n:], *b["nodes"], degenerate=1.0)
               + _norm(mem[n:], *b["mem"], degenerate=1.0)
               ) * _col(running, "run_time").astype(F)
        for a, v in zip(accounts[n:], val):
            service[a] = F(service[a] + v)
    sv_lo, sv_hi = min(service.values()), max(service.values())
    mine = np.array([service[a] for a in accounts[:n]], F)
    fshare_f = (F(1.0) - _norm(mine, sv_lo, sv_hi) if sv_hi > sv_lo
                else np.zeros(n, F))
    return (F(w["age"]) * age_f + F(w["partition"]) * part_f
            + F(w["job_size"]) * size_f + F(w["fair_share"]) * fshare_f
            + F(w["qos"]) * qos_f).astype(F)


def cut_by_priority(pending, running, weights, now, limit):
    """``(inside, priority)``: the indices into ``pending`` of the
    ``limit`` jobs a cycle solves, in the order it solves them, and every
    pending job's priority.  ``pending`` is in queue order."""
    pri = priorities(pending, running, weights, now)
    order = np.argsort(-pri, kind="stable")
    return order[:limit], pri


def ties_at_the_edge(pri, inside, limit):
    """The jobs whose float32 priority equals the cut's last: where a
    last-place difference of two float32 implementations can move the
    edge.  ``(inside the cut, outside it)`` as two counts."""
    if len(pri) <= limit or not len(inside):
        return 0, 0
    edge = pri[inside[-1]]
    same = np.nonzero(pri == edge)[0]
    taken = np.isin(same, inside)
    return int(taken.sum()), int((~taken).sum())
