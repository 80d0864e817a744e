"""Simulated craned cluster with a virtual clock.

Plays the role of the reference's node daemons (reference:
src/Craned/Core/JobManager.h:94 — AllocJobs/ExecuteStep/Terminate, SIGCHLD
→ StepStatusChange back to ctld) for integration tests and replay
benchmarks: no processes, no sleeping — a priority queue of completion
events driven by ``advance_to(now)``.

Execution semantics mirrored: a step runs for its ``sim_runtime``;
if that exceeds the job's time limit the supervisor would kill it at the
limit and report ExceedTimeLimit (reference TaskManager
AddTerminationTimer_, TaskManager.h:565); terminate requests kill
immediately and report Cancelled.
"""

from __future__ import annotations

import dataclasses
import heapq

from cranesched_tpu.ctld.defs import Job, JobStatus, StepStatus
from cranesched_tpu.ctld.scheduler import JobScheduler


@dataclasses.dataclass(order=True)
class _Completion:
    time: float
    job_id: int = dataclasses.field(compare=False)
    status: JobStatus = dataclasses.field(compare=False)
    exit_code: int = dataclasses.field(compare=False)
    # incarnation token: a stale event from a dispatch that predates a
    # requeue must not complete the job's NEW run
    requeue_count: int = dataclasses.field(compare=False, default=0)
    # step-level completion (None = whole-job / implicit batch step)
    step_id: int | None = dataclasses.field(compare=False, default=None)


class SimCraned:
    """One simulated node daemon: tracks its running steps."""

    def __init__(self, node_id: int):
        self.node_id = node_id
        self.steps: set[int] = set()

    def alloc_step(self, job_id: int) -> None:
        self.steps.add(job_id)

    def free_step(self, job_id: int) -> None:
        self.steps.discard(job_id)


class SimCluster:
    """All simulated craneds + the shared virtual event queue.

    Wire-up: ``scheduler.dispatch = cluster.dispatch`` and
    ``scheduler.dispatch_terminate = cluster.terminate``; then alternate
    ``scheduler.schedule_cycle(now)`` / ``cluster.advance_to(now)``.
    """

    def __init__(self, scheduler: JobScheduler,
                 default_runtime: float = 60.0):
        self.scheduler = scheduler
        self.default_runtime = default_runtime
        self.craneds: dict[int, SimCraned] = {
            node_id: SimCraned(node_id)
            for node_id in scheduler.meta.nodes
        }
        self._events: list[_Completion] = []
        self._frozen: dict[int, tuple] = {}
        self.now = 0.0

    # -- ctld-facing stubs (the dispatch seam) --

    def wire(self, scheduler) -> None:
        """Attach every dispatch seam in one place.  dispatch_free_alloc
        keeps the scheduler default (delegates to terminate — the sim
        has no allocation state to free)."""
        scheduler.dispatch = self.dispatch
        scheduler.dispatch_step = self.dispatch_step
        scheduler.dispatch_terminate = self.terminate
        scheduler.dispatch_terminate_step = self.terminate_step
        scheduler.dispatch_suspend = self.suspend
        scheduler.dispatch_resume = self.resume

    def dispatch(self, job: Job, node_ids: list[int]) -> None:
        """AllocJobs/AllocSteps fan-out analog (JobScheduler.cpp:1732-1839):
        register the step on every allocated node and schedule its
        completion."""
        for node_id in node_ids:
            self.craneds[node_id].alloc_step(job.job_id)
        trace = getattr(self.scheduler, "jobtrace", None)
        if trace is not None:
            # the simulated node plane is synchronous and shares the
            # ctld clock: stamp the craned-side edges inline, skew 0
            start = (job.start_time if job.start_time is not None
                     else self.now)
            node = node_ids[0] if node_ids else -1
            for edge in ("craned_received", "cgroup_ready",
                         "step_start"):
                trace.stamp(job.job_id, job.requeue_count, edge, start,
                            node_id=node)
        if job.spec.alloc_only:
            return  # the allocation just sits; steps arrive separately
        runtime = (job.spec.sim_runtime if job.spec.sim_runtime is not None
                   else self.default_runtime)
        start = job.start_time if job.start_time is not None else self.now
        if runtime > job.spec.time_limit:
            heapq.heappush(self._events, _Completion(
                start + job.spec.time_limit, job.job_id,
                JobStatus.EXCEED_TIME_LIMIT, 124, job.requeue_count))
        else:
            status = (JobStatus.COMPLETED if job.spec.sim_exit_code == 0
                      else JobStatus.FAILED)
            heapq.heappush(self._events, _Completion(
                start + runtime, job.job_id, status,
                job.spec.sim_exit_code, job.requeue_count))

    def dispatch_step(self, job: Job, step) -> None:
        """ExecuteStep-per-step analog: schedule the step's completion
        (its script is virtual; sim_runtime drives the clock)."""
        runtime = (step.spec.sim_runtime
                   if step.spec.sim_runtime is not None
                   else self.default_runtime)
        start = step.start_time if step.start_time is not None else self.now
        status = (JobStatus.COMPLETED if step.spec.sim_exit_code == 0
                  else JobStatus.FAILED)
        heapq.heappush(self._events, _Completion(
            start + runtime, job.job_id, status, step.spec.sim_exit_code,
            job.requeue_count, step_id=step.step_id))

    def terminate_step(self, job_id: int, step_id: int,
                       now: float | None = None) -> None:
        """Kill exactly one step: drop its completion event and deliver
        a Cancelled step report."""
        job = self.scheduler.running.get(job_id)
        if job is None:
            return
        when = self.now if now is None else max(now, self.now)
        for i, ev in enumerate(self._events):
            if ev.job_id == job_id and ev.step_id == step_id:
                self._events.pop(i)
                heapq.heapify(self._events)
                break
        self.scheduler.step_report(job_id, step_id, StepStatus.CANCELLED,
                                   130, when,
                                   incarnation=job.requeue_count)

    def suspend(self, job_id: int, now: float) -> None:
        """Freezer analog: pull the completion event, remember remaining
        runtime (reference cgroup freezer keeps the process image)."""
        job = self.scheduler.running.get(job_id)
        rqc = job.requeue_count if job is not None else 0
        for i, ev in enumerate(self._events):
            if ev.job_id == job_id and ev.requeue_count == rqc:
                self._events.pop(i)
                heapq.heapify(self._events)
                self._frozen[job_id] = (max(ev.time - now, 0.0),
                                        ev.status, ev.exit_code,
                                        ev.requeue_count)
                return

    def resume(self, job_id: int, now: float) -> None:
        frozen = self._frozen.pop(job_id, None)
        if frozen is None:
            return
        remaining, status, exit_code, rqc = frozen
        heapq.heappush(self._events, _Completion(
            now + remaining, job_id, status, exit_code, rqc))

    def terminate(self, job_id: int, now: float | None = None,
                  incarnation: int | None = None,
                  skip_node: int | None = None) -> None:
        """TerminateSteps analog: immediate kill + Cancelled upcall.
        ``now`` is the ctld-side cancel time (the cluster clock may lag).
        ``incarnation`` guards the kill (stale system kills must miss a
        re-placed run); ``skip_node`` is irrelevant here (the sim kills
        the whole job atomically)."""
        job = self.scheduler.running.get(job_id)
        if job is None:
            return
        if incarnation is not None and job.requeue_count != incarnation:
            return
        when = self.now if now is None else max(now, self.now)
        self._frozen.pop(job_id, None)
        self._free_steps(job)
        # stamp the incarnation we killed: ctld may requeue + re-place the
        # job before this report drains (e.g. on_craned_down terminates
        # the gang then requeues in the same call) and the stale Cancelled
        # must not finalize the new incarnation
        self.scheduler.step_status_change(job_id, JobStatus.CANCELLED,
                                          130, when,
                                          incarnation=job.requeue_count)

    # -- clock --

    def advance_to(self, now: float) -> int:
        """Deliver every completion due at or before ``now``; returns the
        number of status changes sent."""
        self.now = max(self.now, now)
        sent = 0
        while self._events and self._events[0].time <= now:
            ev = heapq.heappop(self._events)
            job = self.scheduler.running.get(ev.job_id)
            # skip steps already killed (terminate/cancel raced the finish)
            # and stale events from a pre-requeue incarnation
            if job is None or job.requeue_count != ev.requeue_count:
                continue
            if ev.step_id is not None:
                # per-step completion within a live allocation
                self.scheduler.step_report(
                    ev.job_id, ev.step_id, StepStatus(ev.status.value),
                    ev.exit_code, ev.time, incarnation=ev.requeue_count)
                sent += 1
                continue
            self._free_steps(job)
            self.scheduler.step_status_change(ev.job_id, ev.status,
                                              ev.exit_code, ev.time,
                                              incarnation=ev.requeue_count)
            sent += 1
        return sent

    def next_event_time(self) -> float | None:
        return self._events[0].time if self._events else None

    def _free_steps(self, job: Job) -> None:
        # only the job's own nodes hold its step: a walk over every
        # craned made each completion O(cluster) — at 10k nodes the
        # sim plane, not the scheduler, set the completion rate
        for node_id in job.node_ids:
            self.craneds[node_id].free_step(job.job_id)

    # -- convenience driver --

    def run_until_drained(self, start: float = 0.0, cycle_s: float = 1.0,
                          max_cycles: int = 100_000) -> float:
        """Alternate cycles and clock advances until no pending/running
        jobs remain (the 1 Hz ScheduleThread_ loop, virtualized).  When a
        cycle makes no progress the clock jumps straight to the next
        completion (or begin_time), so drain time is O(events), not
        O(simulated seconds).  Held jobs never drain — callers release
        them first."""
        now = start
        sched = self.scheduler
        for _ in range(max_cycles):
            self.advance_to(now)
            started = sched.schedule_cycle(now)
            if not sched.pending and not sched.running and not self._events:
                return now
            if started:
                now += cycle_s
                continue
            # no placement: jump to whatever unblocks something next
            horizons = []
            nxt = self.next_event_time()
            if nxt is not None:
                horizons.append(nxt)
            horizons.extend(j.spec.begin_time
                            for j in sched.pending.values()
                            if j.spec.begin_time is not None
                            and j.spec.begin_time > now and not j.held)
            # per-edge dependency delays become satisfiable in the future
            for j in sched.pending.values():
                if j.held:
                    continue
                times = [v for v in j.dep_state.values()
                         if v is not None and v != float("inf")
                         and v > now]
                if times:
                    horizons.append(max(times) if not j.spec.deps_is_or
                                    else min(times))
            if not horizons:
                if all(j.held for j in sched.pending.values()):
                    return now  # only held jobs remain
                raise RuntimeError(
                    f"stuck at t={now}: {len(sched.pending)} pending, "
                    "nothing running, no future events")
            now = max(min(horizons), now + cycle_s)
        raise RuntimeError("simulation did not drain")
