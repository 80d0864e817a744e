"""A batch waits at the door for a cycle that compiles (ISSUE 36, REVIEW).

What a batch caller pushes behind a compiling cycle is the next cycle's
candidates: a larger J bucket, another compile, and more behind that.  So
``SubmitBatchJobs`` waits, with no lock held and before its first chunk,
for a cycle that has met a jit signature new to the process to end
(``JobScheduler.wait_out_compiling_cycle``).  A cycle that compiles
nothing holds nobody; a single ``SubmitBatchJob`` and the queries never
wait; the wait ends with the cycle, however it ends.  The "compile" here
is a real instrumented jit met under a shape of its own, so the tests do
not depend on which solver shapes the process has already compiled."""

import itertools
import threading

import jax
import jax.numpy as jnp
import pytest

from cranesched_tpu.craned import SimCluster
from cranesched_tpu.ctld import JobScheduler, MetaContainer, SchedulerConfig
from cranesched_tpu.obs import introspect
from cranesched_tpu.rpc import CtldClient, crane_pb2 as pb, serve

_probe = introspect.instrument_jit("t_cold_cycle_probe",
                                   jax.jit(lambda x, k=1: x + k))
_sizes = itertools.count(3)


def _meet_a_new_signature():
    _probe(jnp.zeros(next(_sizes)))


def _sched():
    meta = MetaContainer()
    for i in range(8):
        meta.add_node(f"cn{i}", meta.layout.encode(
            cpu=16.0, mem_bytes=64 << 30, memsw_bytes=64 << 30,
            is_capacity=True))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(backfill=False,
                                               priority_type="basic"))
    sim = SimCluster(sched)
    sim.wire(sched)
    return sched, sim


def _spec():
    return pb.JobSpec(res=pb.ResourceSpec(cpu=1.0, mem_bytes=1 << 30,
                                          memsw_bytes=1 << 30),
                      time_limit=600, sim_runtime=30.0)


def _submit(sched, n, now=0.0):
    from cranesched_tpu.rpc.convert import spec_from_pb
    return [sched.submit(spec_from_pb(_spec()), now=now) for _ in range(n)]


# ---------------------------------------------------------------------------
# the signal: a call is known to be fresh BEFORE it compiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("second, fresh", [
    (lambda: ((jnp.zeros(7),), {}), False),                 # the same again
    (lambda: ((jnp.zeros(8),), {}), True),                  # another shape
    (lambda: ((jnp.zeros(7, jnp.int32),), {}), True),       # another dtype
    (lambda: ((jnp.zeros(7),), {"k": 2}), True),            # another static
    (lambda: (((jnp.zeros(7), jnp.zeros(7)),), {}), True),  # another tree
], ids=["same", "shape", "dtype", "static", "tree"])
def test_a_new_signature_counts_before_the_call_runs(second, fresh):
    inside = []

    def entry(x, k=1):          # stands for the jit: the count, seen from
        inside.append(introspect.fresh_calls())     # inside the call
        return x

    obs = introspect.instrument_jit("t_fresh_probe", entry)
    base = introspect.fresh_calls()
    obs(jnp.zeros(7))
    assert inside == [base + 1]
    args, kwargs = second()
    obs(*args, **kwargs)
    assert inside[1] == base + 1 + fresh
    assert introspect.fresh_calls() == base + 1 + fresh


# ---------------------------------------------------------------------------
# the scheduler's reading of it: from the fresh call to the cycle's end
# ---------------------------------------------------------------------------

def _cycle(sched, now, inside_solve=None):
    """One cycle, driven as the server drives it; ``inside_solve`` runs
    in the first lock-released closure.  Returns what
    ``cycle_compiling`` said at each yield and after the end."""
    said = []
    gen = sched.cycle_phases(now)
    try:
        fn = next(gen)
        first = True
        while True:
            said.append(sched.cycle_compiling())
            if first and inside_solve is not None:
                inside_solve()
                said.append(sched.cycle_compiling())
            first = False
            fn = gen.send(fn())
    except StopIteration:
        said.append(sched.cycle_compiling())
    return said


def test_a_cycle_compiles_from_its_first_fresh_call_to_its_end():
    sched, _ = _sched()
    assert not sched.cycle_compiling()          # between cycles: never
    _submit(sched, 3)
    for now in (1.0, 1.5):                      # whatever they met is met
        _cycle(sched, now)
        _submit(sched, 3, now=now)
    warm = _cycle(sched, 2.0)
    assert warm and not any(warm), warm         # the same shapes: nothing
    _submit(sched, 3, now=2.0)
    cold = _cycle(sched, 3.0, inside_solve=_meet_a_new_signature)
    # not before the call, then at every later yield, and not past the end
    assert cold[0] is False and cold[-1] is False
    assert len(cold) >= 3 and all(cold[1:-1]), cold


@pytest.mark.parametrize("ending", ["closed", "raised"])
def test_a_cycle_that_dies_compiling_lets_the_waiters_go(ending):
    sched, _ = _sched()
    _submit(sched, 3)
    gen = sched.cycle_phases(1.0)
    next(gen)
    _meet_a_new_signature()
    assert sched.cycle_compiling()
    waiter = threading.Thread(target=sched.wait_out_compiling_cycle)
    waiter.start()
    waiter.join(0.3)
    assert waiter.is_alive()                    # it does wait
    sched.wait_out_compiling_cycle()            # the driving thread: never
    if ending == "closed":
        gen.close()                             # the watchdog's unwind
    else:
        with pytest.raises(RuntimeError):
            gen.throw(RuntimeError("the solve fell over"))
    waiter.join(10.0)
    assert not waiter.is_alive()
    assert not sched.cycle_compiling()


# ---------------------------------------------------------------------------
# the server: who waits at the door, and who does not
# ---------------------------------------------------------------------------

def test_a_batch_waits_out_a_compiling_cycle_and_nobody_else_does():
    sched, sim = _sched()
    in_solve, release = threading.Event(), threading.Event()
    inner = sched._immediate_solve

    def solve(*a, **kw):
        if not in_solve.is_set():               # the first solve only
            _meet_a_new_signature()
            in_solve.set()
            release.wait(30.0)
        return inner(*a, **kw)

    sched._immediate_solve = solve
    server, port = serve(sched, sim=sim, address="127.0.0.1:0",
                         cycle_interval=0.02)
    client = CtldClient(f"127.0.0.1:{port}")
    batch = []
    pusher = threading.Thread(target=lambda: batch.extend(
        r.job_id for r in client.submit_many([_spec()] * 40).replies))
    try:
        first = client.submit(_spec()).job_id
        assert in_solve.wait(30.0)              # the cycle is in its compile
        pusher.start()
        pusher.join(0.5)
        assert pusher.is_alive() and not batch  # the batch is at the door
        # the lock is free: one spec and a query go straight through
        single = client.submit(_spec()).job_id
        assert single == first + 1
        rows = client.query_jobs().jobs
        assert sorted(j.job_id for j in rows) == [first, single]
        assert pusher.is_alive()
        release.set()
        pusher.join(30.0)
        assert not pusher.is_alive()
        assert batch == list(range(single + 1, single + 41))
        # and once nothing compiles, a batch goes straight in
        again = client.submit_many([_spec()] * 40).replies
        assert [r.job_id for r in again] == list(
            range(single + 41, single + 81))
    finally:
        release.set()
        client.close()
        server.stop()


# ---------------------------------------------------------------------------
# the ladder of J: a closed loop of 250-spec batches stands at 500
# candidates and a long period leaves 750: one program; what the cycle
# between two chunks of the stream's last batch leaves (250 - 32 k) is
# the program of a whole batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n, rows", [
    (0, 256), (26, 256), (58, 256), (122, 256), (250, 256), (257, 1024),
    (500, 1024), (750, 1024), (1024, 1024), (1025, 2048), (51_200, 65_536),
    (101_376, 131_072)])
def test_candidates_pad_to_256_to_1024_then_by_twos(n, rows):
    assert JobScheduler._job_bucket(n) == rows
    # never tighter than the power-of-two ladder the other shapes keep
    assert rows >= JobScheduler._bucket(n)


def test_a_third_batch_or_a_batch_in_part_meets_no_new_program():
    """500 and 750 candidates build batches of one shape, and so do 26
    and 250: the cycle after a long period, and the one that finds the
    rest of the stream's last batch, call no jit under a signature new
    to the process."""
    sched, _sim = _sched()
    _submit(sched, 500)
    ordered = list(sched.pending.values())
    two, _ = sched._build_batch(ordered, len(sched.meta.nodes))
    _submit(sched, 250)
    three, _ = sched._build_batch(list(sched.pending.values()),
                                  len(sched.meta.nodes))
    assert two.valid.shape == three.valid.shape == (1024,)
    assert int(two.valid.sum()) == 500 and int(three.valid.sum()) == 750
    queue = list(sched.pending.values())
    rest, _ = sched._build_batch(queue[:26], len(sched.meta.nodes))
    whole, _ = sched._build_batch(queue[:250], len(sched.meta.nodes))
    assert rest.valid.shape == whole.valid.shape == (256,)
