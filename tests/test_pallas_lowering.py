"""The Pallas solve must lower — and compile — for the TPU without one.

Every other Pallas test runs the kernel with ``interpret=True``, which
never meets the Mosaic lowering: a ``fori_loop(unroll=4)`` the installed
JAX refuses shipped that way for rounds.  Here each jitted entry point is
lowered for the TPU platform on the CPU backend, then compiled against
libtpu's compile-only v5e topology, which enforces the chip's real
scoped-VMEM limit.  Node axis at the north-star 10,000 (it sets the
kernel's VMEM shape); the job axis is kept short because it only scales
the surrounding XLA sort/scatter, not the kernel — but for the two cases
at the `widegang10k-gangs64` cell's own block count, which sizes the
outputs the kernel keeps whole in VMEM.

The compiles run in CHILD processes (this file run as a script), one
child for all cases: before PR 35 a gang bound of 16 overflowed the
stack of Mosaic's layout inference, and a compiler that dies must fail
one case, not take the pytest worker (and every test after it) down.
The worker itself never loads libtpu, so a child can.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cranesched_tpu.models import pallas_solver as ps
from cranesched_tpu.models.solver import make_cluster_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NUM_JOBS = 8192
NUM_NODES = 10_000
NUM_DIMS = 3
NUM_CLASSES = 4
BLOCK_JOBS = 256
# the static gang bounds a cycle can ask for (the buckets of K up to
# MaxNodesPerJob 64): K = 1 is straight-line code, every larger one runs
# the passes after the first in ONE loop (ISSUE 35), so neither the depth
# nor the size of the kernel follows K
GANG_BOUNDS = [1, 2, 4, 8, 16, 32, 64]
# `widegang10k-gangs64`: 131,072 candidates, 4 streams of 34,816 slots
CELL_JOBS, CELL_STREAM_LEN = 131_072, 34_816


def _abstract_args(num_nodes, sharding=None, num_jobs=NUM_JOBS):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = jax.eval_shape(lambda: make_cluster_state(
        jnp.zeros((num_nodes, NUM_DIMS), jnp.int32),
        jnp.zeros((num_nodes, NUM_DIMS), jnp.int32),
        jnp.ones(num_nodes, bool), jnp.zeros(num_nodes, jnp.int32)))
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    jobs = (spec((num_jobs, NUM_DIMS), jnp.int32),   # req
            spec((num_jobs,), jnp.int32),            # node_num
            spec((num_jobs,), jnp.int32),            # time_limit
            spec((num_jobs,), bool),                 # valid
            spec((num_jobs,), jnp.int32),            # job_class
            spec((NUM_CLASSES, num_nodes), bool))    # class_masks
    stream_of_class = spec((NUM_CLASSES,), jnp.int32)
    return state, jobs, stream_of_class


def _lower_serial(max_nodes, num_nodes=NUM_NODES, sharding=None,
                  num_jobs=NUM_JOBS):
    state, jobs, _ = _abstract_args(num_nodes, sharding, num_jobs)
    return jax.jit(
        ps._solve_serial_impl, static_argnames=ps._SERIAL_STATICS
    ).trace(state, *jobs, max_nodes=max_nodes, block_jobs=BLOCK_JOBS,
            interpret=False).lower(lowering_platforms=("tpu",))


def _lower_streamed(max_nodes, num_streams, sharding=None,
                    num_jobs=NUM_JOBS, stream_len=NUM_JOBS // 4):
    state, jobs, stream_of_class = _abstract_args(NUM_NODES, sharding,
                                                  num_jobs)
    return jax.jit(
        ps._solve_streamed_impl, static_argnames=ps._STREAM_STATICS
    ).trace(state, *jobs, stream_of_class, max_nodes=max_nodes,
            block_jobs=BLOCK_JOBS, num_streams=num_streams,
            stream_len=stream_len, interpret=False
            ).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_serial_kernel_lowers_for_tpu(max_nodes):
    assert "tpu_custom_call" in _lower_serial(max_nodes).as_text()


@pytest.mark.parametrize("num_streams", [1, 4])
@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_streamed_kernel_lowers_for_tpu(max_nodes, num_streams):
    text = _lower_streamed(max_nodes, num_streams).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("lower", [
    lambda k: _lower_serial(k), lambda k: _lower_streamed(k, 4)],
    ids=["serial", "streamed"])
def test_the_traced_kernel_does_not_grow_with_the_bound(lower):
    """The passes after the first are one loop: the program at K = 64 is
    the program at K = 8 but for the width of its output (before PR 35 it
    nested a branch a pass and grew eightfold)."""
    assert len(lower(64).as_text()) < 2 * len(lower(8).as_text())


# ---------------------------------------------------------------------------
# compiled for a v5e, each case in a child process
# ---------------------------------------------------------------------------

def _compile_cases():
    cases = {}
    for k in GANG_BOUNDS:
        cases[f"serial-K{k}"] = ("serial", dict(max_nodes=k))
        cases[f"streamed-K{k}"] = ("streamed", dict(max_nodes=k,
                                                    num_streams=4))
    cases["serial-K64-cell"] = ("serial", dict(max_nodes=64,
                                               num_jobs=CELL_JOBS))
    cases["streamed-K64-cell"] = ("streamed", dict(
        max_nodes=64, num_streams=4, num_jobs=CELL_JOBS,
        stream_len=CELL_STREAM_LEN))
    # the compile is only a check if it can fail: a node axis whose
    # resident state outgrows scoped VMEM must be refused
    cases["serial-K1-1M-nodes"] = ("serial", dict(max_nodes=1,
                                                  num_nodes=1_000_000))
    # and the fixture only isolates a compiler that dies if it outlives
    # one: this case aborts its child
    cases["selftest-abort"] = ("abort", {})
    return cases


def _child_main(names):
    """Compile the named cases one after the other; a JSON line each as
    it ends, so that the parent knows which one a dead child died in."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this installation
        print(json.dumps({"skip": f"compile-only TPU topology "
                          f"unavailable: {exc}"}), flush=True)
        return
    v5e = SingleDeviceSharding(topo.devices[0])
    cases = _compile_cases()
    for name in names:
        kind, kw = cases[name]
        if kind == "abort":
            sys.stdout.flush()
            os.abort()
        lower, kernel = ((_lower_serial, ps.KERNEL_SERIAL)
                         if kind == "serial"
                         else (_lower_streamed, ps.KERNEL_STREAMED))
        try:
            text = lower(sharding=v5e, **kw).compile().as_text()
            # a device trace names an op by its HLO instruction: the
            # kernel's is the name the program gave it plus XLA's
            # numbering, which benchmark/readers/device_op.py keys on
            out = {"named": bool(re.search(
                rf"%{kernel}(\.\d+)? = [^\n]*tpu_custom_call", text))}
        except Exception as exc:
            out = {"error": f"{type(exc).__name__}: {exc}"[:2000]}
        print(json.dumps({"case": name, **out}), flush=True)


@pytest.fixture(scope="module")
def compiled():
    """{case: {"named": bool} | {"error": str} | {"died": str}} for every
    compile-only case.  A child that dies has died in the first case it
    had not reported; the others go to a new child."""
    pending, results = list(_compile_cases()), {}
    while pending:
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *pending],
            capture_output=True, text=True, timeout=900, check=False,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=os.pathsep.join(
                         [ROOT, os.environ.get("PYTHONPATH", "")])))
        for line in done.stdout.splitlines():
            if line.startswith("{"):
                doc = json.loads(line)
                if "skip" in doc:
                    pytest.skip(doc["skip"])
                results[doc.pop("case")] = doc
        left = [name for name in pending if name not in results]
        if left:
            results[left[0]] = {"died": f"exit {done.returncode}: "
                                + done.stderr[-600:]}
        pending = left[1:]
    return results


@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_serial_kernel_compiles_for_v5e(compiled, max_nodes):
    assert compiled[f"serial-K{max_nodes}"] == {"named": True}


@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_streamed_kernel_compiles_for_v5e(compiled, max_nodes):
    assert compiled[f"streamed-K{max_nodes}"] == {"named": True}


@pytest.mark.parametrize("kernel", ["serial", "streamed"])
def test_the_kernels_compile_at_the_wide_cell_s_block_count(compiled,
                                                            kernel):
    assert compiled[f"{kernel}-K64-cell"] == {"named": True}


def test_v5e_compile_enforces_the_vmem_limit(compiled):
    assert re.search("(?i)vmem|scoped",
                     compiled["serial-K1-1M-nodes"].get("error", ""))


def test_a_compiler_that_dies_fails_its_own_case_only(compiled):
    assert "died" in compiled["selftest-abort"]
    assert all("died" not in doc for name, doc in compiled.items()
               if name != "selftest-abort")


if __name__ == "__main__":
    _child_main(sys.argv[1:])
