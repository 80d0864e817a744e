"""The Pallas solve must lower — and compile — for the TPU without one.

Every other Pallas test runs the kernel with ``interpret=True``, which
never meets the Mosaic lowering: a ``fori_loop(unroll=4)`` the installed
JAX refuses shipped that way for rounds.  Here each jitted entry point is
lowered for the TPU platform on the CPU backend, then compiled against
libtpu's compile-only v5e topology, which enforces the chip's real
scoped-VMEM limit.  Node axis at the north-star 10,000 (it sets the
kernel's VMEM shape); the job axis is kept short because it only scales
the surrounding XLA sort/scatter, not the kernel.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cranesched_tpu.models import pallas_solver as ps
from cranesched_tpu.models.solver import make_cluster_state

NUM_JOBS = 8192
NUM_NODES = 10_000
NUM_DIMS = 3
NUM_CLASSES = 4
BLOCK_JOBS = 256
# the static gang bounds a cycle can ask for (the buckets of K up to
# MaxNodesPerJob 8): K = 1 is straight-line code, every larger one nests
# a branch a selection pass (ISSUE 32)
GANG_BOUNDS = [1, 2, 4, 8]


def _abstract_args(num_nodes, sharding=None):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = jax.eval_shape(lambda: make_cluster_state(
        jnp.zeros((num_nodes, NUM_DIMS), jnp.int32),
        jnp.zeros((num_nodes, NUM_DIMS), jnp.int32),
        jnp.ones(num_nodes, bool), jnp.zeros(num_nodes, jnp.int32)))
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    jobs = (spec((NUM_JOBS, NUM_DIMS), jnp.int32),   # req
            spec((NUM_JOBS,), jnp.int32),            # node_num
            spec((NUM_JOBS,), jnp.int32),            # time_limit
            spec((NUM_JOBS,), bool),                 # valid
            spec((NUM_JOBS,), jnp.int32),            # job_class
            spec((NUM_CLASSES, num_nodes), bool))    # class_masks
    stream_of_class = spec((NUM_CLASSES,), jnp.int32)
    return state, jobs, stream_of_class


def _lower_serial(max_nodes, num_nodes=NUM_NODES, sharding=None):
    state, jobs, _ = _abstract_args(num_nodes, sharding)
    return jax.jit(
        ps._solve_serial_impl, static_argnames=ps._SERIAL_STATICS
    ).trace(state, *jobs, max_nodes=max_nodes, block_jobs=BLOCK_JOBS,
            interpret=False).lower(lowering_platforms=("tpu",))


def _lower_streamed(max_nodes, num_streams, sharding=None):
    state, jobs, stream_of_class = _abstract_args(NUM_NODES, sharding)
    return jax.jit(
        ps._solve_streamed_impl, static_argnames=ps._STREAM_STATICS
    ).trace(state, *jobs, stream_of_class, max_nodes=max_nodes,
            block_jobs=BLOCK_JOBS, num_streams=num_streams,
            stream_len=NUM_JOBS // 4, interpret=False
            ).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_serial_kernel_lowers_for_tpu(max_nodes):
    assert "tpu_custom_call" in _lower_serial(max_nodes).as_text()


@pytest.mark.parametrize("num_streams", [1, 4])
@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_streamed_kernel_lowers_for_tpu(max_nodes, num_streams):
    text = _lower_streamed(max_nodes, num_streams).as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def v5e():
    """One device of libtpu's compile-only v5e topology (no chip)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"compile-only TPU topology unavailable: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def _assert_kernel_named(compiled, name):
    """A device trace names an op by its HLO instruction: the kernel's
    is the name the program gave it plus XLA's numbering, which is what
    benchmark/readers/device_op.py keys on."""
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call",
                     compiled.as_text())


@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_serial_kernel_compiles_for_v5e(v5e, max_nodes):
    _assert_kernel_named(_lower_serial(max_nodes, sharding=v5e).compile(),
                         ps.KERNEL_SERIAL)


@pytest.mark.parametrize("max_nodes", GANG_BOUNDS)
def test_streamed_kernel_compiles_for_v5e(v5e, max_nodes):
    _assert_kernel_named(
        _lower_streamed(max_nodes, 4, sharding=v5e).compile(),
        ps.KERNEL_STREAMED)


def test_v5e_compile_enforces_the_vmem_limit(v5e):
    """The compile above is only a check if it can fail: a node axis
    whose resident state outgrows scoped VMEM must be refused."""
    with pytest.raises(Exception, match="(?i)vmem|scoped"):
        _lower_serial(1, num_nodes=1_000_000, sharding=v5e).compile()
