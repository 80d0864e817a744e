"""Mesh/sharding layer: multi-device scheduling solves.

The node axis of the cluster tensors is sharded over the device mesh with
``shard_map``; cross-shard decisions (which k nodes are globally cheapest)
travel over ICI as ``all_gather``/``psum`` collectives.  See
``parallel.sharded`` for the design notes.
"""

# Lazy exports: parallel.acquire must be importable WITHOUT pulling
# jax into the process (its pre-flight report is taken before jax is
# imported), and sharded.py imports jax at module scope.
_SHARDED = ("make_node_mesh", "shard_cluster_state",
            "solve_greedy_sharded", "solve_greedy_sharded_classes")
_ACQUIRE = ("acquire_backend", "expected_platform", "preflight_report")

__all__ = [*_SHARDED, *_ACQUIRE]


def __getattr__(name):
    import importlib
    if name in _SHARDED:
        mod = importlib.import_module("cranesched_tpu.parallel.sharded")
    elif name in _ACQUIRE:
        mod = importlib.import_module("cranesched_tpu.parallel.acquire")
    else:
        raise AttributeError(name)
    return getattr(mod, name)
