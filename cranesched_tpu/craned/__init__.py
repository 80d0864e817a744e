"""Node plane: craned daemons.

``sim`` provides in-process simulated craneds with a virtual clock — the
integration-test seam the reference lacks (SURVEY.md §4).  ``daemon`` is
the REAL craned (registration FSM, supervisor processes, cgroups);
``supervisor`` is the per-step process.  Imports are lazy so the
supervisor subprocess never pulls the scheduler (and with it JAX: a chip
belongs to the one ctld process).
"""

__all__ = ["SimCluster", "SimCraned", "CranedDaemon", "CranedState"]


def __getattr__(name):
    if name in ("SimCluster", "SimCraned"):
        from cranesched_tpu.craned import sim
        return getattr(sim, name)
    if name in ("CranedDaemon", "CranedState"):
        from cranesched_tpu.craned import daemon
        return getattr(daemon, name)
    raise AttributeError(name)
