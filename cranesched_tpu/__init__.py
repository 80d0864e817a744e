"""cranesched-tpu: a TPU-native cluster job scheduling framework.

A from-scratch rebuild of the capability surface of PKUHPC/CraneSched
(reference: /root/reference) designed TPU-first:

- ``ops/``      JAX primitives for the scheduler's resource algebra
                (fixed-point cpu, feasibility masks, fit counts).
- ``models/``   jit-compiled solvers mapping (cluster state, job batch) ->
                placements: the greedy scan, the time-axis backfill grid,
                task packing/exclusive, the Pallas kernels, and the
                multifactor priority sort (reference:
                src/CraneCtld/JobScheduler.cpp:6507,7606).
- ``parallel/`` Mesh/sharding layer: shard_map'd solvers splitting the node
                axis across devices with ICI collectives for the merges.
- ``ctld/``     Host control plane: job lifecycle, queues, accounting/QoS,
                licenses, reservations, dependencies, arrays, preemption,
                WAL persistence + recovery (reference: src/CraneCtld/).
- ``craned/``   Node plane: the real daemon (registration FSM, supervisor
                processes, cgroups, health checks) and the simulated
                cluster used by tests and replays.
- ``rpc/``      gRPC control fabric + CLI client (protos/crane.proto).
- ``utils/``    Hostlist grammar, YAML config, native C++ bridge.

See ARCHITECTURE.md for the full component map against the reference.
"""

__version__ = "0.1.0"
