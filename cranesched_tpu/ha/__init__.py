"""High availability: snapshot + WAL replication, leader lease with
fencing, hot-standby failover.

The reference runs CraneCtld as a single process kept available by
Keepalived (PAPER: "CONTROL PLANE — CraneCtld (one process, HA via
Keepalived)") over the embedded DB.  This package is the reproduction's
equivalent, built from four parts:

- :mod:`snapshot` — periodic fsync'd, atomically-renamed snapshots of
  scheduler + meta + accounting state, with WAL segment rotation so
  recovery replays snapshot + tail instead of the full log;
- :mod:`follower` — a standby ctld that pulls a snapshot and streams
  WAL records over the existing gRPC plane into a shadow scheduler
  (no cycles, no dispatch);
- :mod:`lease` — an OS-level file lock on the WAL directory as the
  leader lease, plus a monotonically increasing fencing epoch stamped
  into every craned dispatch/registration so a deposed leader's
  in-flight RPCs are rejected after failover;
- promotion (in :mod:`follower`) — on leader death the standby takes
  the lock, bumps the epoch, rebuilds device-resident scheduler state
  (mask-table class rows, run ledger, timed buckets), re-adopts running
  jobs via craned re-registration, and starts the cycle loop.
"""

from cranesched_tpu.obs.metrics import REGISTRY

# 1 = leader, 0 = standby (labelless; one ctld process = one role)
ROLE_GAUGE = REGISTRY.gauge(
    "crane_ha_role", "HA role of this ctld (1=leader, 0=standby)")
LAG_GAUGE = REGISTRY.gauge(
    "crane_ha_replication_lag_records",
    "standby only: WAL records the shadow state trails the leader by")
FAILOVERS = REGISTRY.counter(
    "crane_ha_failovers_total", "standby->leader promotions")
SNAPSHOTS = REGISTRY.counter(
    "crane_ha_snapshots_total", "durable snapshots written")
WAL_SEQ_GAUGE = REGISTRY.gauge(
    "crane_ha_wal_seq", "last durable WAL sequence number")
SNAPSHOT_LOCK_HELD = REGISTRY.histogram(
    "crane_snapshot_lock_held_seconds",
    "server-lock-held time of one snapshot (capture + WAL rotate)")
SNAPSHOT_SECONDS = REGISTRY.histogram(
    "crane_snapshot_seconds",
    "whole length of one snapshot pass (lock wait, capture, save, prune)")

from cranesched_tpu.ha.lease import FencingEpoch, LeaderLease  # noqa: E402
from cranesched_tpu.ha.snapshot import (  # noqa: E402
    SnapshotStore,
    Snapshotter,
    capture_snapshot,
    restore_snapshot,
)
from cranesched_tpu.ha.follower import HaFollower  # noqa: E402

__all__ = [
    "ROLE_GAUGE", "LAG_GAUGE", "FAILOVERS", "SNAPSHOTS", "WAL_SEQ_GAUGE",
    "SNAPSHOT_LOCK_HELD", "SNAPSHOT_SECONDS",
    "FencingEpoch", "LeaderLease", "SnapshotStore", "Snapshotter",
    "capture_snapshot", "restore_snapshot", "HaFollower",
]
