"""Device-resident ClusterState across scheduling cycles.

The immediate-fit solve used to rebuild its device state from full host
arrays every cycle — a complete ``[N, R]`` host→device transfer per
tick even though the incremental prelude already tracks exactly which
rows moved.  ResidentClusterState keeps the ClusterState buffers on
device between ticks and ships only ``(dirty_idx, dirty_rows)``:

- **Dirty tracking** piggybacks on MetaContainer's ``_touch_node`` hook
  (``dirty_listeners``): every snapshot-relevant node mutation lands in
  ``_pending``.  Rows the solver subtracted on device but the host then
  rejected at commit (license cap, QoS, malloc race, stale dirty row)
  are fed back through ``mark_diverged`` — those are the only rows
  where device and host can disagree without a host-side mutation.
- **Ownership discipline** for buffer donation: ``acquire()`` hands the
  state to the solve and forgets it; the solve runs a donating jit
  (``donate_argnums=(0,)``) and the scheduler gives the *returned*
  state back via ``adopt()``.  The donated input is dead after the
  call — on TPU its buffers were rewritten in place — and this class
  guarantees nothing else holds a reference to it.
- **Invalidation contract**: the caller passes a ``key`` (solver
  backend label, node count, resource dims, mask-table generation).
  Any mismatch — backend switch, craned (de)registration changing N,
  mask-table reset (reservation epoch / node-count change), topology
  permutation toggle (the scheduler calls ``invalidate()`` directly
  for that and for ``rebuild_device_state``) — drops the resident
  state and the next acquire pays one full rebuild.
- **Double buffering**: ``stage()`` runs right after commit and issues
  the *next* cycle's patch rows as an async ``jax.device_put`` while
  the dispatch drain and the following prelude run.  ``acquire()``
  consumes the staged upload only if nothing moved since (same
  ``meta_epoch`` and same row set), so steady-state cycles pay
  ``max(solve, patch-upload)`` instead of the sum and the patch itself
  is a device-side scatter with no host wait.

Cost seed note: ``RunLedger.cost0`` is time-dependent — it changes for
*every* node every cycle — so the ``[N]`` int32 cost ledger always
ships full and is excluded from the dirty-row delta.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np

from cranesched_tpu.models.solver import (
    make_cluster_state,
    patch_cluster_state,
    refresh_cost_ledger,
)

# dirty-row counts are bucketed to powers of two (floor 16) so the
# patch jit sees a handful of static shapes instead of one per count
_ROW_FLOOR = 16


def _bucket(n: int, floor: int = _ROW_FLOOR) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def padded_rows(dirty: int, num_nodes: int) -> int:
    """Padded patch length: power-of-two bucketed (floor 16) so the
    scatter jit sees few static shapes, capped at the node count (a
    pad larger than N would ship more than a full rebuild)."""
    return min(_bucket(dirty), max(num_nodes, 1))


def patch_row_bytes(num_dims: int) -> int:
    """Host→device bytes for one patched row: int32 index + int32
    avail[R] + int32 total[R] + bool alive."""
    return 4 + 4 * num_dims + 4 * num_dims + 1


def full_state_bytes(num_nodes: int, num_dims: int) -> int:
    """Host→device bytes for a full rebuild (avail+total int32 [N,R],
    alive bool [N], cost int32 [N])."""
    return num_nodes * (8 * num_dims + 1) + 4 * num_nodes


class ResidentClusterState:
    """Owns the cross-cycle device ClusterState for one scheduler."""

    def __init__(self, meta, enabled: bool = True):
        self.meta = meta
        self.enabled = enabled
        self._state = None
        self._key = None
        self._pending: set[int] = set()
        self._diverged: set[int] = set()
        # (meta_epoch, rowset, idx_dev, avail_dev, total_dev, alive_dev)
        self._staged = None
        # telemetry (persistent; per-cycle mode is consumed by the
        # scheduler via pop_cycle_mode)
        self.full_rebuilds = 0
        self.patch_cycles = 0
        self.ledger_cycles = 0
        self.staged_hits = 0
        self.last_mode: str | None = None
        self.last_h2d_rows = 0
        self.last_h2d_bytes = 0
        self.last_overlap = False
        self.last_issued_id: int | None = None
        self._cycle_mode: str | None = None
        if enabled:
            meta.dirty_listeners.append(self._note_dirty)

    # ---- dirty feeds ----

    def _note_dirty(self, node_id: int) -> None:
        self._pending.add(node_id)

    def mark_diverged(self, node_ids: Iterable[int]) -> None:
        """Commit rejected solver placements on these nodes: the device
        subtracted resources the host never allocated, and no host
        mutation will ever dirty the row.  Force-patch them next cycle."""
        if self.enabled and self._state is not None:
            self._diverged.update(int(i) for i in node_ids)

    def invalidate(self) -> None:
        """Drop the resident state; the next acquire() fully rebuilds."""
        self._state = None
        self._key = None
        self._staged = None
        self._pending.clear()
        self._diverged.clear()

    # ---- cycle protocol ----

    def acquire(self, avail, total, alive, cost0, key):
        """Hand a current device ClusterState to this cycle's solve.

        Ownership transfers to the caller: the solve donates the
        buffers, so this object forgets the state here and must be
        given the solve's returned state via adopt().  Returns
        ``(state, mode)`` with mode "rebuild", "patch", or "ledger"
        ("ledger" = empty delta, only the time-dependent cost ledger
        shipped — exactly 4*N bytes; kept apart from "patch" so that
        steady-state H2D does not read as patch traffic with zero
        dirty rows).
        """
        state, self._state = self._state, None
        n = int(np.asarray(avail).shape[0])
        r = int(np.asarray(avail).shape[1])
        if state is None or key != self._key:
            self.invalidate()
            self._key = key
            state = make_cluster_state(avail, total, alive, cost0)
            self.full_rebuilds += 1
            self.last_mode = self._cycle_mode = "rebuild"
            self.last_h2d_rows = n
            self.last_h2d_bytes = full_state_bytes(n, r)
            self.last_overlap = False
            self.last_issued_id = id(state)
            return state, "rebuild"

        rows = frozenset(self._pending | self._diverged)
        staged, self._staged = self._staged, None
        if not rows:
            # empty delta: nothing moved, so only the time-dependent
            # cost ledger ships — no scatter, trivially overlapped
            state = refresh_cost_ledger(state, cost0)
            self.patch_cycles += 1
            self.ledger_cycles += 1
            self.staged_hits += 1
            self.last_mode = self._cycle_mode = "ledger"
            self.last_overlap = True
            self.last_h2d_rows = 0
            self.last_h2d_bytes = 4 * n
            self.last_issued_id = id(state)
            return state, "ledger"
        if (staged is not None and staged[0] == self.meta.meta_epoch
                and staged[1] == rows):
            # overlap hit: the delta was uploaded asynchronously at the
            # end of the previous cycle and nothing moved since
            _, _, idx, av, tot, al = staged
            self.staged_hits += 1
            self.last_overlap = True
        else:
            idx, av, tot, al = self._gather_live(rows, n, r)
            self.last_overlap = False
        state = patch_cluster_state(state, idx, av, tot, al, cost0)
        # only retire the rows this patch covered; concurrent dirties
        # that land after the frozenset copy stay pending for next tick
        self._pending -= rows
        self._diverged -= rows
        self.patch_cycles += 1
        self.last_mode = self._cycle_mode = "patch"
        self.last_h2d_rows = len(rows)
        # padded rows + the always-full [N] cost ledger
        self.last_h2d_bytes = (padded_rows(len(rows), n)
                               * patch_row_bytes(r) + 4 * n)
        self.last_issued_id = id(state)
        return state, "patch"

    def adopt(self, new_state) -> None:
        """Take ownership of the solve's returned (post-placement)
        state; it becomes the resident state for the next cycle."""
        if self.enabled:
            self._state = new_state

    def stage(self) -> None:
        """Post-commit: asynchronously upload the rows dirtied by this
        cycle's commit so the next acquire() finds them already on
        device (the device_put overlaps the dispatch drain and the next
        prelude).  No-op when the resident path is idle."""
        if not self.enabled or self._state is None:
            return
        import jax

        rows = frozenset(self._pending | self._diverged)
        if not rows:
            # empty delta: acquire()'s fast path needs no upload
            self._staged = None
            return
        n = len(self.meta.nodes)
        r = self.meta.layout.num_dims
        idx, av, tot, al = self._gather_live(rows, n, r)
        self._staged = (self.meta.meta_epoch, rows,
                        jax.device_put(idx), jax.device_put(av),
                        jax.device_put(tot), jax.device_put(al))

    # ---- helpers ----

    def _gather_live(self, rows, n, r):
        """Padded (idx, avail, total, alive) read straight from the
        live ledger (meta.nodes).  Pad index = n → dropped by the
        scatter's mode="drop"."""
        p = padded_rows(len(rows), n)
        idx = np.full(p, n, np.int32)
        av = np.zeros((p, r), np.int32)
        tot = np.zeros((p, r), np.int32)
        al = np.zeros(p, bool)
        nodes = self.meta.nodes
        for k, i in enumerate(sorted(rows)):
            node = nodes[i]
            idx[k] = i
            av[k] = node.avail
            tot[k] = node.total
            al[k] = node.schedulable
        return idx, av, tot, al

    def pop_cycle_mode(self) -> str | None:
        """Mode of the acquire() this cycle performed, if any;
        consumed by _record_cycle_stats so cycles that bypass the
        resident path (backfill, packed, topo) report nothing."""
        mode, self._cycle_mode = self._cycle_mode, None
        return mode

    def overlap_share(self) -> float:
        """Share of patch cycles whose delta upload was pre-staged."""
        return self.staged_hits / self.patch_cycles if self.patch_cycles else 0.0
