"""Stall forensics: the always-on flight recorder and the persistent
XLA compilation cache wiring.

* :class:`FlightRecorder` — a bounded ring of recent phase stamps (the
  scheduler stamps cycle_begin/prelude/commit/dispatch/cycle_end per
  cycle; ~6 appends, microseconds) plus a stall sentry the cycle loop
  arms around every cycle.  If the deadline passes while armed, the
  sentry captures ``sys._current_frames()`` for every thread into
  ``last_stall`` alongside the ring tail — the "what was the scheduler
  doing when it stopped" answer, without attaching a debugger to a
  wedged daemon.

* :func:`enable_xla_cache` — turns on jax's persistent compilation
  cache with the size and compile-time floors dropped so every
  executable is cached, and registers a ``jax.monitoring`` listener
  that counts ``/jax/compilation_cache/cache_hits`` / ``cache_misses``
  into ``crane_xla_cache_*``.  The directory is the operator's
  ``JAX_COMPILATION_CACHE_DIR`` when that is set (jax reads it itself;
  nothing is set in code), else ``profiles/xla_cache/`` of the checkout
  this package was imported from — a fixed path, because the path is
  part of the cache key and a directory that moves never hits.

jax is imported only inside :func:`enable_xla_cache`.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from collections import deque
from typing import Callable, Optional

from cranesched_tpu.obs.metrics import REGISTRY as _OBS

_MET_STALLS = _OBS.counter(
    "crane_flight_stalls_total",
    "stall-sentry firings (armed deadline passed; stacks captured)")
_MET_XLA_HITS = _OBS.counter(
    "crane_xla_cache_hits_total",
    "persistent XLA compilation cache hits")
_MET_XLA_MISSES = _OBS.counter(
    "crane_xla_cache_misses_total",
    "persistent XLA compilation cache misses (fresh compiles cached)")
_MET_XLA_ENTRIES = _OBS.gauge(
    "crane_xla_cache_entries",
    "executables in the persistent XLA cache directory")


def dump_all_stacks() -> dict[str, list[str]]:
    """Formatted stack of every live thread, keyed ``name (tid)``.

    Pure-Python ``sys._current_frames`` — works on a RUNNING process
    (the sentry's case), unlike ``faulthandler`` which wants a file and
    C-level signal safety (the probe child's case)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: dict[str, list[str]] = {}
    for tid, frame in sys._current_frames().items():
        key = f"{names.get(tid, '?')} ({tid})"
        out[key] = [ln.rstrip("\n")
                    for ln in traceback.format_stack(frame)]
    return out


class FlightRecorder:
    """Bounded ring of phase stamps + an armable stall sentry.

    The scheduler owns one instance and stamps its cycle phases; the
    server's cycle loop arms the sentry before each cycle and disarms
    after.  A deadline that passes while armed fires ONCE: the sentry
    snapshots every thread's stack plus the ring tail (and the lock
    ledger's holder of the server lock, with the age of its hold) into
    :attr:`last_stall`, bumps ``crane_flight_stalls_total``, emits a
    ``flight_stall`` event through ``event_sink``, and disarms (the
    next cycle re-arms).  Nothing here ever raises into the loop."""

    def __init__(self, capacity: int = 256,
                 event_sink: Optional[Callable] = None,
                 lock_ledger=None):
        self.capacity = max(int(capacity), 16)
        self.event_sink = event_sink
        # obs/trace.py LockLedger or None: who holds the server lock,
        # and since when, as the sentry fires
        self.lock_ledger = lock_ledger
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.stalls_total = 0
        self.last_stall: dict | None = None
        # sentry state: deadline on the monotonic clock, None = disarmed
        self._deadline: float | None = None
        self._label = ""
        self._cond = threading.Condition(self._lock)
        self._sentry: threading.Thread | None = None
        self._closed = False

    # -- the hot path --

    def stamp(self, phase: str, detail: str = "",
              t: float | None = None) -> None:
        """Append one phase stamp (wall time, phase, detail)."""
        rec = {"t": time.time() if t is None else t, "phase": phase}
        if detail:
            rec["detail"] = detail
        with self._lock:
            self._ring.append(rec)

    # -- the stall sentry --

    def arm(self, timeout_s: float, label: str = "cycle") -> None:
        """Start (or reset) the deadline; lazily spawns the sentry."""
        if timeout_s <= 0:
            return
        with self._cond:
            self._deadline = time.monotonic() + timeout_s
            self._label = label
            if self._sentry is None:
                self._sentry = threading.Thread(
                    target=self._sentry_loop, daemon=True,
                    name="flight-sentry")
                self._sentry.start()
            self._cond.notify()

    def disarm(self) -> None:
        with self._cond:
            self._deadline = None
            self._cond.notify()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._deadline = None
            self._cond.notify()

    def _sentry_loop(self) -> None:
        while True:
            with self._cond:
                if self._closed:
                    return
                if self._deadline is None:
                    self._cond.wait()
                    continue
                wait = self._deadline - time.monotonic()
                if wait > 0:
                    self._cond.wait(wait)
                    continue
                # expired while still armed: fire once and disarm
                label = self._label
                self._deadline = None
            try:
                self._record_stall(label)
            except Exception:  # never kill the sentry
                pass

    def _record_stall(self, label: str) -> None:
        stacks = dump_all_stacks()
        with self._lock:
            phases = list(self._ring)[-16:]
        stall = {"time": time.time(), "label": label,
                 "phases": phases, "stacks": stacks}
        holder = ""
        if self.lock_ledger is not None:
            holder, held_s = self.lock_ledger.current()
            stall["lock_holder"] = holder
            stall["lock_held_s"] = round(held_s, 3)
        with self._lock:
            self.last_stall = stall
            self.stalls_total += 1
        _MET_STALLS.inc()
        if self.event_sink is not None:
            last = phases[-1]["phase"] if phases else "(no stamps)"
            self.event_sink(
                "flight_stall", "error",
                detail=f"{label} stalled; last phase {last}; "
                       + (f"lock held by {holder}; " if holder else "")
                       + f"{len(stacks)} thread stacks captured")

    # -- reading --

    def report(self, tail: int = 64) -> dict:
        """JSON-friendly dump for QueryStats / cflight."""
        with self._lock:
            return {"phases": list(self._ring)[-tail:],
                    "stalls_total": self.stalls_total,
                    "last_stall": self.last_stall,
                    "armed": self._deadline is not None}


# ---------------------------------------------------------------------------
# persistent XLA compilation cache
# ---------------------------------------------------------------------------

_xla_lock = threading.Lock()
_xla_state = {"enabled": False, "dir": "", "hits": 0, "misses": 0}
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_cache_event(event: str, **kw) -> None:
    if event == _HIT_EVENT:
        with _xla_lock:
            _xla_state["hits"] += 1
        _MET_XLA_HITS.inc()
    elif event == _MISS_EVENT:
        with _xla_lock:
            _xla_state["misses"] += 1
        _MET_XLA_MISSES.inc()


#: default cache directory: ``profiles/xla_cache`` of the checkout this
#: package lives in — never the cwd, a temp name, a pid or the time
DEFAULT_XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "profiles", "xla_cache")


def enable_xla_cache() -> str:
    """Turn on jax's persistent compilation cache and start counting
    hits/misses; returns the directory in use.  Idempotent.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax has already taken the
    directory from the environment and none is set here; otherwise the
    cache goes to :data:`DEFAULT_XLA_CACHE_DIR`.  A cache that cannot
    be enabled (unwritable directory) raises — on the paths that call
    this, an uncached start is minutes of recompiles, not a detail."""
    import jax
    import jax.monitoring as _mon

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_XLA_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    if not os.access(cache_dir, os.W_OK):
        raise PermissionError(
            f"XLA compilation cache directory {cache_dir!r} is not "
            "writable")
    # cache EVERYTHING: the small fast executables the default floors
    # skip are most of a scheduler's programs
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _xla_lock:
        if not _xla_state["enabled"]:
            _mon.register_event_listener(_on_cache_event)
        _xla_state["enabled"] = True
        _xla_state["dir"] = cache_dir
    return cache_dir


def xla_cache_stats() -> dict:
    """Hit/miss counters + on-disk entry count (JSON-friendly)."""
    with _xla_lock:
        st = dict(_xla_state)
    entries = 0
    if st["dir"]:
        try:
            entries = sum(1 for fn in os.listdir(st["dir"])
                          if fn.endswith("-cache"))
        except OSError:
            entries = 0
    _MET_XLA_ENTRIES.set(entries)
    total = st["hits"] + st["misses"]
    return {"enabled": st["enabled"], "dir": st["dir"],
            "hits": st["hits"], "misses": st["misses"],
            "entries": entries,
            "hit_rate": round(st["hits"] / total, 4) if total else 0.0}
