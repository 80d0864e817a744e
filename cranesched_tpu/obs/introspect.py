"""JIT-compile telemetry, device-memory gauges, and on-demand profiler
capture windows.

ROADMAP item 1 rests on a claim nothing used to measure: that the
bucketed-padding contract (``_bucket`` in ctld/scheduler.py pads every
batch dimension to a power of two) keeps the steady-state cycle at ZERO
fresh XLA compiles.  This module makes that claim observable:

* :func:`instrument_jit` wraps each jit entry point (models/solver.py
  and the pallas/sharded/donating twins) with a cache-size observer.
  ``jax.jit`` callables expose ``_cache_size()``; if the cache grew
  across a call, that call paid a trace+compile — we count it
  (``crane_jit_compiles_total{fn}``) and attribute the call's wall time
  to ``crane_jit_compile_seconds{fn}``.  The probe is two dict-len
  reads per call (~1 µs) — cheap enough to leave on always.
* :func:`sample_device_memory` reads
  ``jax.local_devices()[0].memory_stats()`` into the
  ``crane_device_bytes_live`` / ``crane_device_peak_bytes`` /
  ``crane_device_buffers_live`` gauges, with a CPU-safe fallback
  (backends without allocator stats report bytes=-1, buffers still
  counted via ``jax.live_arrays``).
* :class:`ProfilerWindow` arms an N-cycle ``jax.profiler`` capture from
  an RPC (``CaptureProfile``); the scheduler ticks it at cycle
  boundaries and the trace lands under ``profiles/``.

The compile counters are process-global (the jit caches they observe
are), but per-cycle attribution is delta-based: the scheduler snapshots
:func:`total_compiles` at cycle start and records the delta in the
cycle trace (``recompiles``), emitting a ``recompile_steady`` event
when a warm cycle pays one.

A cache that grew is known only once the call is back.  What is known
BEFORE it is the call's signature: :func:`fresh_calls` counts the calls
begun under one the entry point had not met, each about to trace and
compile (or to load from the persistent cache).  The scheduler reads it
to tell, while a cycle runs, that the cycle compiles
(``JobScheduler.cycle_compiling``).  A signature costs one tree flatten
and one set probe a call (~10 µs for a solve's two pytrees).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Optional

from cranesched_tpu.obs.metrics import REGISTRY as _OBS

log = logging.getLogger("obs.introspect")

_MET_COMPILES = _OBS.counter(
    "crane_jit_compiles_total",
    "fresh XLA traces+compiles paid by a jit entry point, by fn")
_MET_COMPILE_SECONDS = _OBS.histogram(
    "crane_jit_compile_seconds",
    "wall time of calls that paid a fresh compile, by fn")
_MET_DEV_BYTES = _OBS.gauge(
    "crane_device_bytes_live",
    "bytes in use on device 0 (-1 when the backend has no stats)")
_MET_DEV_PEAK = _OBS.gauge(
    "crane_device_peak_bytes",
    "peak bytes in use on device 0 (-1 when unavailable)")
_MET_DEV_BUFFERS = _OBS.gauge(
    "crane_device_buffers_live",
    "live jax arrays in the process")

_lock = threading.Lock()
_total_compiles = 0
_fresh_calls = 0


def total_compiles() -> int:
    """Process-wide count of observed fresh compiles (cycle-delta base)."""
    with _lock:
        return _total_compiles


def _note(n: int, dt: float) -> None:
    global _total_compiles
    with _lock:
        _total_compiles += n


def fresh_calls() -> int:
    """Process-wide count of calls BEGUN under a signature new to their
    entry point: it moves before the compile starts, where
    :func:`total_compiles` moves after it."""
    return _fresh_calls


def _note_signature(seen: set, args, kwargs) -> None:
    """Count the call as fresh if the entry point has not met its
    signature: what a jit keys its cache on, near enough (the tree with
    its static fields, the shape and dtype of every array leaf, the
    value of any other leaf: static ints, bools, strings)."""
    global _fresh_calls
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = (treedef, tuple(
        (x.shape, x.dtype) if hasattr(x, "shape") and hasattr(x, "dtype")
        else x for x in leaves))
    if sig in seen:
        return
    seen.add(sig)
    with _lock:
        _fresh_calls += 1


def instrument_jit(name: str, jitted: Callable) -> Callable:
    """Wrap a ``jax.jit`` callable with the compile observer.

    The wrapper preserves the jit object's surface that callers rely
    on: ``__wrapped__`` still reaches the plain-python function (so
    donating twins can re-jit it), and ``lower`` / ``clear_cache`` /
    ``_cache_size`` pass through.  Backends or jax versions without
    ``_cache_size`` degrade to a pass-through call (no counting, no
    breakage)."""
    cell = _MET_COMPILES.labels(fn=name)
    hcell = _MET_COMPILE_SECONDS.labels(fn=name)
    probe = getattr(jitted, "_cache_size", None)
    seen: set = set()

    def wrapper(*args, **kwargs):
        _note_signature(seen, args, kwargs)
        if probe is None:
            return jitted(*args, **kwargs)
        try:
            before = probe()
        except Exception:  # pragma: no cover - defensive vs jax internals
            return jitted(*args, **kwargs)
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        t1 = time.perf_counter()
        try:
            grew = probe() - before
        except Exception:  # pragma: no cover
            grew = 0
        if grew > 0:
            cell.inc(grew)
            hcell.observe(t1 - t0)
            _note(grew, t1 - t0)
            log.debug("jit compile: %s (+%d entries, %.3fs)",
                      name, grew, t1 - t0)
        return out

    wrapper.__name__ = f"observed_{name}"
    wrapper.__qualname__ = wrapper.__name__
    wrapper.__doc__ = getattr(jitted, "__doc__", None)
    # the plain python fn, NOT the jit object: donating twins re-jit it
    wrapper.__wrapped__ = getattr(jitted, "__wrapped__", jitted)
    wrapper._observed_jit = jitted
    for attr in ("lower", "clear_cache", "_cache_size", "trace"):
        member = getattr(jitted, attr, None)
        if member is not None:
            setattr(wrapper, attr, member)
    return wrapper


def sample_device_memory(peak_reset: bool = False) -> dict:
    """Device-0 allocator stats as a small dict, CPU-safe.

    Returns ``{"bytes": int, "peak_bytes": int, "buffers": int}``;
    bytes/peak are -1 when the backend exposes no ``memory_stats()``
    (the stock CPU client).  ``buffers`` counts live jax arrays in the
    process, which works on every backend."""
    bytes_live = peak = -1
    buffers = -1
    try:
        import jax
        try:
            devs = jax.local_devices()
            stats = devs[0].memory_stats() if devs else None
        except Exception:
            stats = None
        if stats:
            bytes_live = int(stats.get("bytes_in_use", -1))
            peak = int(stats.get("peak_bytes_in_use", -1))
        try:
            buffers = len(jax.live_arrays())
        except Exception:
            buffers = -1
    except Exception:  # jax itself unavailable/broken
        pass
    _MET_DEV_BYTES.set(bytes_live)
    _MET_DEV_PEAK.set(peak)
    if buffers >= 0:
        _MET_DEV_BUFFERS.set(buffers)
    return {"bytes": bytes_live, "peak_bytes": peak, "buffers": buffers}


class ProfilerWindow:
    """RPC-armed ``jax.profiler`` capture spanning N scheduling cycles.

    ``request(cycles, out_dir)`` arms the window; the scheduler calls
    :meth:`tick` once per cycle (cheap no-op while disarmed).  The
    first tick after arming starts the trace; after ``cycles`` more
    ticks a helper thread stops it and records the capture directory
    in :attr:`last_capture`.  Never raises into the cycle loop."""

    def __init__(self, base_dir: str = "profiles",
                 event_sink: Optional[Callable] = None,
                 namespace: "str | Callable[[], str] | None" = None):
        self.base_dir = base_dir
        self.event_sink = event_sink
        # shard id (str, or callable resolved at request time — the
        # scheduler learns its shard name AFTER construction when the
        # fed plane attaches): federated shards often share one
        # filesystem, and two shards arming in the same instant must
        # not write traces into the same capture dir
        self.namespace = namespace
        self._lock = threading.Lock()
        self._armed = 0          # cycles requested, 0 = disarmed
        self._remaining = 0      # cycles left in an active capture
        self._active_dir = ""
        self._capture_seq = 0    # per-process uniquifier
        self.last_capture = ""
        self.last_error = ""
        self.captures_done = 0

    def _namespace(self) -> str:
        ns = self.namespace
        if callable(ns):
            try:
                ns = ns()
            except Exception:
                ns = ""
        return str(ns) if ns else ""

    def request(self, cycles: int, out_dir: str = "") -> tuple:
        """Arm a capture.  Returns (ok, dir-or-error)."""
        cycles = int(cycles)
        if cycles <= 0:
            return False, "cycles must be > 0"
        with self._lock:
            if self._armed or self._remaining or self._active_dir:
                return False, "capture already in progress"
            self._capture_seq += 1
            ns = self._namespace()
            tag = (f"capture-{ns}-" if ns else "capture-")
            d = out_dir or os.path.join(
                self.base_dir,
                "%s%d-%d-%d" % (tag, int(time.time() * 1000),
                                os.getpid(), self._capture_seq))
            self._armed = cycles
            self._active_dir = d
        return True, d

    def tick(self) -> None:
        """Cycle-boundary hook: start / count down / stop the trace."""
        with self._lock:
            armed, remaining, d = (self._armed, self._remaining,
                                   self._active_dir)
        if not armed and not remaining:
            return
        if armed:
            try:
                os.makedirs(d, exist_ok=True)
                import jax

                # host tracer on (TraceAnnotations are its events), the
                # Python tracer off: it stretched every traced cycle
                # 3-6x and stalled the daemon ~18 s at stop_trace
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(d, profiler_options=options)
                with self._lock:
                    self._remaining = self._armed
                    self._armed = 0
                if self.event_sink is not None:
                    self.event_sink("profile_capture", "info",
                                    detail="started: %s" % d)
            except Exception as e:  # never break the cycle loop
                with self._lock:
                    self._armed = 0
                    self._active_dir = ""
                    self.last_error = str(e)
                log.warning("profiler capture failed to start: %s", e)
            return
        with self._lock:
            self._remaining = max(self._remaining - 1, 0)
            done = self._remaining == 0
        if done:
            # off the cycle thread, and so off the server lock the tick
            # runs under: with the Python tracer off, stop_trace still
            # took 13.8 s with four minload-5k cycles in the trace (PERF.md)
            threading.Thread(target=self._stop, name="profiler-stop",
                             daemon=True).start()

    def _stop(self) -> None:
        """Stop the trace and record the capture; the window stays
        taken (``_active_dir``) until the file is written."""
        t0 = time.perf_counter()
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            with self._lock:
                self.last_error = str(e)
            log.warning("profiler capture failed to stop: %s", e)
        with self._lock:
            self.last_capture = self._active_dir
            self._active_dir = ""
            self.captures_done += 1
        if self.event_sink is not None:
            self.event_sink(
                "profile_capture", "info",
                detail="written: %s (stop_trace took %.2f s)" % (
                    self.last_capture, time.perf_counter() - t0))

    @property
    def capturing(self) -> bool:
        """True between the tick that started a trace and the tick
        that stops it."""
        return self._remaining > 0

    def status(self) -> dict:
        with self._lock:
            return {"armed": self._armed, "remaining": self._remaining,
                    "active_dir": self._active_dir,
                    "last_capture": self.last_capture,
                    "last_error": self.last_error,
                    "captures_done": self.captures_done}
