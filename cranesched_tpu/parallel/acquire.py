"""Backend bring-up for the one process that owns the chip.

A chip belongs to one process at a time, so the process that will
schedule is the one — the only one — that initialises JAX.  There is no
probe subprocess (a killed child is how a stale libtpu lock is left for
the parent) and no CPU downgrade: a daemon that asked for a TPU and did
not get one stops, with the evidence, instead of serving from a backend
nobody deploys.

* :func:`expected_platform` — what the operator asked for: the first
  entry of ``JAX_PLATFORMS``, or ``tpu`` when it is unset (the product's
  target).  ``JAX_PLATFORMS=cpu`` set explicitly is how tests and
  laptops run.

* :func:`preflight_report` — a stdlib-only snapshot of the environment
  the PJRT plugin is about to trust: ``TPU_*`` env vars, the libtpu
  shared object the plugin will dlopen, accelerator chip visibility
  (``/dev/accel*`` / ``/dev/vfio``), and the ``JAX_PLATFORMS`` routing.
  Printed before jax is imported, so a wedged plugin can never blind it.

* :func:`acquire_backend` — initialise JAX in THIS process under a
  ``faulthandler`` deadline (``CRANE_ACQUIRE_TIMEOUT`` seconds): a
  handshake that neither returns nor raises dumps every thread's stack
  and exits the process non-zero.  Returns the device document
  (``platform``, ``device_kind``, ``device_count``) the boot banner and
  ``QueryStats`` carry; raises :class:`BackendUnavailable` when JAX
  fails to initialise or comes up on another platform than the one
  asked for.

Metric: ``crane_backend_acquire_seconds`` (histogram).
"""

from __future__ import annotations

import faulthandler
import glob
import json
import os
import sys
import time

from cranesched_tpu.obs.metrics import REGISTRY as _OBS

#: boot-path budget (seconds) for the in-process handshake before the
#: stack dump and exit; override with CRANE_ACQUIRE_TIMEOUT.
DEFAULT_BOOT_TIMEOUT_S = 120.0

_MET_ACQ_SECONDS = _OBS.histogram(
    "crane_backend_acquire_seconds",
    "wall time of the in-process JAX backend bring-up at boot")


class BackendUnavailable(RuntimeError):
    """JAX did not come up on the platform that was asked for.  The
    message ends with ``preflight``, the :func:`preflight_report` taken
    before the attempt, so printing the exception is the diagnosis."""

    def __init__(self, message: str, preflight: dict):
        super().__init__(f"{message}\npre-flight report: "
                         f"{json.dumps(preflight, indent=1)}")
        self.preflight = preflight


def expected_platform() -> str:
    """The platform the operator asked for: first entry of
    ``JAX_PLATFORMS``; ``tpu`` when unset."""
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return asked.lower() or "tpu"


def _tpu_env() -> dict:
    """Every env var the TPU PJRT plugin reads, values truncated."""
    keys = {k: v for k, v in os.environ.items()
            if k.startswith(("TPU_", "LIBTPU", "PJRT_"))}
    for extra in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                  "XLA_FLAGS", "LD_LIBRARY_PATH"):
        if extra in os.environ:
            keys[extra] = os.environ[extra]
    return {k: (v[:120] + "..." if len(v) > 120 else v)
            for k, v in sorted(keys.items())}


def _find_libtpu() -> str:
    """The shared object ``initialize_pjrt_plugin`` will dlopen, if
    discoverable without importing jax."""
    explicit = os.environ.get("TPU_LIBRARY_PATH", "")
    if explicit and os.path.exists(explicit):
        return explicit
    import importlib.util
    spec = importlib.util.find_spec("libtpu")
    if spec is not None and spec.submodule_search_locations:
        for loc in spec.submodule_search_locations:
            for name in ("libtpu.so", "libtpu.so.1"):
                cand = os.path.join(loc, name)
                if os.path.exists(cand):
                    return cand
            return loc  # package present, .so layout unknown
    return ""


def preflight_report() -> dict:
    """Stdlib-only environment snapshot taken before any jax import —
    the "why could the plugin fail" half of a boot diagnosis."""
    accel = sorted(glob.glob("/dev/accel*"))
    vfio = sorted(glob.glob("/dev/vfio/*"))
    return {
        "jax_platforms": os.environ.get("JAX_PLATFORMS", "(unset)"),
        "expected_platform": expected_platform(),
        "libtpu_path": _find_libtpu() or "(not found)",
        "tpu_env": _tpu_env(),
        "chips": {"dev_accel": accel, "dev_vfio": vfio,
                  "visible": len(accel) + len(vfio)},
    }


def acquire_backend(timeout_s: float | None = None) -> dict:
    """Initialise JAX in this process and return what it holds:
    ``{"platform", "device_kind", "device_count", "acquire_seconds"}``.

    Raises :class:`BackendUnavailable` when backend initialisation
    raises or yields another platform than :func:`expected_platform`.
    A handshake still running after ``timeout_s`` (default
    ``CRANE_ACQUIRE_TIMEOUT``, else 120 s) has every thread's stack
    dumped to stderr by ``faulthandler`` and the process exits 1."""
    if timeout_s is None:
        timeout_s = float(os.environ.get("CRANE_ACQUIRE_TIMEOUT",
                                         DEFAULT_BOOT_TIMEOUT_S))
    preflight = preflight_report()
    expected = preflight["expected_platform"]
    t0 = time.monotonic()
    faulthandler.dump_traceback_later(timeout_s, exit=True,
                                      file=sys.stderr)
    try:
        import jax
        try:
            devices = jax.devices()
        except RuntimeError as exc:
            raise BackendUnavailable(
                f"JAX backend {expected!r} failed to initialise: "
                f"{exc}", preflight) from exc
    finally:
        faulthandler.cancel_dump_traceback_later()
    elapsed = time.monotonic() - t0
    dev = devices[0]
    if dev.platform != expected:
        raise BackendUnavailable(
            f"asked for a {expected!r} backend, JAX came up on "
            f"{dev.platform!r} ({dev.device_kind})", preflight)
    _MET_ACQ_SECONDS.observe(elapsed)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(devices),
            "acquire_seconds": round(elapsed, 3)}
