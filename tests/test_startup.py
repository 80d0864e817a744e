"""Start-up rules: one process touches the chip, nothing falls back.

* a start that expects a TPU and finds none exits non-zero with the
  pre-flight report;
* ``JAX_PLATFORMS=cpu`` set explicitly boots, and banner and QueryStats
  say ``cpu``;
* the compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, with
  no directory set in code, else under the checkout whatever the cwd;
* ``Solver: auto`` picks from the platform JAX reports, and
  ``Solver: pallas`` on a backend that cannot run the kernel raises
  instead of interpreting.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from cranesched_tpu.ctld import (
    JobScheduler,
    JobSpec,
    MetaContainer,
    ResourceSpec,
    SchedulerConfig,
)
from cranesched_tpu.rpc.client import CtldClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides):
    """Subprocess environment; a value of None removes the variable."""
    env = dict(os.environ, PYTHONPATH=REPO)
    for key, value in overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return env


def _config(tmp_path):
    cfg = tmp_path / "config.yaml"
    cfg.write_text("ClusterName: t\nListen: 127.0.0.1:0\n"
                   "Nodes: [{name: 'n[0-3]', cpu: 4, memory: 4G}]\n"
                   "Partitions: [{name: default}]\n")
    return str(cfg)


# ---------------------------------------------------------------------------
# no TPU, no CPU fallback
# ---------------------------------------------------------------------------

def test_ctld_expecting_tpu_without_one_exits_nonzero(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "cranesched_tpu.ctld_main", "-c",
         _config(tmp_path), "--sim"],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env=_env(JAX_PLATFORMS=None))
    assert out.returncode != 0
    assert "listening on port" not in out.stdout
    assert "asked for a 'tpu' backend" in out.stderr
    # the pre-flight report says why: what was asked, what was visible
    assert "pre-flight report" in out.stderr
    assert '"expected_platform": "tpu"' in out.stderr
    assert '"libtpu_path"' in out.stderr and '"chips"' in out.stderr


def test_explicit_cpu_boots_and_says_cpu(tmp_path):
    ctld = subprocess.Popen(
        [sys.executable, "-m", "cranesched_tpu.ctld_main", "-c",
         _config(tmp_path), "--sim"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=str(tmp_path),
        env=_env(JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla")))
    try:
        banner = ctld.stdout.readline()
        assert "listening on port" in banner, ctld.stderr.read()
        assert "backend cpu" in banner
        port = int(banner.split("port")[1].split()[0])
        client = CtldClient(f"127.0.0.1:{port}")
        device = json.loads(client.query_stats().json)["device"]
        client.close()
        assert device["platform"] == "cpu"
        assert device["device_kind"] and device["device_count"] >= 1
        assert device["xla_cache_dir"] == str(tmp_path / "xla")
    finally:
        ctld.send_signal(signal.SIGTERM)
        _, err = ctld.communicate(timeout=30)
    assert ctld.returncode == 0
    assert "JAX_PLATFORMS=cpu was set explicitly" in err


def test_acquire_backend_reports_what_jax_holds(monkeypatch):
    import jax

    from cranesched_tpu.parallel.acquire import (
        BackendUnavailable,
        acquire_backend,
        expected_platform,
    )
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert expected_platform() == "cpu"
    doc = acquire_backend()
    assert doc["platform"] == "cpu"
    assert doc["device_kind"] == jax.devices()[0].device_kind
    assert doc["device_count"] == len(jax.devices())
    # unset means the product's target, and the CPU this process
    # really runs on is then the wrong answer — with the evidence
    monkeypatch.delenv("JAX_PLATFORMS")
    assert expected_platform() == "tpu"
    with pytest.raises(BackendUnavailable) as err:
        acquire_backend()
    assert "came up on 'cpu'" in str(err.value)
    assert err.value.preflight["expected_platform"] == "tpu"


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import json, sys
import jax
updates = []
real_update = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real_update(k, v))[1]
from cranesched_tpu.obs.flight import enable_xla_cache, xla_cache_stats
used = enable_xla_cache()
if sys.argv[1] == "compile":
    import jax.numpy as jnp
    jax.jit(lambda v: v * 3.0)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"used": used, "updates": updates,
                  "configured": jax.config.jax_compilation_cache_dir,
                  "stats": xla_cache_stats()}))
"""


def _cache_probe(tmp_path, mode, **env):
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, mode],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env=_env(JAX_PLATFORMS="cpu", **env))
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_is_not_set_in_code(tmp_path):
    elsewhere = tmp_path / "elsewhere"
    doc = _cache_probe(tmp_path, "compile",
                       JAX_COMPILATION_CACHE_DIR=str(elsewhere))
    assert doc["used"] == doc["configured"] == str(elsewhere)
    assert "jax_compilation_cache_dir" not in doc["updates"]
    # written there ...
    assert doc["stats"]["misses"] >= 1 and doc["stats"]["entries"] >= 1
    # ... and nowhere else: nothing appeared under the cwd
    assert sorted(os.listdir(tmp_path)) == ["elsewhere"]


def test_cache_dir_default_is_anchored_to_the_checkout(tmp_path):
    doc = _cache_probe(tmp_path, "configure",
                       JAX_COMPILATION_CACHE_DIR=None)
    anchored = os.path.join(REPO, "profiles", "xla_cache")
    assert doc["used"] == doc["configured"] == anchored
    assert "jax_compilation_cache_dir" in doc["updates"]
    assert os.listdir(tmp_path) == []     # the cwd played no part


def test_cache_that_cannot_be_enabled_is_an_error(tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, "configure"],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path),
        env=_env(JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(blocker / "xla")))
    assert out.returncode != 0
    assert "NotADirectoryError" in out.stderr


# ---------------------------------------------------------------------------
# solver choice
# ---------------------------------------------------------------------------

def _scheduler(solver: str):
    meta = MetaContainer()
    for i in range(12):
        meta.add_node(f"n{i:02d}", meta.layout.encode(
            cpu=16.0, mem_bytes=64 << 30, is_capacity=True),
            partitions=(f"p{i % 3}",))
        meta.craned_up(i)
    sched = JobScheduler(meta, SchedulerConfig(backfill=False,
                                               solver=solver))
    rng = np.random.default_rng(3)
    # 64 jobs a partition: under a 256-row batch's padding class the
    # stream planner would turn a smaller queue down as too skewed
    for i in range(192):
        sched.submit(JobSpec(
            res=ResourceSpec(cpu=float(rng.integers(1, 6)),
                             mem_bytes=int(rng.integers(1, 9)) << 30),
            node_num=int(rng.integers(1, 3)),
            time_limit=int(rng.integers(60, 7200)),
            partition=f"p{i % 3}"), now=0.0)
    return sched


def _cycle(sched):
    started = sched.schedule_cycle(now=1.0)
    placement = {jid: sorted(sched.running[jid].node_ids)
                 for jid in started}
    return sched.cycle_trace.snapshot()[-1]["solver"], placement


def test_auto_picks_the_solver_from_the_platform(monkeypatch):
    import jax

    host_solver, host_placement = _cycle(_scheduler("auto"))
    assert host_solver in ("native", "immediate")

    on_chip = _scheduler("auto")
    on_chip.pallas_interpret = True       # the kernel itself needs a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip_solver, chip_placement = _cycle(on_chip)
    assert chip_solver == "pallas-stream"
    assert chip_placement == host_placement


def test_pallas_solver_without_a_tpu_raises_instead_of_interpreting():
    sched = _scheduler("pallas")
    assert sched.pallas_interpret is False
    with pytest.raises(ValueError, match="interpret"):
        sched.schedule_cycle(now=1.0)
