"""A deployment from its configuration file: the cluster from the seed,
the daemon's YAML, and the daemon child that alone owns the chip.

Copied in shape from chip_smoke.py (make_cluster / write_config / banner
wait / stop_group), which stays the bring-up proof; the benchmark imports
nothing from it."""

from __future__ import annotations

import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time

from . import check
from .spec import ROOT

OUT_DIR = os.path.join(ROOT, "bench_out")


class DeployError(RuntimeError):
    """The daemon did not come up as the cell needs it."""


def make_cluster(cfg: dict, seed: int) -> dict:
    """Node capacities and partitions.  The multiset of node sizes comes
    from the configuration's own `base_seed`, the same in every run; the
    run's seed only deals them to the nodes in another order and picks
    which nodes are drained — so every seed has the same cluster to fill,
    differently laid out."""
    n = int(cfg["nodes"])
    base = random.Random(int(cfg["base_seed"]))
    cpu_lo, cpu_hi = cfg["node_cpu"]
    mem_lo, mem_hi = cfg["node_mem_gib"]
    sizes = [(base.randint(cpu_lo, cpu_hi), base.randint(mem_lo, mem_hi))
             for _ in range(n)]
    rng = random.Random(seed)
    rng.shuffle(sizes)
    part_of = []
    for part in cfg["partitions"]:
        part_of += [part["name"]] * int(part["nodes"])
    if len(part_of) != n:
        raise DeployError(f"partitions hold {len(part_of)} nodes, "
                          f"the cluster {n}")
    names = [f"cn{i:05d}" for i in range(n)]
    drained = sorted(rng.sample(range(n),
                                int(round(cfg.get("drained_share", 0) * n))))
    return {"names": names, "cpu": [s[0] for s in sizes],
            "mem_gib": [s[1] for s in sizes], "part": part_of,
            "drained": drained}


def _yaml_block(title: str, block: dict) -> list[str]:
    if not block:
        return []
    return [f"{title}:"] + [f"  {k}: {json.dumps(v)}"
                            for k, v in block.items()]


def write_config(path: str, cfg: dict, cluster: dict, wal: str) -> None:
    lines = [f"ClusterName: {cfg['cluster_name']}", "Listen: 127.0.0.1:0"]
    if cfg.get("wal", True):
        lines.append(f"Wal: {wal}")
    lines.append("Partitions:")
    lines += [f"  - name: {p['name']}" for p in cfg["partitions"]]
    lines += _yaml_block("Scheduler", cfg.get("scheduler", {}))
    lines += _yaml_block("Priority", cfg.get("priority", {}))
    lines.append("Nodes:")
    lines += [f"  - {{name: {name}, cpu: {cpu}, memory: {mem}G, "
              f"partitions: [{part}]}}"
              for name, cpu, mem, part in zip(
                  cluster["names"], cluster["cpu"], cluster["mem_gib"],
                  cluster["part"])]
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(lines) + "\n")


class ServedSystem:
    """The system under test as the harness sees it:
    `python -m cranesched_tpu.ctld_main -c <yaml> <daemon_args>` as a
    child in its own process group, alone with the chip, and a gRPC
    client to it; `stop()` leaves nothing behind."""

    BANNER_TIMEOUT_S = 300.0

    def __init__(self, cfg: dict, cluster: dict, cell: str,
                 control: str = ""):
        work = os.path.join(OUT_DIR, cell)
        env = dict(os.environ)
        # a fixed path inside the checkout: only a cell's first run here
        # compiles (the program takes the directory from the environment)
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(OUT_DIR, "xla_cache"))
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(os.path.join(work, "wal"))
        self.work = work
        self.wal = os.path.join(work, "wal", "ctld.wal")
        self.stdout_path = os.path.join(work, "ctld.stdout.log")
        self.stderr_path = os.path.join(work, "ctld.stderr.log")
        cfg_path = os.path.join(work, "config.yaml")
        write_config(cfg_path, cfg, cluster, self.wal)
        # a control run starts the same daemon through lib/control_daemon.py,
        # which switches one weakened path on (see there)
        entry = (["-m", "cranesched_tpu.ctld_main"] if not control else
                 [os.path.join(os.path.dirname(__file__),
                               "control_daemon.py"), control])
        daemon_args = list(cfg.get("daemon_args", ["--sim"]))
        if cfg.get("wal", True):
            # the deployment's interval, stated once: in its file
            daemon_args += ["--snapshot-interval",
                            str(cfg["snapshot_interval_s"])]
        with open(self.stdout_path, "w") as so, \
                open(self.stderr_path, "w") as se:
            self.proc = subprocess.Popen(
                [sys.executable, *entry, "-c", cfg_path, *daemon_args],
                stdout=so, stderr=se, cwd=ROOT, env=env,
                start_new_session=True)
        self.t_banner = None
        self.client = None
        self.profile_dir = os.path.join(work, "profile")

    def start(self) -> dict:
        """Wait for the banner, connect, and say what device the daemon
        holds."""
        from cranesched_tpu.rpc.client import CtldClient
        port = self._wait_for_banner()
        self.client = CtldClient(f"127.0.0.1:{port}", timeout=240.0)
        return json.loads(self.client.query_stats().json).get("device", {})

    def _wait_for_banner(self) -> int:
        deadline = time.monotonic() + self.BANNER_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise DeployError(
                    f"ctld exited with code {self.proc.returncode} before "
                    f"listening: {self.stderr_tail()}")
            with open(self.stdout_path, encoding="utf-8") as fh:
                for line in fh:
                    if "listening on port" in line and line.endswith("\n"):
                        self.t_banner = time.time()
                        return int(line.split("port")[1].split()[0])
            time.sleep(0.1)
        raise DeployError("ctld did not listen within "
                          f"{self.BANNER_TIMEOUT_S:.0f} s")

    def stderr_tail(self, n: int = 1500) -> str:
        try:
            with open(self.stderr_path, encoding="utf-8",
                      errors="replace") as fh:
                return fh.read()[-n:]
        except OSError:
            return ""

    def snapshot_mtime(self) -> float:
        """When the snapshotter last finished (0.0 = never): the rename
        of `<wal>.snap` is the last thing a snapshot does."""
        try:
            return os.stat(self.wal + ".snap").st_mtime
        except OSError:
            return 0.0

    def durable_state(self) -> dict:
        """What a restart would read back; call after `kill()`."""
        return check.read_durable_state(self.wal)

    def kill(self) -> bool:
        """The host dies: SIGKILL to the whole group, at once and with no
        graceful stop, so that what is on disk is what was there at the
        last acknowledgement.  Returns whether the daemon had already
        ended by itself (a crash).  Leaves nothing behind."""
        died = self.proc.poll() is not None
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        if self.client is not None:
            self.client.close()
            self.client = None
        return died
