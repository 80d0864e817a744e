"""An in-process stand-in for the daemon and its client: a plain first-fit
scheduler behind the handful of client calls the harness makes.  It lets a
test drive a whole run without a chip, and break the timed path underneath
(`broken="ignore_memory"`: placements that over-commit memory;
`broken="forget_job"`: an acknowledged job that vanishes;
`broken="short_sighted"`: a placer that looks at the first 16 waiting jobs
only; `broken="no_fsync"`: a log that never counts a durability barrier;
`broken="ack_before_write"`: what was acknowledged in the last 50 ms
before the kill is not on disk)."""

import json
import threading
import time
import types


class FakeCtld:
    def __init__(self, cfg, cluster, cell, broken=""):
        self.cluster, self.broken = cluster, broken
        self.client = self
        self.profile_dir = ""
        self._lock = threading.Lock()
        self._jobs = {}
        self._next = 1
        self._free = [[c, m] for c, m in zip(cluster["cpu"],
                                             cluster["mem_gib"])]
        self._drained = set()
        self._rows = []
        self._started = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._forgotten = False
        self._fsyncs = 0
        self._killed_at = None
        self.t_banner = time.time()

    # ---- the system, as run.py sees it ----
    def start(self):
        self._thread.start()
        return {"platform": "fake", "device_kind": "none", "device_count": 1}

    SNAPSHOT_EVERY_S = 2.0

    def snapshot_mtime(self):
        """A snapshot lands every SNAPSHOT_EVERY_S after the banner."""
        periods = int((time.time() - self.t_banner) / self.SNAPSHOT_EVERY_S)
        return self.t_banner + periods * self.SNAPSHOT_EVERY_S if periods \
            else 0.0

    def kill(self):
        self._killed_at = time.time()
        self._stop.set()
        self._thread.join(10)
        return False

    def durable_state(self):
        lag = 0.05 if self.broken == "ack_before_write" else 0.0
        with self._lock:
            return {"jobs": {i: {"partition": j["partition"],
                                 "user": j["user"], "cpu": float(j["cpu"]),
                                 "mem_bytes": j["mem"] << 30}
                             for i, j in self._jobs.items()
                             if j["acked"] <= self._killed_at - lag},
                    "starts": {i: 1 for i, j in self._jobs.items()
                               if j["start"] > 0}}

    def stderr_tail(self):
        return ""

    def close(self):
        pass

    # ---- the client calls ----
    def modify_node(self, name, action):
        with self._lock:
            self._drained.add(self.cluster["names"].index(name))
        return types.SimpleNamespace(ok=True)

    def _insert(self, spec):
        job_id = self._next
        self._next += 1
        self._jobs[job_id] = {
            "partition": spec.partition, "user": spec.user,
            "cpu": int(spec.res.cpu), "mem": int(spec.res.mem_bytes) >> 30,
            "node_num": int(spec.node_num), "runtime": spec.sim_runtime,
            "nodes": (), "start": 0.0, "end": 0.0, "acked": time.time()}
        return job_id

    def _barrier(self):
        if self.broken != "no_fsync":
            self._fsyncs += 1

    def submit(self, spec):
        with self._lock:
            self._barrier()
            return types.SimpleNamespace(job_id=self._insert(spec))

    def submit_many(self, specs):
        with self._lock:
            self._barrier()
            return types.SimpleNamespace(replies=[
                types.SimpleNamespace(job_id=self._insert(s))
                for s in specs])

    def query_jobs(self, user="", limit=0, **_):
        with self._lock:
            jobs = [self._row(i, j) for i, j in self._jobs.items()
                    if j["user"] == user]
        return types.SimpleNamespace(jobs=jobs[:limit or None])

    def query_jobs_stream(self, include_history=False):
        with self._lock:
            return [self._row(i, j) for i, j in self._jobs.items()]

    def query_stats(self):
        with self._lock:
            pending = sum(1 for j in self._jobs.values() if not j["start"])
            doc = {
                "device": {"platform": "fake", "device_kind": "none",
                           "device_count": 1},
                "jobs_started_total": self._started,
                "cycle_trace": list(self._rows[-64:]),
                "watchdog": {"cycle_crashes_total": 0},
                "metrics": {
                    "crane_pending_jobs": {"values": {"": pending}},
                    "crane_wal_fsync_total": {"values": {"": self._fsyncs}},
                    "crane_device_peak_bytes": {"values": {"": -1}},
                    "crane_job_latency_seconds": {"values": {
                        '{edge="craned_received"}': {
                            "count": self._started, "sum": 0.0}}}}}
        return types.SimpleNamespace(json=json.dumps(doc))

    # ---- the scheduler underneath ----
    def _row(self, job_id, j):
        status = ("Pending" if not j["start"]
                  else "Completed" if j["end"] else "Running")
        return types.SimpleNamespace(
            job_id=job_id, status=status, partition=j["partition"],
            user=j["user"], start_time=j["start"], end_time=j["end"],
            node_names=[self.cluster["names"][n] for n in j["nodes"]])

    def _loop(self):
        while not self._stop.wait(0.02):
            with self._lock:
                self._cycle(time.time())

    def _cycle(self, now):
        for j in self._jobs.values():
            if j["start"] and not j["end"] and \
                    j["start"] + j["runtime"] <= now:
                j["end"] = j["start"] + j["runtime"]
                for n in j["nodes"]:
                    self._free[n][0] += j["cpu"]
                    self._free[n][1] += j["mem"]
        placed = looked_at = 0
        for job_id, j in self._jobs.items():
            if j["start"]:
                continue
            looked_at += 1
            if self.broken == "short_sighted" and looked_at > 16:
                break
            nodes = [n for n, part in enumerate(self.cluster["part"])
                     if part == j["partition"] and n not in self._drained
                     and self._free[n][0] >= j["cpu"]
                     and (self.broken == "ignore_memory"
                          or self._free[n][1] >= j["mem"])][:j["node_num"]]
            if len(nodes) < j["node_num"]:
                continue
            for n in nodes:
                self._free[n][0] -= j["cpu"]
                self._free[n][1] -= j["mem"]
            j["nodes"], j["start"] = tuple(nodes), now
            placed += 1
        self._started += placed
        if self.broken == "forget_job" and not self._forgotten \
                and len(self._jobs) > 50:
            del self._jobs[sorted(self._jobs)[25]]
            self._forgotten = True
        self._rows.append({"now": now, "solver": "fake", "placed": placed,
                           "recompiles": 0, "total_ms": 1.0})
