"""The one general traffic generator: it reads a mix file (streams of
submits, batch submits and queries; open loops with a rate, closed loops
with clients; job-size ranges; a preload) and drives a `CtldClient`.

Steadiness rules (the builder's contract): every seed gets the same
multiset of job sizes and of arrival gaps, drawn from the mix's own
`base_seed`, in an order the run's seed shuffles; an open loop times each
request from the instant it was due; load comes from this one process."""

from __future__ import annotations

import collections
import random
import threading
import time

from .stats import window_offsets

Job = collections.namedtuple(
    "Job", "cpu mem_gib node_num time_limit sim_runtime user partition")

# what the harness remembers of every submit it sent, keyed by the job id
# the daemon acknowledged: the client's own truth for the comparison
Ack = collections.namedtuple("Ack", "job stream phase due done")


def zipf_weights(n: int, s: float) -> list[float]:
    return [1.0 / (k ** s) for k in range(1, n + 1)]


def draw_jobs(mix: dict, partitions, n: int, base_seed: int, seed: int,
              salt: str) -> list[Job]:
    """n jobs of one mix: sizes from `base_seed` (the same multiset for
    every run), dealt round-robin over the partitions, then shuffled by
    the run's seed."""
    base = random.Random(f"{base_seed}/{salt}")
    users = [f"u{k:02d}" for k in range(int(mix.get("users", 1)))]
    weights = zipf_weights(len(users), float(mix.get("zipf", 1.0)))

    def between(key):
        lo, hi = mix[key]
        return base.randint(int(lo), int(hi))

    jobs = [Job(cpu=between("cpu"), mem_gib=between("mem_gib"),
                node_num=between("node_num"),
                time_limit=between("time_limit_s"),
                sim_runtime=float(between("sim_runtime_s")),
                user=base.choices(users, weights)[0],
                partition=partitions[i % len(partitions)])
            for i in range(n)]
    random.Random(f"{seed}/{salt}").shuffle(jobs)
    return jobs


def to_pb(job: Job, name: str, begin_time: float = 0.0):
    from cranesched_tpu.rpc import crane_pb2 as pb
    return pb.JobSpec(
        begin_time=begin_time,
        name=name, user=job.user, partition=job.partition,
        res=pb.ResourceSpec(cpu=float(job.cpu),
                            mem_bytes=int(job.mem_gib) << 30),
        node_num=int(job.node_num), time_limit=int(job.time_limit),
        sim_runtime=float(job.sim_runtime))


class Ledger:
    """Every acknowledged submit, and every refusal, of the whole run."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acks: dict[int, Ack] = {}
        self.duplicate_ids = 0
        self.refused = 0             # submits answered with job id 0
        self.rpc_errors = 0          # submits or queries that raised

    def ack(self, job_id: int, ack: Ack) -> None:
        with self._lock:
            if not job_id:
                self.refused += 1
            elif job_id in self.acks:
                self.duplicate_ids += 1
            else:
                self.acks[job_id] = ack

    def error(self) -> None:
        with self._lock:
            self.rpc_errors += 1


class OpenStream:
    """An open loop: Poisson arrivals at `rate_per_s`, sent by a pool of
    threads so that a stalled reply delays no later request.  Before the
    window opens the gaps come from a warm-up generator; the window's own
    arrivals are a fixed multiset of gaps in the seed's order, so every
    run of the cell sends the same number of requests."""

    def __init__(self, cfg: dict, mixes: dict, client, ledger: Ledger,
                 base_seed: int, seed: int, seconds: float):
        self.cfg, self.client, self.ledger = cfg, client, ledger
        self.name = cfg["name"]
        self.kind = cfg["kind"]
        self.rate = float(cfg["rate_per_s"])
        self.seconds = seconds
        n = int(round(self.rate * seconds))
        base = random.Random(f"{base_seed}/{self.name}/gaps")
        gaps = [base.expovariate(1.0) for _ in range(n + 1)]
        random.Random(f"{seed}/{self.name}/gaps").shuffle(gaps)
        self.offsets = window_offsets(gaps, seconds)[:n]
        self._warm = random.Random(f"{seed}/{self.name}/warm")
        if self.kind == "query":
            users = [f"u{k:02d}" for k in range(int(cfg.get("users", 1)))]
            pick = random.Random(f"{base_seed}/{self.name}/users")
            self.items = pick.choices(
                users, zipf_weights(len(users), float(cfg.get("zipf", 1.0))),
                k=n)
            random.Random(f"{seed}/{self.name}/users").shuffle(self.items)
            self.warm_items = users
        else:
            mix = mixes[cfg["mix"]]
            self.items = draw_jobs(mix, cfg["partitions"], n, base_seed,
                                   seed, self.name)
            self.warm_items = draw_jobs(mix, cfg["partitions"], 512,
                                        base_seed, seed, self.name + "/warm")
        self._lock = threading.Lock()
        self._next_warm = None
        self._t0 = None
        self._k = 0
        self._stop = False
        # one row per request of the window, by its index
        self.due = [0.0] * n
        self.sent = [0.0] * n
        self.done = [0.0] * n
        self.ok = [False] * n
        self.rows = [0] * n
        self.job_ids = [0] * n
        self.warm_sent = 0
        self._threads = [threading.Thread(target=self._worker, daemon=True,
                                          name=f"{self.name}-{i}")
                         for i in range(int(cfg.get("threads", 8)))]

    def start(self) -> None:
        self._next_warm = time.time()
        for t in self._threads:
            t.start()

    def open_window(self, t0: float) -> None:
        with self._lock:
            self._t0 = t0

    def stop(self) -> None:
        with self._lock:
            self._stop = True

    def join(self, timeout: float) -> bool:
        deadline = time.time() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.time()))
        return not any(t.is_alive() for t in self._threads)

    def _take(self):
        with self._lock:
            if self._stop:
                return None
            if self._t0 is None or self._next_warm < self._t0:
                due = self._next_warm
                self._next_warm += self._warm.expovariate(self.rate)
                self.warm_sent += 1
                return "warm", self.warm_sent, due
            if self._k < len(self.offsets):
                k = self._k
                self._k += 1
                return "window", k, self._t0 + self.offsets[k]
            return None

    def _worker(self) -> None:
        while True:
            nxt = self._take()
            if nxt is None:
                return
            phase, k, due = nxt
            delay = due - time.time()
            if delay > 0:
                time.sleep(delay)
            item = (self.items[k] if phase == "window"
                    else self.warm_items[k % len(self.warm_items)])
            sent = time.time()
            try:
                job_id, rows = self._send(item)
                ok = True
            except Exception:      # a failed RPC is a failed request
                self.ledger.error()
                job_id, rows, ok = 0, 0, False
            done = time.time()
            if self.kind != "query" and ok:
                self.ledger.ack(job_id, Ack(item, self.name, phase, due,
                                            done))
                ok = bool(job_id)
            if phase == "window":
                self.due[k], self.sent[k], self.done[k] = due, sent, done
                self.ok[k], self.rows[k], self.job_ids[k] = ok, rows, job_id

    def _send(self, item):
        if self.kind == "query":
            reply = self.client.query_jobs(
                user=item, limit=int(self.cfg.get("row_limit", 0)))
            return 0, len(reply.jobs)
        reply = self.client.submit(to_pb(item, self.name))
        return reply.job_id, 0


class ClosedStream:
    """A closed loop: `clients` callers, each sending its next
    `submit_many` of `batch` specs when the last is acknowledged."""

    def __init__(self, cfg: dict, mixes: dict, client, ledger: Ledger,
                 base_seed: int, seed: int, seconds: float):
        self.cfg, self.client, self.ledger = cfg, client, ledger
        self.name = cfg["name"]
        self.batch = int(cfg["batch"])
        # one multiset of sizes for every run; each client walks it from
        # its own offset in the seed's order
        self.pool = draw_jobs(mixes[cfg["mix"]], cfg["partitions"],
                              max(4096, 16 * self.batch), base_seed, seed,
                              self.name)
        self._t0 = None
        self._stop = threading.Event()
        self.batches = []            # (phase, sent, done, n_acked)
        self._lock = threading.Lock()
        self._threads = [threading.Thread(target=self._client, args=(i,),
                                          daemon=True,
                                          name=f"{self.name}-{i}")
                         for i in range(int(cfg["clients"]))]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def open_window(self, t0: float) -> None:
        self._t0 = t0

    def stop(self) -> None:
        self._stop.set()

    def join(self, timeout: float) -> bool:
        deadline = time.time() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.time()))
        return not any(t.is_alive() for t in self._threads)

    def _client(self, idx: int) -> None:
        at = idx * len(self.pool) // len(self._threads)
        while not self._stop.is_set():
            jobs = [self.pool[(at + j) % len(self.pool)]
                    for j in range(self.batch)]
            at += self.batch
            specs = [to_pb(job, self.name) for job in jobs]
            sent = time.time()
            phase = ("window" if self._t0 is not None and sent >= self._t0
                     else "warm")
            try:
                replies = self.client.submit_many(specs).replies
            except Exception:      # a failed RPC is a failed batch
                self.ledger.error()
                with self._lock:
                    self.batches.append((phase, sent, time.time(), 0))
                continue
            done = time.time()
            for job, reply in zip(jobs, replies):
                self.ledger.ack(reply.job_id,
                                Ack(job, self.name, phase, sent, done))
            with self._lock:
                self.batches.append(
                    (phase, sent, done, sum(1 for r in replies if r.job_id)))


def make_stream(cfg: dict, *args):
    loop = cfg.get("loop")
    if loop == "open":
        return OpenStream(cfg, *args)
    if loop == "closed":
        return ClosedStream(cfg, *args)
    raise ValueError(f"stream {cfg.get('name')!r}: loop is open or closed, "
                     f"not {loop!r}")


def preload(cfg: dict, mixes: dict, client, ledger: Ledger, base_seed: int,
            seed: int, settled_pending, log, idle_until) -> dict:
    """Fill the partitions and leave `pending_target` jobs waiting: one
    bulk round sized from `fill_estimate`, then top-up rounds until the
    daemon's own pending count is within `tolerance` of the target.

    The first round's jobs carry a `begin_time` (sbatch --begin) a little
    after the round is reckoned to be in, at `ingest_rate_estimate` jobs a
    second: until then no cycle has a candidate, so ingest runs against an
    idle scheduler instead of sharing the lock with ever longer cycles,
    and the whole backlog becomes eligible at once.  Afterwards they are
    plain pending jobs (the gate is one vectorized compare for all rows).

    Each round ends with one job sent alone: every submit kicks the
    cycle loop, so a cycle begins after that job's — and hence after the
    whole round's — arrival, and `settled_pending(t)` can wait for a
    cycle that began after t and placed nothing."""
    target = int(cfg["pending_target"])
    tol = max(1, int(target * float(cfg.get("tolerance", 0.005))))
    chunk = int(cfg.get("chunk", 2000))
    jobs = draw_jobs(mixes[cfg["mix"]], cfg["partitions"],
                     int(cfg["max_jobs"]), base_seed, seed, "preload")
    sent = 0
    rounds = 0
    want = target + int(cfg["fill_estimate"])

    release = time.time() + 2.0 + want / float(
        cfg.get("ingest_rate_estimate", 1e9))

    def send(part) -> None:
        t_sent = time.time()
        begin = release if release > t_sent + 1.0 else 0.0
        replies = client.submit_many(
            [to_pb(j, "preload", begin) for j in part]).replies
        done = time.time()
        for job, reply in zip(part, replies):
            ledger.ack(reply.job_id,
                       Ack(job, "preload", "setup", t_sent, done))

    while True:
        rounds += 1
        if want >= len(jobs):
            raise RuntimeError(f"the preload wants {want} jobs and the "
                               f"mix file allows {len(jobs)}")
        while sent < want - 1:
            part = jobs[sent:min(sent + chunk, want - 1)]
            send(part)
            sent += len(part)
        idle_until(release)    # no load offered: not set-up work
        t_all = time.time()
        send(jobs[sent:sent + 1])
        sent += 1
        pending = settled_pending(t_all)
        log(f"preload round {rounds}: {sent} sent, {pending} pending "
            f"(target {target} +- {tol})")
        if pending >= target - tol:
            return {"sent": sent, "pending": pending, "rounds": rounds}
        want = sent + max(2, target - pending)


LAST_SINGLES = 8


def last_words(stream_cfgs, mixes: dict, client, ledger: Ledger,
               base_seed: int, seed: int) -> int:
    """The last thing the daemon acknowledges before it is killed: one
    more request through each submitting stream's own RPC (a batch for a
    `submit_many` stream, LAST_SINGLES submits one after the other for a
    `submit` stream), each recorded in the ledger under the phase
    `last_words`.  The caller kills the daemon the instant this returns;
    the comparison then looks for every one of them in the log."""
    sent = 0
    for cfg in stream_cfgs:
        if cfg["kind"] not in ("submit", "submit_many"):
            continue
        many = cfg["kind"] == "submit_many"
        n = int(cfg["batch"]) if many else LAST_SINGLES
        jobs = draw_jobs(mixes[cfg["mix"]], cfg["partitions"], n, base_seed,
                         seed, cfg["name"] + "/last")
        groups = [jobs] if many else [[job] for job in jobs]
        for group in groups:
            specs = [to_pb(job, "last") for job in group]
            t_sent = time.time()
            replies = (client.submit_many(specs).replies if many
                       else [client.submit(specs[0])])
            done = time.time()
            for job, reply in zip(group, replies):
                ledger.ack(reply.job_id, Ack(job, cfg["name"], "last_words",
                                             t_sent, done))
            sent += len(group)
    return sent
