"""cranectld: the control-plane daemon entry point.

Mirrors the reference's CraneCtld bootstrap (reference:
src/CraneCtld/CraneCtld.cpp:1019-1279 — config parse, global init in
dependency order, recovery from the embedded DB, then serve):

    python -m cranesched_tpu.ctld_main -c etc/config.yaml
    python -m cranesched_tpu.ctld_main -c etc/config.yaml --sim

``--sim`` attaches the in-process simulated node plane (every configured
node is immediately alive and runs jobs on the virtual completion queue);
without it, nodes come alive as real craned daemons register.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cranectld")
    ap.add_argument("--config", "-c", required=True)
    ap.add_argument("--sim", action="store_true",
                    help="simulated node plane (no real craneds)")
    ap.add_argument("--listen", default="",
                    help="override the config listen address")
    ap.add_argument("--cycle-interval", type=float, default=1.0)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="Prometheus /metrics port (overrides config "
                         "Observability.MetricsPort; 0 = ephemeral)")
    ap.add_argument("--log-file", default="",
                    help="rotating log file (32 MiB x 5 by default)")
    ap.add_argument("--log-level", default="info")
    ap.add_argument("--ha-standby", action="store_true",
                    help="start as a hot standby: replicate from "
                         "--ha-peer, serve queries only, and promote "
                         "when the leader's lease frees")
    ap.add_argument("--ha-peer", default="",
                    help="the other ctld's address (the leader to "
                         "replicate from when --ha-standby; advertised "
                         "to redirected clients otherwise)")
    ap.add_argument("--snapshot-interval", type=float, default=60.0,
                    help="seconds between WAL snapshots (leader only; "
                         "0 disables)")
    args = ap.parse_args(argv)
    if args.ha_standby and not args.ha_peer:
        ap.error("--ha-standby requires --ha-peer")

    from cranesched_tpu.utils.logging import setup_logging
    log = setup_logging("ctld", args.log_file, args.log_level)
    log.info("cranectld starting (config=%s)", args.config)

    # One process touches the chip: this one.  JAX comes up here, under
    # a deadline, before anything else imports it; a daemon that asked
    # for a TPU (JAX_PLATFORMS unset, or naming one) and did not get it
    # stops with the pre-flight report instead of serving from the CPU.
    # JAX_PLATFORMS=cpu set explicitly is how tests and laptops run.
    from cranesched_tpu.parallel.acquire import (
        BackendUnavailable,
        acquire_backend,
    )
    try:
        device = acquire_backend()
    except BackendUnavailable as exc:
        print(f"FATAL: {exc}", file=sys.stderr, flush=True)
        return 3
    # persistent compile cache before the first compile: a restart
    # reloads its solver programs instead of recompiling them
    from cranesched_tpu.obs.flight import enable_xla_cache
    xla_cache_dir = enable_xla_cache()
    print(f"backend: {device['platform']} ({device['device_kind']} x"
          f"{device['device_count']}, up in "
          f"{device['acquire_seconds']}s)"
          + (" — JAX_PLATFORMS=cpu was set explicitly; not a "
             "deployment" if device["platform"] == "cpu" else "")
          + f"; XLA cache {xla_cache_dir}",
          file=sys.stderr, flush=True)

    from cranesched_tpu.craned.sim import SimCluster
    from cranesched_tpu.ctld.wal import WriteAheadLog
    from cranesched_tpu.rpc.dispatcher import GrpcDispatcher
    from cranesched_tpu.rpc.server import serve
    from cranesched_tpu.utils.config import load_config

    cfg = load_config(args.config)
    meta, scheduler = cfg.build()
    # what this daemon holds, for QueryStats (`cstats`)
    scheduler.stats["device"] = dict(device, xla_cache_dir=xla_cache_dir)

    if cfg.acct_store_path and scheduler.accounts is not None:
        print(f"accounting store: {cfg.acct_store_path} "
              f"({len(scheduler.accounts.accounts)} accounts, "
              f"{len(scheduler.accounts.users)} users, "
              f"{len(scheduler.accounts.qos)} qos)", flush=True)

    if cfg.archive_path:
        from cranesched_tpu.ctld.archive import JobArchive
        os.makedirs(os.path.dirname(cfg.archive_path) or ".",
                    exist_ok=True)
        scheduler.attach_archive(JobArchive(cfg.archive_path))
        print(f"history archive: {cfg.archive_path} "
              f"({scheduler.archive.count()} jobs)", flush=True)

    # federation plane BEFORE recovery: the replay must filter
    # committed migrations' jobs and rebuild imported node meta
    # (fed.prepare_recovery inside recover_from_snapshot), and the
    # UsageBook must exist before scheduler.recover backfills
    # note_submit/note_run for boot-restored jobs — a restarted leader
    # that published zero usage would let every peer's gate overshoot.
    shard_map = cfg.shard_map()
    shard_name = cfg.shard_name
    if shard_map is not None:
        # leases + live-migration WAL protocol ride on the scheduler
        # (fed/shard.py self-attaches as .fed), and Federation:
        # Limits: turns on the cluster-wide UsageBook
        from cranesched_tpu.fed.shard import FedShardPlane
        FedShardPlane(scheduler, shard_name)
        limits = cfg.global_limits()
        if limits is not None:
            from cranesched_tpu.fed.usage import (
                UsageBook,
                effective_publish_slack,
            )
            # PublishSlack = admissions a shard may run ahead of what
            # its slowest peer CONFIRMED pulling (the conservative
            # gate subtracts (shards-1)*slack from every global
            # limit); 8 absorbs a burst of submits inside one gossip
            # interval.  Clamped so a small global limit stays
            # satisfiable — unclamped, limit <= (shards-1)*slack
            # would deny every submit forever.
            asked = int((cfg.federation.get("Limits") or {})
                        .get("PublishSlack", 8))
            n_shards = len(shard_map.shards)
            slack, asked = effective_publish_slack(
                limits, n_shards, asked)
            if slack != asked:
                print(f"WARNING: PublishSlack={asked} leaves no "
                      f"admissible headroom under the configured "
                      f"global limits with {n_shards} shards — "
                      f"clamped to {slack}",
                      file=sys.stderr, flush=True)
            scheduler.global_usage = UsageBook(
                shard_name, limits,
                n_shards=n_shards,
                publish_slack=slack,
                seq_source=lambda: (scheduler.wal.durable_seq
                                    if scheduler.wal is not None
                                    else 0),
                peers=tuple(sorted(shard_map.shards)))
        print(f"federation shard {shard_name!r}: "
              f"{len(shard_map.shards)} shards, map epoch "
              f"{shard_map.epoch}"
              + (", global limits on" if limits is not None else ""),
              flush=True)

    # recovery before serving (reference JobScheduler::Init).  A leader
    # takes the WAL-dir lease FIRST: a second ctld pointed at the same
    # WAL (operator error, or a fenced-off old leader restarting) fails
    # fast instead of corrupting the log (VERDICT row 43).  A standby
    # skips all of this — its follower thread seeds from its own local
    # snapshot+WAL and only opens them for writing at promotion.
    lease = None
    if cfg.wal_path:
        # both roles write under the WAL dir (the standby keeps its
        # replicated WAL, snapshot, and observed epoch there)
        os.makedirs(os.path.dirname(cfg.wal_path) or ".", exist_ok=True)
    if cfg.wal_path and not args.ha_standby:
        from cranesched_tpu.ha import LeaderLease
        from cranesched_tpu.ha.snapshot import recover_from_snapshot
        from cranesched_tpu.utils.filelock import FileLockHeld
        lease = LeaderLease(cfg.wal_path)
        try:
            epoch = lease.acquire()
        except FileLockHeld:
            print(f"FATAL: another ctld holds the lease on "
                  f"{cfg.wal_path} (is a leader already running?); "
                  f"start this one with --ha-standby to follow it",
                  file=sys.stderr, flush=True)
            return 1
        scheduler.fencing_epoch = epoch
        if args.sim:
            for node in meta.nodes.values():
                node.alive = True
        count, snap_seq = recover_from_snapshot(
            scheduler, WriteAheadLog, cfg.wal_path, now=time.time())
        # stderr: the first STDOUT line stays the "listening on port"
        # banner (wrappers parse the bound port out of it)
        if count:
            print(f"recovered {count} jobs from {cfg.wal_path}"
                  + (f" (snapshot @seq={snap_seq} + tail)"
                     if snap_seq else ""),
                  file=sys.stderr, flush=True)
        scheduler.wal = WriteAheadLog(cfg.wal_path)
        fed = getattr(scheduler, "fed", None)
        if fed is not None:
            # lease tombstoning + migrated-away node re-death, and any
            # begin with no commit/abort surfaces unresolved (the RPC
            # server's resolve loop settles it against the dest)
            fed.recover(time.time())
            unresolved = fed.recover_migrations(time.time())
            if unresolved:
                mids = ", ".join(r["mid"] for r in unresolved)
                print(f"WARNING: {len(unresolved)} unresolved "
                      f"migration(s) [{mids}] — partitions stay "
                      f"sealed until the destination's has_import "
                      f"answer settles them",
                      file=sys.stderr, flush=True)
        print(f"leader lease acquired (fencing epoch {epoch})",
              file=sys.stderr, flush=True)

    sim = None
    dispatcher = None
    tls = cfg.tls_config()
    if args.sim:
        for node in meta.nodes.values():
            node.alive = True
        sim = SimCluster(scheduler)
        sim.wire(scheduler)
    else:
        dispatcher = GrpcDispatcher(
            scheduler, tls=tls.for_client() if tls else None)
        dispatcher.wire(scheduler)

    if cfg.node_event_hook_path:
        from cranesched_tpu.utils.config import (
            make_node_event_script_hook)
        scheduler.node_event_hook = make_node_event_script_hook(
            cfg.node_event_hook_path)

    auth = None
    if cfg.auth_token_file:
        from cranesched_tpu.ctld.auth import AuthManager
        os.makedirs(os.path.dirname(cfg.auth_token_file) or ".",
                    exist_ok=True)
        auth = AuthManager(cfg.auth_token_file,
                           admins=tuple(cfg.auth_admins),
                           accounts=scheduler.accounts)
        print(f"auth enabled (token table {cfg.auth_token_file}; "
              f"root + craned tokens inside)", flush=True)

    metrics_port = (args.metrics_port if args.metrics_port is not None
                    else cfg.metrics_port)
    address = args.listen or cfg.listen
    server, port = serve(scheduler, sim=sim, address=address,
                         cycle_interval=args.cycle_interval,
                         dispatcher=dispatcher, auth=auth, tls=tls,
                         metrics_port=metrics_port,
                         shard_name=shard_name, shard_map=shard_map,
                         standby=args.ha_standby,
                         peer_address=args.ha_peer)
    print(f"cranectld [{cfg.cluster_name}] listening on port {port} "
          f"({'simulated' if args.sim else 'real'} node plane, "
          f"{len(meta.nodes)} nodes configured, "
          f"backend {device['platform']} {device['device_kind']} "
          f"x{device['device_count']}"
          f"{', TLS' if tls else ''}"
          f"{', STANDBY of ' + args.ha_peer if args.ha_standby else ''}"
          ")", flush=True)
    if server.metrics_port is not None:
        print(f"metrics: http://0.0.0.0:{server.metrics_port}/metrics",
              flush=True)

    # HA plumbing needs the server lock, so it starts after serve()
    snapshotter = None
    follower = None
    if cfg.wal_path:
        from cranesched_tpu import ha as _ha

        def _start_snapshotter():
            nonlocal snapshotter
            if args.snapshot_interval <= 0:
                return
            snapshotter = _ha.Snapshotter(
                scheduler, scheduler.wal, server._lock, cfg.wal_path,
                interval=args.snapshot_interval)
            snapshotter.start()

        if args.ha_standby:
            follower = _ha.HaFollower(
                server, args.ha_peer, cfg.wal_path,
                token=(auth.craned_token if auth is not None else ""),
                tls=tls.for_client() if tls else None,
                on_promote=lambda epoch: _start_snapshotter())
            server.ha_follower = follower
            follower.start()
            print(f"hot standby: replicating from {args.ha_peer}",
                  flush=True)
        else:
            _ha.ROLE_GAUGE.set(1)
            _start_snapshotter()

    syncer = None
    if cfg.license_sync.get("Program"):
        from cranesched_tpu.ctld.licenses import LicenseSyncer
        syncer = LicenseSyncer(
            scheduler.licenses, str(cfg.license_sync["Program"]),
            interval=float(cfg.license_sync.get("Interval", 60)),
            lock=server._lock)
        syncer.sync_once()   # first observation before the first cycle
        syncer.start()
        print(f"license sync: {cfg.license_sync['Program']} "
              f"every {syncer.interval:g}s", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    if syncer is not None:
        syncer.stop()
    if follower is not None:
        follower.stop()
    if snapshotter is not None:
        snapshotter.stop()
    server.stop()
    if dispatcher is not None:
        dispatcher.close()
    if lease is not None:
        lease.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
