#!/usr/bin/env bash
# tier1-perf — cycle-prelude smoke lane (`make tier1-perf`).
#
# Runs bench.py at a tiny CPU shape and asserts the scheduler-cycle
# phase split it records: the prelude (status drains + priority sort +
# batch build) must stay a small share of cycle wall time.  This is the
# guard for the device-resident prelude work — a regression that
# reintroduces a per-cycle dense [J, N] mask build or an unstable jit
# shape (recompile every cycle) shows up here as a prelude blow-up,
# without waiting for the full-scale bench.
set -euo pipefail
cd "$(dirname "$0")/.."
out=$(timeout -k 10 600 env JAX_PLATFORMS=cpu \
  BENCH_JOBS=2048 BENCH_NODES=256 BENCH_REPEATS=2 BENCH_SOLVER=native \
  BENCH_SCHED_JOBS=2048 BENCH_SCHED_NODES=256 \
  BENCH_COMMIT_JOBS=2048 BENCH_COMMIT_NODES=256 \
  BENCH_CHURN_JOBS=8192 BENCH_CHURN_NODES=128 BENCH_CHURN_CYCLES=3 \
  python bench.py --churn)
echo "$out"
python - "$out" <<'PY'
import json
import sys

doc = json.loads(sys.argv[1])
sc = doc["detail"]["sched_cycle"]
assert sc and "error" not in sc, f"sched_cycle measurement failed: {sc}"
share = sc["prelude_share"]
assert share <= 0.25, (
    f"prelude is {share:.1%} of cycle wall time (limit 25%): {sc}")
# the group-commit guard: total LOCK-HELD time (prelude + commit, never
# the solve or the post-lock dispatch drain) must stay a minority share
# of the cycle — a regression that drags fsyncs or pushes back under
# the lock shows up here
lock_share = sc["lock_held_share"]
assert lock_share <= 0.35, (
    f"lock-held (prelude+commit) is {lock_share:.1%} of cycle wall "
    f"time (limit 35%): {sc}")
cb = doc["detail"]["commit"]
assert cb and "error" not in cb, f"commit bench failed: {cb}"
assert cb["fsyncs_equal_groups"] and cb["groups_le_3"], (
    f"group commit broke its fsync amortization contract: {cb}")
# incremental-cycle guards: an idle tick must actually hit the no-op
# fingerprint, and cost <5% of a full cycle's wall time
ch = doc["detail"]["churn"]
assert ch and "error" not in ch, f"churn bench failed: {ch}"
assert ch["idle_skipped"], (
    f"idle tick did not short-circuit (fingerprint never armed): {ch}")
assert ch["idle_tick_share"] < 0.05, (
    f"skipped idle cycle cost {ch['idle_tick_share']:.1%} of a full "
    f"cycle (limit 5%): {ch}")
assert ch["placements_match"], (
    f"incremental vs rebuild placed different first waves: {ch}")
# device-resident state guards: steady-state churn cycles must run the
# dirty-row scatter patch or the ledger-only refresh (never a silent
# full [N,R] rebuild), the host->device bytes must stay under the
# mode-appropriate bound, the delta upload must be double-buffered
# (staged by the previous cycle), and sched_cycle must report the new
# pipeline-shape fields for BENCH_r06.  ISSUE 17: empty-delta cycles
# now label themselves "ledger" (only the [N] cost seed ships — the
# BENCH_r10 "patch with dirty_nodes=0" anomaly), and an all-ledger
# steady state is held to EXACTLY 4*N bytes, not the padded dirty-row
# formula.
rs = ch["resident"]
assert rs["steady_state_patch"], (
    f"a steady churn cycle fell back to a full [N,R] rebuild: {rs}")
assert rs["h2d_bytes_per_cycle"] <= rs["dirty_bound_bytes"], (
    f"resident patch shipped {rs['h2d_bytes_per_cycle']}B/cycle, over "
    f"the dirty-rows bound {rs['dirty_bound_bytes']}B: {rs}")
if rs["steady_state_ledger_only"]:
    assert rs["h2d_bytes_per_cycle"] == rs["dirty_bound_bytes"], (
        f"all-ledger steady state must ship exactly the 4*N cost seed "
        f"({rs['dirty_bound_bytes']}B), saw "
        f"{rs['h2d_bytes_per_cycle']}B: {rs}")
assert rs["h2d_bytes_per_cycle"] < rs["full_state_bytes"], (
    f"resident patch bytes not below a full rebuild: {rs}")
assert rs["patch_overlap_share"] >= 0.99, (
    f"delta uploads were not overlapped with the previous cycle "
    f"(share {rs['patch_overlap_share']}): {rs}")
assert rs["placements_match"], (
    f"resident vs rebuild placed different first waves: {rs}")
assert ("host_to_device_bytes_per_cycle" in sc
        and "patch_overlap_share" in sc), (
    f"sched_cycle detail lost the resident pipeline fields: {sc}")
# job-trace overhead guard: the per-job timeline recorder stamps every
# lifecycle edge inside the cycle — it must cost <=2% of churn cycle
# wall time (measured trace-on vs trace-off on the same seed)
tg = ch["tracing"]
assert tg["trace_overhead_share"] <= 0.02, (
    f"job tracing added {tg['trace_overhead_share']:.1%} to the churn "
    f"cycle (limit 2%): {tg}")
# introspection-plane guards (ISSUE 14): warm churn cycles must pay
# ZERO fresh jit compiles (the bucketed-padding zero-recompile
# contract, now measured per cycle via the compile observer), and the
# observer probes + device-memory sampling must cost <=2% of the cycle
ig = ch["introspection"]
assert ig["zero_steady_recompiles"], (
    f"steady-state churn cycles paid fresh jit compiles "
    f"(recompiles per cycle {ig['recompiles_per_cycle']}): {ig}")
assert ig["introspect_overhead_share"] <= 0.02, (
    f"introspection plane cost {ig['introspect_overhead_share']:.1%} "
    f"of the churn cycle (limit 2%): {ig}")
assert "recompiles" in sc and "device_buffers" in sc, (
    f"sched_cycle detail lost the introspection fields: {sc}")
# flight-recorder guards (ISSUE 16): the always-on phase ring must cost
# <=1% of the churn cycle wall time, and the churn leg must report the
# persistent-XLA-cache hit rate (a restart's warm compiles depend on
# the cache actually being wired)
fg = ch["flight"]
assert fg["flight_overhead_share"] <= 0.01, (
    f"flight recorder added {fg['flight_overhead_share']:.1%} to the "
    f"churn cycle (limit 1%): {fg}")
xc = fg["xla_cache"]
assert "hit_rate" in xc and "enabled" in xc, (
    f"churn leg lost the XLA cache stats: {fg}")
assert xc["enabled"], f"XLA cache not enabled: {xc}"
print(f"TIER1_PERF_OK prelude_share={share:.3f} "
      f"lock_held_share={lock_share:.3f} "
      f"wal_fsyncs_per_cycle={sc['wal_fsyncs_per_cycle']} "
      f"churn_prelude_speedup={ch['prelude_speedup']} "
      f"idle_tick_share={ch['idle_tick_share']} "
      f"resident_h2d_bytes={rs['h2d_bytes_per_cycle']} "
      f"resident_modes={rs['steady_state_modes']} "
      f"patch_overlap_share={rs['patch_overlap_share']} "
      f"trace_overhead_share={tg['trace_overhead_share']} "
      f"flight_share={fg['flight_overhead_share']} "
      f"xla_cache_hit_rate={xc['hit_rate']} "
      f"introspect_share={ig['introspect_overhead_share']} "
      f"recompiles={ig['recompiles_per_cycle']} "
      f"solver={sc['solver']}")
PY

# federated control-plane smoke (ISSUE 15): two subprocess shards vs
# one controller over the union, each saturated IN ISOLATION (one
# server process at a time — the CI box may have a single core, and
# concurrent shard processes would only time-slice it).  Asserts the
# federation acceptance pair: 2-shard aggregate submit throughput at
# least 2x the single controller, and query p99 under 50 ms against a
# shard absorbing its own storm, plus an exactly-once arbiter ledger.
fed=$(timeout -k 10 420 env JAX_PLATFORMS=cpu python - <<'PY'
import json
import bench
print(json.dumps(bench._measure_federation(
    n_specs=2000, nodes_per_part=16)))
PY
)
python - "$fed" <<'PY'
import json
import sys

doc = json.loads(sys.argv[1])
assert doc["speedup_ge_2x"], (
    f"2-shard aggregate submit throughput is only "
    f"{doc['submit_speedup']}x the single controller (limit >= 2x): "
    f"single={doc['single']} federated={doc['federated']}")
assert doc["query_p99_lt_50ms"], (
    f"federated query p99 {doc['federated']['query_p99_ms']}ms over "
    f"the 50ms budget: {doc['federated']}")
assert doc["arbiter"]["ledger_ok"], (
    f"federation drill lost or doubled work: {doc['arbiter']}")
print(f"TIER1_FED_OK submit_speedup={doc['submit_speedup']} "
      f"fed_query_p99_ms={doc['federated']['query_p99_ms']} "
      f"single_submits_per_s={doc['single']['submits_per_s']} "
      f"fed_submits_per_s={doc['federated']['submits_per_s']} "
      f"arbiter_commits={doc['arbiter']['commits']}")
PY

# multi-host solve smoke (ISSUE 17): the tier1-multihost pytest lane
# (2-rank hierarchical solve vs the single-process oracle + the real
# 2-process CPU-mesh smoke), then the bench scenario at a small shape
# asserting parity, the expected 2x4 mesh, and a per-cycle fence count
# that matches the solve's step loop (one barrier per scan step).
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m multihost -p no:cacheprovider -p no:xdist -p no:randomly
mh=$(timeout -k 10 420 env JAX_PLATFORMS=cpu python - <<'PY'
import json
import bench
print(json.dumps(bench._measure_multihost(
    num_jobs=96, num_nodes=64)))
PY
)
python - "$mh" <<'PY'
import json
import sys

doc = json.loads(sys.argv[1])
assert doc["parity_with_single_process"], (
    f"multi-host solve diverged from the single-process oracle: {doc}")
assert doc["mesh"] == "2x4", (
    f"expected a 2-process x 4-device mesh, got {doc['mesh']}: {doc}")
assert doc["fence_count_per_cycle"] > 0, (
    f"the hierarchical solve never fenced — it did not actually run "
    f"the cross-process merge: {doc}")
assert doc["warm_cycle_s"] < doc["cold_cycle_s"] * 2, (
    f"warm multi-host cycle slower than 2x cold (jit cache broken?): "
    f"{doc}")
print(f"TIER1_MULTIHOST_OK mesh={doc['mesh']} "
      f"warm_cycle_s={doc['warm_cycle_s']} "
      f"decisions_per_sec={doc['decisions_per_sec']} "
      f"fence_share={doc['fence_share']} "
      f"placed={doc['placed']}")
PY

# elastic-federation migration smoke (ISSUE 18): seal a loaded
# partition on one live shard and hand it to another over the
# four-phase WAL protocol, with the cluster-wide usage gossip running.
# Asserts the handoff's acceptance shape: every job moved exactly once
# (audited BY NAME across shards — ids renumber on import), the map
# epoch flipped, post-flip submits route to the adopter, and the
# submit-outage window (seal->flip) stays under 5 s at this shape.
rb=$(timeout -k 10 420 env JAX_PLATFORMS=cpu python - <<'PY'
import json
import bench
print(json.dumps(bench._measure_rebalance(
    n_jobs=400, nodes_per_part=16)))
PY
)
python - "$rb" <<'PY'
import json
import sys

doc = json.loads(sys.argv[1])
assert doc["exactly_once"], (
    f"migration lost, doubled, or stranded jobs: {doc['audit']} "
    f"(full: {doc})")
assert doc["jobs_moved"] > 0 and doc["map_epoch"] >= 1, (
    f"the handoff moved nothing or never flipped the map: {doc}")
assert doc["submit_outage_s"] < 5.0, (
    f"seal->flip submit outage {doc['submit_outage_s']}s over the 5s "
    f"budget: {doc}")
assert doc["usage_gossip_docs"] >= 2, (
    f"the usage gossip round exchanged fewer documents than shards: "
    f"{doc}")
print(f"TIER1_REBALANCE_OK jobs_moved={doc['jobs_moved']} "
      f"handoff_s={doc['handoff_s']} "
      f"per_job_ms={doc['per_job_ms']} "
      f"map_epoch={doc['map_epoch']} "
      f"gossip_ms={doc['usage_gossip_ms']}")
PY
