"""`correct` comes out true on a sound system and false where it must.

* The controls (lib/controls.py), at a size a test run can hold: the
  answers of a sound plain placer pass the comparison, and with each
  control put in its place the comparison says not correct.
* The broken timed path: the harness drives a whole run, minus its look
  for a chip, against an in-process daemon whose scheduler over-commits
  memory, loses an acknowledged job, looks at the head of the queue only,
  never fsyncs, or acknowledges ahead of its write, and `correct` is
  false.
* The daemon itself (on the CPU, at the dry run's size) with its log
  weakened, `--control fsync_off|late_write`, driven by `run.py` as a
  whole: not correct; and sound, the same way: nothing lost, nothing
  unsynced."""

import functools
import json
import os
import subprocess
import sys

import pytest

import run as bench_run
from fake_ctld import FakeCtld
from lib import check, controls
from lib.spec import Benchmark


class QuickBenchmark(Benchmark):
    """The cells as they are, but opened 1.5 s into the fake daemon's 2 s
    snapshot period instead of 5 or 25 s into a real one's 60 s, and with
    jobs short enough, and a drain long enough, for some to be held to
    their end within a run of a few seconds."""

    def traffic_file(self, cell):
        doc = super().traffic_file(cell)
        doc["setup"].update(open_at_phase_s=1.5, warm_seconds=0.3)
        doc["drain_seconds"] = 5
        for mix in doc["mixes"].values():
            if mix["sim_runtime_s"][0] < 60:
                mix["sim_runtime_s"] = [1, 3]
        return doc


def drive(cell, broken="", with_controls=False, seed=4294967311):
    return bench_run.run_cell(
        QuickBenchmark(), cell, seed=seed, seconds=2.0, trace=False,
        system_factory=functools.partial(FakeCtld, broken=broken),
        dry_run=50, with_controls=with_controls)


@pytest.mark.parametrize("cell", ["minload5k-backlog", "fifo1k-flood",
                                  "minload5k-flood"])
def test_a_sound_run_is_correct_and_every_control_is_not(cell):
    result = drive(cell, with_controls=True)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["controls"]) == set(controls.CONTROLS)
    # where the queue is never 1,024 deep (the real daemon's flood) a
    # placer that sees the first 1,024 candidates IS the program; the
    # backlog cell is the one it has to fail
    if cell == "minload5k-backlog":
        assert "applies" not in result["controls"]["short_sighted"]
    for name, out in result["controls"].items():
        if out.get("applies") is False:
            continue
        assert out["correct"] is False, name
        target = controls.CONTROLS[name][0]
        assert out[target] > check.LIMITS[target], (name, out)


@pytest.mark.parametrize("broken,number", [
    ("ignore_memory", "overcommit"), ("forget_job", "acked_lost"),
    ("short_sighted", "idle_fit"), ("no_fsync", "unsynced_acks"),
    ("ack_before_write", "wal_lost")])
def test_a_broken_timed_path_is_not_correct(broken, number):
    result = drive("minload5k-backlog", broken=broken)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0
    assert all(c["value"] == 0 for name, c in result["compared"].items()
               if name != number), result["compared"]


@pytest.mark.parametrize("control,number", [
    ("", ""), ("fsync_off", "unsynced_acks"), ("late_write", "wal_lost")])
def test_the_daemon_with_a_weakened_log_is_not_correct(control, number):
    """The whole of `run.py` against the real daemon on the CPU, killed
    after its last acknowledgement."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--workload", "fifo1k-flood", "--seed", "2147483659",
           "--seconds", "4", "--trace", "0", "--dry-run", "25"]
    if control:
        cmd += ["--control", control]
    done = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=300, check=False)
    out = json.loads(done.stdout.strip().splitlines()[-1])
    compared = out["compared"]
    if not control:
        # the numbers this test is about; a CPU daemon falls behind the flood
        # (its queue grows, its completions lag), so the others may stir
        assert compared["wal_lost"]["value"] == 0, compared
        assert compared["unsynced_acks"]["value"] == 0, compared
        assert compared["acked_lost"]["value"] == 0, compared
        return
    assert out["correct"] is False
    assert compared[number]["value"] > 0, compared


def test_the_latency_cell_reports_its_tails():
    result = drive("minload5k-backlog")
    assert set(result["metrics"]) == {
        "start_p95_ms", "submit_p95_ms", "query_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
