"""The `northstar10k-gangs` cell on the CPU, a fiftieth of its size: the
whole of `run.py` against the real daemon (default Scheduler block,
gangs 1-8 nodes wide, WAL, SIGKILL after the last acknowledgement) ends
`correct`, every count of the replay 0.  Counts only: a CPU run gives no
metric (about two minutes, most of it the 60 s snapshot period the
window is pinned to)."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_the_cell_s_dry_run_is_correct():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "northstar10k-gangs", "--seed", "2147483659",
         "--seconds", "8", "--trace", "0", "--dry-run", "50"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600, check=False)
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert done.returncode == 0 and out["correct"] is True, (
        out, done.stderr[-1500:])
    assert out["failed"] == 0 and out["attempted"] > 300
    assert all(c["value"] == 0 for c in out["compared"].values())
