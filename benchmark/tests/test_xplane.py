"""The device-trace reduction: on hand-made intervals whose answer is
known, and on a small trace recorded on the chip."""

import glob
import os

import pytest

from lib import xplane

MS = 1_000_000


def test_union_and_cover():
    merged = xplane.union([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert merged == [(0, 4), (5, 10)]
    assert xplane.covered(merged, 1, 7) == 3 + 2


def test_reduction_of_two_cycles():
    # two cycles, each a `backfill` and an `immediate` span that close at
    # ENQUEUE; the device runs on after them and rests until the next
    spans = [("backfill", 0 * MS, 2 * MS), ("immediate", 12 * MS, 14 * MS),
             ("backfill", 100 * MS, 102 * MS),
             ("immediate", 112 * MS, 114 * MS),
             ("backfill", 200 * MS, 201 * MS)]
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", 1 * MS, 4 * MS),
           ("%while.2 = (s32[]) while(...)", 3 * MS, 9 * MS),
           ("%kernel = custom-call(...)", 13 * MS, 25 * MS),
           ("%prio = fusion(...)", 90 * MS, 91 * MS),
           ("%fusion.1 = f32[8]{0} fusion(...)", 101 * MS, 105 * MS),
           ("%kernel = custom-call(...)", 113 * MS, 129 * MS)]
    out = xplane.reduce({"/device:TPU:0": ops}, spans)
    assert out["window_s"] == pytest.approx(0.201)
    assert out["busy_s"] == pytest.approx((8 + 12 + 1 + 4 + 16) / 1e3)
    # the third cycle is cut by the trace's end and is left out
    assert out["cycles"] == 3
    assert out["cycle_busy_ms"] == pytest.approx([21.0, 20.0])
    assert out["device_ops"][0] == ["kernel", pytest.approx(0.028)]
    gaps = dict((n, s) for n, s in out["idle_gaps"] if n.startswith("sum:"))
    # cycle 1: idle 9-13 is inside its solve, 25-90 (its longest pause)
    # and 91-101 are between solves; cycle 2: 105-113 inside, 129-201
    # between; 0-1 precedes every op
    assert gaps["sum:inside_solve"] == pytest.approx(0.004 + 0.008 + 0.001)
    assert gaps["sum:between_solves"] == pytest.approx(
        0.065 + 0.010 + 0.072)
    assert len(out["idle_gaps"]) <= 10 and len(out["device_ops"]) <= 10


def test_one_solve_per_cycle_groups_by_the_first_label():
    spans = [("immediate", 0, 5 * MS), ("immediate", 50 * MS, 55 * MS)]
    assert xplane.cycle_starts(spans) == [0, 50 * MS]


def test_op_names_are_cut_to_the_op():
    assert xplane.short("%while.102 = (s32[]{:T(128)}) while(...)") == \
        "while.102"


def test_no_device_plane_gives_nothing_rather_than_zero():
    assert xplane.reduce({}, [("immediate", 0, MS)]) == {}


def test_a_trace_recorded_on_the_chip():
    files = glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                   "*.xplane.pb"))
    assert files, "benchmark/tests/data holds the recorded trace"
    out = xplane.reduce(*xplane.load(files[0]))
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["solve_spans"] == 7 and out["cycles"] == 4
    # the solve-heavy cell's device work is the same in every cycle
    assert out["cycle_busy_ms"] == pytest.approx([132.25] * 3, rel=0.01)
    assert out["device_ops"][0][0] == "while.102"
    sums = dict(g for g in out["idle_gaps"] if g[0].startswith("sum:"))
    assert sums["sum:between_solves"] > 10 * sums["sum:inside_solve"]
