"""Reads the daemon's cycle-trace ring (QueryStats `cycle_trace`), which
the traced run polls so that no cycle of the window falls off its 64
slots.  Cycles that short-circuited (`solver: skip`) did no work and are
left out."""

import statistics


def read(ctx, args):
    cycles = [c for c in ctx["cycles"] if c.get("solver") != "skip"]
    values = [float(c[args["field"]]) for c in cycles if args["field"] in c]
    if not values:
        return None
    stat = args.get("stat", "median")
    if stat == "median":
        return statistics.median(values)
    if stat == "max":
        return max(values)
    if stat == "share_of_window_pct":
        t0, t1 = ctx["window"]
        return 100.0 * sum(values) / 1e3 / (t1 - t0)
    raise ValueError(f"cycle_trace: no stat {stat!r}")
