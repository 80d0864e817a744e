"""Sum of one cycle-trace field over the sum of another, over the window's
cycles that did work (`solver: skip` rows are left out, as readers/cycle_trace
leaves them out): a mean per take (`rpc_query_held_ms` / `rpc_query_n`) or a
share of the cycles' own time (`rpc_query_held_ms` / `period_ms`, `scale` 100).
A row that lacks a field counts 0 for it: the lock ledger writes a class's
fields only on rows of periods in which the class took the lock.  Where no row
carries the numerator (the parent's program), or the denominators sum to 0
(no take in the window), there is nothing to read: None, never a 0.

`stat: median_num` reads the median of the numerator over the cycles whose
denominator is above 0 (a per-cycle maximum over the cycles that had a take)."""

import statistics


def read(ctx, args):
    num, den = args["num"], args["den"]
    cycles = [c for c in ctx["cycles"] if c.get("solver") != "skip"]
    taken = [c for c in cycles if num in c and float(c.get(den) or 0.0) > 0.0]
    if not taken:
        return None
    stat = args.get("stat", "ratio")
    if stat == "median_num":
        return statistics.median(float(c[num]) for c in taken)
    if stat != "ratio":
        raise ValueError(f"cycle_ratio: no stat {stat!r}")
    total = sum(float(c.get(den) or 0.0) for c in cycles)
    return float(args.get("scale", 1.0)) \
        * sum(float(c[num]) for c in taken) / total
