"""Reads one device op's time a traced cycle out of the trace's
reduction (lib/xplane.py): `device_ops` lists the ten longest ops of the
capture by name, `cycles` how many cycles it held.

XLA numbers what it names (`while.102`, `crane_greedy_streamed.1`) and
the numbers move with every change to a program, so an op is named by
its stem: `op` matches `op` and `op.<digits>`.  A Pallas kernel carries
the name the program gave it.  A loop cannot: XLA calls every one
`while`, so `pick: max` takes the longest op of the stem alone (the
backfill head's scan, an order above the tail program's searchsorted
loop), where the default sums them.  No trace, or no such op among the
ten: nothing, never a 0."""

import re


def read(ctx, args):
    pick = args.get("pick", "sum")
    if pick not in ("sum", "max"):
        raise ValueError(f"device_op: no pick {pick!r}")
    trace = ctx.get("trace") or {}
    cycles = trace.get("cycles")
    stem = re.compile(re.escape(args["op"]) + r"(\.\d+)?$")
    seconds = [s for name, s in trace.get("device_ops", ())
               if stem.match(name)]
    if not cycles or not seconds:
        return None
    return 1e3 * (max(seconds) if pick == "max" else sum(seconds)) / cycles
