"""Reads the reduction of the profiler trace that the traced run took
through the daemon's CaptureProfile RPC (lib/xplane.py).  No trace, or a
trace with no device plane, gives nothing: never a 0."""

import statistics


def read(ctx, args):
    trace = ctx.get("trace") or {}
    field = args["field"]
    if field == "idle_share_pct":
        if not trace.get("window_s") or not trace.get("busy_s"):
            return None
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if field == "cycle_busy_ms_median":
        values = [v for v in trace.get("cycle_busy_ms", ()) if v > 0]
        return statistics.median(values) if values else None
    raise ValueError(f"device_trace: no field {field!r}")
