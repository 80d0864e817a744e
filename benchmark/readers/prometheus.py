"""Reads the daemon's metric registry as QueryStats returns it at the
window's two ends: histograms as count and sum, counters and gauges as
values, each keyed by its label string."""


def _cells(doc, metric, labels):
    values = doc.get("metrics", {}).get(metric, {}).get("values", {})
    return [v for k, v in values.items()
            if all(lab in k for lab in labels)]


def read(ctx, args):
    labels = args.get("labels", [])
    opened = _cells(ctx["stats_open"], args["metric"], labels)
    closed = _cells(ctx["stats_close"], args["metric"], labels)
    stat = args["stat"]
    if stat == "gauge":
        return max(closed) if closed and max(closed) >= 0 else None
    if stat == "sum_delta_s":
        return (sum(c["sum"] for c in closed)
                - sum(c["sum"] for c in opened))
    if stat == "mean_ms":
        n = sum(c["count"] for c in closed) - sum(c["count"] for c in opened)
        if n <= 0:
            return None
        return 1e3 * (sum(c["sum"] for c in closed)
                      - sum(c["sum"] for c in opened)) / n
    raise ValueError(f"prometheus: no stat {stat!r}")
