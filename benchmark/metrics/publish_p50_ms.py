"""Median over the measured window's published windows of the snapshot
bus's delivery time minus the window's flush tick: when the querier can
serve the window."""

import statistics


def read(run):
    lags = [(p["delivered"] - p["wall_time"]) * 1e3 for p in run.publishes]
    return statistics.median(lags) if lags else None
