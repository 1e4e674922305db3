"""Median over the measured window's window closes of the Tracer `window`
span (`flush_window`: feed drain, snapshot publish, flush program)."""

import statistics


def read(run):
    return statistics.median(run.window_spans) if run.window_spans else None
