"""Exporter-worker seconds spent waiting to take the sketch lane's state
lock (Tracer stage `export.lock_wait`; a window close holds the lock)
per record absorbed."""


def read(run):
    st = run.stages.get("export.lock_wait")
    if not st or not run.records:
        return None
    return st["sum_s"] / run.records * 1e9
