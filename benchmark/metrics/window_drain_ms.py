"""Mean time a window close waits on the feed barrier (Tracer stage
`window.drain`: every queued and in-flight update applied and fenced),
while it holds the state lock."""


def read(run):
    st = run.stages.get("window.drain")
    if not st or not st["count"]:
        return None
    return st["sum_s"] / st["count"] * 1e3
