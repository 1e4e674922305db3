"""Mean time a pod window close waits for its slowest shard (Tracer
stage `pod.merge_wait`: from posting the epoch markers until every
shard's contribution is in, or the deadline), while it holds the state
lock: the cost of shard skew and of each shard's queued backlog."""


def read(run):
    st = run.stages.get("pod.merge_wait")
    if not st or not st["count"]:
        return None
    return st["sum_s"] / st["count"] * 1e3
