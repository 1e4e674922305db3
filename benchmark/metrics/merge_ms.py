"""Mean time a pod window close spends in its merge (Tracer stage
`pod.merge`: the stacked upload of the shards' contributions, the merged
flush program and the bus publish), while it holds the state lock. A
program without the pod, or without the span, gives nothing to read."""


def read(run):
    st = run.stages.get("pod.merge")
    if not st or not st["count"]:
        return None
    return st["sum_s"] / st["count"] * 1e3
