"""Mean time a window close spends publishing the sketch state on the
snapshot bus (Tracer stage `window.publish`: the state's device_get,
subscriber delivery, the npz when due), while it holds the state lock."""


def read(run):
    st = run.stages.get("window.publish")
    if not st or not st["count"]:
        return None
    return st["sum_s"] / st["count"] * 1e3
