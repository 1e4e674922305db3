"""Feed-thread seconds (Tracer stage `kernel`: host pack, transfer and
asynchronous dispatch, not device time) per record absorbed. The pod's
shard workers record no such span."""


def read(run):
    st = run.stages.get("kernel")
    if not st or not run.records:
        return None
    return st["sum_s"] / run.records * 1e9
