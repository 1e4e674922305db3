"""Decoder-thread seconds (Tracer stage `decode`) per record absorbed in
the window. The stage covers the whole decoder tail (decode, enrich,
exporter fan-out, throttled store write) and every stream: the server's
own telemetry frames add a few spans every 10 s."""


def read(run):
    st = run.stages.get("decode")
    if not st or not run.records:
        return None
    return st["sum_s"] / run.records * 1e9
