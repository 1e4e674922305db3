"""Decoder-thread CPU seconds (Tracer stage `cpu.decode`, the thread's
CPU time inside each `decode` span) per record absorbed. The gap to
`decode_ns_per_rec` is time the decoders waited: for the interpreter
lock, a lock or a full queue."""


def read(run):
    st = run.stages.get("cpu.decode")
    if not st or not run.records:
        return None
    return st["sum_s"] / run.records * 1e9
