"""Exporter-worker CPU seconds (Tracer stage `cpu.export`, the thread's
CPU time inside each `export` span) per record absorbed. The gap to
`export_ns_per_rec` is time the exporter waited."""


def read(run):
    st = run.stages.get("cpu.export")
    if not st or not run.records:
        return None
    return st["sum_s"] / run.records * 1e9
