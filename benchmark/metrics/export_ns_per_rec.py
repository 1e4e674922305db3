"""Exporter-worker seconds (Tracer stage `export`: the tpu_sketch lane's
`process`, which packs and stages the batches) per record absorbed. The
stage sums every exporter of the deployment; here only tpu_sketch runs."""


def read(run):
    st = run.stages.get("export")
    if not st or not run.records:
        return None
    return st["sum_s"] / run.records * 1e9
