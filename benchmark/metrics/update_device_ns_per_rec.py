"""Device seconds of the sketch update programs (device trace, XLA
modules named `jit_sketch_update...`, summed over the chips) per record
absorbed. A program that carries no such name, as before the programs
were named, gives nothing to read."""


def read(run):
    if not run.trace or not run.records:
        return None
    secs = sum(t for d in run.trace["devices"]
               for name, t in d["programs"].items()
               if name.startswith("jit_sketch_update"))
    return secs / run.records * 1e9 if secs else None
