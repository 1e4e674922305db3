"""Host-to-device bytes of the sketch lane (`h2d_bytes` counter delta) per
record absorbed in the window."""


def read(run):
    if not run.h2d_bytes or not run.records:
        return None
    return run.h2d_bytes / run.records
