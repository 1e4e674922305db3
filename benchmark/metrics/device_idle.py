"""Share of the traced window in which no operation ran on the chip, from
the device trace; on several chips, that of the least idle one."""


def read(run):
    if not run.trace or not run.trace["devices"]:
        return None
    busy = max(d["busy_s"] for d in run.trace["devices"])
    return 100.0 * (1.0 - busy / run.trace["window_s"])
