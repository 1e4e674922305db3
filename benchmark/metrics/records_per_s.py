"""Records in the windows published between the first and the last publish
inside the measured window, over the time between those publishes (each
publish stamped with its window's flush tick)."""


def read(run):
    pubs = sorted(run.publishes, key=lambda p: p["wall_time"])
    if len(pubs) < 2:
        return None
    span = pubs[-1]["wall_time"] - pubs[0]["wall_time"]
    return sum(p["rows"] for p in pubs[1:]) / span if span > 0 else None
