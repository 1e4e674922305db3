"""Share of the measured window in which the feed thread waited for work
with nothing in flight on the device (Tracer stage `feed.starved`): the
chip starved by the host stages upstream of the feed."""


def read(run):
    st = run.stages.get("feed.starved")
    if not st or not run.seconds:
        return None
    return 100.0 * st["sum_s"] / run.seconds
