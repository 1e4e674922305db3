"""Tests of the readers of the program's stage spans and child spans
(feed starvation, lock waits, CPU time, window-close parts) and of the
named update programs' device time, each on a hand-built run.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import glob
import os
from types import SimpleNamespace

import pytest

import test_harness
from conftest import HERE

READERS = ["feed_starved_pct", "export_lock_wait_ns_per_rec",
           "decode_cpu_ns_per_rec", "export_cpu_ns_per_rec",
           "window_drain_ms", "window_publish_ms",
           "update_device_ns_per_rec"]

# test_harness checks every reader in benchmark/metrics against its own
# table; its fixture run carries none of the stages these readers read,
# so there each reads nothing
for _m in READERS:
    test_harness.EXPECTED.setdefault(_m, None)


def run_of(**kw):
    base = dict(
        seconds=40.0, records=16_000_000,
        stages={"feed.starved": {"count": 300, "sum_s": 38.0},
                "export.lock_wait": {"count": 4000, "sum_s": 4.0},
                "cpu.decode": {"count": 9000, "sum_s": 48.0},
                "cpu.export": {"count": 4000, "sum_s": 16.0},
                "window.drain": {"count": 40, "sum_s": 2.0},
                "window.publish": {"count": 40, "sum_s": 6.0}},
        trace={"window_s": 40.0, "devices": [
            {"id": 0, "busy_s": 1.4, "programs": {
                "jit_sketch_update_dict": 1.0,
                "jit_sketch_update_lanes": 0.05,
                "jit_sketch_flush": 0.37}}]})
    base.update(kw)
    return SimpleNamespace(**base)


EXPECTED = {
    "feed_starved_pct": 95.0,
    "export_lock_wait_ns_per_rec": 250.0,
    "decode_cpu_ns_per_rec": 3000.0,
    "export_cpu_ns_per_rec": 1000.0,
    "window_drain_ms": 50.0,
    "window_publish_ms": 150.0,
    "update_device_ns_per_rec": 65.625,
}


@pytest.mark.parametrize("metric", READERS)
def test_each_reader_computes_its_number_from_a_hand_built_run(metric):
    read = test_harness.load_run().reader(metric)
    assert read(run_of()) == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    """What a program without these spans and names leaves to read."""
    read = test_harness.load_run().reader(metric)
    assert read(run_of(stages={}, trace=None)) is None


@pytest.mark.parametrize("metric", ["window_drain_ms", "window_publish_ms",
                                    "update_device_ns_per_rec"])
def test_a_mean_over_no_samples_reads_nothing(metric):
    empty = {k: {"count": 0, "sum_s": 0.0} for k in run_of().stages}
    trace = {"window_s": 40.0, "devices": [
        {"id": 0, "busy_s": 0.4, "programs": {"jit_prog": 0.4}}]}
    read = test_harness.load_run().reader(metric)
    assert read(run_of(stages=empty, trace=trace)) is None


def test_a_feed_that_never_starved_reads_zero():
    read = test_harness.load_run().reader("feed_starved_pct")
    stages = {"feed.starved": {"count": 0, "sum_s": 0.0}}
    assert read(run_of(stages=stages)) == 0.0


def test_update_device_time_is_nothing_on_a_trace_of_unnamed_programs():
    """The recorded churn trace predates the program names: its updates
    are `jit_prog`, so the reader returns nothing, not a wrong number."""
    from harness import xplane

    files = glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz"))
    assert files, "the recorded trace is missing"
    trace = xplane.reduce(files[0])
    names = {n for d in trace["devices"] for n in d["programs"]}
    assert "jit_prog" in names
    assert not any(n.startswith("jit_sketch_update") for n in names)
    read = test_harness.load_run().reader("update_device_ns_per_rec")
    assert read(run_of(trace=trace, records=21 * 32768)) is None


def test_the_manifest_lists_each_reader_for_both_cells():
    b = test_harness.manifest()
    entries = {m["name"]: m for m in b["per_layer"]}
    for m in READERS:
        e = entries[m]
        assert e["moves"] == "records_per_s"
        assert e["workloads"] == ["l4_dict.churn", "l4_dict.resident"]
        assert e["source"] == ("device_trace" if m.startswith("update_")
                               else "program_span")
