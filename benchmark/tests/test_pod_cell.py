"""Tests of the four-chip pod cell `l4_pod4.churn`: its merge readers on
a hand-built run, its manifest entries, and a small run of the cell on
four virtual CPU devices that must come out correct.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import contextlib
import io
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import test_harness
from conftest import BENCH

READERS = ["merge_ms", "merge_wait_ms"]
CELL = "l4_pod4.churn"

# test_harness checks every reader in benchmark/metrics against its own
# table; its fixture run carries no pod stage, so there each reads
# nothing
for _m in READERS:
    test_harness.EXPECTED.setdefault(_m, None)


def run_of(**kw):
    base = dict(seconds=40.0, records=24_000_000,
                stages={"pod.merge": {"count": 40, "sum_s": 1.2},
                        "pod.merge_wait": {"count": 40, "sum_s": 0.2},
                        "pod.update": {"count": 2900, "sum_s": 9.0}},
                trace=None)
    base.update(kw)
    return SimpleNamespace(**base)


EXPECTED = {"merge_ms": 30.0, "merge_wait_ms": 5.0}


@pytest.mark.parametrize("metric", READERS)
def test_each_merge_reader_computes_its_mean_from_a_hand_built_run(metric):
    read = test_harness.load_run().reader(metric)
    assert read(run_of()) == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", READERS)
def test_a_merge_reader_reads_nothing_without_the_pod(metric):
    """What the single-chip lane, or a program without the pod spans,
    leaves to read: no stage, or a stage with no sample."""
    read = test_harness.load_run().reader(metric)
    assert read(run_of(stages={})) is None
    empty = {k: {"count": 0, "sum_s": 0.0} for k in run_of().stages}
    assert read(run_of(stages=empty)) is None


def test_the_manifest_holds_the_pod_cell_and_its_metrics():
    b = test_harness.manifest()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("l4_pod4", "churn", 4)
    config = next(c for c in b["configs"] if c["name"] == "l4_pod4")
    with open(os.path.join(os.path.dirname(BENCH), config["file"])) as f:
        server = json.load(f)["server"]
    assert server["ingester"]["tpu_sketch_pod_shards"] == 4
    entries = {m["name"]: m for m in b["end_to_end"] + b["per_layer"]}
    for m in READERS:
        e = entries[m]
        assert (e["layer"], e["moves"], e["workloads"]) == \
            ("pod merge", "records_per_s", [CELL])
    # the pod has no feed and no single-chip close children
    for m in ("dispatch_ns_per_rec", "feed_starved_pct",
              "window_drain_ms", "window_publish_ms"):
        assert CELL not in entries[m]["workloads"]
    for m in ("records_per_s", "h2d_bytes_per_rec",
              "update_device_ns_per_rec", "device_idle"):
        assert CELL in entries[m]["workloads"]


def _small_pod_run() -> dict:
    """The small run of the cell, in this process: the result line and
    the pod's shard count."""
    seen = {}

    def patch(served):
        seen["shards"] = served.sketch.pod.n_shards

    buf = io.StringIO()
    capsys = SimpleNamespace(
        readouterr=lambda: SimpleNamespace(out=buf.getvalue()))
    with contextlib.redirect_stdout(buf):
        res = test_harness.small_run(CELL, 2 ** 31 + 79, patch=patch,
                                     capsys=capsys)
    return {"res": res, "shards": seen.get("shards")}


def test_a_small_run_of_the_pod_cell_is_correct_on_four_devices():
    """A child process with four virtual CPU devices (this one may
    already hold a one-device backend) builds the pod from the cell's
    YAML and runs it end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       cwd=os.path.dirname(BENCH), env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    res = got["res"]
    assert got["shards"] == 4 and res["device"]["count"] == 4
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["program_faults"]["value"] == 0


if __name__ == "__main__":
    print(json.dumps(_small_pod_run()))
