"""Tests of the benchmark harness, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They check the manifest and the files it names, the traffic encoder
against the synthetic agent it copies, every metric reader, the trace
reduction on a recorded chip trace, that a cell added as files is found,
that a run off a TPU exits nonzero, and that `correct` holds for a sound
run and fails for the control and for each planted fault.
"""

import glob
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH, HERE

ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_run(root=ROOT):
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(root))}", os.path.join(root, "benchmark",
                                                     "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the manifest -------------------------------------------------------------

def test_manifest_loads_with_the_contract_keys():
    b = manifest()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark"]
    assert 1 <= b["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) \
        <= max(1, len(b["workloads"]) // 2)


def test_names_and_units_use_the_allowed_characters():
    b = manifest()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    names += [w["config"] for w in b["workloads"]]
    names += [w["traffic"] for w in b["workloads"]]
    names += [r for c in b["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = b["end_to_end"] + b["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)


def test_every_workload_names_existing_files():
    b = manifest()
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(ROOT, configs[w["config"]]["file"]))
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_every_layer_metric_moves_a_metric_its_cells_report():
    b = manifest()
    cells = [w["name"] for w in b["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in b["end_to_end"]}
    for m in b["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 2 ** 31 + 12345])
def test_encoder_is_byte_identical_to_the_synthetic_agent(seed):
    from deepflow_tpu.replay.generator import SyntheticAgent
    from deepflow_tpu.wire import MessageType
    from harness import traffic

    n = 5000
    mix = {"pool_records": n, "fresh_share": 0.5, "heavy_pool": 4096,
           "zipf_a": 1.25}
    cols = traffic.mix_columns(mix, seed)
    agent = SyntheticAgent(seed=seed)
    fresh = agent.l4_columns(n - n // 2)
    heavy = agent.l4_columns_pooled(n // 2, pool=4096)
    order = np.random.default_rng(seed).permutation(n)
    for k in fresh:
        want = np.concatenate([fresh[k], heavy[k]])[order]
        if k != "flow_id":
            assert np.array_equal(cols[k], want), k
    recs = [agent.l4_record(cols, i) for i in range(n)]
    pool = traffic.Pool(cols, 2048)
    assert [bytes(pool.payload(i, 1))[4:] for i in range(n)] == recs
    frames = list(SyntheticAgent(seed=seed).frames(
        recs, MessageType.TAGGEDFLOW, per_frame=2048))
    mine = []
    for k, s in enumerate(range(0, n, 2048)):
        body = bytes(pool.payload(s, min(2048, n - s)))
        mine.append(traffic.frame_header(len(body), k + 1, 7) + body)
    assert mine == frames


def test_each_agent_advances_its_own_sequence_across_resends(monkeypatch):
    from harness import sender, traffic

    sent = []

    class Sock:
        def __init__(self, i):
            self.i = i

        def sendall(self, data):
            sent.append((self.i, bytes(data)))

        def close(self):
            pass

    socks = iter(range(100))
    monkeypatch.setattr(sender.socket, "create_connection",
                        lambda addr: Sock(next(socks)))
    mix = {"pool_records": 300, "fresh_share": 0.5, "heavy_pool": 64,
           "zipf_a": 1.25}
    pool = traffic.Pool(traffic.mix_columns(mix, 3), 100)
    agents = sender.Agents(pool, 1, 2)
    for _ in range(7):                       # two passes and a bit
        agents.send(100)
    headers = [d for _, d in sent[0::2]]
    seen = {}
    for h in headers:
        seq, vtap = int.from_bytes(h[9:17], "little"), \
            int.from_bytes(h[17:19], "little")
        assert seq == seen.get(vtap, 0) + 1
        seen[vtap] = seq
    assert seen == {1: 4, 2: 3}
    assert agents.sent == 700 and agents.cursor == 100


# -- metric readers -----------------------------------------------------------

def observed(**kw):
    base = dict(
        setup_s=21.5, seconds=4.0, records=1_000_000, h2d_bytes=6_000_000,
        publishes=[{"delivered": 10.02, "wall_time": 10.0, "rows": 300_000,
                    "step": 1},
                   {"delivered": 11.03, "wall_time": 11.0, "rows": 350_000,
                    "step": 2},
                   {"delivered": 12.05, "wall_time": 12.0, "rows": 350_000,
                    "step": 3}],
        stages={"decode": {"count": 10, "sum_s": 1.0},
                "export": {"count": 10, "sum_s": 0.5},
                "kernel": {"count": 10, "sum_s": 0.25}},
        window_spans=[5.0, 7.0, 9.0],
        trace={"window_s": 4.0, "devices": [
            {"id": 0, "busy_s": 0.4, "programs": {
                "jit_update": 0.2, "jit__lambda_": 0.1}},
            {"id": 1, "busy_s": 1.0, "programs": {"jit_update": 0.3}}]},
        device_kind="TPU v5 lite")
    base.update(kw)
    return SimpleNamespace(**base)


EXPECTED = {
    "records_per_s": 350_000.0,
    "publish_p50_ms": 30.0,
    "setup_s": 21.5,
    "decode_ns_per_rec": 1000.0,
    "export_ns_per_rec": 500.0,
    "dispatch_ns_per_rec": 250.0,
    "h2d_bytes_per_rec": 6.0,
    "device_idle": 75.0,
    "window_ms": 7.0,
}


@pytest.mark.parametrize("metric", sorted(
    os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(BENCH, "metrics", "*.py"))))
def test_each_reader_computes_its_number_from_fixture_spans(metric):
    read = load_run().reader(metric)
    assert read(observed()) == pytest.approx(EXPECTED[metric], rel=1e-6)


@pytest.mark.parametrize("metric", ["device_idle", "dispatch_ns_per_rec",
                                    "decode_ns_per_rec", "window_ms",
                                    "records_per_s", "publish_p50_ms"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    empty = observed(trace=None, stages={}, window_spans=[], publishes=[])
    assert load_run().reader(metric)(empty) is None


def test_trace_reduction_on_a_recorded_chip_trace():
    from harness import xplane

    files = glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz"))
    assert files, "the recorded trace is missing"
    with open(os.path.join(HERE, "data", "reduced.json")) as f:
        want = json.load(f)
    got = xplane.reduce(files[0])
    assert len(got["devices"]) == len(want["devices"])
    for g, w in zip(got["devices"], want["devices"]):
        assert g["busy_s"] == pytest.approx(w["busy_s"])
        assert g["programs"] == pytest.approx(w["programs"])
    assert got["device_ops"] == want["device_ops"]
    assert [n for n, _ in got["idle_gaps"]] == \
        [n for n, _ in want["idle_gaps"]]
    assert 0 < got["devices"][0]["busy_s"]


# -- the harness finds what is added as files ---------------------------------

def test_a_cell_added_as_files_only_is_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    b = manifest()
    b["workloads"].append({"name": "l4_dict.extra", "config": "l4_dict_1chip",
                           "traffic": "extra", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "extra_metric", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "decode", "moves": "publish_p50_ms",
                           "workloads": ["l4_dict.extra"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "benchmark" / "traffic" / "extra.json").write_text(
        json.dumps({"pool_records": 1024, "fresh_share": 0.25}))
    (tmp_path / "benchmark" / "metrics" / "extra_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    run = load_run(str(tmp_path))
    cell = run.load_cell("l4_dict.extra")
    assert cell.mix == {"pool_records": 1024, "fresh_share": 0.25}
    assert [m["name"] for m in cell.per_layer] == ["extra_metric"]
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert run.reader("extra_metric")(None) == 42.0


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "l4_dict.churn",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


# -- correct: a sound run passes, the control and each fault fail -------------

# the generator's open loop with dashboard reads, which PERF.md keeps
# for a later cell (Open questions): the churn mix on a schedule
OPEN_LOOP = {"loop": "open", "rate": 20000, "reads_per_s": 10,
             "read_threads": 2, "read_queries": [
                 "SELECT sketch.topk(100) FROM sketch",
                 "SELECT sketch.cms_point({heavy_key}) FROM sketch",
                 "SELECT sketch.hll_card() FROM sketch",
                 "SELECT sketch.entropy FROM sketch"]}


def small_run(workload, seed, patch=None, capsys=None):
    run = load_run()
    full = run.load_cell

    def small(name):
        c = full("l4_dict.churn" if name == "open_loop" else name)
        if name == "open_loop":
            c.mix.update(OPEN_LOOP)
        c.mix.update(pool_records=1 << 16, records_per_frame=1024)
        c.mix["in_flight"] = 1 << 15
        return c

    run.load_cell = small
    run.PARTIAL_SIZES = [100, 1000, 5000]
    run.OPEN_WARM_S = 2.0
    args = SimpleNamespace(workload=workload, seed=seed, seconds=3.0,
                           trace=0)
    res = run.run(args, require_tpu=False, patch=patch)
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out) == res
    return res


@pytest.mark.parametrize("workload", ["l4_dict.churn", "l4_dict.resident",
                                      "open_loop"])
def test_a_sound_run_is_correct(workload, capsys):
    res = small_run(workload, 2 ** 31 + 77, capsys=capsys)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert ("read_mismatch" in res["checks"]) == (workload == "open_loop")


@pytest.mark.parametrize("workload,fault,fails", [
    ("l4_dict.churn", "control", "hll_err"),
    ("l4_dict.churn", "control", "topk_over_share"),
    ("l4_dict.resident", "control", "cms_under"),
    ("l4_dict.churn", "state_unchanged", "rows_gap"),
    ("l4_dict.churn", "half_batch", "ent_mass_gap"),
    ("l4_dict.churn", "answer_altered", "rows_gap"),
])
def test_the_control_and_each_fault_come_out_not_correct(
        workload, fault, fails, capsys):
    from harness import faults

    res = small_run(workload, 2 ** 31 + 78, faults.PATCHES[fault], capsys)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] > c["limit"], res["checks"]


def test_the_union_sums_each_flows_ring_counts_over_the_windows():
    from harness import check

    def snap(keys, counts, rows):
        ring_k = np.full(8, 0xFFFFFFFF, np.uint32)
        ring_c = np.full(8, -1, np.int32)
        ring_k[:len(keys)], ring_c[:len(counts)] = keys, counts
        leaves = (np.ones((2, 4), np.int32), None, ring_k, ring_c,
                  np.zeros(4, np.uint8), np.ones((2, 3), np.int32), None,
                  np.int64(rows), np.int64(0))
        return SimpleNamespace(leaves=leaves)

    lv = check.union([snap([5, 9], [3, 4], 10), snap([9, 2], [6, 1], 7)])
    assert list(lv[2]) == [2, 5, 9] and list(lv[3]) == [1, 3, 10]
    assert int(lv[7]) == 17 and lv[0].sum() == 16
    assert list(check.ring_counts(lv, np.array([9, 4, 2], np.uint32))) \
        == [10, 0, 1]
