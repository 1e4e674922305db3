"""All-in-one server: one process, full control+data+query plane."""

import json
import socket
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest
import yaml

from deepflow_tpu.server import Server


def _req(url, body=None, form=None):
    data, headers = None, {}
    if body is not None:
        data = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    elif form is not None:
        data = form.encode()
        headers["Content-Type"] = "application/x-www-form-urlencoded"
    r = urllib.request.Request(url, data=data, headers=headers)
    with urllib.request.urlopen(r, timeout=5) as resp:
        return json.load(resp)


@pytest.fixture
def server(tmp_path):
    cfg = {
        "controller": {"enabled": True, "port": 0,
                       "lease_path": str(tmp_path / "lease.json")},
        "ingester": {"port": 0, "store_path": str(tmp_path / "store")},
        "querier": {"enabled": True, "port": 0},
        "self_telemetry": False,
    }
    path = tmp_path / "server.yaml"
    path.write_text(yaml.safe_dump(cfg))
    srv = Server(str(path))
    srv.start()
    yield srv
    srv.close()


def test_all_in_one(server):
    from deepflow_tpu.replay.generator import SyntheticAgent
    from deepflow_tpu.wire.framing import MessageType

    # controller is up and leading
    ctl = f"http://127.0.0.1:{server.controller.port}"
    assert _req(f"{ctl}/v1/election")["leader"] is True

    # agent sync against the controller
    r = _req(f"{ctl}/v1/sync", body={"ctrl_ip": "10.1.2.3", "host": "n1"})
    assert r["vtap_id"] == 1

    # push a domain -> platform data reaches the in-process ingester
    _req(f"{ctl}/v1/domains/k8s/resources", body={"resources": [
        {"type": "pod", "id": 77, "name": "api-0", "ip": "10.0.0.5",
         "epc_id": 1, "region_id": 3}]})
    assert server.ingester.platform.info.version == server.model.version

    # firehose traffic lands in the store
    agent = SyntheticAgent()
    _, records = agent.l4_batch(200)
    with socket.create_connection(("127.0.0.1", server.ingester.port),
                                  timeout=5) as s:
        for fr in agent.frames(records, MessageType.TAGGEDFLOW):
            s.sendall(fr)
    deadline = time.time() + 10
    decs = [d for d in server.ingester.flow_log.decoders
            if d.stream == "l4_flow_log"]
    while sum(d.records for d in decs) < 200 and time.time() < deadline:
        time.sleep(0.05)
    server.ingester.flush()

    # querier sees it
    q = f"http://127.0.0.1:{server.querier.port}"
    out = _req(f"{q}/v1/query", form=urllib.parse.urlencode({
        "db": "flow_log",
        "sql": "SELECT Count(*) AS n FROM l4_flow_log"}))
    assert out["result"]["values"][0][0] == 200


def test_config_reload(tmp_path):
    cfg = {
        "controller": {"enabled": False},
        "ingester": {"port": 0, "store_path": str(tmp_path / "store"),
                     "throttle_per_s": 1000},
        "querier": {"enabled": False},
        "self_telemetry": False,
    }
    path = tmp_path / "server.yaml"
    path.write_text(yaml.safe_dump(cfg))
    srv = Server(str(path))
    srv.start()
    try:
        assert srv.controller is None and srv.querier is None
        cfg["ingester"]["throttle_per_s"] = 9000
        cfg["querier"] = {"enabled": True, "port": 0}
        path.write_text(yaml.safe_dump(cfg))
        srv.reload()
        assert srv.ingester.cfg.throttle_per_s == 9000
        assert srv.querier is not None
    finally:
        srv.close()


@pytest.mark.parametrize("key,want", [({}, True),
                                      ({"trace_enabled": False}, False)])
def test_ingester_trace_enabled_key(tmp_path, key, want):
    """`ingester.trace_enabled` switches the flight recorder; absent, it
    stays on."""
    cfg = {"controller": {"enabled": False},
           "ingester": {"port": 0, **key},
           "querier": {"enabled": False}, "self_telemetry": False}
    path = tmp_path / "server.yaml"
    path.write_text(yaml.safe_dump(cfg))
    srv = Server(str(path))
    try:
        assert srv.ingester.cfg.trace_enabled is want
    finally:
        srv.close()


def test_controller_self_report(tmp_path):
    """Controller counters ride the DFSTATS self-telemetry loop into
    deepflow_system (reference: controller statsd report)."""
    cfg = {
        "controller": {"enabled": True, "port": 0,
                       "lease_path": str(tmp_path / "lease.json")},
        "ingester": {"port": 0, "store_path": str(tmp_path / "store")},
        "querier": {"enabled": False},
        "self_telemetry": True,
    }
    path = tmp_path / "server.yaml"
    path.write_text(yaml.safe_dump(cfg))
    srv = Server(str(path))
    srv.start()
    try:
        _req(f"http://127.0.0.1:{srv.controller.port}/v1/sync",
             body={"ctrl_ip": "10.1.1.1", "host": "h1"})
        srv.ingester.stats.collect()   # one scrape -> shipper -> firehose
        srv.stats_shipper.flush()      # push the buffered DFSTATS batch
        table = srv.ingester.store.table("deepflow_system", "ext_samples")
        deadline = time.time() + 10
        found = set()
        md = srv.ingester.tag_dicts.get("metric_name")
        while time.time() < deadline:
            srv.ingester.flush()
            rows = table.scan()
            found = {md.decode(int(h)) for h in set(rows["metric"].tolist())}
            if any(f and f.startswith("controller.fleet") for f in found):
                break
            time.sleep(0.2)
        assert any(f and f.startswith("controller.fleet.vtaps")
                   for f in found), found
        assert any(f and f.startswith("controller.recorder") for f in found)
    finally:
        srv.close()
