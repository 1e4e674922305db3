"""Deploy manifests + agent bootstrap runner.

Reference: manifests/ (helm charts, docker-compose) — the env has no
k8s/docker, so the manifests are validated structurally: every yaml
parses, the k8s objects carry the fields kubectl requires, and the
config files they embed or mount drive the REAL entrypoints
(python -m deepflow_tpu.agent --dry-run, server.load_config).
"""

import io
import os
import subprocess
import sys

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFESTS = os.path.join(REPO, "manifests")


def _load_all(path):
    with open(path) as f:
        return [d for d in yaml.safe_load_all(f) if d is not None]


def test_manifest_yamls_parse():
    found = []
    for root, _, files in os.walk(MANIFESTS):
        for fn in files:
            if fn.endswith((".yaml", ".yml")):
                p = os.path.join(root, fn)
                _load_all(p)
                found.append(fn)
    assert {"server.yaml", "agent.yaml", "docker-compose.yaml",
            "deepflow-tpu.yaml"} <= set(found)


def test_server_yaml_keys_match_server_build():
    """Every key in the example server.yaml must be one Server._build
    actually reads — a stale example config is worse than none."""
    from deepflow_tpu.server import load_config
    cfg = load_config(os.path.join(MANIFESTS, "server.yaml"))
    assert set(cfg) <= {"controller", "ingester", "querier",
                        "self_telemetry"}
    ing = cfg["ingester"]
    assert set(ing) <= {"host", "port", "debug_port", "store_path",
                        "n_decoders", "throttle_per_s", "store_max_bytes",
                        "tpu_sketch_window_s", "app_red_window_s"}
    assert cfg["controller"]["port"] == 20417
    assert ing["port"] == 30033


def test_agent_bootstrap_dry_run(tmp_path):
    """The shipped agent.yaml validates through the real entrypoint
    (capture engine swapped to none: no NET_RAW needed, no eth0)."""
    with open(os.path.join(MANIFESTS, "agent.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["capture"] = {"engine": "none"}
    p = tmp_path / "agent.yaml"
    p.write_text(yaml.safe_dump(cfg))
    r = subprocess.run(
        [sys.executable, "-m", "deepflow_tpu.agent", "-f", str(p),
         "--dry-run"],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, "PYTHONPATH": REPO,
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert "config ok" in r.stdout


def test_agent_bootstrap_rejects_unknown_keys(tmp_path):
    p = tmp_path / "agent.yaml"
    p.write_text("controller_ur: http://x\n")   # typo'd key
    from deepflow_tpu.agent.__main__ import load_bootstrap
    import pytest
    with pytest.raises(ValueError, match="controller_ur"):
        load_bootstrap(str(p))
    p.write_text("capture: {engin: raw}\n")
    with pytest.raises(ValueError, match="engin"):
        load_bootstrap(str(p))
    p.write_text("capture: {engine: rings}\n")   # typo'd engine VALUE
    with pytest.raises(ValueError, match="rings"):
        load_bootstrap(str(p))
    p.write_text("capture: {engine: pcap}\n")    # pcap without path
    with pytest.raises(ValueError, match="path"):
        load_bootstrap(str(p))


def test_agent_bootstrap_missing_pcap_fails_at_startup(tmp_path):
    """A dry-run-blessed config whose pcap vanished must exit rc=2 with
    a message, not crash-loop on a raw traceback."""
    from deepflow_tpu.agent.__main__ import build_source
    import pytest
    with pytest.raises(OSError, match="not found"):
        build_source({"engine": "pcap", "path": str(tmp_path / "no.pcap")})


def test_native_decoder_build_dir_override(tmp_path, monkeypatch):
    """DEEPFLOW_TPU_NATIVE_DIR redirects the .so build cache (read-only
    installs: the compose manifest mounts the repo :ro)."""
    from deepflow_tpu.decode import native
    monkeypatch.setenv("DEEPFLOW_TPU_NATIVE_DIR", str(tmp_path / "cache"))
    p = native._so_path()
    assert p.startswith(str(tmp_path / "cache"))
    monkeypatch.delenv("DEEPFLOW_TPU_NATIVE_DIR")
    assert native._so_path().endswith(os.path.join(
        "native_src", f"_native_decoder-{native.build_key()}.so"))


def test_native_decoder_unwritable_cache_degrades(tmp_path, monkeypatch):
    """An unwritable cache dir must degrade to the Python fallback via
    build_error(), never crash the import/build."""
    from deepflow_tpu.decode import native
    blocked = tmp_path / "file-not-dir"
    blocked.write_text("")   # a FILE where the cache dir should go
    monkeypatch.setattr(native, "_SO",
                        str(blocked / "sub" / "_native_decoder.so"))
    err = native._build()
    assert err is not None and "native cache dir" in err


def test_capture_loop_surfaces_source_failure():
    """A capture source that throws stops the loop observably (counters
    carry the failure), instead of a silent dead thread + zombie agent."""
    import time as _t
    from deepflow_tpu.agent.afpacket import CaptureLoop

    class BadSource:
        def read_batch(self):
            raise OSError("iface torn down")

        def close(self):
            pass

    class NullAgent:
        def feed(self, frames, stamps):
            return len(frames)

    loop = CaptureLoop(BadSource(), NullAgent())
    loop.start()
    for _ in range(100):
        if loop.failed:
            break
        _t.sleep(0.02)
    loop.close()
    assert loop.failed and "iface torn down" in loop.failed
    assert loop.counters()["failed"]


def test_agent_bootstrap_cross_engine_keys_rejected(tmp_path):
    from deepflow_tpu.agent.__main__ import load_bootstrap
    import pytest
    p = tmp_path / "a.yaml"
    p.write_text("capture: {engine: ring, snaplen: 2048}\n")
    with pytest.raises(ValueError, match="snaplen"):
        load_bootstrap(str(p))
    p.write_text("capture: {engine: raw, block_size: 4096}\n")
    with pytest.raises(ValueError, match="block_size"):
        load_bootstrap(str(p))


def test_agent_bootstrap_builds_real_config(tmp_path):
    from deepflow_tpu.agent.__main__ import build_source, load_bootstrap
    p = tmp_path / "agent.yaml"
    p.write_text(
        "controller_url: http://c:20417\n"
        "ingester_addr: i:30033\n"
        "local_macs: ['02:00:00:00:00:01']\n"
        "capture: {engine: none}\n")
    cfg, capture = load_bootstrap(str(p))
    assert cfg.controller_url == "http://c:20417"
    assert cfg.local_macs == ("02:00:00:00:00:01",)
    assert build_source(capture) is None


def test_agent_bootstrap_pcap_source(tmp_path):
    from deepflow_tpu.agent.__main__ import build_source, load_bootstrap
    from deepflow_tpu.agent.pcap import write_pcap
    pcap = tmp_path / "t.pcap"
    write_pcap(str(pcap), [b"\x00" * 60], [1_000_000_000])
    p = tmp_path / "agent.yaml"
    p.write_text(f"capture: {{engine: pcap, path: {pcap}}}\n")
    _, capture = load_bootstrap(str(p))
    src = build_source(capture)
    try:
        frames, stamps = src.read_batch()
        assert len(frames) == 1
    finally:
        src.close()


def test_k8s_objects_have_required_fields():
    docs = _load_all(os.path.join(MANIFESTS, "k8s", "deepflow-tpu.yaml"))
    kinds = [d["kind"] for d in docs]
    for required in ("Namespace", "Deployment", "DaemonSet", "Service",
                     "ConfigMap", "ServiceAccount", "ClusterRole",
                     "ClusterRoleBinding"):
        assert required in kinds
    for d in docs:
        assert d.get("apiVersion") and d.get("kind")
        assert d["metadata"].get("name")
        if d["kind"] in ("Deployment", "DaemonSet"):
            tpl = d["spec"]["template"]
            sel = d["spec"]["selector"]["matchLabels"]
            # selector must actually select the pod template
            assert set(sel.items()) <= set(
                tpl["metadata"]["labels"].items())
            for c in tpl["spec"]["containers"]:
                assert c.get("image") and c.get("command")
    # the server configmap must itself be a valid server config
    cm = next(d for d in docs
              if d["kind"] == "ConfigMap"
              and d["metadata"]["name"] == "deepflow-tpu-server-config")
    cfg = yaml.safe_load(cm["data"]["server.yaml"])
    assert cfg["ingester"]["port"] == 30033
    # the agent template must render with the daemonset's env
    cm = next(d for d in docs
              if d["kind"] == "ConfigMap"
              and d["metadata"]["name"] == "deepflow-tpu-agent-config")
    import string
    rendered = string.Template(cm["data"]["agent.yaml.tpl"]).substitute(
        DEEPFLOW_NODE_IP="10.0.0.1", DEEPFLOW_NODE_NAME="n1",
        DEEPFLOW_SA_TOKEN="tok")
    acfg = yaml.safe_load(rendered)
    from deepflow_tpu.agent.trident import AgentConfig
    fields = set(AgentConfig.__dataclass_fields__)
    assert set(acfg) - {"capture"} <= fields


def test_controller_health_endpoint(tmp_path):
    """/v1/health — the k8s readiness probe target."""
    import json
    import urllib.request
    from deepflow_tpu.controller import (ControllerServer, ResourceModel,
                                         VTapRegistry)
    model = ResourceModel(str(tmp_path / "m.json"))
    reg = VTapRegistry(str(tmp_path / "v.json"))
    srv = ControllerServer(model, reg, port=0)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/health", timeout=5) as r:
            body = json.loads(r.read())
        assert body["ok"] is True
        assert body["is_leader"] is True
    finally:
        srv.close()
