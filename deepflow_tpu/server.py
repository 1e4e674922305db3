"""All-in-one server: controller + ingester + querier in one process.

Reference: server/cmd/server/main.go — one binary starts the controller
(election -> resource model -> trisolaris), the ingester (receiver +
pipelines), and the querier behind a single /etc/server.yaml, plus a
config watcher that restarts on change (server/ingester/config/
watcher.go). Same shape here: `Server(config_path).start()`, or
`python -m deepflow_tpu.server -f server.yaml`.

Config (all keys optional):

    controller:
      enabled: true
      port: 20417
      lease_path: /tmp/df-lease.json
    ingester:
      port: 30033
      store_path: /var/lib/deepflow-tpu
      debug_port: 30035
      throttle_per_s: 50000
      tpu_sketch_window_s: 1.0
      app_red_window_s: 1.0
    querier:
      enabled: true
      port: 20416
    self_telemetry: true
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
import time
from typing import Optional

import yaml

from deepflow_tpu.runtime.supervisor import default_supervisor


def load_config(path: Optional[str]) -> dict:
    if path is None or not os.path.exists(path):
        return {}
    with open(path) as f:
        return yaml.safe_load(f) or {}


class Server:
    def __init__(self, config_path: Optional[str] = None) -> None:
        self.config_path = config_path
        self.cfg = load_config(config_path)
        self._watch_thread = None      # supervisor ThreadHandle
        self.reload_error: Optional[str] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._build()

    # -- construction ------------------------------------------------------
    def _build(self) -> None:
        from deepflow_tpu.controller import (ControllerServer, ResourceModel,
                                             VTapRegistry)
        from deepflow_tpu.controller.election import Election
        from deepflow_tpu.controller.monitor import FleetMonitor
        from deepflow_tpu.controller.platform_compiler import PlatformPusher
        from deepflow_tpu.controller.tagrecorder import TagRecorder
        from deepflow_tpu.pipelines import Ingester, IngesterConfig
        from deepflow_tpu.querier.server import QuerierServer
        from deepflow_tpu.runtime.stats import StatsShipper

        c = self.cfg
        ing_cfg = c.get("ingester", {})
        store_path = ing_cfg.get("store_path")

        ctl_cfg = c.get("controller", {})
        self.controller = None
        self.election = None
        self.tagrecorder = None
        if ctl_cfg.get("enabled", True):
            state_dir = store_path or "/tmp/deepflow-tpu"
            os.makedirs(state_dir, exist_ok=True)
            self.model = ResourceModel(os.path.join(state_dir, "model.json"))
            self.registry = VTapRegistry(
                os.path.join(state_dir, "vtaps.json"))
            self.monitor = FleetMonitor(self.registry)
            self.election = Election(
                ctl_cfg.get("lease_path",
                            os.path.join(state_dir, "lease.json")))
            self.tagrecorder = TagRecorder(self.model, root=state_dir)
            self.controller = ControllerServer(
                self.model, self.registry, self.monitor,
                election=self.election, tagrecorder=self.tagrecorder,
                port=ctl_cfg.get("port", 20417),
                host=ctl_cfg.get("host", "127.0.0.1"))

        self.ingester = Ingester(IngesterConfig(
            listen_port=ing_cfg.get("port", 30033),
            listen_host=ing_cfg.get("host", "127.0.0.1"),
            store_path=store_path,
            debug_port=ing_cfg.get("debug_port"),
            n_decoders=ing_cfg.get("n_decoders", 2),
            throttle_per_s=ing_cfg.get("throttle_per_s", 50_000),
            store_max_bytes=ing_cfg.get("store_max_bytes", 100 << 30),
            tpu_sketch_window_s=ing_cfg.get("tpu_sketch_window_s"),
            app_red_window_s=ing_cfg.get("app_red_window_s"),
        ))
        if self.controller is not None:
            # in-process ingester enriches from this controller's model
            PlatformPusher(self.model, self.ingester.platform)
        # trident gRPC bridge: the reference-agent control plane
        # (message/trident.proto Synchronizer) over the same registry.
        # grpc_port 0 = ephemeral; None/absent with no grpcio = skip.
        self.trident_grpc = None
        self._grpc_parts = None
        if self.controller is not None and \
                ctl_cfg.get("grpc_enabled", True):
            try:
                from deepflow_tpu.controller import trident_grpc
                self._grpc_parts = (trident_grpc,
                                    ctl_cfg.get("grpc_port", 30035),
                                    ctl_cfg.get("host", "127.0.0.1"))
            except ImportError:
                pass          # grpcio not in this image: JSON-only

        q_cfg = c.get("querier", {})
        self.querier = None
        self.sketch_tables = None
        self.anomaly_tables = None
        if q_cfg.get("enabled", True) and self.ingester.store is not None:
            # ISSUE 7 serving read path: when the tpu_sketch lane runs,
            # mount its snapshot bus as the `sketch` datasource — SQL
            # SELECT sketch.* / PromQL sketch_*() answer from the
            # in-process cache with staleness-bounded reads, never
            # touching the device or the feed/drain hot path
            if self.ingester.tpu_sketch is not None:
                from deepflow_tpu.serving import (SketchTables,
                                                  SnapshotCache)
                cache = SnapshotCache(
                    self.ingester.tpu_sketch.snapshot_bus,
                    max_staleness_s=q_cfg.get("sketch_max_staleness_s",
                                              5.0))
                self.sketch_tables = SketchTables(cache)
                self.sketch_tables.register_datasource()
                self.ingester.stats.register("serving",
                                             self.sketch_tables.counters)
                # ISSUE 15 anomaly plane: when the detection lane runs,
                # mount its alert bus as the `anomaly` datasource —
                # SELECT * FROM anomaly / anomaly_score{detector=...}
                # answer from the same snapshot-cache posture
                if self.ingester.tpu_sketch.anomaly is not None:
                    from deepflow_tpu.serving import AnomalyTables
                    acache = SnapshotCache(
                        self.ingester.tpu_sketch.anomaly.bus,
                        max_staleness_s=q_cfg.get(
                            "sketch_max_staleness_s", 5.0))
                    self.anomaly_tables = AnomalyTables(acache)
                    self.anomaly_tables.register_datasource()
                    self.ingester.stats.register(
                        "serving_anomaly", self.anomaly_tables.counters)
            self.querier = QuerierServer(
                self.ingester.store, self.ingester.tag_dicts,
                port=q_cfg.get("port", 20416),
                host=q_cfg.get("host", "127.0.0.1"),
                tagrecorder=self.tagrecorder,
                external_apm=q_cfg.get("external_apm", []),
                sketch=self.sketch_tables,
                anomaly=self.anomaly_tables)

        self.stats_shipper = None
        if c.get("self_telemetry", True):
            # the server monitors itself through its own firehose
            addr = f"127.0.0.1:{ing_cfg.get('port', 30033)}"
            self.stats_shipper = StatsShipper(self.ingester.stats, addr)
            if self.controller is not None:
                # controller self-report rides the same DFSTATS loop
                # (reference: controller statsd -> deepflow_system)
                stats = self.ingester.stats
                stats.register("controller.recorder",
                               self.controller.recorder.counters)
                stats.register("controller.genesis",
                               self.controller.genesis_sync.counters)
                stats.register(
                    "controller.fleet",
                    lambda: {"vtaps": len(self.registry.list()),
                             "ingesters": len(self.monitor.ingesters()),
                             "resources": len(self.model.list()),
                             "model_version": self.model.version,
                             "is_leader": int(self.election.is_leader)
                             if self.election else 1})

    # -- lifecycle ---------------------------------------------------------
    def _start_components(self) -> None:
        """ONE start sequence shared by start() and reload() — a
        duplicated copy silently diverged once (reload forgot the gRPC
        bridge) and must not exist again."""
        if self.election is not None:
            self.election.start()
        if self.controller is not None:
            self.controller.start()
        if self._grpc_parts is not None:
            mod, port, host = self._grpc_parts
            server, bound, svc = mod.serve(
                self.registry, self.controller.package_bytes,
                platform_version=lambda: self.model.version,
                genesis_report=self.controller.genesis_report,
                assign=self.monitor.assign,
                host=host, port=port)
            if bound == 0:
                # grpc's add_insecure_port reports bind failure as 0
                # and start() would otherwise proceed silently deaf
                server.stop(grace=0)
                raise OSError(
                    f"trident gRPC bridge failed to bind {host}:{port}")
            self.trident_grpc = (server, bound, svc)
        self.ingester.start()
        if self.stats_shipper is not None:
            # shipper targets the real bound port (port may have been 0)
            self.stats_shipper.sender.set_target(
                f"127.0.0.1:{self.ingester.port}")
            self.ingester.stats.start(interval_s=10.0)
        if self.querier is not None:
            self.querier.start()

    def start(self) -> None:
        self._start_components()
        if self.config_path is not None:
            # supervised: a reload that raises past the guard in
            # reload() restarts the watcher instead of silently ending
            # config reloads for the life of the process
            self._watch_thread = default_supervisor().spawn(
                "config-watcher", self._watch_config, beat_period_s=5.0)

    def close(self) -> None:
        self._stop.set()
        if self._watch_thread is not None:
            self._watch_thread.stop()
            self._watch_thread.join(timeout=2)
        with self._lock:
            self._close_components()

    def _close_components(self) -> None:
        if self.trident_grpc is not None:
            self.trident_grpc[0].stop(grace=1).wait()
            self.trident_grpc = None
        if self.querier is not None:
            self.querier.close()
        if self.anomaly_tables is not None:
            self.anomaly_tables.unregister_datasource()
            self.anomaly_tables.cache.close()
            self.ingester.stats.deregister("serving_anomaly")
            self.anomaly_tables = None
        if self.sketch_tables is not None:
            self.sketch_tables.unregister_datasource()
            self.sketch_tables.cache.close()
            self.ingester.stats.deregister("serving")
            self.sketch_tables = None
        if self.stats_shipper is not None:
            self.ingester.stats.stop()
            self.stats_shipper.close()
        self.ingester.close()
        if self.controller is not None:
            self.controller.close()
        if self.election is not None:
            self.election.close()

    # -- config watcher ----------------------------------------------------
    def _watch_config(self) -> None:
        """Restart components when the config file changes (reference:
        ingester/config/watcher.go exits for the supervisor to restart;
        in-process we rebuild)."""
        try:
            last = os.path.getmtime(self.config_path)
        except OSError:
            last = 0.0
        while not self._stop.wait(5.0):
            default_supervisor().beat()
            try:
                cur = os.path.getmtime(self.config_path)
            except OSError:
                continue
            if cur != last:
                last = cur
                self.reload()

    def reload(self) -> None:
        with self._lock:
            new_cfg = load_config(self.config_path)
            if new_cfg == self.cfg:
                return
            self._close_components()
            self.cfg = new_cfg
            self._build()
            # restart everything except the watcher (already running).
            # A start failure here (e.g. a port the new config picked is
            # taken) must NOT propagate: it would kill the watcher
            # thread with components half-stopped and no way back —
            # record it and keep watching so the next edit can recover.
            try:
                self._start_components()
                self.reload_error = None
            except Exception as e:
                self.reload_error = repr(e)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="deepflow-tpu-server")
    ap.add_argument("-f", "--config", default=None)
    args = ap.parse_args(argv)
    from deepflow_tpu.utils import compile_cache
    compile_cache.configure()
    server = Server(args.config)
    server.start()
    print(f"deepflow-tpu server up: ingester :{server.ingester.port}"
          + (f", controller :{server.controller.port}"
             if server.controller else "")
          + (f", querier :{server.querier.port}" if server.querier else ""))
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
