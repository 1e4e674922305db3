"""Builds the system under test from a configuration file: the all-in-one
`deepflow_tpu.server.Server`, from the YAML mapping under `"server"` (the
store lands in the run's scratch dir).

`"sketch"` states the sketch sizes the deployment runs; a program that
runs other sizes is not the configuration, and the run stops.
"""

from __future__ import annotations

import os
from typing import Optional

import yaml


class Served:
    def __init__(self, cfg: dict, workdir: str) -> None:
        from deepflow_tpu.server import Server

        store = os.path.join(workdir, "store")
        doc = {k: dict(v) if isinstance(v, dict) else v
               for k, v in cfg["server"].items()}
        doc["ingester"] = dict(doc.get("ingester", {}), store_path=store)
        path = os.path.join(workdir, "server.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(doc, f)
        self.server = Server(path)
        self.server.start()
        self.ingester = self.server.ingester
        self.query_port: Optional[int] = (
            self.server.querier.port if self.server.querier is not None
            else None)
        self.sketch = self.ingester.tpu_sketch
        if self.sketch is None:
            self.close()
            raise RuntimeError("the configuration runs no tpu_sketch lane")
        got = {k: getattr(self.sketch.cfg, k) for k in cfg["sketch"]}
        if got != cfg["sketch"]:
            self.close()
            raise RuntimeError(f"the program runs sketch sizes {got}, the "
                               f"configuration states {cfg['sketch']}")

    @property
    def port(self) -> int:
        return self.ingester.port

    def fallbacks(self) -> dict:
        """Every counter by which the lane could absorb rows off its
        device path or lose them; all must read 0."""
        c = self.sketch.counters()
        out = {k: c.get(k, 0) for k in ("device_errors", "degraded",
                                        "host_rows", "lost_rows",
                                        "lost_windows")}
        for k in ("pod_rows_host", "pod_rows_lost", "pod_rows_excluded",
                  "pod_merge_missed", "pod_device_errors",
                  "pod_shards_degraded", "pod_shards_lost"):
            if k in c:
                out[k] = c[k]
        out["supervisor_crashes"] = \
            self.ingester.supervisor.counters()["crashes"]
        out["decode_errors"] = sum(
            d.counters()["decode_errors"]
            for d in self.ingester.flow_log.decoders
            if d.stream == "l4_flow_log")
        for k in ("overwritten", "closed_dropped", "process_errors"):
            out[f"exporter_{k}"] = c.get(k, 0)
        return out

    def close(self) -> None:
        self.server.close()
