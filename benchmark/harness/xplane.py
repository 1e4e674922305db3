"""Reduction of a JAX profiler trace (`.xplane.pb`) to device numbers.

- busy: the union of the intervals in which an operation ran, per chip
  (`XLA Ops` events of each `/device:TPU:<n>` plane);
- programs: device seconds per XLA module (`XLA Modules` events), keyed
  by the module name without its numeric suffix;
- top device operations by total time;
- the longest idle gaps of the busiest chip, each named by the host
  event that overlaps it most (events far longer than the gap, such as a
  thread's whole life, name nothing).

Run as a script on a trace file to print the reduction as JSON.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"[.(]\d+\)?$")
_LAYOUT = re.compile(r"\{[^}]*\}")


def _op_name(hlo: str) -> str:
    """"%fusion.1 = s32[24576]{0:T(1024)} fusion(...)" -> "fusion.1
    s32[24576]": the instruction and its result shape (op names repeat
    across programs)."""
    name, _, rest = hlo.partition(" = ")
    shape = _LAYOUT.sub("", rest.split(" ")[0]) if rest else ""
    return f"{name.lstrip('%')} {shape}".strip()


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb*"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def reduce(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices = []
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m is None:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.duration_ns > 0:
                            host.append((ev.start_ns, ev.start_ns
                                         + ev.duration_ns, ev.name))
            continue
        ops: List[Tuple[float, float]] = []
        op_time: Dict[str, float] = defaultdict(float)
        programs: Dict[str, float] = defaultdict(float)
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    op_time[_op_name(ev.name)] += ev.duration_ns * 1e-9
            elif line.name == "XLA Modules":
                for ev in line.events:
                    programs[_SUFFIX.sub("", ev.name)] += ev.duration_ns * 1e-9
        merged = _union(ops)
        devices.append({
            "id": int(m.group(1)),
            "busy_s": sum(e - s for s, e in merged) * 1e-9,
            "programs": dict(programs), "op_time": dict(op_time),
            "merged": merged})
    if not devices:
        return {"devices": [], "device_ops": [], "idle_gaps": []}
    devices.sort(key=lambda d: d["id"])
    busiest = max(devices, key=lambda d: d["busy_s"])
    gaps = [(merged_e, nxt_s) for (_, merged_e), (nxt_s, _) in
            zip(busiest["merged"], busiest["merged"][1:])]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        best, best_overlap = "host:none", 0.0
        for hs, he, name in host:
            if he - hs > 10 * (e - s):
                continue
            ov = min(e, he) - max(s, hs)
            if ov > best_overlap:
                best, best_overlap = name, ov
        named.append([best, (e - s) * 1e-9])
    ops_total: Dict[str, float] = defaultdict(float)
    for d in devices:
        for k, v in d["op_time"].items():
            ops_total[k] += v
    device_ops = sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]
    for d in devices:
        del d["merged"], d["op_time"]
    return {"devices": devices,
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": named}


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_trace(target)
    print(json.dumps(reduce(target), indent=1))
