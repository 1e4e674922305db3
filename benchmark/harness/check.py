"""The comparison that decides `correct`.

Every window a run publishes is captured off the snapshot bus, the same
bus the querier serves from. The ingester's clock decides which window a
record lands in, but the union of all the windows a run published holds
exactly the records it sent, so the union is compared with the plain
reference over everything sent:

- rows: the windows' row counts sum to the records sent (exact);
- packet mass: each entropy feature's histogram sums to the packets sent
  (exact: it covers the entropy path);
- Count-Min, for the 100 heaviest flows and a seeded sample of 1000
  others: never under the exact count, and over it by more than e*N/w
  for at most a share e^-d of the keys (the sketch's stated bound);
- HyperLogLog: distinct clients per service group within 3 standard
  errors of the exact count;
- top-100 membership: the candidates every window's ring admitted,
  ranked by the union's Count-Min, hold every exact top-100 flow whose
  count clears the 100th by more than e*N/w (while the bound holds, no
  flow outside the exact top-100 can outrank such a flow);
- top-100 counts, the program's own answer: a flow's ring counts summed
  over the windows whose ring holds it stay within e*N/w of its exact
  count, for all but a share e^-d of the exact top-100. (A ring admits a
  sampled sixteenth of each batch's keys, so a flow may miss the ring of
  a window with few of its records; its sum can then fall short, and
  only the upper side is held.)

The union is merged the way the sketches merge (counts add, registers
take the max) and read through the serving layer's own estimators. In a
cell that reads, every served answer must equal the serving layer's
answer for the published window it names. Each number is printed beside
its limit.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from harness.reference import Reference, cms_bound, hll_limit

_SENTINEL = 0xFFFFFFFF
TOP_K = 100
CMS_SAMPLE = 1000


def _view(leaves):
    from deepflow_tpu.runtime.snapbus import SketchSnapshot
    from deepflow_tpu.serving.tables import _SketchView

    return _SketchView(SketchSnapshot(step=-1, seq=-1, wall_time=0.0,
                                      leaves=tuple(leaves)))


def union(snaps) -> list:
    """The windows merged, as leaves in FlowSuiteState order: Count-Min
    counters and histograms add, registers take the max, and the ring
    holds every key some window admitted with its ring counts summed
    over those windows."""
    first = snaps[0].leaves
    cms = np.zeros(first[0].shape, np.int64)
    hll = np.zeros(first[4].shape, first[4].dtype)
    ent = np.zeros(first[5].shape, np.int64)
    rows, ring_keys, ring_counts = 0, [], []
    for s in snaps:
        lv = s.leaves
        cms += lv[0]
        np.maximum(hll, lv[4], out=hll)
        ent += lv[5]
        rows += int(lv[7])
        live = (lv[3] > 0) & (lv[2] != _SENTINEL)
        ring_keys.append(lv[2][live])
        ring_counts.append(lv[3][live])
    cand, inverse = np.unique(np.concatenate(ring_keys), return_inverse=True)
    summed = np.bincount(inverse, weights=np.concatenate(ring_counts),
                         minlength=len(cand)).astype(np.int64)
    return [cms, first[1], cand.astype(np.uint32), summed, hll, ent,
            first[6], np.int64(rows), np.int64(0)]


def ring_counts(leaves, keys: np.ndarray) -> np.ndarray:
    """The union's summed ring count of each key, 0 where no ring held it."""
    cand, summed = leaves[2], leaves[3]
    if not len(cand):
        return np.zeros(len(keys), np.int64)
    i = np.minimum(np.searchsorted(cand, keys), len(cand) - 1)
    return np.where(cand[i] == keys, summed[i], 0)


def compare(snaps, ref: Reference, sketch_cfg: dict, seed: int,
            fallbacks: Dict[str, int]) -> Dict[str, Dict[str, float]]:
    leaves = union(snaps)
    v = _view(leaves)
    out: Dict[str, Dict[str, float]] = {}

    def put(name: str, value: float, limit: float) -> None:
        out[name] = {"value": float(value), "limit": float(limit)}

    put("rows_gap", abs(int(leaves[7]) - ref.records), 0)
    mass = np.asarray(leaves[5]).sum(axis=1)
    put("ent_mass_gap", int(np.abs(mass - ref.packets).max()), 0)
    keys = np.unique(np.concatenate(
        [ref.heaviest(TOP_K), ref.sample(CMS_SAMPLE, seed)])).astype(np.uint32)
    est = v.cms_points(keys).astype(np.int64)
    exact = ref.count(keys)
    put("cms_under", max(0, int((exact - est).max())), 0)
    bound = cms_bound(ref.records, sketch_cfg["cms_log2_width"])
    put("cms_over_share", float(((est - exact) > bound).mean()),
        math.exp(-sketch_cfg["cms_depth"]))
    card = v.hll_card()
    put("hll_err", abs(card - ref.distinct_clients) / ref.distinct_clients,
        hll_limit(sketch_cfg["hll_precision"]))
    top = ref.heaviest(TOP_K)
    exact_top = ref.count(top)
    sure = top[exact_top > ref.kth_count(TOP_K) + bound]
    cand = leaves[2]
    ranked = cand[np.argsort(-v.cms_points(cand), kind="stable")][:TOP_K]
    put("topk_miss", int((~np.isin(sure, ranked)).sum()), 0)
    over = ring_counts(leaves, top) - exact_top > bound
    put("topk_over_share", float(over.mean()),
        math.exp(-sketch_cfg["cms_depth"]))
    put("program_faults", sum(fallbacks.values()), 0)
    return out


def served_answer(stmt: str, view) -> Optional[list]:
    """What the serving layer answers for `stmt` from one window's view,
    as the rows' value columns after the (time, window) pair."""
    if "topk" in stmt:
        return [[k, c] for k, c in view.topk(TOP_K)]
    if "cms_point" in stmt:
        key = int(stmt.split("(")[1].split(")")[0])
        return [[key & 0xFFFFFFFF, view.cms_point(key)]]
    if "hll_card" in stmt:
        return [[-1, round(view.hll_card(None), 2)]]
    if "entropy" in stmt:
        return [[float(e) for e in view.entropies()]]
    return None


def read_mismatches(reads: List[dict], snaps) -> int:
    """Reads that failed, came back empty, or differ from the serving
    layer's answer for the window they name."""
    from deepflow_tpu.serving.tables import _SketchView

    by_step = {s.step: s for s in snaps}
    views: Dict[int, object] = {}
    answers: Dict[Tuple[str, int], Optional[list]] = {}
    bad = 0
    for r in reads:
        vals = (r.get("result") or {}).get("values") or []
        if "error" in r or not vals:
            bad += 1
            continue
        step = vals[0][1]
        if step not in by_step:
            bad += 1
            continue
        if (r["sql"], step) not in answers:
            view = views.get(step)
            if view is None:
                view = views[step] = _SketchView(by_step[step])
            answers[(r["sql"], step)] = served_answer(r["sql"], view)
        want = answers[(r["sql"], step)]
        got = [row[2:] if "topk" not in r["sql"] else row[3:5]
               for row in vals]
        bad += got != want
    return bad
