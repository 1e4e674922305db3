"""The anomaly plane's detectors: device state + jitted window step.

ROADMAP item 4 made first-class: the three anomaly ops that until now
only ever ran in bench printouts (``ops/entropy.py`` through the
suite's window entropies, ``ops/pca.py``, ``ops/matrix_profile.py``)
become a detection lane that runs BESIDE the sketch lane and turns
window closes into scored, durable, queryable alert records
(``anomaly/alerts.py``). Three detectors, one jitted window step:

- **entropy_ddos** — per-window traffic-entropy DDoS scoring: EWMA
  z-scores of the suite's 4 feature entropies, combined directionally
  (source dispersion RISES under spoofing while destination entropy
  COLLAPSES onto the victim — the classic volumetric signature,
  BASELINE.json config 4). The score is fed by a **device-resident
  active-flow working set**: a bounded direct-mapped key table in
  device memory (the in-DRAM active-flows table of PAPERS.md
  1902.04143 mapped onto HBM), fed per batch from the same staged
  lanes the sketch path eats and evicted LRU-by-window — a slot's
  occupant survives a collision only while it was seen this window,
  so the table tracks the CURRENT working set and ``active_flows`` /
  ``new_flows`` surges ride the golden-signal vector.
- **pca_residual** — streaming-PCA reconstruction residual over the
  per-window golden-signal vector (``GOLDEN_FEATURES`` below): the
  ``ops/pca.py`` Oja state is finally STATEFUL ACROSS WINDOWS —
  one ``pca.update`` per window close, score standardized against an
  EWMA of its own residual history.
- **mp_discord** — matrix-profile discord detection over the rollup
  window series: the ``ops/matrix_profile.py`` ring is pushed at every
  flush with the golden vector and the newest subsequence is priced
  against history (one matvec per window — the streaming fast path).
  Catches the time-SHAPE anomalies the instantaneous detectors can't
  (a latency plateau, a slow ramp, silence).

All three advance inside ONE jitted window step dispatched at the
window-flush boundary, so the feed/prefetch posture of the sketch lane
is unchanged; the per-batch active-flow offers reuse the device arrays
the sketch update already transferred (zero extra h2d bytes — only one
extra small dispatch per batch). The anomaly state is its own pytree:
the sketch state is bit-identical with the plane on or off
(tests/test_anomaly.py asserts leaf equality against a detectors-off
twin run).

deepflow-lint's host-sync-in-device-path rule covers this file:
``close_window`` is the ONE sanctioned sync — it materializes the
window's scores host-side at the same boundary ``flush_window`` already
fetches the window output.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deepflow_tpu.ops import matrix_profile, pca
from deepflow_tpu.utils.twinmark import host_twin_of
from deepflow_tpu.utils.u32 import mix32

__all__ = ["AnomalyConfig", "AnomalyState", "WindowScores", "DETECTORS",
           "GOLDEN_FEATURES", "init", "offer", "window_step",
           "ddos_score_np", "make_window_step"]

# detector order is the wire order: scores[i] / thresholds[i] /
# alerts_total[i] all index this tuple (alerts.py re-exports it)
DETECTORS = ("entropy_ddos", "pca_residual", "mp_discord")

# the golden-signal vector (one value per window close) the PCA and
# matrix-profile detectors consume. Counts are log1p-compressed;
# entropies and the heavy-hitter share are already in [0, 1].
GOLDEN_FEATURES = (
    "log_rows", "log_active_flows", "log_new_flows",
    "entropy_ip_src", "entropy_ip_dst", "entropy_port_src",
    "entropy_port_dst", "log_distinct_clients", "top1_share",
)

_SENTINEL = jnp.uint32(0xFFFFFFFF)       # empty active-table slot
# EWMA-variance floor for the z-scores (the ops/pca.py _VAR_FLOOR
# posture): a dead-quiet signal's variance decays toward 0 and an
# unfloored z would alarm on one count of jitter
_VAR_FLOOR = 1e-4


@dataclass(frozen=True)
class AnomalyConfig:
    """Threshold and sizing knobs (IngesterConfig.anomaly_* mirrors)."""

    active_log2: int = 14        # active-flow table slots (2^n); 0 disables
    entropy_z: float = 4.0       # entropy_ddos alert threshold (z units)
    pca_z: float = 4.0           # pca_residual alert threshold (z units)
    mp_threshold: float = 3.0    # mp_discord threshold (z-norm distance)
    warmup_windows: int = 8      # windows before any detector may score
    ewma_alpha: float = 0.05
    pca_k: int = 3
    mp_length: int = 128         # windows of golden-vector history
    mp_m: int = 8                # discord subsequence length (windows)
    top_contributors: int = 5    # ring top-K keys attached to an alert
    seed: int = 0xA70A17

    @property
    def thresholds(self) -> Tuple[float, float, float]:
        return (self.entropy_z, self.pca_z, self.mp_threshold)


class AnomalyState(NamedTuple):
    """The anomaly plane's device pytree — separate from FlowSuiteState
    by construction (bit-invisibility is structural, not disciplined)."""

    # active-flow working set (direct-mapped, LRU-by-window)
    keys: jnp.ndarray          # [cap] uint32, _SENTINEL = empty
    born: jnp.ndarray          # [cap] int32 window the key first appeared
    last_window: jnp.ndarray   # [cap] int32 window the key was last seen
    offers: jnp.ndarray        # [] int32 rows offered to the table
    evictions: jnp.ndarray     # [] int32 LRU-by-window displacements
    window: jnp.ndarray        # [] int32 current (open) window index
    # entropy_ddos EWMA baseline over the suite's 4 feature entropies
    ent_mean: jnp.ndarray      # [4] f32
    ent_var: jnp.ndarray       # [4] f32
    # pca_residual: Oja subspace + EWMA of its own residual
    pca: pca.PCAState
    res_mean: jnp.ndarray      # [] f32
    res_var: jnp.ndarray       # [] f32
    # mp_discord: golden-vector rings
    mp: matrix_profile.MPState


class WindowScores(NamedTuple):
    """One window step's device outputs (host-read in close_window)."""

    scores: jnp.ndarray        # [3] f32, DETECTORS order, 0 pre-warmup
    z: jnp.ndarray             # [4] f32 entropy z-scores
    feats: jnp.ndarray         # [9] f32 golden-signal vector
    active_flows: jnp.ndarray  # [] int32 table slots seen this window
    new_flows: jnp.ndarray     # [] int32 of those, first seen this window
    rows: jnp.ndarray          # [] int32 the window's row count


def init(cfg: AnomalyConfig, window: int = 0) -> AnomalyState:
    """Fresh plane state; ``window`` seeds the window counter (a
    detection reset mid-run keeps the LRU epoch aligned with the host
    window count)."""
    cap = 1 << cfg.active_log2 if cfg.active_log2 > 0 else 1
    f = len(GOLDEN_FEATURES)
    return AnomalyState(
        keys=jnp.full((cap,), _SENTINEL, jnp.uint32),
        born=jnp.zeros((cap,), jnp.int32),
        last_window=jnp.full((cap,), -1, jnp.int32),
        offers=jnp.zeros((), jnp.int32),
        evictions=jnp.zeros((), jnp.int32),
        window=jnp.asarray(int(window), jnp.int32),
        ent_mean=jnp.full((4,), 0.5, jnp.float32),
        ent_var=jnp.full((4,), 0.25, jnp.float32),
        pca=pca.init(f, cfg.pca_k, seed=cfg.seed & 0xFFFF),
        res_mean=jnp.zeros((), jnp.float32),
        res_var=jnp.ones((), jnp.float32),
        mp=matrix_profile.init(f, cfg.mp_length),
    )


# -- active-flow working set (per batch, on device) -------------------------

def offer(state: AnomalyState, fkeys: jnp.ndarray, mask: jnp.ndarray,
          cfg: AnomalyConfig) -> AnomalyState:
    """Offer one batch of flow keys to the active-flow table.

    Direct-mapped by multiply-shift hash; a slot admits the incoming
    key when it is empty, already holds the key, or its occupant was
    NOT seen in the current window (LRU-by-window eviction: the stale
    occupant is displaced, counted). An occupant seen this window wins
    the collision, so the bounded table degrades by refusing NEW keys
    — never by thrashing the standing working set. Within one batch,
    later rows win slot races against earlier rows (scatter order);
    the table is a working-set tracker, not an exact dictionary."""
    w = state.window
    cap = state.keys.shape[0]
    salt = jnp.uint32(cfg.seed & 0xFFFFFFFF)
    slot = (mix32(fkeys ^ salt) >> jnp.uint32(32 - cfg.active_log2)
            ).astype(jnp.int32)
    occ_key = state.keys[slot]
    occ_last = state.last_window[slot]
    empty = occ_key == _SENTINEL
    same = occ_key == fkeys
    stale = occ_last < w
    admit = mask & (empty | same | stale)
    tgt = jnp.where(admit, slot, cap)            # OOB -> dropped
    keys = state.keys.at[tgt].set(fkeys, mode="drop")
    born = state.born.at[tgt].set(
        jnp.where(same, state.born[slot], w), mode="drop")
    last = state.last_window.at[tgt].set(w, mode="drop")
    evicted = admit & ~empty & ~same
    return state._replace(
        keys=keys, born=born, last_window=last,
        offers=state.offers + jnp.sum(mask.astype(jnp.int32)),
        evictions=state.evictions + jnp.sum(evicted.astype(jnp.int32)))


# -- the window step (one jitted program per flush) -------------------------

def _golden_vector(entropies, topk_counts, card, rows, active, new):
    rows_f = rows.astype(jnp.float32)
    top1 = jnp.maximum(jnp.max(topk_counts), 0).astype(jnp.float32)
    return jnp.stack([
        jnp.log1p(rows_f),
        jnp.log1p(active.astype(jnp.float32)),
        jnp.log1p(new.astype(jnp.float32)),
        entropies[0], entropies[1], entropies[2], entropies[3],
        jnp.log1p(jnp.maximum(jnp.sum(card), 0.0)),
        top1 / jnp.maximum(rows_f, 1.0),
    ]).astype(jnp.float32)


def _ddos_score(z: jnp.ndarray) -> jnp.ndarray:
    """Directional combination of the 4 entropy z-scores: source
    dispersion rising (spoofed randoms) or destination entropy
    collapsing (one victim) both push the score up; either alone can
    cross the threshold, both together compound."""
    up = jnp.maximum(z[0], 0.0) + jnp.maximum(z[2], 0.0)      # src rise
    down = jnp.maximum(-z[1], 0.0) + jnp.maximum(-z[3], 0.0)  # dst collapse
    return jnp.maximum(jnp.maximum(up, down), (up + down) / 2.0)


def window_step(state: AnomalyState, entropies: jnp.ndarray,
                topk_counts: jnp.ndarray, card: jnp.ndarray,
                rows: jnp.ndarray, cfg: AnomalyConfig
                ) -> Tuple[AnomalyState, WindowScores]:
    """Close one window: score all three detectors against the settled
    window output, then advance every cross-window state (EWMA
    baselines, Oja subspace, matrix-profile ring, window counter).

    Scoring uses the PRE-update baselines (the anomaly must stand out
    against history, not against a baseline it already polluted); an
    empty window (rows == 0) scores 0 and leaves the EWMAs untouched
    so an idle gap can't fake an entropy collapse."""
    w = state.window
    rows = jnp.asarray(rows, jnp.int32)
    busy = rows > 0
    warm = w >= cfg.warmup_windows
    live = busy & warm

    active = jnp.sum((state.last_window == w).astype(jnp.int32))
    new = jnp.sum(((state.last_window == w)
                   & (state.born == w)).astype(jnp.int32))
    ent = jnp.asarray(entropies, jnp.float32)
    g = _golden_vector(ent, topk_counts, card, rows, active, new)

    # entropy_ddos
    z = (ent - state.ent_mean) / jnp.sqrt(
        jnp.maximum(state.ent_var, _VAR_FLOOR))
    s_ddos = _ddos_score(z)

    # pca_residual (score with the pre-update basis and baselines)
    r = pca.score(state.pca, g[None, :])[0]
    s_pca = (r - state.res_mean) / jnp.sqrt(
        jnp.maximum(state.res_var, _VAR_FLOOR))

    # mp_discord: push the window's vector, price the newest
    # subsequence against history (latest_score gates on its own
    # 2m-window warmup internally)
    mp = matrix_profile.push(state.mp, g)
    s_mp = jnp.max(matrix_profile.latest_score(mp, cfg.mp_m))

    scores = jnp.where(live, jnp.stack([s_ddos, s_pca, s_mp]), 0.0)

    # EWMA/baseline advancement — busy windows only. The effective
    # alpha is max(alpha, 1/(w+1)): a plain running average while young
    # (the init priors wash out in a handful of windows instead of
    # 1/alpha of them — the z-scores are meaningless until the variance
    # reflects the stream, which is also why warmup_windows gates
    # scoring), decaying into the standard EWMA once 1/(w+1) < alpha.
    # Anomaly exclusion: a window a detector is ALERTING on does not
    # update that detector's own baseline — one attack window would
    # otherwise inflate the variance enough to mute the rest of the
    # attack (observed: z 47 -> 3.7 one window later without this).
    # A sustained attack therefore keeps alerting until traffic
    # actually normalizes, which is the CI smoke's "sustained" phase.
    a = jnp.maximum(jnp.float32(cfg.ewma_alpha),
                    1.0 / (w.astype(jnp.float32) + 1.0))
    ent_calm = busy & ~(live & (s_ddos >= cfg.entropy_z))
    res_calm = busy & ~(live & (s_pca >= cfg.pca_z))
    ent_mean = jnp.where(ent_calm, (1 - a) * state.ent_mean + a * ent,
                         state.ent_mean)
    ent_var = jnp.where(
        ent_calm, (1 - a) * state.ent_var + a * (ent - ent_mean) ** 2,
        state.ent_var)
    res_mean = jnp.where(res_calm, (1 - a) * state.res_mean + a * r,
                         state.res_mean)
    res_var = jnp.where(
        res_calm, (1 - a) * state.res_var + a * (r - res_mean) ** 2,
        state.res_var)
    p_new = pca.update(state.pca, g[None, :])
    p = jax.tree_util.tree_map(
        lambda new_leaf, old_leaf: jnp.where(res_calm, new_leaf,
                                             old_leaf),
        p_new, state.pca)
    mp_kept = jax.tree_util.tree_map(
        lambda new_leaf, old_leaf: jnp.where(busy, new_leaf, old_leaf),
        mp, state.mp)

    out = WindowScores(scores=scores, z=z, feats=g,
                       active_flows=active, new_flows=new, rows=rows)
    return state._replace(
        window=w + 1, ent_mean=ent_mean, ent_var=ent_var,
        pca=p, res_mean=res_mean, res_var=res_var, mp=mp_kept), out


def make_window_step(cfg: AnomalyConfig):
    """The jitted window-step program (state donated: the anomaly chain
    is linear like the sketch chain, and the pre-step state is never a
    checkpoint payload — alerts are the durable artifact)."""
    def anomaly_window(s, ent, topk, card, rows):
        return window_step(s, ent, topk, card, rows, cfg)

    return jax.jit(anomaly_window, donate_argnums=0)


# -- per-wire batch-feed programs -------------------------------------------

def feed_lanes(state: AnomalyState, lanes: Dict[str, jnp.ndarray],
               mask: jnp.ndarray, cfg: AnomalyConfig) -> AnomalyState:
    """Offer one packed-lane batch (the device arrays the sketch update
    already transferred — zero extra h2d)."""
    from deepflow_tpu.models import flow_suite

    cols = flow_suite.unpack_lanes(lanes)
    return offer(state, flow_suite.flow_key(cols), mask, cfg)


def feed_cols(state: AnomalyState, cols: Dict[str, jnp.ndarray],
              mask: jnp.ndarray, cfg: AnomalyConfig) -> AnomalyState:
    """Offer one full-column batch (the staged wire's form)."""
    from deepflow_tpu.models import flow_suite

    return offer(state, flow_suite.flow_key(cols), mask, cfg)


def feed_flat(state: AnomalyState, flat: jnp.ndarray, k: int,
              capacity: int, cfg: AnomalyConfig) -> AnomalyState:
    """Offer a K-slot coalesced staging transfer (the feed/zero-copy
    wire): every slot's plane parsed exactly like
    flow_suite.make_coalesced_update, one fused offer per slot."""
    from deepflow_tpu.models import flow_suite

    slots = flat.reshape(k, flow_suite.slot_words(capacity))
    for i in range(k):
        plane = slots[i, 1:].reshape(4, capacity)
        n = slots[i, 0]
        lanes = {"ip_src": plane[0], "ip_dst": plane[1],
                 "ports": plane[2], "proto_pkts": plane[3]}
        mask = jnp.arange(capacity) < n
        state = feed_lanes(state, lanes, mask, cfg)
    return state


def feed_news(state: AnomalyState, plane: jnp.ndarray, n: jnp.ndarray,
              cfg: AnomalyConfig) -> AnomalyState:
    """Offer one dictionary-wire (6, C) news plane (rows 1..3 are the
    lane key words, row 4 the raw proto byte — flow_dict.update_news'
    layout)."""
    lanes = {"ip_src": plane[1], "ip_dst": plane[2], "ports": plane[3],
             "proto_pkts": plane[4] << jnp.uint32(24)}
    mask = jnp.arange(plane.shape[1]) < n
    return feed_lanes(state, lanes, mask, cfg)


def feed_dict_flat(state: AnomalyState, table: jnp.ndarray,
                   flat: jnp.ndarray, sig, cfg: AnomalyConfig
                   ) -> AnomalyState:
    """Offer one coalesced dictionary-wire staging transfer (the feed
    path's form): the same [n-headers | raveled planes] layout
    flow_dict.make_wire_update reads, one offer per plane. Hits gather
    from the POST-group dictionary table — within-group index reuse can
    mis-key the rare displaced hit; the table is a working-set tracker,
    so the approximation is bounded and documented, never state
    corruption."""
    from deepflow_tpu.models.flow_dict import _KIND_ROWS

    off = len(sig)
    for i, (kind, w) in enumerate(sig):
        n = flat[i]
        nwords = _KIND_ROWS[kind] * w
        plane = flat[off:off + nwords].reshape(_KIND_ROWS[kind], w)
        off += nwords
        if kind == "news":
            state = feed_news(state, plane, n, cfg)
        else:
            state = feed_hits(state, table, plane, n, cfg)
    return state


def feed_hits(state: AnomalyState, table: jnp.ndarray,
              plane: jnp.ndarray, n: jnp.ndarray,
              cfg: AnomalyConfig) -> AnomalyState:
    """Offer one dictionary-wire (3, H) pairs-packed hits plane: key
    words gathered from the device dictionary table (the post-update
    table — news in the same group already scattered, so every hit's
    index resolves)."""
    from deepflow_tpu.models import flow_dict

    idx, _pkts = flow_dict.unpack_hits(plane)
    rows = table[:, idx]
    lanes = {"ip_src": rows[0], "ip_dst": rows[1], "ports": rows[2],
             "proto_pkts": rows[3]}
    mask = jnp.arange(2 * plane.shape[1]) < n
    return feed_lanes(state, lanes, mask, cfg)


# -- host twin (the detection-audit scorer) ---------------------------------

@host_twin_of("deepflow_tpu/anomaly/detectors.py:_ddos_score")
def ddos_score_np(z: np.ndarray) -> float:
    """Host twin of `_ddos_score` (plain numpy): the shadow auditor
    scores its EXACT entropies with the same directional combination,
    so detection precision/recall is measured against the same rule the
    device runs — not a different detector that happens to share a
    name (twin-drift gated like every other host/device pair)."""
    up = max(float(z[0]), 0.0) + max(float(z[2]), 0.0)
    down = max(-float(z[1]), 0.0) + max(-float(z[3]), 0.0)
    return max(up, down, (up + down) / 2.0)
