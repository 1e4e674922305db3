"""The flagship streaming model: all l4_flow_log sketches in one jitted step.

One `update` consumes a static-shape L4 TensorBatch (as device arrays) and
advances, in a single XLA program:

- Count-Min (MXU-histogram update) over the 5-tuple -> heavy-hitter counts
- candidate ring                                  -> top-K flows
- per-service HyperLogLog                         -> distinct client IPs
- 4-feature entropy histograms                    -> DDoS signals
- per-service byte/packet accumulators            -> service meters

`flush` closes a 1s-style window: reads top-K / cardinalities / entropies,
then resets window state. This is the TPU re-design of the reference's
decode->enrich->aggregate ingester stage (SURVEY.md §3.2 hot path): where
the reference fans records across threads into per-thread stashes, we fan
lanes across a batch axis into device-resident sketch state; where it merges
stashes over queues, we merge sketch pytrees with ICI collectives
(deepflow_tpu.parallel).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Dict, NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deepflow_tpu.ops import cms, entropy, hll, topk
from deepflow_tpu.utils.twinmark import host_twin_of
from deepflow_tpu.utils.u32 import fold_columns

ENTROPY_FEATURES = ("ip_src", "ip_dst", "port_src", "port_dst")


@dataclass(frozen=True)
class FlowSuiteConfig:
    cms_depth: int = 4
    cms_log2_width: int = 17
    ring_size: int = 2048
    top_k: int = 100
    hll_groups: int = 1024       # service hash space
    hll_precision: int = 10
    entropy_log2_buckets: int = 12
    # Plain (MXU-histogram) CMS update at 2x width beats conservative update
    # on TPU: the conservative variant needs a full-batch sort + scatter-max
    # (~6x slower) for ~the same top-K recall at these widths.
    conservative: bool = False
    # Admit a 1/2^s stride-sample of lanes to the top-K ring per batch
    # (scores stay full-sketch; see ops/topk.py:offer).
    topk_sample_log2: int = 4
    # Fused Pallas unpack+sketch kernel (ops/pallas_sketch.py): the CMS
    # and entropy histogram passes of a staged lane batch run as ONE
    # VMEM-resident kernel with the unpack prologue inlined. None =
    # auto (TPU backend + DEEPFLOW_SKETCH_PALLAS=1 opt-in only — the
    # ops/pallas_hist.py posture); True forces it (a TPU only: tests
    # interpret it through pallas_hist.INTERPRET); False never.
    fused_hists: bool | None = None
    seed: int = 0xDEC0DE


class FlowSuiteState(NamedTuple):
    sketch: cms.CMSState
    ring: topk.TopKState
    services: hll.HLLState
    ent: entropy.EntropyState
    rows_seen: jnp.ndarray       # [] int32 valid rows this window
    batches_seen: jnp.ndarray    # [] int32


class FlowWindowOutput(NamedTuple):
    topk_keys: jnp.ndarray       # [K] uint32 flow-key hashes
    topk_counts: jnp.ndarray     # [K] int32
    service_cardinality: jnp.ndarray  # [hll_groups] float32 distinct clients
    entropies: jnp.ndarray       # [4] normalized src/dst ip/port entropy
    rows: jnp.ndarray            # [] int32


def init(cfg: FlowSuiteConfig) -> FlowSuiteState:
    return FlowSuiteState(
        sketch=cms.init(cfg.cms_depth, cfg.cms_log2_width, cfg.seed),
        ring=topk.init(cfg.ring_size),
        services=hll.init(cfg.hll_groups, cfg.hll_precision),
        ent=entropy.init(len(ENTROPY_FEATURES), cfg.entropy_log2_buckets,
                         cfg.seed ^ 0xE27),
        rows_seen=jnp.zeros((), jnp.int32),
        batches_seen=jnp.zeros((), jnp.int32),
    )


def flow_key(cols: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """uint32 flow key from the 5-tuple (the heavy-hitter key space)."""
    return fold_columns([cols["ip_src"], cols["ip_dst"], cols["port_src"],
                         cols["port_dst"], cols["proto"]])


def service_key(cols: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """uint32 service key: (server ip, server port, proto)."""
    return fold_columns([cols["ip_dst"], cols["port_dst"], cols["proto"]])


def _advance_sketches(state: FlowSuiteState, cols: Dict[str, jnp.ndarray],
                      mask: jnp.ndarray, cfg: FlowSuiteConfig,
                      hists=None):
    """Everything except ring admission — shared by the fused `update`,
    the staged pipeline and the Pallas-fused lane path so the paths
    cannot drift. Returns the advanced state (ring untouched) plus the
    batch flow keys. `hists` (the fused kernel's precomputed
    (cms_hist, ent_hist) f32 deltas) replaces the CMS/entropy histogram
    ops only; HLL, row/batch bookkeeping and key derivation stay the
    one definition here."""
    fkey = flow_key(cols)
    skey = service_key(cols)
    if hists is None:
        upd = cms.update_conservative if cfg.conservative else cms.update
        sketch = upd(state.sketch, fkey, mask=mask)
        feats = jnp.stack([cols[f] for f in ENTROPY_FEATURES])
        packets = cols["packet_tx"] + cols["packet_rx"]
        # 2 weight planes: per-record packet counts saturate at 65535
        # (ample for 1s flow ticks); the third plane cost a full matmul
        # pass
        ent = entropy.update(state.ent, feats, packets.astype(jnp.int32),
                             mask, weight_planes=2)
    else:
        cms_h, ent_h = hists
        sketch = state.sketch._replace(
            counts=state.sketch.counts
            + cms_h.astype(state.sketch.counts.dtype))
        ent = state.ent._replace(
            hist=state.ent.hist + ent_h.astype(state.ent.hist.dtype))
    group = (skey % np.uint32(cfg.hll_groups)).astype(jnp.int32)
    services = hll.update(state.services, group, cols["ip_src"], mask=mask)
    mid = FlowSuiteState(
        sketch=sketch,
        ring=state.ring,
        services=services,
        ent=ent,
        rows_seen=state.rows_seen + jnp.sum(mask.astype(jnp.int32)),
        batches_seen=state.batches_seen + 1,
    )
    return mid, fkey


SKETCH_LANE_NAMES = ("ip_src", "ip_dst", "ports", "proto_pkts")


def pack_lanes(cols: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Host-side pack of the 7 sketch-consumed columns into 4 uint32
    planes (16B/record instead of the 68B full schema row).

    On a transfer-bound feed, bytes moved per record is the e2e
    throughput ceiling; the reference ships full rows because PCIe
    doesn't care (SURVEY §7 "Hard parts" names the host->device
    boundary as the real constraint). Layout:
      ip_src, ip_dst: as-is
      ports:      port_src << 16 | port_dst
      proto_pkts: proto << 24 | min(packet_tx + packet_rx, 0xFFFFFF)
    Equivalence with the full-row path is bit-exact for IN-RANGE rows:
    ports < 2^16, proto < 2^8, packet_tx+packet_rx < 2^24 (every value
    a real packet header can produce). Out-of-range values — possible
    on the u32 wire columns from a buggy sender — are masked to range
    here, where the full-row path would hash the raw u32; such rows get
    a different flow key on the two wires, never corrupt state.
    """
    u32 = np.uint32
    pkts = np.minimum(cols["packet_tx"].astype(np.uint64)
                      + cols["packet_rx"], 0xFFFFFF).astype(u32)
    return {
        "ip_src": cols["ip_src"].astype(u32, copy=False),
        "ip_dst": cols["ip_dst"].astype(u32, copy=False),
        "ports": ((cols["port_src"].astype(u32) & u32(0xFFFF)) << u32(16))
                 | (cols["port_dst"].astype(u32) & u32(0xFFFF)),
        "proto_pkts": ((cols["proto"].astype(u32) & u32(0xFF)) << u32(24))
                      | pkts,
    }


def pack_lanes_into(cols: Dict[str, np.ndarray], out: np.ndarray) -> None:
    """`pack_lanes` writing into a preallocated (4, n) uint32 view of a
    coalesced staging buffer (runtime/feed.py): same bit-exact lane
    words, zero intermediate allocations — the staging buffer is the
    ONLY host copy between the TensorBatch and the single device_put."""
    u32 = np.uint32
    np.copyto(out[0], cols["ip_src"], casting="unsafe")
    np.copyto(out[1], cols["ip_dst"], casting="unsafe")
    out[2][:] = ((cols["port_src"].astype(u32) & u32(0xFFFF)) << u32(16)) \
        | (cols["port_dst"].astype(u32) & u32(0xFFFF))
    out[3][:] = ((cols["proto"].astype(u32) & u32(0xFF)) << u32(24)) \
        | np.minimum(cols["packet_tx"].astype(np.uint64)
                     + cols["packet_rx"], 0xFFFFFF).astype(u32)


# Coalesced staging layout for K packed-lane batches of capacity C
# (flat uint32, ONE transfer): K slot-contiguous records, slot k at
# [k*(1+4C), (k+1)*(1+4C)) holding [n_k | plane_k (4*C)]. The program
# recovers each batch's mask on device from its n word, so not even
# the bool mask crosses the link. Slot-contiguity (vs the ISSUE 5
# header-block layout) is what makes PREFIX emission possible: a
# partially-filled staging buffer of k < K complete slots is already a
# valid k-batch coalesced transfer — the zero-copy stager
# (batch/staging.py) fills slots in place and ships whatever is
# complete at a window boundary without moving a byte.
def slot_words(capacity: int) -> int:
    return 1 + 4 * capacity


def coalesced_lanes_words(k_batches: int, capacity: int) -> int:
    return k_batches * slot_words(capacity)


def slot_plane(flat: np.ndarray, k: int, capacity: int) -> np.ndarray:
    """(4, C) uint32 view of slot k's lane plane inside a coalesced
    staging buffer — the destination `pack_lanes_into` (or a sharded
    pack worker) writes without any intermediate copy. Callers stamp
    the slot's n word at `flat[k * slot_words(capacity)]` themselves:
    valid-row counts come from the batch (TensorBatch.valid, the
    stager's fill cursor), never from a column length."""
    s = slot_words(capacity)
    return flat[k * s + 1:(k + 1) * s].reshape(4, capacity)


def make_coalesced_update(cfg: FlowSuiteConfig, k_batches: int,
                          capacity: int):
    """One jitted program advancing the suite by K stacked packed-lane
    batches read from a single coalesced staging transfer (the
    multi-batch fused step: `lax.scan` amortizes per-dispatch overhead
    that dominates at small batch_rows). Applies the K batches in
    order with per-batch masks, so the final state is bit-identical to
    K separate `update_packed` dispatches — including ring admission,
    whose phase rides state.batches_seen exactly as before. Returns
    fn(state, flat) -> (state, fence) with `state` donated and `fence`
    a small fresh scalar the feed can block on without touching the
    donated chain.

    When the fused Pallas unpack+sketch kernel is enabled (see
    ops/pallas_sketch.py and `use_fused_hists`), the CMS + entropy
    histogram work of each batch runs as ONE VMEM-resident kernel with
    the lane unpack inlined; HLL/ring/counters stay XLA. Off by
    default — the kernel is opt-in exactly like ops/pallas_hist.py."""
    K, C = int(k_batches), int(capacity)
    fused = use_fused_hists(cfg)

    def _one(state: FlowSuiteState, plane: jnp.ndarray,
             n: jnp.ndarray) -> FlowSuiteState:
        if fused:
            return update_lanes_fused(state, plane, n, cfg)
        lanes = {"ip_src": plane[0], "ip_dst": plane[1],
                 "ports": plane[2], "proto_pkts": plane[3]}
        mask = jnp.arange(plane.shape[1]) < n
        return update(state, unpack_lanes(lanes), mask, cfg)

    def sketch_update_lanes(state: FlowSuiteState, flat: jnp.ndarray):
        slots = flat.reshape(K, slot_words(C))
        if K == 1:                     # no scan machinery for the common case
            out = _one(state, slots[0, 1:].reshape(4, C), slots[0, 0])
            return out, slots[0, 0] + jnp.uint32(0)

        def body(s, slot):
            return _one(s, slot[1:].reshape(4, C), slot[0]), None

        out, _ = jax.lax.scan(body, state, slots)
        return out, jnp.sum(slots[:, 0])

    return jax.jit(sketch_update_lanes, donate_argnums=0)


def use_fused_hists(cfg: FlowSuiteConfig) -> bool:
    """Dispatch for the fused Pallas unpack+sketch kernel: forced by
    `cfg.fused_hists` True/False; None (auto) takes it only on a TPU
    backend under the DEEPFLOW_SKETCH_PALLAS=1 opt-in — the same
    conservative posture as ops/mxu_hist._use_pallas: no chip
    measurement has chosen the kernel yet (ROADMAP S3). Forced on
    another backend, the kernel's lowering refuses (no interpreter
    fallback). Conservative CMS update has no fused form (it needs a
    batch sort + scatter-max)."""
    import os

    if cfg.conservative:
        return False
    if cfg.fused_hists is not None:
        return bool(cfg.fused_hists)
    return (jax.default_backend() == "tpu"
            and os.environ.get("DEEPFLOW_SKETCH_PALLAS", "") == "1")


def update_lanes_fused(state: FlowSuiteState, plane: jnp.ndarray,
                       n: jnp.ndarray,
                       cfg: FlowSuiteConfig) -> FlowSuiteState:
    """`update` over one staged lane plane with the CMS + entropy
    histogram passes fused into a single Pallas kernel (in-kernel
    unpack + fold + bucket hashing, VMEM-resident accumulators —
    ops/pallas_sketch.py). HLL's scatter-max, the top-K ring and the
    window counters stay the one `_advance_sketches` definition, XLA
    ops in the same jitted program. Bit-exact with the unfused path
    while every histogram cell stays an integer sum below 2^24 — the
    regime tests/test_staging.py asserts leaf equality in; past it the
    two paths' f32 partial-sum orders differ and entropy cells may
    round apart (see `fused_lane_hists` for the bound)."""
    from deepflow_tpu.ops import pallas_hist, pallas_sketch

    cols = unpack_lanes({"ip_src": plane[0], "ip_dst": plane[1],
                         "ports": plane[2], "proto_pkts": plane[3]})
    mask = jnp.arange(plane.shape[1]) < n
    hists = pallas_sketch.fused_lane_hists(
        plane, n, state.sketch.seeds, state.ent.seeds,
        cms_log2_width=cfg.cms_log2_width,
        ent_log2_buckets=cfg.entropy_log2_buckets,
        interpret=pallas_hist.INTERPRET)
    mid, fkey = _advance_sketches(state, cols, mask, cfg, hists=hists)
    ring = topk.offer(state.ring, fkey, mid.sketch, mask=mask,
                      sample_log2=cfg.topk_sample_log2,
                      phase=state.batches_seen)
    return mid._replace(ring=ring)


def unpack_lanes(lanes: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Device-side unpack back to the column dict `update` consumes —
    bit-exact with the unpacked path (tests/test_cms.py asserts state
    equality), so recall/keys are identical on either wire."""
    u = jnp.uint32
    return {
        "ip_src": lanes["ip_src"],
        "ip_dst": lanes["ip_dst"],
        "port_src": lanes["ports"] >> u(16),
        "port_dst": lanes["ports"] & u(0xFFFF),
        "proto": lanes["proto_pkts"] >> u(24),
        "packet_tx": lanes["proto_pkts"] & u(0xFFFFFF),
        "packet_rx": jnp.zeros_like(lanes["ip_src"]),
    }


@host_twin_of("deepflow_tpu/models/flow_suite.py:unpack_lanes")
def unpack_lanes_np(plane: np.ndarray, n: int) -> Dict[str, np.ndarray]:
    """Host twin of `unpack_lanes` over one (4, C) staged plane,
    trimmed to the n valid rows — what degraded mode consumes when a
    staged group must be absorbed by the host-numpy fallback sketch
    after the device is lost: the lanes ARE the batch by then (the
    zero-copy path never materialized a TensorBatch). Same packet
    split as the device unpack (tx carries the capped sum, rx zero),
    so the fallback sees exactly what the device would have."""
    u = np.uint32
    return {
        "ip_src": plane[0, :n],
        "ip_dst": plane[1, :n],
        "port_src": plane[2, :n] >> u(16),
        "port_dst": plane[2, :n] & u(0xFFFF),
        "proto": plane[3, :n] >> u(24),
        "packet_tx": plane[3, :n] & u(0xFFFFFF),
        "packet_rx": np.zeros(n, u),
    }


def update_packed(state: FlowSuiteState, lanes: Dict[str, jnp.ndarray],
                  mask: jnp.ndarray, cfg: FlowSuiteConfig) -> FlowSuiteState:
    """`update` over the packed 4-plane wire batch."""
    return update(state, unpack_lanes(lanes), mask, cfg)


def unpack_plane(plane: jnp.ndarray,
                 schema=None) -> Dict[str, jnp.ndarray]:
    """One (n_cols, n) uint32 device plane -> the cols dict, on device.

    The full-row wire (SKETCH_L4_SCHEMA: 17 four-byte columns) is
    ALREADY a contiguous u32 matrix on the host — frombuffer + reshape
    is free — so the whole batch can cross the link as ONE transfer
    instead of 17: per-transfer overhead, not bandwidth, is what one
    copy per column costs. Signed columns are bitcast back on device (free:
    XLA folds it into the consumer)."""
    from jax import lax

    from deepflow_tpu.batch.schema import SKETCH_L4_SCHEMA
    schema = schema or SKETCH_L4_SCHEMA
    cols: Dict[str, jnp.ndarray] = {}
    for i, (name, dt) in enumerate(schema.columns):
        row = plane[i]
        if np.dtype(dt) == np.int32:
            row = lax.bitcast_convert_type(row, jnp.int32)
        cols[name] = row
    return cols


def update_plane(state: FlowSuiteState, plane: jnp.ndarray,
                 mask: jnp.ndarray,
                 cfg: FlowSuiteConfig) -> FlowSuiteState:
    """`update` over the single-transfer full-row plane batch."""
    return update(state, unpack_plane(plane), mask, cfg)


def update(state: FlowSuiteState, cols: Dict[str, jnp.ndarray],
           mask: jnp.ndarray, cfg: FlowSuiteConfig,
           hists=None) -> FlowSuiteState:
    """Advance all sketches by one static-shape batch. Fully jittable.
    `hists` passes a fused Pallas kernel's precomputed (cms, entropy)
    histogram deltas through to `_advance_sketches` — the dict wire's
    fused news/hits path rides this hook (models/flow_dict.py) exactly
    like `update_lanes_fused` rides `_advance_sketches` directly."""
    mid, fkey = _advance_sketches(state, cols, mask, cfg, hists=hists)
    ring = topk.offer(state.ring, fkey, mid.sketch, mask=mask,
                      sample_log2=cfg.topk_sample_log2,
                      phase=state.batches_seen)
    return mid._replace(ring=ring)


def make_staged_update(cfg: FlowSuiteConfig):
    """`update` as a chain of four small jitted programs (the runtime
    uses the fused `update`; this form served a remote runtime that is
    gone, and nothing needs it now: ROADMAP D8). Each compare-bearing
    stage of ring admission is its own program whose moved operands
    arrive as fresh inputs:

      S1 movement: sketches advance + candidate concat + CMS gather
      S2 compare : sentinel blend (inputs only)
      S3 movement: two-key sort
      S4 compare+movement: run-boundary blend (on S3's output as input),
                   top_k, gather

    Intermediate values stay on device between stages; the extra cost is
    three dispatch round-trips per batch.
    """
    sl = cfg.topk_sample_log2

    def s1_core(state, cols, mask):
        mid, fkey = _advance_sketches(state, cols, mask, cfg)
        all_keys = topk.candidate_keys(state.ring.keys, fkey, mask=mask,
                                       sample_log2=sl,
                                       phase=state.batches_seen)
        est = cms.query(mid.sketch, all_keys)
        return mid, all_keys, est

    j1 = jax.jit(s1_core, donate_argnums=0)
    j2 = jax.jit(topk.blend_counts)
    j3 = jax.jit(topk.sort_pairs)
    j4 = jax.jit(lambda k, c: topk.select_ring(k, c, cfg.ring_size))

    def staged_update(state: FlowSuiteState, cols, mask) -> FlowSuiteState:
        mid, ak, est = j1(state, cols, mask)
        try:
            k, c = j3(ak, j2(ak, est))
            ring = j4(k, c)
        except Exception:
            # j1 already donated the old state; mid is the only valid
            # state left. Skip this batch's ring admission (standing
            # candidates rescore from the full sketch next batch) rather
            # than leaving the caller holding deleted buffers. The
            # counter makes the skip observable in deepflow_system (the
            # tpu_sketch exporter surfaces it), not just in logs.
            staged_update.admission_failures += 1
            logging.getLogger(__name__).exception(
                "staged ring admission failed; batch skipped")
            return mid
        return mid._replace(ring=ring)

    staged_update.admission_failures = 0
    return staged_update


def flush(state: FlowSuiteState, cfg: FlowSuiteConfig
          ) -> Tuple[FlowSuiteState, FlowWindowOutput]:
    """Read window outputs, then reset window-scoped state."""
    keys, counts = topk.result(state.ring, cfg.top_k)
    out = FlowWindowOutput(
        topk_keys=keys,
        topk_counts=counts,
        service_cardinality=hll.estimate(state.services),
        entropies=entropy.entropies(state.ent),
        rows=state.rows_seen,
    )
    fresh = FlowSuiteState(
        sketch=cms.reset(state.sketch),
        ring=topk.reset(state.ring),
        services=hll.reset(state.services),
        ent=entropy.reset(state.ent),
        rows_seen=jnp.zeros((), jnp.int32),
        batches_seen=jnp.zeros((), jnp.int32),
    )
    return fresh, out


def merge(a: FlowSuiteState, b: FlowSuiteState, cfg: FlowSuiteConfig) -> FlowSuiteState:
    """Merge two window states (e.g. per-chip partials). All components are
    mergeable: CMS add, HLL max, histogram add, ring re-top-k."""
    sketch = cms.merge(a.sketch, b.sketch)
    all_keys = jnp.concatenate([a.ring.keys, b.ring.keys])
    all_counts = jnp.concatenate([a.ring.counts, b.ring.counts])
    k, c = topk._dedup_keep_max(all_keys, all_counts)
    top_c, top_i = jax.lax.top_k(c, a.ring.keys.shape[0])
    ring = topk.TopKState(keys=k[top_i], counts=top_c)
    return FlowSuiteState(
        sketch=sketch,
        ring=ring,
        services=hll.merge(a.services, b.services),
        ent=entropy.merge(a.ent, b.ent),
        rows_seen=a.rows_seen + b.rows_seen,
        batches_seen=a.batches_seen + b.batches_seen,
    )
