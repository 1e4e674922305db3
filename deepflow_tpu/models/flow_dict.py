"""Dictionary lane: SmartEncoding applied to the host->device wire.

The reference's SmartEncoding insight (server/ingester flow_tag /
`docs/deepflow_sigcomm2023.pdf` §5.2: strings become dictionary
integers once, rows carry the small code) applied to THIS framework's
host->device link (SURVEY §7 "Hard parts"): flow-log traffic
re-reports the same live flows every window (per-minute ticks of
long-lived flows; Zipf-shaped record streams), so the 5-tuple most
records carry is redundant on the wire.

- A flow's first record crosses as a NEWS row: assigned dictionary
  index + the four packed-lane key words + its packet count
  (SKETCH_NEWS_SCHEMA, 24B).
- Every later record of that flow rides a PAIRS-PACKED hits plane:
  two records per three u32 words {idx_a, idx_b, pkts_a|pkts_b<<16}
  (SKETCH_HITS_SCHEMA) — 6B/record, one transfer per batch, vs the
  16B packed-lane row and the 68B full row. Packet counts saturate
  at 65535 on this wire; entropy (the only sketch that reads them)
  saturates per-record weights there on BOTH its update paths, so
  sketch state stays bit-identical to the packed lane regardless.

The device keeps the key table resident — (4, capacity) uint32, the
TagDict role with the table living in HBM — scatters news rows into
it, and gathers hit rows back into exactly the lane columns
`flow_suite.unpack_lanes` consumes, so CMS / HLL / entropy / row
counts are BIT-IDENTICAL to the packed-lane path (the top-K ring sees
the same flows through a different batch partition, so its stride
sample admits different candidates — same class of difference as
`topk_sample_log2` itself; recall is pinned by test instead of state
equality). Batches apply strictly in emission order, which is what
makes index reuse after eviction safe (FlowDictPacker's docstrings
carry the argument).

Steady state ships pure hit batches: separate `update_news` /
`update_hits` programs mean a quiet stream pays ZERO news bytes
rather than a padded news plane per batch.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deepflow_tpu.models import flow_suite
from deepflow_tpu.models.flow_suite import (FlowSuiteConfig,
                                            FlowSuiteState, unpack_lanes)
from deepflow_tpu.utils.twinmark import host_twin_of

# ONE saturation point for the whole dict wire (news and hits): u16,
# the pairs-plane field width. The packed lane's 24-bit cap is wider,
# but pkts' only sketch consumer (entropy's bf16 weight planes)
# saturates at 65535 on the MXU path anyway — capping news the same
# as hits keeps a flow's first record and its repeats on identical
# semantics (SKETCH_HITS_SCHEMA's comment carries the full argument)
PKTS_CAP = 0xFFFF


class FlowDictState(NamedTuple):
    """Device-resident flow-key dictionary: row i of `table` holds the
    four packed-lane key words (ip_src, ip_dst, ports, proto<<24) of
    the flow the host assigned index i."""

    table: jnp.ndarray       # (4, capacity) uint32


def init_dict(capacity: int = 1 << 20) -> FlowDictState:
    return FlowDictState(table=jnp.zeros((4, capacity), jnp.uint32))


def update_news(state: FlowSuiteState, dstate: FlowDictState,
                plane: jnp.ndarray, n: jnp.ndarray,
                cfg: FlowSuiteConfig,
                count_mask: jnp.ndarray = None
                ) -> Tuple[FlowSuiteState, FlowDictState]:
    """Apply one (6, C) news plane: scatter the C key rows into the
    table AND count the records themselves (a news row IS that flow's
    first record, packets included — it must not be counted again).
    Rows >= n are padding: their scatter is routed out of bounds and
    dropped, their count masked.

    `count_mask` (sharded path) narrows which rows THIS caller counts
    while every valid row is still scattered: news planes replicate
    across a mesh so every table replica stays identical, but each
    record must land in exactly one shard's sketches."""
    cap = dstate.table.shape[1]
    idx = plane[0].astype(jnp.int32)
    mask = jnp.arange(plane.shape[1]) < n
    safe = jnp.where(mask, idx, cap)             # OOB -> dropped
    # plane row 4 is the raw proto byte; the table stores the lane
    # word proto<<24 so hit gathers rebuild proto_pkts with one OR
    proto_word = plane[4] << jnp.uint32(24)
    key_rows = jnp.concatenate([plane[1:4], proto_word[None]], axis=0)
    table = dstate.table.at[:, safe].set(key_rows, mode="drop")
    lanes = {
        "ip_src": plane[1],
        "ip_dst": plane[2],
        "ports": plane[3],
        "proto_pkts": proto_word | plane[5],
    }
    hists = None
    if count_mask is None and flow_suite.use_fused_hists(cfg):
        # fused Pallas unpack+fold over the raw NEWS plane: the kernel's
        # arange<n validity IS this path's count_mask, so the fused form
        # only applies when no sharding override narrows the count (the
        # sharded path keeps the unfused ops — its mask and the scatter
        # mask genuinely differ)
        from deepflow_tpu.ops import pallas_hist, pallas_sketch
        hists = pallas_sketch.fused_news_hists(
            plane, n, state.sketch.seeds, state.ent.seeds,
            cms_log2_width=cfg.cms_log2_width,
            ent_log2_buckets=cfg.entropy_log2_buckets,
            interpret=pallas_hist.INTERPRET)
    if count_mask is None:
        count_mask = mask
    state = flow_suite.update(state, unpack_lanes(lanes), count_mask, cfg,
                              hists=hists)
    return state, FlowDictState(table=table)


def unpack_hits(plane: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(3, H) pairs plane -> (idx, pkts) arrays of 2H records in the
    packer's original record order: the packer fills the a-lanes
    completely (records [0, min(n, H))) and spills into the b-lanes
    ([H, n)), so concatenation restores the stream with its valid
    records contiguous at [0, n) — the sketch state is bit-identical
    to an unpacked one-record-per-slot wire, ring admission
    included."""
    idx = jnp.concatenate([plane[0], plane[1]]).astype(jnp.int32)
    pkts = jnp.concatenate([plane[2] & jnp.uint32(0xFFFF),
                            plane[2] >> jnp.uint32(16)])
    return idx, pkts


def update_hits(state: FlowSuiteState, dstate: FlowDictState,
                plane: jnp.ndarray, n: jnp.ndarray,
                cfg: FlowSuiteConfig,
                mask: jnp.ndarray = None) -> FlowSuiteState:
    """Apply one (3, H) pairs-packed hits plane (2H records): gather
    each record's key words from the table and advance the sketches
    exactly as the packed-lane path would for the same records.
    `mask` (sharded path) overrides the default arange<n validity
    when the plane is a shard of a larger batch and n indexes the
    GLOBAL row space."""
    idx, pkts = unpack_hits(plane)
    fused = mask is None and flow_suite.use_fused_hists(cfg)
    if mask is None:
        mask = jnp.arange(2 * plane.shape[1]) < n
    rows = dstate.table[:, idx]                  # (4, 2H) gather
    lanes = {
        "ip_src": rows[0],
        "ip_dst": rows[1],
        "ports": rows[2],
        "proto_pkts": rows[3] | pkts,
    }
    hists = None
    if fused:
        # hits need no kernel of their own: the table gather is an XLA
        # op either way, and the gathered rows ARE a (4, 2H) lane plane
        # — stack them and ride the lane kernel (table word rows[3] is
        # proto<<24 with zero low bits, so | pkts rebuilds proto_pkts
        # exactly as the packed-lane wire would carry it)
        from deepflow_tpu.ops import pallas_hist, pallas_sketch
        lane_plane = jnp.stack([rows[0], rows[1], rows[2],
                                rows[3] | pkts])
        hists = pallas_sketch.fused_lane_hists(
            lane_plane, n, state.sketch.seeds, state.ent.seeds,
            cms_log2_width=cfg.cms_log2_width,
            ent_log2_buckets=cfg.entropy_log2_buckets,
            interpret=pallas_hist.INTERPRET)
    return flow_suite.update(state, unpack_lanes(lanes), mask, cfg,
                             hists=hists)


# plane rows per wire kind (the only two shapes the wire carries)
_KIND_ROWS = {"news": 6, "hits": 3}


def wire_signature(wire) -> Tuple[Tuple[str, int], ...]:
    """Static shape signature of one emitted wire sequence: a tuple of
    (kind, plane_width). The signature fully determines the fused
    program `make_wire_update` builds, so the runtime can cache one
    jitted program per signature — the packer's power-of-two width
    buckets (`_bucket`) keep the signature space small."""
    return tuple((kind, plane.shape[1]) for kind, plane, _ in wire)


def wire_words(sig: Tuple[Tuple[str, int], ...]) -> int:
    """uint32 words one coalesced staging buffer needs for `sig`:
    one n-header word per plane, then the planes raveled in order."""
    return len(sig) + sum(_KIND_ROWS[kind] * w for kind, w in sig)


def stage_wire(wire, flat: np.ndarray) -> None:
    """Host-pack one emitted wire sequence into a flat uint32 staging
    buffer (layout: [n_0..n_{P-1} | plane_0.ravel() | ...]) — the
    single-transfer form `make_wire_update` consumes. Emission order is
    preserved exactly (the consumer rule the packer's docstring
    carries)."""
    P = len(wire)
    off = P
    for i, (_, plane, n) in enumerate(wire):
        flat[i] = n
        flat[off:off + plane.size] = plane.ravel()
        off += plane.size


def mirror_news_np(wire, table: np.ndarray) -> None:
    """Scatter one wire emission's NEWS keys into a HOST mirror of the
    device table ((4, capacity) uint32, same lane-word layout:
    proto<<24 in row 3). The dict stager calls this at stage time for
    EVERY emitted group — device-bound or not — so when degraded mode
    must absorb staged hits on the host (`unpack_wire_np`), the mirror
    holds every index announced so far. Eager stage-time scatter means
    an index evicted and REUSED by a later already-staged group can
    show its new tenant to an older in-flight hit absorbed after
    degradation — a bounded approximation confined to the degraded
    fallback plane, which is itself a 1/host_stride sample (the device
    path is exact: its table applies strictly in emission order)."""
    u = np.uint32
    for kind, plane, n in wire:
        if kind != "news":
            continue
        idx = plane[0, :n].astype(np.int64)
        table[0, idx] = plane[1, :n]
        table[1, idx] = plane[2, :n]
        table[2, idx] = plane[3, :n]
        table[3, idx] = plane[4, :n] << u(24)


@host_twin_of("deepflow_tpu/models/flow_dict.py:make_wire_update")
def unpack_wire_np(flat: np.ndarray, sig: Tuple[Tuple[str, int], ...],
                   table: np.ndarray):
    """Host twin of the staged wire program: decode one coalesced flat
    buffer back into the per-plane column dicts `flow_suite.update`
    consumes, trimmed to each plane's n valid records — what degraded
    mode feeds the host-numpy fallback sketch when a staged dict group
    must be absorbed after the device is lost. `table` is the host key
    mirror `mirror_news_np` maintains; hits gather their 5-tuples from
    it exactly as `update_hits` gathers from the device table. Returns
    [(cols, n)] in emission order."""
    u = np.uint32
    out = []
    off = len(sig)
    for i, (kind, w) in enumerate(sig):
        n = int(flat[i])
        r = _KIND_ROWS[kind]
        plane = flat[off:off + r * w].reshape(r, w)
        off += r * w
        if kind == "news":
            cols = {
                "ip_src": plane[1, :n],
                "ip_dst": plane[2, :n],
                "port_src": plane[3, :n] >> u(16),
                "port_dst": plane[3, :n] & u(0xFFFF),
                "proto": plane[4, :n] & u(0xFF),
                "packet_tx": plane[5, :n],
                "packet_rx": np.zeros(n, u),
            }
        else:
            # a-lanes then b-lane spill: valid records contiguous at
            # [0, n) after the concat, exactly like unpack_hits
            idx = np.concatenate([plane[0], plane[1]])[:n].astype(np.int64)
            pkts = np.concatenate([plane[2] & u(0xFFFF),
                                   plane[2] >> u(16)])[:n]
            rows = table[:, idx]
            cols = {
                "ip_src": rows[0],
                "ip_dst": rows[1],
                "port_src": rows[2] >> u(16),
                "port_dst": rows[2] & u(0xFFFF),
                "proto": rows[3] >> u(24),
                "packet_tx": pkts,
                "packet_rx": np.zeros(n, u),
            }
        out.append((cols, n))
    return out


def make_wire_update(cfg: FlowSuiteConfig,
                     sig: Tuple[Tuple[str, int], ...]):
    """One jitted program applying a whole staged wire sequence — every
    news/hits plane of one (possibly multi-batch) group — from a single
    coalesced transfer, in emission order. The per-plane math is
    exactly `update_news`/`update_hits`, so sketch state is
    bit-identical to the per-plane dispatch path; what changes is the
    boundary: one device_put and one dispatch per group instead of one
    of each per plane. Returns fn(state, dstate, flat) ->
    (state, dstate, fence); state and dstate are donated (a pure-hits
    program returns dstate through input-output aliasing), `fence` is a
    small fresh scalar safe to block on after the donation."""
    sig = tuple(sig)

    def prog(state: FlowSuiteState, dstate: FlowDictState,
             flat: jnp.ndarray):
        rows = jnp.uint32(0)
        off = len(sig)
        for i, (kind, w) in enumerate(sig):
            n = flat[i]
            nwords = _KIND_ROWS[kind] * w
            plane = flat[off:off + nwords].reshape(_KIND_ROWS[kind], w)
            off += nwords
            if kind == "news":
                state, dstate = update_news(state, dstate, plane, n, cfg)
            else:
                state = update_hits(state, dstate, plane, n, cfg)
            rows = rows + n
        return state, dstate, rows

    import jax
    return jax.jit(prog, donate_argnums=(0, 1))


class FlowDictPacker:
    """Host side: streaming records -> ordered news/hits wire batches.

    Correctness rests on ONE consumer rule (and `apply_batches`
    encodes it): batches apply strictly in emission order. Within one
    `pack()` call, the call's OWN hit rows are buffered/emitted only
    after its news batches (a hit may reference an index its own
    call's news assigned) — but hits PRE-DRAINED from earlier calls
    (the eviction-safety flush below) may legitimately precede this
    call's news in the emitted stream, so grouping batches by kind
    instead of preserving emission order is incorrect.

    Index reuse after eviction is made safe by the PRE-DRAIN in
    pack(): eviction can only happen once the dictionary is full,
    pack() flushes every buffered hit row before resolving keys
    whenever this call could fill it, and the current call's hit rows
    are appended only after every key has resolved — so at any
    eviction, no emitted-or-buffered hit row references the freed
    index, and the index's next tenant is scattered (its news batch)
    before any hit row referencing the reused index can exist.
    `_assign` enforces the invariant rather than trusting it.

    The packer is windowless: it never needs flushing on window
    boundaries because sketch windows close on the DEVICE (flush
    reads+resets sketch state, the table persists across windows —
    a flow's dictionary row outlives any one window, exactly like a
    TagDict entry outliving one segment)."""

    def __init__(self, capacity: int = 1 << 20,
                 hits_batch: int = 1 << 17, news_batch: int = 1 << 13):
        if capacity <= hits_batch:
            # the eviction-safety argument (_assign) needs an LRU head
            # that the current call has not touched; a dictionary
            # smaller than one wire batch cannot guarantee it
            raise ValueError("capacity must exceed hits_batch")
        if hits_batch % 2:
            raise ValueError("hits_batch must be even (pairs planes)")
        self.capacity = capacity
        self.hits_batch = hits_batch
        self.news_batch = news_batch
        self._idx: "OrderedDict[bytes, int]" = OrderedDict()  # LRU
        self._free = list(range(capacity - 1, -1, -1))        # pop() asc
        self._hit_idx: List[np.ndarray] = []     # buffered hit rows
        self._hit_pkts: List[np.ndarray] = []
        self._hit_count = 0
        self.evictions = 0
        self.bytes_news = 0
        self.bytes_hits = 0

    # -- wire accounting ----------------------------------------------------

    @staticmethod
    def _bucket(n: int, full: int) -> int:
        """Plane width for n live rows: the smallest power-of-two
        bucket >= n (floor 256), capped at the full batch width. A
        partial batch padded all the way to `full` would make a
        TRICKLE of new flows cost a full plane per pack() call on the
        wire — a steady few news/batch must stay a few hundred bytes,
        not erase the hit lane's savings (review r5). Buckets bound
        the distinct plane shapes (and so the consumer's jit
        specializations) to log2(full/256) + 1 per kind."""
        b = 256
        while b < n:
            b <<= 1
        return min(b, full)

    def _emit_news(self, out: List[Tuple[str, np.ndarray, int]],
                   idx: np.ndarray, keys: np.ndarray,
                   pkts: np.ndarray) -> None:
        """Emit (6, bucket) planes; partial batches flush eagerly —
        news must never sit buffered past the call whose hits may
        reference them."""
        C = self.news_batch
        for s in range(0, len(idx), C):
            e = min(s + C, len(idx))
            plane = np.zeros((6, self._bucket(e - s, C)), np.uint32)
            plane[0, :e - s] = idx[s:e]
            plane[1:5, :e - s] = keys[s:e].T
            plane[5, :e - s] = pkts[s:e]
            out.append(("news", plane, e - s))
            self.bytes_news += plane.nbytes
        # note: keys arrive as the four lane words with row 4 the RAW
        # proto byte (update_news shifts it into the table word)

    def _flush_hits(self, out: List[Tuple[str, np.ndarray, int]],
                    partial: bool = False) -> None:
        """Emit (3, H) PAIRS planes: the a-lanes fill COMPLETELY (records
        [0, min(count, H))), the b-lanes take the spill ([H, count)) —
        the device concat then holds its valid records at positions
        [0, count) exactly, so the standard arange<n mask covers
        partial planes too. pkts were saturated at PKTS_CAP when
        buffered (pack())."""
        B = self.hits_batch
        if not self._hit_count:
            return
        idx = np.concatenate(self._hit_idx)
        pkts = np.concatenate(self._hit_pkts)    # PKTS_CAP'd in pack()
        end = len(idx) if partial else (len(idx) // B) * B
        for s in range(0, end, B):
            e = min(s + B, end)
            cnt = e - s
            H = self._bucket((cnt + 1) // 2, B // 2)
            k = min(cnt, H)
            plane = np.zeros((3, H), np.uint32)
            plane[0, :k] = idx[s:s + k]
            plane[2, :k] = pkts[s:s + k]
            if cnt > H:
                m = cnt - H
                plane[1, :m] = idx[s + H:e]
                plane[2, :m] |= pkts[s + H:e] << np.uint32(16)
            out.append(("hits", plane, cnt))
            self.bytes_hits += plane.nbytes
        rest_i, rest_p = idx[end:], pkts[end:]
        self._hit_idx = [rest_i] if len(rest_i) else []
        self._hit_pkts = [rest_p] if len(rest_p) else []
        self._hit_count = len(rest_i)

    # -- packing ------------------------------------------------------------

    def _assign(self, key: bytes) -> int:
        """Index for a NEW key, evicting LRU when full.

        Eviction is only reached with the hit buffer empty (pack()'s
        pre-drain — enforced here, since reusing an index a buffered
        hit still references would gather the new tenant's key), and
        pops the LRU head, which is always a key NOT touched by the
        current call (touched keys re-order to the tail as they
        resolve; the `len(uniq) < capacity` guard in pack() means an
        untouched one exists)."""
        if not self._free:
            if self._hit_count:
                raise RuntimeError(
                    "flow dict eviction with hits buffered: pack() "
                    "must pre-drain first (bug, not load)")
            _, old_idx = self._idx.popitem(last=False)
            self.evictions += 1
            self._free.append(old_idx)
        idx = self._free.pop()
        self._idx[key] = idx
        return idx

    def pack(self, cols: Dict[str, np.ndarray]
             ) -> List[Tuple[str, np.ndarray, int]]:
        """One record batch -> ordered wire batches [(kind, plane, n)].
        `cols` is the same column dict `flow_suite.pack_lanes` takes."""
        out: List[Tuple[str, np.ndarray, int]] = []
        u32 = np.uint32
        n = len(cols["ip_src"])
        if n == 0:
            return out
        pkts = np.minimum(cols["packet_tx"].astype(np.uint64)
                          + cols["packet_rx"], PKTS_CAP).astype(u32)
        keys = np.empty((n, 4), u32)
        keys[:, 0] = cols["ip_src"]
        keys[:, 1] = cols["ip_dst"]
        keys[:, 2] = ((cols["port_src"].astype(u32) & u32(0xFFFF))
                      << u32(16)) | (cols["port_dst"].astype(u32)
                                     & u32(0xFFFF))
        keys[:, 3] = cols["proto"].astype(u32) & u32(0xFF)   # raw byte
        kbytes = np.ascontiguousarray(keys).view("V16").ravel()  # (n,)
        uniq, first, inverse = np.unique(
            kbytes, return_index=True, return_inverse=True)
        if len(uniq) >= self.capacity:
            # with fewer uniques than capacity, a full dict always
            # holds >= 1 key untouched by this call, so the LRU head
            # _assign evicts can never be a key whose index this
            # call's already-computed hit rows reference
            raise ValueError(
                f"{len(uniq)} unique flows in one pack() call >= "
                f"dictionary capacity {self.capacity}")
        # resolve each UNIQUE key once (python cost scales with new
        # flows, not records); LRU order refreshed per appearance
        uidx = np.empty(len(uniq), u32)
        is_new = np.zeros(len(uniq), bool)
        if len(self._idx) + len(uniq) > self.capacity and self._hit_count:
            # eviction is possible this call: drain buffered hits
            # FIRST so an old reference can never gather a reused
            # index's new tenant (conservative — len(uniq) bounds the
            # truly-new count from above)
            self._flush_hits(out, partial=True)
        for i, kb in enumerate(uniq):
            k = bytes(kb)
            got = self._idx.get(k)
            if got is None:
                is_new[i] = True
                uidx[i] = self._assign(k)
            else:
                self._idx.move_to_end(k)
                uidx[i] = got
        rec_idx = uidx[inverse]
        # news rows = the FIRST occurrence of each new unique key; all
        # other records are hits (including later same-batch records
        # of a new key — their news is emitted first, below)
        news_rows = first[is_new]
        self._emit_news(out, rec_idx[news_rows], keys[news_rows],
                        pkts[news_rows])
        hit_mask = np.ones(n, bool)
        hit_mask[news_rows] = False
        self._hit_idx.append(rec_idx[hit_mask])
        self._hit_pkts.append(pkts[hit_mask])
        self._hit_count += int(hit_mask.sum())
        self._flush_hits(out)                    # full batches only
        return out

    def flush(self) -> List[Tuple[str, np.ndarray, int]]:
        """Drain the partial hit buffer (end of stream / forced tick)."""
        out: List[Tuple[str, np.ndarray, int]] = []
        self._flush_hits(out, partial=True)
        return out


def apply_batches(state: FlowSuiteState, dstate: FlowDictState,
                  batches, cfg: FlowSuiteConfig, *,
                  news_fn=None, hits_fn=None
                  ) -> Tuple[FlowSuiteState, FlowDictState]:
    """Reference consumer: apply packer output in emission order.
    `news_fn`/`hits_fn` default to the unjitted updates; the bench and
    runtime pass jitted (donated) versions."""
    news_fn = news_fn or (lambda s, d, p, n: update_news(s, d, p, n, cfg))
    hits_fn = hits_fn or (lambda s, d, p, n: update_hits(s, d, p, n, cfg))
    for kind, plane, n in batches:
        nn = np.uint32(n)
        if kind == "news":
            state, dstate = news_fn(state, dstate, jnp.asarray(plane), nn)
        else:
            state = hits_fn(state, dstate, jnp.asarray(plane), nn)
    return state, dstate
