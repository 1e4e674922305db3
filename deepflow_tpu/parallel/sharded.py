"""Multi-chip suites: batch-sharded updates, collective window merges.

State carries a leading device axis sharded over the mesh's `data` axis; each
chip updates its own sketch shard from its batch shard inside `shard_map`
(zero cross-chip traffic on the hot path). At window flush the partial
sketches merge — CMS/histograms by add, HLL by max, rings by re-top-k — in
one jitted program whose collectives XLA lays onto ICI. This is the
TPU-physical form of the reference's per-thread stash merge
(agent/src/collector/quadruple_generator.rs SubQuadGen) and the design
SURVEY.md §7 Phase 4 calls for.

Three suites share the pattern (scaffolding in _ShardedSuiteBase):

- ShardedFlowSuite — the l4 sketch suite (CMS top-K / HLL / entropy),
  comm-free updates, merge-at-flush.
- ShardedAppSuite — per-service RED + DDSketch quantiles; every state
  field merges by add, so flush is one whole-state psum.
- ShardedMetricsSuite — the flow_metrics anomaly suite (BASELINE.md
  config 5): entropy histograms shard like the sketches, while the
  streaming-PCA basis stays REPLICATED — each chip computes the Oja
  gradient of its batch shard and one ICI `psum` merges (count, sums,
  gradient) before the identical basis update runs everywhere.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepflow_tpu.models import flow_suite, metrics_suite
from deepflow_tpu.models.flow_suite import (
    FlowSuiteConfig,
    FlowSuiteState,
    FlowWindowOutput,
)
from deepflow_tpu.models.metrics_suite import (
    MetricsSuiteConfig,
    MetricsSuiteState,
    MetricsWindowOutput,
)
from deepflow_tpu.ops import cms, entropy, hll, pca, topk


def _replicate_init(single, n_devices: int, sharding: NamedSharding):
    """Broadcast a single-device state pytree onto the device axis."""
    return jax.device_put(
        jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_devices,) + x.shape),
            single),
        sharding)


def _put_sharded(cols: Dict, mask, sharding: NamedSharding):
    """Host->device transfer of a batch, sharded along the data axis."""
    cols_d = {k: jax.device_put(v, sharding) for k, v in cols.items()}
    return cols_d, jax.device_put(mask, sharding)


def _merge_axis0(state: FlowSuiteState) -> FlowSuiteState:
    """Merge per-device partial states stacked on axis 0 into one."""
    ring_keys = state.ring.keys.reshape(-1)
    ring_counts = state.ring.counts.reshape(-1)
    k, c = topk._dedup_keep_max(ring_keys, ring_counts)
    ring_size = state.ring.keys.shape[1]
    top_c, top_i = jax.lax.top_k(c, ring_size)
    return FlowSuiteState(
        sketch=cms.CMSState(counts=jnp.sum(state.sketch.counts, axis=0),
                            seeds=state.sketch.seeds[0]),
        ring=topk.TopKState(keys=k[top_i], counts=top_c),
        services=hll.HLLState(registers=jnp.max(state.services.registers, axis=0)),
        ent=entropy.EntropyState(hist=jnp.sum(state.ent.hist, axis=0),
                                 seeds=state.ent.seeds[0]),
        rows_seen=jnp.sum(state.rows_seen, axis=0),
        batches_seen=jnp.sum(state.batches_seen, axis=0),
    )


def rescore_ring(merged: FlowSuiteState) -> FlowSuiteState:
    """Re-score merged ring candidates against the globally-merged
    sketch (per-shard estimates only saw 1/n of the stream) — the
    shared post-merge step of the mesh flush AND the pod epoch merge
    (parallel/pod.py), factored out so the two lanes cannot drift.
    (compare-free sentinel mask: see topk._not_sentinel)"""
    est = cms.query(merged.sketch, merged.ring.keys).astype(jnp.int32)
    live = topk._not_sentinel(merged.ring.keys)
    return merged._replace(
        ring=merged.ring._replace(counts=live * (est + 1) - 1))


class _ShardedSuiteBase:
    """Mesh/spec/plumbing shared by the three sharded suites: state
    carries a leading device axis over `axis`, batches shard over the
    same axis, updates run comm-free per shard inside shard_map.
    Subclasses build self._update / self._flush in __init__ (their
    merge topologies differ) via self._shard()."""

    def __init__(self, cfg, mesh: Mesh, axis: str,
                 init_single: Callable) -> None:
        from deepflow_tpu.runtime.tracing import default_tracer

        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.n_devices = mesh.shape[axis]
        self._dev_spec = P(axis)
        self._state_sharding = NamedSharding(mesh, self._dev_spec)
        self._batch_sharding = NamedSharding(mesh, P(axis))
        self._init_single = init_single
        self._state_specs = jax.tree.map(lambda _: self._dev_spec,
                                         init_single())
        # flight recorder: sharded suites attribute mesh h2d and update
        # dispatch like the single-chip exporter (runtime/tracing.py).
        # h2d attribution blocks on the placed batch — the only way to
        # separate transfer from compute — so it is SAMPLED (every
        # _attrib_every-th traced put); dispatch spans never block, so
        # the async pipeline shape is preserved on traced batches.
        self._tracer = default_tracer()
        self._suite = type(self).__name__
        self._attrib_every = 16
        self._puts_traced = 0
        # accuracy observatory hook (runtime/audit.py): an attached
        # ShadowAuditor mirrors host batches before transfer and is
        # closed against the MERGED window output at flush — so the
        # future pod-merged sketch path (ROADMAP item 1) inherits the
        # same exact-shadow audit the single-chip exporter runs, with
        # per-shard sampled-row attribution (construct the auditor with
        # shards=n_devices).
        self._auditor = None
        from deepflow_tpu.runtime.profiler import default_profiler
        self._prof = default_profiler()

    def attach_auditor(self, auditor) -> None:
        """Attach a ShadowAuditor; host-side only (device-placed
        batches are skipped, counted in audit_device_skipped)."""
        self._auditor = auditor
        self.audit_device_skipped = 0

    def _shard(self, fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=self.mesh,
                                     in_specs=in_specs, out_specs=out_specs,
                                     check_vma=False))

    def init(self):
        return _replicate_init(self._init_single(), self.n_devices,
                               self._state_sharding)

    def put_batch(self, cols: Dict, mask) -> Tuple[Dict, jnp.ndarray]:
        if self._auditor is not None:
            import numpy as _np
            needed = ("ip_src", "ip_dst", "port_src", "port_dst",
                      "proto", "packet_tx", "packet_rx")
            # host-side only: a batch already living on device would
            # cost a D2H fetch to mirror — skipped and counted instead
            # of silently bending the host-only audit rule
            if all(isinstance(cols.get(k), _np.ndarray) for k in needed) \
                    and isinstance(mask, _np.ndarray):
                # the device excludes masked (padding) rows; so must
                # the shadow, or the exact counts drift per batch and
                # the alarm fires on its own bookkeeping
                m = mask.astype(bool, copy=False)
                if m.all():
                    self._auditor.absorb({k: cols[k] for k in needed})
                else:
                    self._auditor.absorb({k: cols[k][m] for k in needed})
            else:
                self.audit_device_skipped += 1
        tr = self._tracer
        if not tr.enabled:
            return _put_sharded(cols, mask, self._batch_sharding)
        detailed = self._puts_traced % self._attrib_every == 0
        self._puts_traced += 1
        if not detailed:
            return _put_sharded(cols, mask, self._batch_sharding)
        import time
        nbytes = sum(getattr(v, "nbytes", 0) for v in cols.values())
        t0 = time.perf_counter()
        out = _put_sharded(cols, mask, self._batch_sharding)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        tr.observe("shard.h2d", dt, stream=self._suite)
        if dt > 0 and nbytes:
            tr.gauge("mesh_h2d_mb_s", nbytes / 1e6 / dt)
        return out

    def update(self, state, cols: Dict, mask):
        tr = self._tracer
        if not tr.enabled:
            return self._update(state, cols, mask)
        import time as _time
        t0 = _time.perf_counter()
        with tr.span("shard.update", stream=self._suite):
            out = self._update(state, cols, mask)
        self._prof.record("dispatch", f"shard:{self._suite}",
                          _time.perf_counter() - t0)
        return out

    def flush(self, state):
        tr = self._tracer
        if not tr.enabled:
            res = self._flush(state)
        else:
            with tr.span("shard.flush", stream=self._suite):
                res = self._flush(state)
        if self._auditor is not None and isinstance(res, tuple) \
                and len(res) == 2 and hasattr(res[1], "topk_keys"):
            # merged window output vs the exact shadow — the audit the
            # merged-sketch path inherits (close_window materializes
            # the output leaves, its sanctioned sync)
            self._auditor.close_window(res[1])
        return res


class ShardedFlowSuite(_ShardedSuiteBase):
    """FlowSuite sharded over a mesh's `data` axis.

    update(state, cols, mask): cols/mask are [B] arrays, B % n_devices == 0;
    each device consumes its shard. flush(state): merged window output +
    fresh state.
    """

    def __init__(self, cfg: FlowSuiteConfig, mesh: Mesh,
                 axis: str = "data") -> None:
        super().__init__(cfg, mesh, axis, lambda: flow_suite.init(cfg))
        state_specs = self._state_specs
        cfg_ = cfg

        def local_update(state, cols, mask):
            local = jax.tree.map(lambda x: x[0], state)
            local = flow_suite.update(local, cols, mask, cfg_)
            return jax.tree.map(lambda x: x[None], local)

        self._update = self._shard(local_update,
                                   (state_specs, P(axis), P(axis)),
                                   state_specs)

        def local_update_plane(state, plane, mask):
            # the single-transfer full-row form (wire/columnar_wire
            # decode_columnar_plane): plane is (n_cols, B) sharded on
            # its BATCH axis; unpack happens per-shard on device
            local = jax.tree.map(lambda x: x[0], state)
            local = flow_suite.update_plane(local, plane, mask, cfg_)
            return jax.tree.map(lambda x: x[None], local)

        self._update_plane = self._shard(
            local_update_plane,
            (state_specs, P(None, axis), P(axis)), state_specs)
        self._plane_sharding = NamedSharding(mesh, P(None, axis))

        def local_update_lanes(state, plane, n):
            # the coalesced packed-lane form (ISSUE 5): plane is the
            # (4, B) lane matrix sharded on its BATCH axis, n the
            # GLOBAL valid-row count — ONE transfer per device and the
            # mask recovered on device from each shard's global
            # positions, mirroring the single-chip feed's staging
            # discipline (runtime/feed.py)
            local = jax.tree.map(lambda x: x[0], state)
            d = jax.lax.axis_index(axis)
            b = plane.shape[1]                 # per-shard width
            mask = (jnp.arange(b) + d * b) < n
            lanes = {"ip_src": plane[0], "ip_dst": plane[1],
                     "ports": plane[2], "proto_pkts": plane[3]}
            local = flow_suite.update(
                local, flow_suite.unpack_lanes(lanes), mask, cfg_)
            return jax.tree.map(lambda x: x[None], local)

        self._update_lanes = self._shard(
            local_update_lanes,
            (state_specs, P(None, axis), P()), state_specs)

        # -- dictionary lane (models/flow_dict.py) on the mesh ------------
        # Key table REPLICATED (leading device axis, identical content):
        # news planes broadcast so every replica scatters the same rows,
        # with each record COUNTED by exactly one shard (interleaved
        # count_mask); hits planes shard on the batch axis and gather
        # from the local replica — comm-free, like the column update.
        from deepflow_tpu.models import flow_dict as _fd
        self._flow_dict = _fd
        nd = self.n_devices

        def local_update_news(state, dtable, plane, n):
            local = jax.tree.map(lambda x: x[0], state)
            table = _fd.FlowDictState(table=dtable[0])
            d = jax.lax.axis_index(axis)
            rows = jnp.arange(plane.shape[1])
            count = (rows < n) & (rows % nd == d)
            local, table = _fd.update_news(local, table, plane, n, cfg_,
                                           count_mask=count)
            return (jax.tree.map(lambda x: x[None], local),
                    table.table[None])

        self._update_news = self._shard(
            local_update_news,
            (state_specs, P(axis), P(None, None), P()),
            (state_specs, P(axis)))

        def local_update_hits(state, dtable, plane, n):
            # plane is the PAIRS layout (3, H) sharded on its pairs
            # axis: this shard's a-lanes hold global record positions
            # [d*hp, (d+1)*hp) and its b-lanes the same offsets past
            # the global a-half (H_global = hp * n_devices) — validity
            # is global-position < n
            local = jax.tree.map(lambda x: x[0], state)
            table = _fd.FlowDictState(table=dtable[0])
            d = jax.lax.axis_index(axis)
            hp = plane.shape[1]               # per-shard pairs width
            pos_a = jnp.arange(hp) + d * hp
            gmask = jnp.concatenate([pos_a, pos_a + hp * nd]) < n
            local = _fd.update_hits(local, table, plane, n, cfg_,
                                    mask=gmask)
            return jax.tree.map(lambda x: x[None], local)

        self._update_hits = self._shard(
            local_update_hits,
            (state_specs, P(axis), P(None, axis), P()), state_specs)

        def flush_fn(state):
            merged = rescore_ring(_merge_axis0(state))
            fresh, out = flow_suite.flush(merged, cfg_)
            fresh_d = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (self.n_devices,) + x.shape),
                fresh)
            return fresh_d, out

        self._flush = jax.jit(flush_fn, out_shardings=(
            jax.tree.map(lambda _: self._state_sharding, state_specs), None))

    def put_plane(self, plane, mask):
        """Device-place one (n_cols, B) full-row plane + mask, batch
        axis sharded — ONE transfer per device instead of n_cols."""
        return (jax.device_put(plane, self._plane_sharding),
                jax.device_put(jnp.asarray(mask),
                               self._batch_sharding))

    def update_plane(self, state, plane, mask):
        return self._update_plane(state, plane, mask)

    def put_lanes(self, plane):
        """Device-place one (4, B) packed-lane plane, batch axis
        sharded — the mesh form of the coalesced single-transfer feed
        (no mask transfer: update_lanes rebuilds it on device from n)."""
        return jax.device_put(plane, self._plane_sharding)

    def update_lanes(self, state, plane, n):
        """Advance from a coalesced lane plane; n is the GLOBAL valid
        count (rows >= n are padding, masked per shard on device)."""
        return self._update_lanes(state, plane, jnp.uint32(n))

    # -- dictionary lane ---------------------------------------------------

    def init_dict(self, capacity: int = 1 << 20):
        """Replicated key table with the leading device axis (every
        replica identical — news broadcasts keep them so)."""
        return jax.device_put(
            jnp.zeros((self.n_devices, 4, capacity), jnp.uint32),
            self._state_sharding)

    def update_news(self, state, dtable, plane, n):
        """plane (6, C) REPLICATED; each record counted on one shard."""
        return self._update_news(state, dtable, plane, jnp.uint32(n))

    def update_hits(self, state, dtable, plane, n):
        """plane: the (3, H) PAIRS layout (flow_dict.SKETCH_HITS_SCHEMA
        — idx_a/idx_b/pkts_ab rows, 2H records) sharded on its pairs
        axis; n is the GLOBAL valid-record count."""
        return self._update_hits(state, dtable, plane, jnp.uint32(n))


class ShardedAppSuite(_ShardedSuiteBase):
    """AppSuite (per-service RED + DDSketch quantiles) over a mesh.

    Every state field merges by ADD (request/error histograms, DDSketch
    buckets — ddsketch.merge is exact union), so the comm pattern is the
    simplest of the three suites: comm-free per-shard updates, one psum
    of the whole state at flush, identical window close everywhere."""

    def __init__(self, cfg, mesh: Mesh, axis: str = "data") -> None:
        from deepflow_tpu.models import app_suite

        super().__init__(cfg, mesh, axis, lambda: app_suite.init(cfg))
        state_specs = self._state_specs
        cfg_ = cfg

        def local_update(state, cols, mask):
            local = jax.tree.map(lambda x: x[0], state)
            new = app_suite.update(local, cols, mask, cfg_)
            return jax.tree.map(lambda x: x[None], new)

        self._update = self._shard(local_update,
                                   (state_specs, P(axis), P(axis)),
                                   state_specs)

        def local_flush(state):
            local = jax.tree.map(lambda x: x[0], state)
            merged = jax.tree.map(lambda x: jax.lax.psum(x, axis), local)
            fresh, out = app_suite.flush(merged, cfg_)
            return jax.tree.map(lambda x: x[None], fresh), out

        out_specs = (state_specs,
                     app_suite.AppWindowOutput(
                         requests=P(), errors=P(), error_ratio=P(),
                         rrt_quantiles=P(), rrt_hist=P(),
                         rrt_zeros=P()))
        self._flush = self._shard(local_flush, (state_specs,), out_specs)


class ShardedMetricsSuite(_ShardedSuiteBase):
    """MetricsSuite (DDoS entropy + golden-signal PCA) over a mesh.

    Entropy histograms shard per device and merge by `psum` at flush (they
    are integer adds, so sharded == single-device exactly). The PCA basis
    is replicated: `update` computes each chip's Oja gradient locally
    (pca.grad — the Zᵀ(ZW) matmul, MXU work), `psum`s the
    (count, Σx, Σx², gradient) tuple over ICI, and applies the identical
    globally-reduced step on every chip (pca.apply_grad) — the classic
    data-parallel optimizer shape, so the basis never diverges across
    devices (BASELINE.md config 5 "streaming PCA with ICI psum merge").
    """

    def __init__(self, cfg: MetricsSuiteConfig, mesh: Mesh,
                 axis: str = "data") -> None:
        super().__init__(cfg, mesh, axis, lambda: metrics_suite.init(cfg))
        state_specs = self._state_specs
        cfg_ = cfg

        def local_update(state, cols, mask):
            local = jax.tree.map(lambda x: x[0], state)
            # entropy: comm-free per-shard histogram adds (shared helper —
            # identical feature/weighting choices as the single-dev suite)
            ent = metrics_suite.entropy_update(local.ent, cols, mask)
            # PCA: local grad -> ICI psum -> replicated apply. With world
            # size 1 this IS pca.update, which is defined as the same
            # grad+apply composition.
            x = metrics_suite.signal_matrix(cols)
            cnt, s1, s2, g = pca.grad(local.pca, x, mask)
            cnt, s1, s2, g = jax.lax.psum((cnt, s1, s2, g), axis)
            p = pca.apply_grad(local.pca, cnt, s1, s2, g, lr=cfg_.pca_lr)
            # matrix-profile window sums accumulate per shard; the
            # flush-time psum merges them before the ring push
            ws = local.win_sum + metrics_suite.window_sum(cols, mask)
            new = local._replace(ent=ent, pca=p, win_sum=ws)
            return jax.tree.map(lambda x_: x_[None], new)

        self._update = self._shard(local_update,
                                   (state_specs, P(axis), P(axis)),
                                   state_specs)

        def local_flush(state, cols, mask):
            local = jax.tree.map(lambda x: x[0], state)
            # merge the entropy window across chips, then run the identical
            # window close everywhere (EWMA/z/alarm are scalar math on the
            # merged entropies, so every chip computes the same values)
            hist = jax.lax.psum(local.ent.hist, axis)
            ws = jax.lax.psum(local.win_sum, axis)
            merged = local._replace(ent=local.ent._replace(hist=hist),
                                    win_sum=ws)
            # flush pushes the MERGED window vector into the ring, so
            # the replicated rings stay identical on every chip
            fresh, out = metrics_suite.flush(merged, cols, mask, cfg_)
            return jax.tree.map(lambda x_: x_[None], fresh), out

        # anomaly scores stay sharded like the batch; the window scalars
        # are replicated (identical on every chip after the psum)
        out_specs = (state_specs,
                     MetricsWindowOutput(entropies=P(), z_scores=P(),
                                         ddos_alarm=P(),
                                         anomaly_scores=P(axis),
                                         mp_scores=P()))
        self._flush = self._shard(local_flush,
                                  (state_specs, P(axis), P(axis)),
                                  out_specs)

    # update() is the inherited traced wrapper; flush() differs in
    # arity (window close consumes the last batch's cols/mask)
    def flush(self, state: MetricsSuiteState, cols: Dict, mask
              ) -> Tuple[MetricsSuiteState, MetricsWindowOutput]:
        tr = self._tracer
        if not tr.enabled:
            return self._flush(state, cols, mask)
        with tr.span("shard.flush", stream=self._suite):
            return self._flush(state, cols, mask)
