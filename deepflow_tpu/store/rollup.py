"""Rollup manager: coarser-interval tables materialized on device.

Reference: server/ingester/datasource/handle.go builds ClickHouse
materialized views that collapse 1s tables into 1m/1h rows with Sum/Max/Min
aggregate functions. The TPU-native re-design runs the same collapse as a
JAX program: rows are bucketed by (key columns, floor(time/interval)) with
exact group ids computed on the host (np.unique over packed keys — cheap,
and collision-free unlike a folded hash), then every metric column is
segment-reduced in one jitted XLA program at padded static shapes. At
hot-table batch sizes on a real accelerator, group_reduce auto-switches
to the all-device path (_device_group_reduce: one sort + arithmetic
boundary detect + cumsum ids + segment reductions in one program) so no
host lexsort sits in front of the reduction.
"""

from __future__ import annotations

import functools
import os
import shutil
import threading
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deepflow_tpu.store.db import Store, Table
from deepflow_tpu.store.table import AggKind, TableSchema

_I64_MIN = np.int64(np.iinfo(np.int64).min)
_I64_MAX = np.int64(np.iinfo(np.int64).max)


# rollup_schema ttl sentinel (identity object: no integer the debug
# socket could pass collides with it): derive 30x the base retention
TTL_DERIVE = object()

# -- external datasources (ISSUE 7) ----------------------------------------
# Virtual datasources that live beside the rollup tiers in the
# `datasource list` surface but are not derived tables — today the
# serving sketch tables (serving/tables.py), which register a provider
# callable returning their listing rows. Process-scoped like the
# default tracer; providers must be cheap (called per debug command).
_EXTERNAL_DATASOURCES: Dict[str, "Callable[[], List[dict]]"] = {}
_EXTERNAL_LOCK = threading.Lock()


def register_datasource(name: str, provider) -> None:
    """Register a virtual datasource provider (rows for list)."""
    with _EXTERNAL_LOCK:
        _EXTERNAL_DATASOURCES[name] = provider


def unregister_datasource(name: str) -> None:
    with _EXTERNAL_LOCK:
        _EXTERNAL_DATASOURCES.pop(name, None)


def external_datasources() -> List[dict]:
    """Rows from every registered virtual datasource; a broken provider
    contributes an error row instead of killing the listing."""
    with _EXTERNAL_LOCK:
        providers = dict(_EXTERNAL_DATASOURCES)
    rows: List[dict] = []
    for name, provider in sorted(providers.items()):
        try:
            rows.extend(provider())
        except Exception as e:   # the debug socket must still answer
            rows.append({"table": name, "kind": "external",
                         "error": str(e)[:200]})
    return rows

# one shared table for both naming directions; inverse derived
_NAMED_SUFFIXES = {60: "1m", 3600: "1h", 86400: "1d"}
_SUFFIX_INTERVALS = {v: k for k, v in _NAMED_SUFFIXES.items()}


def _interval_suffix(interval: int) -> str:
    return _NAMED_SUFFIXES.get(interval, f"{interval}s")


def interval_from_table_name(base_name: str, table_name: str
                             ) -> Optional[int]:
    """Inverse of rollup_schema's naming: `vtap_flow_port.1h` -> 3600
    for base `vtap_flow_port`; None if not a rollup of this base."""
    if not table_name.startswith(base_name + "."):
        return None
    suffix = table_name[len(base_name) + 1:]
    named = _SUFFIX_INTERVALS.get(suffix)
    if named is not None:
        return named
    if suffix.endswith("s") and suffix[:-1].isdigit():
        return int(suffix[:-1])
    return None


def rollup_schema(base: TableSchema, interval: int,
                  ttl_seconds=TTL_DERIVE) -> TableSchema:
    """Derive the coarser table's schema (name suffixed `.1m`-style).
    ttl_seconds: TTL_DERIVE = 30x base retention, None = keep forever,
    >=0 = explicit seconds."""
    if ttl_seconds is TTL_DERIVE:
        ttl_seconds = None if base.ttl_seconds is None \
            else base.ttl_seconds * 30
    return TableSchema(
        name=f"{base.name}.{_interval_suffix(interval)}",
        columns=base.columns,
        time_column=base.time_column,
        partition_seconds=max(base.partition_seconds, interval * 60),
        ttl_seconds=ttl_seconds,
        version=base.version,
    )


def _next_pow2(n: int) -> int:
    return 1 << max(10, (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("aggs", "num_segments"))
def _segment_reduce(seg: jnp.ndarray, mask: jnp.ndarray, data: jnp.ndarray,
                    aggs: Tuple[str, ...], num_segments: int) -> jnp.ndarray:
    """Reduce [rows, n_cols] int64 into [num_segments, n_cols] by agg kind.
    Padding rows (mask False) map to the trash segment num_segments-1 and
    carry neutral values, so output shape stays static across calls."""
    seg = jnp.where(mask, seg, num_segments - 1)
    outs = []
    for i, agg in enumerate(aggs):
        col = data[:, i]
        if agg == "sum" or agg == "count":
            v = jnp.where(mask, col if agg == "sum" else jnp.ones_like(col), 0)
            r = jax.ops.segment_sum(v, seg, num_segments=num_segments)
        elif agg == "min":
            v = jnp.where(mask, col, _I64_MAX)
            r = jax.ops.segment_min(v, seg, num_segments=num_segments)
        else:  # "max", "last", "key": max is a valid representative
            v = jnp.where(mask, col, _I64_MIN)
            r = jax.ops.segment_max(v, seg, num_segments=num_segments)
        outs.append(r)
    return jnp.stack(outs, axis=1)


def _unique_rows(packed: np.ndarray):
    """np.unique(axis=0) built from per-column argsorts: numpy's axis=0
    unique argsorts a void view (memcmp per compare), which profiles 5-10x
    slower than k stable i64 sorts at flow-map batch sizes. Returns
    (unique_rows, inverse) with rows in lexicographic order, matching
    np.unique's contract."""
    n, k = packed.shape
    if k == 1:
        u, inv = np.unique(packed[:, 0], return_inverse=True)
        return u[:, None], inv
    order = np.lexsort(tuple(packed[:, j] for j in reversed(range(k))))
    skeys = packed[order]
    boundary = np.empty(n, np.bool_)
    boundary[0] = True
    np.any(skeys[1:] != skeys[:-1], axis=1, out=boundary[1:])
    group_of_sorted = np.cumsum(boundary) - 1
    inverse = np.empty(n, np.int64)
    inverse[order] = group_of_sorted
    return skeys[boundary], inverse


@functools.partial(jax.jit, static_argnames=("aggs", "num_segments"))
def _device_group_reduce(keys: Tuple[jnp.ndarray, ...],
                         data: jnp.ndarray, mask: jnp.ndarray,
                         aggs: Tuple[str, ...], num_segments: int):
    """GROUP BY entirely on device: one sort + arithmetic boundary
    detection + cumsum group ids + segment reductions, one program.

    keys: n_keys u32 arrays [n]; data [n, m] i64; mask [n]. Invalid rows
    sort to the end (leading 1-bit key), contribute no boundary, and
    reduce into the trash segment. Returns (keys_out [n_keys, S],
    vals [S, m], n_groups scalar) with groups in lexicographic key
    order in slots [0, n_groups). Boundary predicates are pure
    arithmetic on the sorted lanes — no compare ops on moved data."""
    n_keys = len(keys)
    invalid = jnp.logical_not(mask).astype(jnp.uint32)
    ops = ((invalid,) + tuple(keys)
           + tuple(data[:, i] for i in range(data.shape[1])))
    sorted_ops = jax.lax.sort(ops, num_keys=1 + n_keys)
    svalid = jnp.uint32(1) - sorted_ops[0]
    skeys = sorted_ops[1:1 + n_keys]
    sdata = sorted_ops[1 + n_keys:]

    def _nz(x):   # u32 1 where x != 0, arithmetic only
        return (x | (jnp.uint32(0) - x)) >> jnp.uint32(31)

    diff = jnp.zeros_like(skeys[0][1:])
    for k in skeys:
        diff = diff | _nz(k[1:] - k[:-1])
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.uint32), diff]) * svalid
    # gid <= valid_rows - 1 < num_segments - 1 == the trash segment, so
    # a fully-distinct full batch cannot collide with trash
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg = jnp.where(svalid.astype(bool), gid, num_segments - 1)
    n_groups = jnp.sum(boundary.astype(jnp.int32))

    m = svalid.astype(bool)
    # reuse the shared per-agg dispatch (it inlines into this program);
    # seg already routes invalid rows to trash, and _segment_reduce's own
    # mask handling re-applies the identical mapping
    vals = _segment_reduce(seg, m, jnp.stack(sdata, axis=1), aggs,
                           num_segments)
    # group keys: constant within a group, so segment_max recovers them
    keys_out = [jax.ops.segment_max(
        jnp.where(m, k, jnp.uint32(0)).astype(jnp.int64),
        seg, num_segments=num_segments).astype(jnp.uint32) for k in skeys]
    return (jnp.stack(keys_out), vals, n_groups)


def group_reduce_device(cols: Dict[str, np.ndarray], key_names: List[str],
                        aggs: Dict[str, str]) -> Dict[str, np.ndarray]:
    """`group_reduce` with the group-id stage on device too (the full
    "GROUP BY runs on TPU" path). Key columns must fit uint32 (every
    schema key column does; the rollup time bucket is epoch seconds).
    Exactly equal to the host path, including group order (signed keys
    ride the lanes sign-bit-flipped so they sort like int64) — asserted
    in tests. Costs one scalar fetch (n_groups)."""
    for nm in key_names:
        dt = np.asarray(cols[nm]).dtype
        if dt.kind not in "uib" or dt.itemsize > 4:
            raise ValueError(
                f"device GROUP BY key {nm!r} is {dt} — keys must be "
                "<=32-bit integers to ride the u32 sort lanes (floats "
                "would truncate-merge, 64-bit ints would collide); use "
                "the host path")
    n = len(next(iter(cols.values())))
    if n == 0:
        return {nm: cols[nm][:0] for nm in list(key_names) + list(aggs)}
    rows_pad = _next_pow2(n)
    value_names = list(aggs.keys())

    def pad_u32(a):
        out = np.zeros(rows_pad, np.uint32)
        a = np.asarray(a)
        if a.dtype.kind == "i":
            # sign-bit flip: order-preserving signed -> u32 mapping, so
            # groups come back in the SAME lexicographic order as the
            # host path even with negative keys (e.g. l3_epc_id = -1)
            out[:n] = a.astype(np.int64).astype(np.uint32) ^ np.uint32(
                0x80000000)
        else:
            out[:n] = a.astype(np.uint32)
        return jnp.asarray(out)

    with jax.enable_x64(True):
        keys = tuple(pad_u32(np.asarray(cols[nm])) for nm in key_names)
        data = np.zeros((rows_pad, len(value_names)), np.int64)
        for i, nm in enumerate(value_names):
            data[:n, i] = np.asarray(cols[nm]).astype(np.int64)
        mask = np.zeros(rows_pad, np.bool_)
        mask[:n] = True
        keys_out, vals, n_groups = _device_group_reduce(
            keys, jnp.asarray(data), jnp.asarray(mask),
            tuple(aggs[nm] for nm in value_names), rows_pad + 1)
        g = int(n_groups)
        # materializing the reduced groups IS this function's contract:
        # the rollup/compaction lane hands host arrays to the store
        # layer, and it runs off the feed hot path (tier scheduler)
        keys_np = np.asarray(keys_out)[:, :g]  # lint: disable=host-sync-in-device-path
        vals_np = np.asarray(vals)[:g]  # lint: disable=host-sync-in-device-path
    out: Dict[str, np.ndarray] = {}
    for j, nm in enumerate(key_names):
        k = keys_np[j]
        if np.asarray(cols[nm]).dtype.kind == "i":
            k = k ^ np.uint32(0x80000000)   # undo the sign-bit flip
        out[nm] = k.astype(cols[nm].dtype)
    for i, nm in enumerate(value_names):
        out[nm] = vals_np[:, i]
    return out


def group_reduce(cols: Dict[str, np.ndarray], key_names: List[str],
                 aggs: Dict[str, str],
                 return_inverse: bool = False, method: str = "auto"):
    """Exact GROUP BY: group ids + segment reduction.

    `aggs` maps value column -> sum|max|min|count. Key columns come back
    deduplicated; value columns reduced. Shared by rollups, the querier,
    and the agent flow map. With return_inverse, also returns the [n]
    row->group index (callers needing extra reductions, e.g. bitwise OR,
    reuse it instead of re-grouping).

    method: "host" computes group ids with a host lexsort and reduces on
    device; "device" runs the whole thing in one device program
    (group_reduce_device); "auto" picks device on a real accelerator at
    batch sizes where the host lexsort would dominate (the
    query-over-hot-table regime). return_inverse always takes the host
    path — the device path never materializes the row->group map.
    """
    n = len(next(iter(cols.values())))
    if method == "device" and return_inverse:
        raise ValueError("the device GROUP BY never materializes the "
                         "row->group map; use method='host' with "
                         "return_inverse")
    if not aggs:
        method = "host"   # pure dedup: the host path short-circuits it
    # device keys ride u32 lanes: a 64-bit key (mac_src, flow_id) would
    # collide and a float key would truncate-merge — those group on host
    keys_fit_u32 = all(np.asarray(cols[k]).dtype.kind in "uib"
                       and np.asarray(cols[k]).dtype.itemsize <= 4
                       for k in key_names)
    if method == "device" or (
            method == "auto" and not return_inverse and n >= (1 << 18)
            and keys_fit_u32 and jax.default_backend() == "tpu"):
        return group_reduce_device(cols, key_names, aggs)
    if n == 0:
        empty = {nm: cols[nm][:0] for nm in list(key_names) + list(aggs)}
        return (empty, np.empty(0, np.int64)) if return_inverse else empty
    packed = np.stack([np.ascontiguousarray(cols[nm]).astype(np.int64)
                       for nm in key_names], axis=1)
    uniq, inverse = _unique_rows(packed)
    n_groups = uniq.shape[0]
    value_names = list(aggs.keys())
    if not value_names:   # pure dedup: SELECT k FROM t GROUP BY k
        out = {nm: uniq[:, j].astype(cols[nm].dtype)
               for j, nm in enumerate(key_names)}
        return (out, inverse) if return_inverse else out
    data = np.stack([np.asarray(cols[nm]).astype(np.int64)
                     for nm in value_names], axis=1)

    rows_pad = _next_pow2(n)
    seg = np.zeros(rows_pad, np.int32)
    seg[:n] = inverse
    mask = np.zeros(rows_pad, np.bool_)
    mask[:n] = True
    data_pad = np.zeros((rows_pad, len(value_names)), np.int64)
    data_pad[:n] = data
    seg_pad = _next_pow2(n_groups + 1)

    # Window sums of uint32 counters need 64-bit accumulators (ClickHouse
    # sums into UInt64); scope x64 to this program so the rest of the
    # framework keeps the TPU-friendly 32-bit default.
    with jax.enable_x64(True):
        reduced = np.asarray(_segment_reduce(
            jnp.asarray(seg), jnp.asarray(mask), jnp.asarray(data_pad),
            tuple(aggs[nm] for nm in value_names), seg_pad))[:n_groups]

    out: Dict[str, np.ndarray] = {}
    for j, nm in enumerate(key_names):
        out[nm] = uniq[:, j].astype(cols[nm].dtype)
    for i, nm in enumerate(value_names):
        out[nm] = reduced[:, i]
    return (out, inverse) if return_inverse else out


class RollupManager:
    """Maintains derived tables `<base>.<1m|1h|...>`; advance() builds only
    buckets strictly older than now-allowance, once — late data within the
    allowance still lands (the reference leans on CH background merges for
    this; we lean on build-once-behind-watermark)."""

    def __init__(self, store: Store, db: str, base: TableSchema,
                 intervals: Tuple[int, ...] = (60,),
                 allowance_seconds: int = 10) -> None:
        self.store = store
        self.db = db
        self.base = store.create_table(db, base)
        self.allowance = allowance_seconds
        self.targets: List[Tuple[int, Table]] = []
        # configured tiers UNION tiers found on disk: a runtime
        # `datasource add` persists as its table (the manifest IS the
        # registration, like everything else in this store), so a
        # restarted ingester keeps building tiers an operator added
        # (reference: datasource defs live in the controller DB)
        want = set(intervals)
        for tdb, tname in store.tables():
            if tdb != db:
                continue
            iv = interval_from_table_name(base.name, tname)
            if iv is not None:
                want.add(iv)
        for iv in sorted(want):
            # a tier removed with keep-data left a DETACHED marker: its
            # rows stay queryable but it must not resume building — and
            # the operator's detach outranks the static config list too
            # (only a datasource add clears the marker)
            name = f"{base.name}.{_interval_suffix(iv)}"
            try:
                root = store.table(db, name).root
                if os.path.exists(os.path.join(root, "DETACHED")):
                    continue
            except KeyError:
                pass   # table doesn't exist yet: nothing to detach
            self.targets.append(
                (iv, store.create_table(db, rollup_schema(base, iv))))
        # per-interval high-water mark: everything < mark already built.
        # Recovered from the target table on restart (segments are
        # append-only, so re-building an already-built bucket would
        # double-count) by reading the newest built bucket's timestamp.
        self._built_until: Dict[int, int] = {
            iv: self._recover_watermark(iv, t) for iv, t in self.targets}
        # guards targets/_built_until against runtime datasource CRUD
        # (debug-socket thread) racing advance() (pipeline thread).
        # Builds run OUTSIDE the lock (a backfill can scan days of base
        # data — holding the lock would time out the debug socket);
        # _building marks in-flight tiers, _drop_pending records a del
        # that arrived mid-build so its table is re-dropped afterwards.
        self._lock = threading.Lock()
        self._building: set = set()
        self._drop_pending: Dict[int, str] = {}   # interval -> table root

    # -- runtime datasource CRUD (reference: datasource/handle.go Handle
    # add/mod/del driven by deepflow-ctl; CH materialized views there,
    # derived tables + watermarks here) -----------------------------------
    def list_datasources(self) -> List[dict]:
        with self._lock:
            rows = [{"interval": iv, "table": t.schema.name,
                     "ttl_seconds": t.schema.ttl_seconds,
                     "built_until": self._built_until[iv]}
                    for iv, t in self.targets]
        # virtual datasources (ISSUE 7 sketch tables) ride the same
        # listing — the operator sees every queryable surface in one
        # `datasource list`
        return rows + external_datasources()

    def add_interval(self, interval: int,
                     ttl_seconds: Optional[int] = TTL_DERIVE) -> dict:
        """Create a new rollup tier at runtime. Unlike the reference's
        materialized views (which only see new inserts), the next
        advance() backfills every complete bucket still in the base
        table's retention. ttl_seconds: TTL_DERIVE = 30x base retention,
        None/0 = keep forever, >0 = explicit seconds."""
        if interval <= 0 or interval % 60:
            # the reference constrains custom tiers to whole minutes
            # (handle.go: 1m/1h composition); sub-minute tiers belong to
            # the base table
            raise ValueError("interval must be a positive multiple of 60")
        if ttl_seconds is not TTL_DERIVE and ttl_seconds is not None:
            if int(ttl_seconds) < 0:
                raise ValueError("ttl_seconds must be >= 0")
            if int(ttl_seconds) == 0:
                ttl_seconds = None                   # keep forever
        with self._lock:
            if any(iv == interval for iv, _ in self.targets):
                raise ValueError(f"datasource {interval}s already exists")
            if interval in self._building or interval in self._drop_pending:
                # a del'd tier's backfill is still draining: attaching a
                # fresh table now would let the old build overwrite the
                # new tier's watermark when it lands
                raise ValueError(
                    f"datasource {interval}s busy (build draining); retry")
            t = self.store.create_table(
                self.db, rollup_schema(self.base.schema, interval,
                                       ttl_seconds))
            marker = os.path.join(t.root, "DETACHED")
            if os.path.exists(marker):   # re-attach of a kept-data tier
                os.remove(marker)
            if ttl_seconds is not TTL_DERIVE and \
                    t.schema.ttl_seconds != ttl_seconds:
                # create_table returned an EXISTING table — the
                # requested retention must still win
                t.set_ttl(ttl_seconds)
            self.targets.append((interval, t))
            self.targets.sort()
            self._built_until[interval] = self._recover_watermark(interval, t)
            return {"interval": interval, "table": t.schema.name,
                    "ttl_seconds": t.schema.ttl_seconds}

    def remove_interval(self, interval: int, drop_data: bool = True) -> bool:
        with self._lock:
            for i, (iv, t) in enumerate(self.targets):
                if iv == interval:
                    del self.targets[i]
                    del self._built_until[iv]
                    if drop_data:
                        self.store.drop_table(self.db, t.schema.name)
                        if iv in self._building:
                            # an in-flight build may recreate the table
                            # dir with its append; advance() re-drops it
                            # when the build drains
                            self._drop_pending[iv] = t.root
                    else:
                        # kept data must not resurrect the tier on
                        # restart: mark it detached on disk
                        try:
                            with open(os.path.join(t.root, "DETACHED"),
                                      "w"):
                                pass
                        except OSError:
                            pass
                    return True
        return False

    def set_retention(self, interval: int, ttl_seconds: Optional[int]) -> bool:
        if ttl_seconds is not None and int(ttl_seconds) < 0:
            raise ValueError("ttl_seconds must be >= 0")
        with self._lock:
            for iv, t in self.targets:
                if iv == interval:
                    t.set_ttl(ttl_seconds)
                    return True
        return False

    @staticmethod
    def _recover_watermark(interval: int, target: Table) -> int:
        parts = target.partitions()
        if not parts:
            return 0
        tcol = target.schema.time_column
        psec = target.schema.partition_seconds
        last = target.scan(columns=[tcol],
                           time_range=(parts[-1], parts[-1] + psec))[tcol]
        if len(last) == 0:
            return 0
        return int(last.max()) + interval

    def advance(self, now: float) -> Dict[int, int]:
        """Build all complete buckets older than now-allowance.
        Returns {interval: rows_emitted}."""
        emitted: Dict[int, int] = {}
        with self._lock:
            targets = list(self.targets)
        for iv, target in targets:
            # bookkeeping under the lock, the build itself outside it
            # (a backfill can scan days of base data; the debug socket's
            # datasource commands must stay responsive meanwhile). The
            # _building marker keeps a concurrent del honest: its table
            # drop is re-applied after the build drains.
            with self._lock:
                if iv not in self._built_until or iv in self._building:
                    continue   # removed by datasource del / double run
                safe = int(now - self.allowance) // iv * iv
                lo = self._built_until[iv]
                if lo == 0:
                    parts = self.base.partitions()
                    if not parts:
                        emitted[iv] = 0
                        continue
                    lo = parts[0] // iv * iv
                if safe <= lo:
                    emitted[iv] = 0
                    continue
                self._building.add(iv)
            rows = None
            try:
                rows = self._build_range(iv, target, lo, safe)
            finally:
                with self._lock:
                    self._building.discard(iv)
                    if iv in self._built_until:
                        if rows is not None:   # failed build: retry later
                            self._built_until[iv] = safe
                            emitted[iv] = rows
                    else:
                        pend = self._drop_pending.pop(iv, None)
                        if pend is not None:
                            shutil.rmtree(pend, ignore_errors=True)
        return emitted

    def _build_range(self, interval: int, target: Table,
                     lo: int, hi: int) -> int:
        schema = self.base.schema
        cols = self.base.scan(time_range=(lo, hi))
        tcol = schema.time_column
        n = len(cols[tcol])
        if n == 0:
            return 0
        # keep the bucket in the schema's (u32) dtype: an int64 bucket
        # would disqualify every rollup from the device GROUP BY path
        bucket = cols[tcol] // np.uint32(interval) * np.uint32(interval)
        work = dict(cols)
        work[tcol] = bucket.astype(cols[tcol].dtype)
        key_names = [c.name for c in schema.columns if c.agg is AggKind.KEY]
        if tcol not in key_names:
            key_names.append(tcol)
        aggs = {c.name: c.agg.value for c in schema.columns
                if c.name not in key_names}
        reduced = group_reduce(work, key_names, aggs)
        out = {}
        for c in schema.columns:
            v = reduced[c.name]
            if np.dtype(c.dtype).kind == "u":
                v = np.clip(v, 0, np.iinfo(c.dtype).max)
            out[c.name] = v.astype(c.dtype)
        target.append(out)
        return len(out[tcol])
