"""Fused Pallas unpack+sketch kernel: one VMEM-resident pass per batch.

The coalesced feed (ISSUE 5) got the batch across the link as one
transfer; what is left of the per-batch device cost is XLA scheduling
the step as SEPARATE histogram passes — the CMS rides mxu_hist's scan,
the entropy histogram rides another, and each re-reads the unpacked
lane columns from HBM. This kernel fuses the whole histogram half of
`flow_suite.update` into a single Pallas program:

- the unpack prologue (ports/proto/packet split out of the 4 staged
  lane words) runs IN-KERNEL on each chunk, so the staged plane is
  read from HBM exactly once and the derived columns never exist
  outside VMEM;
- the 5-tuple fold and the multiply-shift bucket hashes are the ACTUAL
  utils/u32.fold_columns / ops/hashing.bucket helpers (plain jnp ops,
  traced straight into the kernel body — they cannot drift from the
  unfused path), run on the same chunk while it is resident;
- the Count-Min rows AND the 4 entropy feature rows accumulate into
  VMEM-resident accumulators via the same one-hot MXU contraction as
  ops/mxu_hist, written back to HBM once at the end (the
  ops/pallas_hist residency pattern, extended across both sketch
  families).

HLL's scatter-max and the top-K ring stay XLA ops in the surrounding
jitted program (flow_suite.update_lanes_fused): a grouped scatter-max
has no MXU form, and the ring path's sort must stay out of Mosaic.

Bit-exactness: the CMS half is unconditional — mask weights are 0/1,
so a cell's per-batch sum is bounded by batch_rows (< 2^24 at any
sane capacity) and the f32 accumulation is exact regardless of
partial-sum order. The entropy half is exact only while a cell's
per-batch weighted sum stays below 2^24: weights saturate at
256**planes - 1 per record exactly like mxu_hist, so a batch that
concentrates many max-weight records on one bucket (a DDoS-shaped
burst) can push a cell sum past 2^24, where f32 rounds — and this
kernel's partial-sum order (chunk=1024, per-plane scaled adds)
differs from mxu_hist's (chunk=8192, planes recombined per chunk),
so the two paths may round apart by a few counts there. Within the
bound they agree bit-for-bit no matter which unit ran them —
asserted in tests/test_staging.py via interpret mode; the identity
tests and the ci.sh equality gates stay inside it by construction.

STATUS (2026-08-03): correctness-pinned (interpret-mode tests beside
the unfused reference) and compiled for v5e at production widths
(tests/test_tpu_compile.py); its speed is not measured on a chip in
this round (ROADMAP S3). Hence the conservative posture: auto dispatch
takes this kernel only on a TPU backend under the
DEEPFLOW_SKETCH_PALLAS=1 opt-in (flow_suite.use_fused_hists).

VMEM budget at the defaults (chunk=1024, CMS [4, 2^17], entropy
[4, 2^12]): CMS accumulator 2 MB + entropy accumulator 64 KB, one-hots
(1024, 512) + (1024, 256) bf16 = 1.5 MB, lane chunk 16 KB — well
inside ~16 MB.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepflow_tpu.ops import hashing
from deepflow_tpu.ops.mxu_hist import _split_hi_lo
from deepflow_tpu.utils.u32 import fold_columns


def _hist_body(fkey, feats, pkts, mask, cms_seed_ref, ent_seed_ref,
               cms_ref, ent_ref, *, chunk, cms_d, cms_width, ent_f,
               ent_width, ent_weight_planes):
    """The kernel's shared histogram half: CMS rows over the folded
    flow key + entropy feature rows, accumulated into the VMEM-resident
    refs. Both the lane kernel and the dict-wire news kernel call this
    after their own unpack prologues — the math is one definition, so
    the wires cannot drift apart."""
    u = jnp.uint32
    cms_hi, cms_lo = _split_hi_lo(cms_width)
    ent_hi, ent_lo = _split_hi_lo(ent_width)
    cms_lw = int(np.log2(cms_width))
    ent_lw = int(np.log2(ent_width))

    # Count-Min rows: mask-only weights (one 0/1 plane)
    w_mask = mask[:, None].astype(jnp.bfloat16)            # [chunk, 1]
    chi_iota = lax.broadcasted_iota(jnp.int32, (chunk, cms_hi), 1)
    clo_iota = lax.broadcasted_iota(jnp.int32, (chunk, cms_lo), 1)
    for j in range(cms_d):
        mult = cms_seed_ref[j, 0].astype(u)   # i32 scalar, bits kept
        salt = cms_seed_ref[j, 1].astype(u)
        idx = hashing.bucket(fkey, mult, salt, cms_lw)
        a = ((idx // cms_lo)[:, None] == chi_iota).astype(jnp.bfloat16) \
            * w_mask
        b = ((idx % cms_lo)[:, None] == clo_iota).astype(jnp.bfloat16)
        cms_ref[j] += lax.dot_general(
            a, b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # entropy features: packet weights, saturated then masked exactly
    # like mxu_hist.hist_masked (min first == mask first for 0/1 masks)
    wm = jnp.minimum(pkts, np.int32(256 ** ent_weight_planes - 1)) \
        * mask.astype(jnp.int32)                           # [chunk]
    ehi_iota = lax.broadcasted_iota(jnp.int32, (chunk, ent_hi), 1)
    elo_iota = lax.broadcasted_iota(jnp.int32, (chunk, ent_lo), 1)
    for f in range(ent_f):
        mult = ent_seed_ref[f, 0].astype(u)
        salt = ent_seed_ref[f, 1].astype(u)
        idx = hashing.bucket(feats[f], mult, salt, ent_lw)
        hi_oh = (idx // ent_lo)[:, None] == ehi_iota
        b = ((idx % ent_lo)[:, None] == elo_iota).astype(jnp.bfloat16)
        for plane in range(ent_weight_planes):
            wp = (((wm >> (8 * plane)) & 0xFF)[:, None]
                  ).astype(jnp.bfloat16)
            a = hi_oh.astype(jnp.bfloat16) * wp
            ent_ref[f] += lax.dot_general(
                a, b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) \
                * np.float32(256.0 ** plane)


def _kernel(n_ref, lanes_ref, cms_seed_ref, ent_seed_ref,
            cms_ref, ent_ref, *, chunk, cms_d, cms_width, ent_f,
            ent_width, ent_weight_planes):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cms_ref[:] = jnp.zeros_like(cms_ref)
        ent_ref[:] = jnp.zeros_like(ent_ref)

    u = jnp.uint32
    lanes = lanes_ref[:]                      # [4, chunk] uint32
    ip_src, ip_dst = lanes[0], lanes[1]
    # unpack prologue, in-kernel (flow_suite.unpack_lanes, op for op)
    port_src = lanes[2] >> u(16)
    port_dst = lanes[2] & u(0xFFFF)
    proto = lanes[3] >> u(24)
    pkts = (lanes[3] & u(0xFFFFFF)).astype(jnp.int32)

    # per-lane validity from the batch's n word: padded (or stale
    # staging) lanes carry weight 0 everywhere, exactly like the
    # unfused mask path
    pos = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)[:, 0]
    mask = (pos + pl.program_id(0) * chunk) < n_ref[0]

    # flow key: the REAL utils/u32.fold_columns — plain jnp ops, so the
    # unfused path's hash helpers trace straight into the kernel body
    # and can never drift from it
    fkey = fold_columns((ip_src, ip_dst, port_src, port_dst, proto))

    _hist_body(fkey, (ip_src, ip_dst, port_src, port_dst), pkts, mask,
               cms_seed_ref, ent_seed_ref, cms_ref, ent_ref,
               chunk=chunk, cms_d=cms_d, cms_width=cms_width,
               ent_f=ent_f, ent_width=ent_width,
               ent_weight_planes=ent_weight_planes)


def _news_kernel(n_ref, rows_ref, cms_seed_ref, ent_seed_ref,
                 cms_ref, ent_ref, *, chunk, cms_d, cms_width, ent_f,
                 ent_width, ent_weight_planes):
    """The dict wire's (6, C) NEWS plane, unpacked in-kernel: row 0 is
    the dictionary index (sketch math never reads it), rows 1-3 the
    three packed key words, row 4 the RAW proto byte, row 5 the
    PKTS_CAP'd packet count. The unpack mirrors flow_dict.update_news's
    lane construction + flow_suite.unpack_lanes op for op:
    (plane[4] << 24) >> 24 == plane[4] & 0xFF on the u8-valued wire
    row, and (proto<<24 | pkts) & 0xFFFFFF == pkts with pkts <= 0xFFFF."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cms_ref[:] = jnp.zeros_like(cms_ref)
        ent_ref[:] = jnp.zeros_like(ent_ref)

    u = jnp.uint32
    rows = rows_ref[:]                        # [6, chunk] uint32
    ip_src, ip_dst = rows[1], rows[2]
    port_src = rows[3] >> u(16)
    port_dst = rows[3] & u(0xFFFF)
    proto = rows[4] & u(0xFF)
    pkts = (rows[5] & u(0xFFFFFF)).astype(jnp.int32)

    pos = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)[:, 0]
    mask = (pos + pl.program_id(0) * chunk) < n_ref[0]

    fkey = fold_columns((ip_src, ip_dst, port_src, port_dst, proto))

    _hist_body(fkey, (ip_src, ip_dst, port_src, port_dst), pkts, mask,
               cms_seed_ref, ent_seed_ref, cms_ref, ent_ref,
               chunk=chunk, cms_d=cms_d, cms_width=cms_width,
               ent_f=ent_f, ent_width=ent_width,
               ent_weight_planes=ent_weight_planes)


def _call_hists(kernel, nrows, plane, n, cms_seeds, ent_seeds, *,
                cms_log2_width, ent_log2_buckets, weight_planes,
                chunk, interpret):
    """Shared pallas_call plumbing for the (nrows, C) plane kernels:
    chunked grid over the column axis, both accumulators mapped to the
    same block every step, scalars riding SMEM as bit-preserved
    int32."""
    C = int(plane.shape[1])
    d = int(cms_seeds.shape[0])
    f = int(ent_seeds.shape[0])
    cms_w, ent_w = 1 << cms_log2_width, 1 << ent_log2_buckets
    cms_hi, cms_lo = _split_hi_lo(cms_w)
    ent_hi, ent_lo = _split_hi_lo(ent_w)
    chunk = min(chunk, C)
    while C % chunk:                 # batch capacities are powers of two;
        chunk //= 2                  # anything else degrades, still correct
    nchunk = C // chunk

    kern = functools.partial(
        kernel, chunk=chunk, cms_d=d, cms_width=cms_w, ent_f=f,
        ent_width=ent_w, ent_weight_planes=weight_planes)
    # scalars ride SMEM as int32 (bit-preserving: the kernel's
    # astype(uint32) wraps the bits back); the lane plane streams
    # through VMEM chunk blocks while both accumulators stay mapped to
    # the SAME block every step — the pallas_hist residency pattern
    cms_h, ent_h = pl.pallas_call(
        kern,
        grid=(nchunk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nrows, chunk), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((d, cms_hi, cms_lo), lambda i: (0, 0, 0)),
            pl.BlockSpec((f, ent_hi, ent_lo), lambda i: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, cms_hi, cms_lo), jnp.float32),
            jax.ShapeDtypeStruct((f, ent_hi, ent_lo), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(
        jnp.asarray(n).astype(jnp.int32).reshape(1),
        plane,
        lax.bitcast_convert_type(cms_seeds, jnp.int32),
        lax.bitcast_convert_type(ent_seeds, jnp.int32),
    )
    return cms_h.reshape(d, cms_w), ent_h.reshape(f, ent_w)


@functools.partial(jax.jit, static_argnames=(
    "cms_log2_width", "ent_log2_buckets", "weight_planes", "chunk",
    "interpret"))
def fused_lane_hists(plane: jnp.ndarray, n: jnp.ndarray,
                     cms_seeds: jnp.ndarray, ent_seeds: jnp.ndarray, *,
                     cms_log2_width: int, ent_log2_buckets: int,
                     weight_planes: int = 2, chunk: int = 1024,
                     interpret: bool = False):
    """One staged (4, C) lane plane + its n word -> (cms_hist, ent_hist)
    f32 deltas, computed in a single fused kernel.

    cms_hist is [d, 2^cms_log2_width] over the folded 5-tuple flow key
    (== mxu_hist.hist_masked over hashing.multi_bucket, bit-exact);
    ent_hist is [4, 2^ent_log2_buckets] over ip_src/ip_dst/port_src/
    port_dst with capped packet weights (== entropy.update's histogram
    delta). The caller adds the deltas into the int32 sketch state.
    """
    return _call_hists(_kernel, 4, plane, n, cms_seeds, ent_seeds,
                       cms_log2_width=cms_log2_width,
                       ent_log2_buckets=ent_log2_buckets,
                       weight_planes=weight_planes, chunk=chunk,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "cms_log2_width", "ent_log2_buckets", "weight_planes", "chunk",
    "interpret"))
def fused_news_hists(plane: jnp.ndarray, n: jnp.ndarray,
                     cms_seeds: jnp.ndarray, ent_seeds: jnp.ndarray, *,
                     cms_log2_width: int, ent_log2_buckets: int,
                     weight_planes: int = 2, chunk: int = 1024,
                     interpret: bool = False):
    """One dict-wire (6, C) NEWS plane + its n word -> the same
    (cms_hist, ent_hist) f32 deltas as `fused_lane_hists` would produce
    for the equivalent lane batch: the news unpack runs in-kernel
    (`_news_kernel`), the histogram math is the shared `_hist_body`.
    Hits planes need no kernel of their own — their table gather is an
    XLA op, and the gathered (4, 2H) lane plane rides
    `fused_lane_hists` unchanged (models/flow_dict.update_hits)."""
    return _call_hists(_news_kernel, 6, plane, n, cms_seeds, ent_seeds,
                       cms_log2_width=cms_log2_width,
                       ent_log2_buckets=ent_log2_buckets,
                       weight_planes=weight_planes, chunk=chunk,
                       interpret=interpret)
