"""Histogram-by-matmul: scatter-add recast as one-hot outer products on the MXU.

XLA lowers `x.at[idx].add(v)` on TPU to a serialized scatter — ~30 ms for 1M
updates into a [4, 65536] Count-Min sketch. The MXU path instead decomposes
each bucket index into (hi, lo) digits and computes

    counts2d[hi, lo] = sum_n onehot_hi[n, hi] * onehot_lo[n, lo]
                     = onehot_hi^T @ onehot_lo

one bf16 matmul per batch chunk, accumulated in f32 (exact for counts < 2^24).
Measured ~5 ms for the same workload — the histogram rides the systolic array
instead of the scatter unit. This is the TPU answer to the reference's
hand-rolled per-thread stash accumulation (agent/src/collector/
quadruple_generator.rs SubQuadGen): where it shards counters across CPU
threads, we turn counting itself into dense matrix work.

Weighted histograms split integer weights into base-256 digit planes so every
matmul operand stays exactly representable in bf16; planes are recombined as
`sum_j 256^j * hist(w_j)` in f32.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def _split_hi_lo(width: int) -> tuple[int, int]:
    """width = hi * lo with lo <= 256 (lane dim) and both MXU-friendly."""
    if width <= 256:
        return 1, width
    lo = 256
    hi, rem = divmod(width, lo)
    if rem:
        raise ValueError(f"width {width} not a multiple of 256")
    return hi, lo


# Below this many lanes the XLA scatter path beats MXU chunk overheads.
MIN_LANES = 8192


def hist_masked(idx: jnp.ndarray, width: int,
                weights: jnp.ndarray | None, mask: jnp.ndarray | None,
                weight_planes: int = 2, chunk: int = 16384,
                method: str = "auto") -> jnp.ndarray:
    """`hist` with the mask folded into the weights (shared dispatch helper
    for cms.update / entropy.update: mask-only batches need just one plane)."""
    if weights is None and mask is not None:
        weights, weight_planes = mask.astype(jnp.int32), 1
    elif weights is not None and mask is not None:
        weights = weights.astype(jnp.int32) * mask.astype(jnp.int32)
    return hist(idx, width, weights, chunk=chunk,
                weight_planes=weight_planes, method=method)


def _use_pallas(method: str, width: int, d: int) -> bool:
    """method dispatch: "pallas" forces the VMEM-resident kernel (a TPU
    only; tests interpret it through pallas_hist.INTERPRET); "auto"
    takes it on a TPU backend when the env opt-in is set — no chip
    measurement has chosen it yet (ROADMAP S3), so auto stays
    conservative. Auto also refuses shapes whose resident accumulator
    would crowd VMEM (d * width * 4B; the one-hot chunk adapts on its
    own)."""
    if method == "pallas":
        return True
    if method == "xla":
        return False
    if method != "auto":
        raise ValueError(f"hist method {method!r}: "
                         "expected auto | xla | pallas")
    if width < MIN_LANES or d * width * 4 > (8 << 20):
        return False
    return (jax.default_backend() == "tpu"
            and os.environ.get("DEEPFLOW_HIST_PALLAS", "") == "1")


def hist(idx: jnp.ndarray, width: int, weights: jnp.ndarray | None = None,
         chunk: int = 16384, weight_planes: int = 2,
         method: str = "auto") -> jnp.ndarray:
    """Batched histogram: idx [d, n] int32 in [0, width) -> [d, width] f32.

    `weights` is [n] (shared across the d rows — the Count-Min case),
    non-negative ints. Weights at or above 256**weight_planes SATURATE to
    256**weight_planes - 1 (never bit-truncate). Per-bucket per-call sums
    stay exact below 2^24 (f32 accumulator); beyond that they round.
    Out-of-range indices must be pre-masked by the caller (zero weight);
    indices are clamped defensively.
    """
    if _use_pallas(method, width, idx.shape[0]):
        from deepflow_tpu.ops import pallas_hist
        return pallas_hist.hist_pallas(
            idx, width, weights, chunk=min(chunk, 4096),
            weight_planes=weight_planes,
            interpret=pallas_hist.INTERPRET)

    d, n = idx.shape
    hi_n, lo_n = _split_hi_lo(width)

    pad = (-n) % chunk
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        if weights is None:
            weights = jnp.concatenate(
                [jnp.ones((n,), jnp.int32), jnp.zeros((pad,), jnp.int32)])
            weight_planes = 1  # synthesized 0/1 weights fit one plane
        else:
            weights = jnp.pad(weights.astype(jnp.int32), (0, pad))
    n_pad = n + pad
    nchunk = n_pad // chunk

    idx = jnp.clip(idx, 0, width - 1)
    # [nchunk, d, chunk] so scan carries one chunk per step
    idx_c = idx.reshape(d, nchunk, chunk).transpose(1, 0, 2)
    hi_iota = jnp.arange(hi_n, dtype=jnp.int32)
    lo_iota = jnp.arange(lo_n, dtype=jnp.int32)

    if weights is None:
        def body(acc, ic):
            a = (ic // lo_n)[:, :, None] == hi_iota[None, None, :]
            b = (ic % lo_n)[:, :, None] == lo_iota[None, None, :]
            out = lax.dot_general(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return acc + out, None
        acc, _ = lax.scan(body, jnp.zeros((d, hi_n, lo_n), jnp.float32), idx_c)
        return acc.reshape(d, width)

    w_max = np.int32(256 ** weight_planes - 1)
    w_c = jnp.minimum(weights.astype(jnp.int32), w_max).reshape(nchunk, chunk)

    def body(acc, xs):
        ic, wc = xs
        hi_oh = (ic // lo_n)[:, :, None] == hi_iota[None, None, :]  # [d,C,hi]
        b = ((ic % lo_n)[:, :, None] == lo_iota[None, None, :]
             ).astype(jnp.bfloat16)                                  # [d,C,lo]
        outs = []
        for plane in range(weight_planes):
            wp = (wc >> (8 * plane)) & 0xFF                          # [C]<256
            a = hi_oh * wp[None, :, None]
            outs.append(lax.dot_general(
                a.astype(jnp.bfloat16), b, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * np.float32(256.0 ** plane))
        return acc + sum(outs), None

    acc, _ = lax.scan(body, jnp.zeros((d, hi_n, lo_n), jnp.float32),
                      (idx_c, w_c))
    return acc.reshape(d, width)
