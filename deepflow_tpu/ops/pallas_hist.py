"""Pallas VMEM-resident histogram kernel: the sketch accumulator that
never leaves the chip.

ops/mxu_hist.py turns scatter-adds into one-hot matmuls, but its
lax.scan carries the [d, hi, lo] f32 accumulator as loop state — XLA
materializes the carry between steps, so every 16k-lane chunk round
trips the accumulator through HBM (~1 MB each way for the 4x2^16 CMS).
This kernel keeps the accumulator VMEM-RESIDENT across the whole batch:
the grid walks input chunks while the output BlockSpec maps every step
to the same block, so Mosaic leaves it on-chip and only writes HBM once
at the end. The per-chunk compute is the same MXU contraction
(one-hot-hi^T @ one-hot-lo per sketch row, weights in base-256 digit
planes so operands stay exact in bf16).

VMEM: the v5e compiler holds the int32 compare results, their bf16
casts and the matmul operands of a chunk at once, so the chunk is
fitted to the 16 MB scoped limit per width (`fit_chunk`: 1024 lanes at
the 2^17-wide CMS, 2048 at 2^16 and 2^12); the accumulator is 2 MB at
the CMS width.

Speed against the XLA scan: not measured on a chip in this round
(ROADMAP S3). mxu_hist.hist "auto" stays on the XLA path; the kernel
is reached through method="pallas" or the DEEPFLOW_HIST_PALLAS=1
opt-in on a TPU.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepflow_tpu.ops.mxu_hist import _split_hi_lo


# Mosaic compiles these kernels for the TPU; the program never falls
# back to the interpreter on its own. Tests that run a kernel on the CPU
# monkeypatch this (or pass interpret=True to the kernel itself).
INTERPRET = False

# Scoped-VMEM cost of one chunk row per one-hot column (hi_n + lo_n),
# in bytes, as the v5e compiler counts it (int32 compare results, their
# bf16 casts and the matmul operands live at once): it asked 13-16 B
# across the CMS, entropy and DDSketch widths (2^17 wide at chunk 2048:
# 23 MB of the 16 MB scoped limit). tests/test_tpu_compile.py pins it.
_VMEM_ROW_BYTES = 16
_VMEM_BUDGET = 16 << 20
# the 1-D weight block tiles by 1024 lanes on the TPU: smaller chunks
# are refused for a layout mismatch
MIN_CHUNK = 1024


def fit_chunk(chunk: int, hi_n: int, lo_n: int) -> int:
    """Largest power-of-two chunk <= `chunk` whose one-hot working set
    fits the scoped-VMEM budget, but never below MIN_CHUNK: where the
    budget allows fewer rows (the 2^19-wide DDSketch allows 455), the
    floor is over budget. The cost model ignores d; at that width only
    d=1 was compiled (tests/test_tpu_compile.py), so d>1 there is
    unproven and the compiler may refuse it."""
    cap = _VMEM_BUDGET // (_VMEM_ROW_BYTES * (hi_n + lo_n))
    c = MIN_CHUNK
    while c * 2 <= min(chunk, cap):
        c *= 2
    return c


def _kernel(idx_ref, w_ref, out_ref, *, d, width, hi_n, lo_n, planes):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ic = jnp.clip(idx_ref[:], 0, width - 1)          # [d, chunk]
    hi = ic // lo_n
    lo = ic % lo_n
    chunk = ic.shape[1]
    lo_iota = lax.broadcasted_iota(jnp.int32, (chunk, lo_n), 1)
    hi_iota = lax.broadcasted_iota(jnp.int32, (chunk, hi_n), 1)
    for plane in range(planes):
        # minor-dim insert while still int32 (Mosaic rejects it on bf16),
        # then cast the [chunk, 1] column
        wp = ((w_ref[:] >> (8 * plane)) & 0xFF)[:, None].astype(jnp.bfloat16)
        scale = np.float32(256.0 ** plane)
        for j in range(d):                           # d is tiny (<= 8)
            a = (hi[j][:, None] == hi_iota).astype(jnp.bfloat16) \
                * wp                                 # [chunk, hi]
            b = (lo[j][:, None] == lo_iota).astype(jnp.bfloat16)
            # contract the chunk dim on the MXU: [hi, lo]
            out = lax.dot_general(
                a, b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out_ref[j] += out * scale


@functools.partial(jax.jit, static_argnames=("width", "chunk",
                                             "weight_planes", "interpret"))
def hist_pallas(idx: jnp.ndarray, width: int,
                weights: jnp.ndarray | None = None, chunk: int = 4096,
                weight_planes: int = 2,
                interpret: bool = False) -> jnp.ndarray:
    """mxu_hist.hist semantics, VMEM-resident accumulator.

    idx [d, n] int32 in [0, width) -> [d, width] f32; `weights` [n]
    non-negative ints shared across rows, saturating at
    256**weight_planes - 1. interpret=True runs the Mosaic interpreter
    (CPU correctness tests)."""
    d, n = idx.shape
    hi_n, lo_n = _split_hi_lo(width)
    chunk = fit_chunk(chunk, hi_n, lo_n)

    pad = (-n) % chunk
    if weights is None:
        weights = jnp.ones((n,), jnp.int32)
        weight_planes = 1
    else:
        weights = jnp.minimum(weights.astype(jnp.int32),
                              np.int32(256 ** weight_planes - 1))
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, (0, pad))   # zero weight = no-op row
    nchunk = (n + pad) // chunk

    kern = functools.partial(_kernel, d=d, width=width, hi_n=hi_n,
                             lo_n=lo_n, planes=weight_planes)
    out = pl.pallas_call(
        kern,
        grid=(nchunk,),
        in_specs=[
            pl.BlockSpec((d, chunk), lambda i: (0, i)),
            pl.BlockSpec((chunk,), lambda i: (i,)),
        ],
        # every grid step maps to the SAME output block: the reduction
        # stays on-chip for the whole batch
        out_specs=pl.BlockSpec((d, hi_n, lo_n), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, hi_n, lo_n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(idx, weights)
    return out.reshape(d, width)
