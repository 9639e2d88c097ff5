"""Pallas kernel: fused prequantization + Lorenzo prediction + postquant.

TPU adaptation of CEAZ Fig 5. The FPGA instantiates N dual-quant pipelines
streaming one value/cycle each; on TPU the analogue is a grid of VMEM
tiles, each program instance transforming an (ROWS x COLS) tile with pure
VPU element-wise ops — there is no loop-carried dependence (that is the
whole point of dual-quantization), so every tile is independent.

Two variants:
  * 1-D stream (`dq1d`): data reshaped (rows, cols); Lorenzo along the
    last axis with the WEST halo supplied by re-reading the input at a
    shifted BlockSpec (same trick as FPGA line buffers). Row boundaries
    reset prediction — rows are the "pipelines".
  * 2-D field (`dq2d`): full 2-D Lorenzo with west/north/north-west halos
    provided by three extra shifted views of the same operand, so the
    kernel matches the GLOBAL 2-D Lorenzo semantics exactly.

Scalars (error bound) are passed as a (1, 1) operand so changing eb does
not recompile (on real TPU this lands in SMEM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..dispatch import resolve_interpret

RADIUS = 512
NUM_SYMBOLS = 1024

# f32 native tile is (8, 128); use a few lanes' worth of columns per block.
ROWS = 8
COLS = 512


def _prequant(x, eb):
    q = jnp.rint(x / (2.0 * eb))
    q = jnp.clip(q, -2.0e9, 2.0e9)
    recon = (q * (2.0 * eb)).astype(jnp.float32)
    err = x - recon
    q = q + (err > eb).astype(q.dtype) - (err < -eb).astype(q.dtype)
    return q.astype(jnp.int32)


def _postquant(q, pred):
    delta = q - pred
    code = delta + RADIUS
    outl = (code < 1) | (code >= NUM_SYMBOLS)
    codes = jnp.where(outl, 0, code)
    return codes.astype(jnp.int32), outl, delta


def _dq1d_kernel(eb_ref, x_ref, xw_ref, codes_ref, outl_ref, delta_ref):
    eb = eb_ref[0, 0]
    j = pl.program_id(1)
    x = x_ref[...]
    q = _prequant(x, eb)
    # west halo: last column of the previous column-block (zeros at j==0)
    qw_halo = _prequant(xw_ref[...], eb)            # (ROWS, 1)
    qw_halo = jnp.where(j == 0, 0, qw_halo)
    pred = jnp.concatenate([qw_halo, q[:, :-1]], axis=1)
    codes, outl, delta = _postquant(q, pred)
    codes_ref[...] = codes
    outl_ref[...] = outl.astype(jnp.int32)
    delta_ref[...] = delta


def _dq2d_kernel(eb_ref, x_ref, xw_ref, xn_ref, xnw_ref,
                 codes_ref, outl_ref, delta_ref):
    eb = eb_ref[0, 0]
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...]
    q = _prequant(x, eb)
    qw = jnp.where(j == 0, 0, _prequant(xw_ref[...], eb))     # (ROWS, 1)
    qn = jnp.where(i == 0, 0, _prequant(xn_ref[...], eb))     # (1, COLS)
    qnw = jnp.where((i == 0) | (j == 0), 0,
                    _prequant(xnw_ref[...], eb))              # (1, 1)
    # assemble the shifted-by-one neighbours with halos
    west = jnp.concatenate([qw, q[:, :-1]], axis=1)
    north = jnp.concatenate([qn, q[:-1, :]], axis=0)
    nw_top = jnp.concatenate([qnw, qn[:, :-1]], axis=1)       # (1, COLS)
    nw_body = jnp.concatenate([qw[:-1, :], q[:-1, :-1]], axis=1)
    northwest = jnp.concatenate([nw_top, nw_body], axis=0)
    pred = west + north - northwest
    codes, outl, delta = _postquant(q, pred)
    codes_ref[...] = codes
    outl_ref[...] = outl.astype(jnp.int32)
    delta_ref[...] = delta


def _out_specs():
    blk = (ROWS, COLS)
    spec = pl.BlockSpec(blk, lambda i, j: (i, j))
    return (spec, spec, spec)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dq1d(x: jax.Array, eb: jax.Array, *, interpret: Optional[bool] = None):
    """x: (rows, cols) f32, rows % ROWS == 0, cols % COLS == 0.

    Lorenzo along axis 1 (each row an independent stream).
    Returns (codes i32, outlier i32, delta i32) of the same shape.
    """
    rows, cols = x.shape
    grid = (rows // ROWS, cols // COLS)
    eb_arr = jnp.asarray(eb, jnp.float32).reshape(1, 1)
    kernel = pl.pallas_call(
        _dq1d_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j)),
            # west halo: width-1 blocks => block index == element column
            pl.BlockSpec((ROWS, 1), lambda i, j: (i, jnp.maximum(j * COLS - 1, 0))),
        ],
        out_specs=_out_specs(),
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )
    return tuple(kernel(eb_arr, x, x))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dq2d(x: jax.Array, eb: jax.Array, *, interpret: Optional[bool] = None):
    """x: (rows, cols) f32 — GLOBAL 2-D Lorenzo via halo views."""
    rows, cols = x.shape
    grid = (rows // ROWS, cols // COLS)
    eb_arr = jnp.asarray(eb, jnp.float32).reshape(1, 1)
    kernel = pl.pallas_call(
        _dq2d_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, 1), lambda i, j: (i, jnp.maximum(j * COLS - 1, 0))),
            pl.BlockSpec((1, COLS), lambda i, j: (jnp.maximum(i * ROWS - 1, 0), j)),
            pl.BlockSpec((1, 1), lambda i, j: (jnp.maximum(i * ROWS - 1, 0),
                                               jnp.maximum(j * COLS - 1, 0))),
        ],
        out_specs=_out_specs(),
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
            jax.ShapeDtypeStruct((rows, cols), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )
    return tuple(kernel(eb_arr, x, x, x, x))


# ---------------------------------------------------------------------------
# dq_center: count-aware VMEM median (value-direct per-chunk centre)
# ---------------------------------------------------------------------------
#
# The jnp reference (`ops.chunk_center`) sorts each row and indexes the
# two middle order statistics of the valid prefix. Sorting is the wrong
# primitive for the TPU VPU; the kernel instead RADIX-SELECTS the ranked
# values: int32 keys are biased to order-preserving uint32 (x ^ 0x8000_
# 0000), invalid entries mapped to the maximal key so they rank last
# (exactly the sort-to-the-top trick of the reference), and the wanted
# rank is found by an MSB->LSB nibble descend — 8 rounds, each counting
# 16 bucket populations with pure compares/reductions (no sort, no
# scatter). Selection is by RANK, so duplicated keys return the
# identical VALUE the sorted reference indexes: the kernel is
# bit-identical to `ops.chunk_center` including its `lo + (hi - lo)//2`
# int32 tie/wrap semantics.

_KEY_BIAS = np.uint32(0x80000000)
_INVALID_KEY_SRC = np.int32(np.iinfo(np.int32).max)


def _select_rank(keys: jax.Array, rank: jax.Array) -> jax.Array:
    """Value of the `rank`-th smallest uint32 key (0-indexed)."""
    n = keys.shape[0]
    matched = jnp.ones((n,), bool)
    val = jnp.uint32(0)
    rr = rank.astype(jnp.int32)
    for shift in range(28, -1, -4):
        nibs = ((keys >> jnp.uint32(shift)) & jnp.uint32(0xF)) \
            .astype(jnp.int32)
        hit = matched[:, None] & (
            nibs[:, None] == jax.lax.broadcasted_iota(jnp.int32, (n, 16), 1))
        cnts = jnp.sum(hit, axis=0, dtype=jnp.int32)         # (16,)
        cum = jnp.cumsum(cnts)
        b = jnp.sum((cum <= rr).astype(jnp.int32))           # bucket of rank
        below = jnp.where(b > 0, cum[jnp.maximum(b - 1, 0)], 0)
        rr = rr - below
        val = val | (b.astype(jnp.uint32) << jnp.uint32(shift))
        matched = matched & (nibs == b)
    return val


def _center_from_q(q: jax.Array, valid: jax.Array) -> jax.Array:
    """Count-aware median of q's valid entries — the in-kernel core
    shared by the `dq_center` kernel and the `ceaz_chunk` megakernel.
    Bitwise-identical to ops.chunk_center on one row."""
    v = q.shape[0]
    keys = jnp.where(valid, q, _INVALID_KEY_SRC).astype(jnp.uint32) \
        ^ _KEY_BIAS
    m = jnp.sum(valid, dtype=jnp.int32)
    lo_i = jnp.maximum(m - 1, 0) // 2
    hi_i = jnp.minimum(m // 2, v - 1)
    lo = (_select_rank(keys, lo_i) ^ _KEY_BIAS).astype(jnp.int32)
    hi = (_select_rank(keys, hi_i) ^ _KEY_BIAS).astype(jnp.int32)
    return jnp.where(m > 0, lo + (hi - lo) // 2, 0).astype(jnp.int32)


def _dq_center_kernel(q_ref, valid_ref, c_ref):
    c_ref[0, 0] = _center_from_q(q_ref[0, :], valid_ref[0, :] != 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dq_center(q2: jax.Array, valid2: jax.Array, *, interpret: Optional[bool] = None):
    """q2 (C, V) i32, valid2 (C, V) -> centers (C,) i32; one radix-select
    program per chunk row (the row must fit VMEM: V <= ~1M values)."""
    C, V = q2.shape
    centers = pl.pallas_call(
        _dq_center_kernel,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, V), lambda c: (c, 0)),
            pl.BlockSpec((1, V), lambda c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q2.astype(jnp.int32), valid2.astype(jnp.int32))
    return centers[:, 0]
