"""Dual-quantization (cuSZ-style) with Lorenzo prediction, adapted for TPU.

The paper (CEAZ §3.1, Fig 5) adopts cuSZ's two-phase dual-quantization to
remove the loop-carried dependency of classic SZ:

  1. *prequantization*   q  = round(d / (2*eb))            (element-wise)
  2. *prediction*        p  = lorenzo(neighbours(q))       (on quantized ints)
  3. *postquantization*  dl = q - p                        (element-wise)

Because prediction runs on already-quantized integers, reconstruction is
EXACT in integer space: the inverse of the Lorenzo operator over deltas is a
multi-axis inclusive prefix-sum (cumsum), so no error feedback loop is
needed and every element can be processed independently — the property the
FPGA (and our TPU kernels) exploit for full pipelining.

Symbols: delta is mapped to a code in [0, 2*RADIUS) with code 0 reserved as
the outlier escape (|delta| >= RADIUS), matching SZ's quantization-bin
layout with 1024 bins.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

RADIUS = 512          # quantization-code radius -> 1024 symbols
NUM_SYMBOLS = 2 * RADIUS
OUTLIER_CODE = 0      # escape symbol: delta stored out-of-band


def value_range(x: np.ndarray) -> float:
    """max - min as a python float: the relative-bound scale. Python
    floats make inf - inf a quiet NaN (numpy scalars warn, and repro
    warnings are errors); NaN/zero ranges fall back to 1.0 so
    non-finite or constant arrays still get a finite bound. Shared by
    the facade, the rate-control calibration and the batched fused
    path so grouping never changes the bound."""
    return extrema_range(float(np.max(x)), float(np.min(x)))


def extrema_range(hi: float, lo: float) -> float:
    """:func:`value_range` from an array's max and min."""
    vrange = float(hi) - float(lo)
    return vrange if np.isfinite(vrange) and vrange != 0.0 else 1.0


def prequantize(x: jax.Array, eb: float) -> jax.Array:
    """q = round(x / (2*eb)) as int32 (the paper's prequantization).

    Includes a bound-tightening step: the guarantee must hold for the
    *float32-rounded* reconstruction f32(2*eb*q), whose cast can add up to
    0.5 ulp on top of eb. Where violated, q is nudged one bin toward x
    (requires 2*eb > ulp(x), true for any practical relative bound).
    """
    xf = x.astype(jnp.float32)
    q = jnp.rint(xf / (2.0 * eb))
    # clamp to int32-safe range; practical value ranges divided by 2*eb stay
    # far below this for any sane relative error bound (>= 1e-8).
    q = jnp.clip(q, -2.0e9, 2.0e9)
    recon = (q * (2.0 * eb)).astype(jnp.float32)
    err = xf - recon
    q = q + (err > eb).astype(q.dtype) - (err < -eb).astype(q.dtype)
    return q.astype(jnp.int32)


def lorenzo_predict(q: jax.Array, ndim: int) -> jax.Array:
    """Lorenzo prediction on the pre-quantized field.

    1-D: p[i]     = q[i-1]
    2-D: p[i,j]   = q[i-1,j] + q[i,j-1] - q[i-1,j-1]
    3-D: p[i,j,k] = q[i-1,.,.] + q[.,j-1,.] + q[.,.,k-1]
                  - q[i-1,j-1,.] - q[i-1,.,k-1] - q[.,j-1,k-1]
                  + q[i-1,j-1,k-1]
    Out-of-range neighbours are 0 (SZ convention).
    """
    if ndim not in (1, 2, 3):
        raise ValueError(f"Lorenzo predictor supports ndim 1..3, got {ndim}")
    if q.ndim != ndim:
        raise ValueError(f"rank mismatch: array rank {q.ndim} vs ndim {ndim}")

    def shift(a, axes):
        """Shift +1 along each axis in `axes`, zero-padding at the front."""
        for ax in axes:
            pad = [(0, 0)] * a.ndim
            pad[ax] = (1, 0)
            a = jnp.pad(a, pad)[tuple(
                slice(0, -1) if i == ax else slice(None) for i in range(a.ndim)
            )]
        return a

    if ndim == 1:
        return shift(q, (0,))
    if ndim == 2:
        return shift(q, (0,)) + shift(q, (1,)) - shift(q, (0, 1))
    return (shift(q, (0,)) + shift(q, (1,)) + shift(q, (2,))
            - shift(q, (0, 1)) - shift(q, (0, 2)) - shift(q, (1, 2))
            + shift(q, (0, 1, 2)))


def postquantize(q: jax.Array, pred: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """delta = q - pred -> (codes uint16, is_outlier bool).

    Codes 1..1023 encode delta in [-RADIUS+1, RADIUS-1]; code 0 escapes.
    """
    delta = q - pred
    code = delta + RADIUS
    outlier = (code < 1) | (code >= NUM_SYMBOLS)
    codes = jnp.where(outlier, OUTLIER_CODE, code).astype(jnp.uint16)
    return codes, outlier


@functools.partial(jax.jit, static_argnames=("ndim",))
def dual_quantize(x: jax.Array, eb: float, ndim: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full dual-quantization: x -> (codes, is_outlier, delta).

    `delta` (int32) is returned densely so callers can extract the sparse
    outlier values on the host (variable-length data lives off the jit path,
    exactly like the FPGA keeps the escape FIFO off the fixed pipeline).
    """
    q = prequantize(x, eb)
    pred = lorenzo_predict(q, ndim)
    delta = q - pred
    codes, outlier = postquantize(q, pred)
    return codes, outlier, delta


def deltas_from_codes(codes: jax.Array, outlier_delta_dense: jax.Array
                      ) -> jax.Array:
    """Merge in-band codes and dense outlier deltas back into delta array."""
    inband = codes.astype(jnp.int32) - RADIUS
    return jnp.where(codes == OUTLIER_CODE, outlier_delta_dense, inband)


@functools.partial(jax.jit, static_argnames=("ndim",))
def inverse_lorenzo(delta: jax.Array, ndim: int) -> jax.Array:
    """Exact inverse of (I - Lorenzo): multi-axis inclusive cumsum.

    The Lorenzo delta is the n-D discrete mixed difference of q, so q is
    recovered by an inclusive prefix sum along each axis in turn. Integer
    arithmetic -> bit-exact reconstruction.
    """
    q = delta
    for ax in range(ndim):
        q = jnp.cumsum(q, axis=ax, dtype=jnp.int32)
    return q


@functools.partial(jax.jit, static_argnames=("ndim",))
def dequantize(delta: jax.Array, eb: float, ndim: int) -> jax.Array:
    """delta codes -> reconstructed floats (|x_hat - x| <= eb guaranteed)."""
    q = inverse_lorenzo(delta, ndim)
    return q.astype(jnp.float32) * (2.0 * eb)


# ---------------------------------------------------------------------------
# Value-direct quantization (predictor='none'): for noise-like data
# (model weights, optimizer moments, turbulent fields) the Lorenzo delta is
# LARGER than the value spread, so CEAZ's checkpoint path quantizes values
# directly around a per-chunk centre code instead. Beyond-paper extension —
# see DESIGN.md §beyond-paper.
# ---------------------------------------------------------------------------

def np_value_quantize(x: np.ndarray, eb: float):
    """-> (codes u16, outlier mask, delta int64, center int64)."""
    xf = np.asarray(x, dtype=np.float64)
    # non-finite inputs produce NaNs mid-computation by design (they
    # quantize to clipped codes; comparisons against NaN are false, so
    # the tighten step leaves q alone) — not a numerics bug to warn on
    with np.errstate(invalid="ignore"):
        q = np.rint(xf / (2.0 * eb))
        q = np.clip(np.nan_to_num(q), -2.0e18, 2.0e18).astype(np.int64)
        out_dtype = (x.dtype if x.dtype in (np.float32, np.float64)
                     else np.float32)
        recon = (q * (2.0 * eb)).astype(out_dtype).astype(np.float64)
        err = xf - recon
        q = q + (err > eb).astype(np.int64) - (err < -eb).astype(np.int64)
    center = int(np.median(q))
    delta = q - center
    code = delta + RADIUS
    outlier = (code < 1) | (code >= NUM_SYMBOLS)
    codes = np.where(outlier, OUTLIER_CODE, code).astype(np.uint16)
    return codes, outlier, delta, center


def np_value_dequantize(delta: np.ndarray, center: int, eb: float,
                        dtype=np.float32) -> np.ndarray:
    q = delta.astype(np.int64) + center
    return (q.astype(np.float64) * (2.0 * eb)).astype(dtype)


_jit_prequantize = jax.jit(prequantize)


@jax.jit
def value_postquantize(q: jax.Array, center: jax.Array
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """delta/codes/outlier for value-direct quantization (device twin).

    `center` broadcasts against `q` (a scalar for one chunk, (C, 1) for
    a batch of chunk rows). int32 arithmetic throughout: delta can wrap
    for |q - center| >= 2^31, exactly as the staged path's int64 delta
    wraps when cast to the int32 escape channel — both paths wrap to
    the same bits, and the wrap only occurs beyond the value/(2*eb)
    ~ 2e9 envelope the f32 prequantize clip already imposes.
    """
    delta = q.astype(jnp.int32) - center.astype(jnp.int32)
    code = delta + RADIUS
    outlier = (code < 1) | (code >= NUM_SYMBOLS)
    codes = jnp.where(outlier, OUTLIER_CODE, code).astype(jnp.uint16)
    return codes, outlier, delta


def value_quantize(x, eb: float, kernel_impl: str = "auto"):
    """Device (f32/int32) twin of :func:`np_value_quantize`.

    Quantizes one chunk with :func:`prequantize` (f32 arithmetic, the
    same formula the Lorenzo fused path uses) and centres it with the
    `dq_center` dispatch op — the device promotion of the host
    ``np.median``. This is the value-direct reference for the jax
    backend: the fused pipeline (runtime/fused.py) runs the identical
    ops batched, so staged backend='jax' and fused outputs are
    bit-identical by construction. The numpy backend keeps
    :func:`np_value_quantize` (float64/int64 headroom) as its own
    reference.

    -> (codes u16, outlier bool, delta int32, center int) as numpy.
    """
    from ..kernels import dispatch  # local import: no cycle at import time
    flat = jnp.asarray(np.asarray(x).reshape(-1), jnp.float32)
    # eb must be a traced argument (not an eager constant): a folded
    # constant lets XLA rewrite x/(2eb) as a reciprocal multiply, whose
    # f32 rounding differs from the fused pass's runtime division
    q = _jit_prequantize(flat, eb)
    center_fn = dispatch.resolve("dq_center", kernel_impl)
    center = center_fn(q[None, :], jnp.ones((1, q.shape[0]), bool))
    codes, outlier, delta = value_postquantize(q, center[0])
    return (np.asarray(codes), np.asarray(outlier), np.asarray(delta),
            int(center[0]))


# ---------------------------------------------------------------------------
# Host-side (numpy) twins used by the checkpoint/restore path where we want
# int64 headroom and no device round-trips.
# ---------------------------------------------------------------------------

def np_dual_quantize(x: np.ndarray, eb: float, ndim: int):
    xf = np.asarray(x, dtype=np.float64)
    # see np_value_quantize: NaNs mid-computation are the designed
    # escape for non-finite inputs, not a numerics bug to warn on
    with np.errstate(invalid="ignore"):
        q = np.rint(xf / (2.0 * eb))
        q = np.clip(np.nan_to_num(q), -2.0e18, 2.0e18).astype(np.int64)
        # bound-tighten against the output-dtype reconstruction (see
        # prequantize)
        out_dtype = (x.dtype if x.dtype in (np.float32, np.float64)
                     else np.float32)
        recon = (q * (2.0 * eb)).astype(out_dtype).astype(np.float64)
        err = xf - recon
        q = q + (err > eb).astype(np.int64) - (err < -eb).astype(np.int64)

    def shift(a, axes):
        for ax in axes:
            a = np.roll(a, 1, axis=ax)
            idx = [slice(None)] * a.ndim
            idx[ax] = 0
            a = a.copy()
            a[tuple(idx)] = 0
        return a

    if ndim == 1:
        pred = shift(q, (0,))
    elif ndim == 2:
        pred = shift(q, (0,)) + shift(q, (1,)) - shift(q, (0, 1))
    elif ndim == 3:
        pred = (shift(q, (0,)) + shift(q, (1,)) + shift(q, (2,))
                - shift(q, (0, 1)) - shift(q, (0, 2)) - shift(q, (1, 2))
                + shift(q, (0, 1, 2)))
    else:
        raise ValueError(ndim)
    delta = q - pred
    code = delta + RADIUS
    outlier = (code < 1) | (code >= NUM_SYMBOLS)
    codes = np.where(outlier, OUTLIER_CODE, code).astype(np.uint16)
    return codes, outlier, delta


def np_dequantize(delta: np.ndarray, eb: float, ndim: int,
                  dtype=np.float32) -> np.ndarray:
    q = delta.astype(np.int64)
    for ax in range(ndim):
        q = np.cumsum(q, axis=ax)
    return (q.astype(np.float64) * (2.0 * eb)).astype(dtype)
