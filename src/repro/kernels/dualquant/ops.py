"""Public jit'd wrappers around the dualquant kernel (padding + reshaping).

Two entry points with DIFFERENT prediction semantics (both faithful):

  * `dual_quantize(x, eb, ndim)` — field compression path.
    ndim==2 uses the Pallas kernel with halo views => EXACT global 2-D
    Lorenzo (bit-identical to core.dualquant). ndim 1/3 fall back to the
    pure-jnp core (global semantics) so the host decompressor's global
    inverse always applies.

  * `stream_quantize(x, eb, pipelines=64)` — streaming path (fixed-ratio
    collectives). Data is laid out as `pipelines` independent rows, each
    row a prediction stream (exactly the paper's N FPGA pipelines, which
    also carry independent prediction contexts). Pair with
    `stream_dequantize` — NOT with the global inverse.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core import dualquant as core_dq
from . import kernel as K


def _pad2d(x, mr, mc):
    r, c = x.shape
    pr = (-r) % mr
    pc = (-c) % mc
    if pr or pc:
        # edge-pad so padded cells quantize near their neighbours (no
        # spurious outliers in the padded region)
        x = jnp.pad(x, ((0, pr), (0, pc)), mode="edge")
    return x, r, c


def dual_quantize(x: jax.Array, eb, ndim: int, *, interpret: Optional[bool] = None):
    """Returns (codes i32, outlier bool, delta i32) with x's shape.

    Global Lorenzo semantics for every ndim (kernel used when ndim==2).
    """
    x = jnp.asarray(x, jnp.float32)
    if ndim == 2:
        padded, r, c = _pad2d(x, K.ROWS, K.COLS)
        codes, outl, delta = K.dq2d(padded, eb, interpret=interpret)
        return (codes[:r, :c], outl[:r, :c].astype(bool), delta[:r, :c])
    codes, outl, delta = core_dq.dual_quantize(x, float(eb), ndim)
    return codes.astype(jnp.int32), outl, delta


def _stream_layout(n: int, pipelines: int):
    rows = pipelines
    cols = -(-n // rows)
    cols = -(-cols // K.COLS) * K.COLS          # multiple of COLS
    rows = -(-rows // K.ROWS) * K.ROWS          # multiple of ROWS
    return rows, cols


def stream_quantize(x: jax.Array, eb, pipelines: int = 64,
                    *, interpret: Optional[bool] = None):
    """Flat stream -> (codes, outlier, delta), row-local prediction.

    Returns arrays flattened back to x's shape. Prediction resets
    `pipelines` times across the stream (<= 64 escapes per array).
    """
    x = jnp.asarray(x, jnp.float32)
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows, cols = _stream_layout(n, pipelines)
    padded = jnp.pad(flat, (0, rows * cols - n), mode="edge")
    padded = padded.reshape(rows, cols)
    codes, outl, delta = K.dq1d(padded, eb, interpret=interpret)
    unflat = lambda a: a.reshape(-1)[:n].reshape(x.shape)
    return unflat(codes), unflat(outl).astype(bool), unflat(delta)


@jax.jit
def chunk_center(q2: jax.Array, valid2: jax.Array) -> jax.Array:
    """Per-chunk centre code: count-aware median of each row's valid set.

    This is the `dq_center` dispatch op — the device promotion of the
    host ``np.median`` the staged value-direct path used. q2 (C, V)
    int32 quantized values, valid2 (C, V) bool. Invalid (padding)
    entries sort to the top and are excluded by indexing with the
    per-row valid count, so a padded batched row computes the SAME
    centre as an unpadded single-chunk row.

    Tie rule for even counts: ``lo + (hi - lo) // 2`` on the two middle
    order statistics — a deliberate, overflow-free integer variant of
    numpy's float median (any consistent centre is a valid model; the
    staged jax-backend twin uses this op, so both paths agree bitwise).
    Rows with no valid entries centre at 0.
    """
    q2 = q2.astype(jnp.int32)
    qm = jnp.where(valid2, q2, jnp.iinfo(jnp.int32).max)
    s = jnp.sort(qm, axis=1)
    m = valid2.sum(axis=1).astype(jnp.int32)
    lo_i = jnp.maximum(m - 1, 0) // 2
    hi_i = jnp.minimum(m // 2, q2.shape[1] - 1)
    lo = jnp.take_along_axis(s, lo_i[:, None], axis=1)[:, 0]
    hi = jnp.take_along_axis(s, hi_i[:, None], axis=1)[:, 0]
    return jnp.where(m > 0, lo + (hi - lo) // 2, 0).astype(jnp.int32)


# One dq_center program holds its whole row in VMEM (V i32 values + the
# one-hot nibble counts); past this the kernel would spill, so the
# wrapper falls back to the bit-identical jnp sort.
_CENTER_ROW_LIMIT = 1 << 20


def dq_center(q2: jax.Array, valid2: jax.Array, *, interpret=None):
    """The `dq_center` dispatch op's 'pallas' implementation: per-row
    radix-select median kernel, bit-identical to :func:`chunk_center`
    (rows larger than VMEM fall back to it)."""
    q2 = jnp.asarray(q2)
    if q2.shape[1] > _CENTER_ROW_LIMIT:
        return chunk_center(q2, jnp.asarray(valid2))
    return K.dq_center(q2, jnp.asarray(valid2), interpret=interpret)


def stream_dequantize(delta: jax.Array, eb, pipelines: int = 64):
    """Inverse of `stream_quantize`: per-row cumsum then de-scale."""
    flat = delta.reshape(-1)
    n = flat.shape[0]
    rows, cols = _stream_layout(n, pipelines)
    d = jnp.pad(flat, (0, rows * cols - n)).reshape(rows, cols)
    q = jnp.cumsum(d, axis=1, dtype=jnp.int32)
    out = q.astype(jnp.float32) * (2.0 * jnp.asarray(eb, jnp.float32))
    return out.reshape(-1)[:n].reshape(delta.shape)
