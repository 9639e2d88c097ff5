"""Pallas kernel: 1024-bin quant-code histogram.

Grid iterates over code tiles; each program instance computes a partial
histogram of its (ROWS x COLS) tile via sliced one-hot reductions (the
TPU-native replacement for scatter-add: compare-against-bins is pure VPU
work and the bin dimension stays a 128-lane multiple), accumulating into a
single (1, 1024) output block that every grid step maps to (TPU grids are
sequential => safe accumulation; first step zero-initializes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..dispatch import resolve_interpret

NUM_SYMBOLS = 1024
ROWS = 8
COLS = 512
BIN_SLICE = 128


def _hist_kernel(codes_ref, hist_ref):
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    c = codes_ref[...].reshape(-1)                    # (ROWS*COLS,)
    for s in range(0, NUM_SYMBOLS, BIN_SLICE):        # static unroll
        bins = s + jax.lax.broadcasted_iota(jnp.int32, (1, BIN_SLICE), 1)
        onehot = (c[:, None] == bins).astype(jnp.int32)
        hist_ref[0, s:s + BIN_SLICE] += onehot.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def histogram(codes: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    """codes: (rows, cols) int32 in [0, 1024); returns (1024,) int32."""
    rows, cols = codes.shape
    grid = (rows // ROWS, cols // COLS)
    out = pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, NUM_SYMBOLS), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, NUM_SYMBOLS), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes)
    return out[0]
