"""Pallas kernel: batched canonical-Huffman table decode (read-side hot loop).

The decode inner loop is the read-path twin of the paper's streaming
encoder: a prefix code forces a serial bit-cursor walk, but ONLY inside a
block — the per-block bit counts the encoder stores are exactly what lets
N blocks walk in parallel (the multi-pipeline FPGA decoder, and FZ-GPU's
block-parallel GPU decode). TPU adaptation:

  * grid = one program per CHUNK; the chunk's blocks are vector lanes.
    The fori_loop carries one bit cursor per block and every iteration
    decodes one symbol per block: window peek -> 2^16-entry table gather
    -> cursor advance. Serial in-block, parallel across blocks — the
    same structure as ``runtime/fused_decode``'s jnp lockstep walk, but
    with the chunk's bitstream and its decode table resident in VMEM for
    the whole walk instead of re-streamed from HBM every step;
  * each chunk selects its codebook's decode-table row via a
    scalar-prefetch index (``PrefetchScalarGridSpec``): the (K, 2^16)
    stacked tables stay in HBM and only the row a chunk actually needs
    is mapped to its block — chunks sharing a codebook share the row.

Bit-exactness contract: identical cursor arithmetic to the staged
decoder (``core.huffman.decode``) on the u32 reinterpretation of the u64
wire words — the same contract ``runtime/fused_decode`` keeps, enforced
by tests/test_dispatch.py against random codebooks.

Sizing: one program holds its chunk's words row, one (2^16,) int32 table
pair and the (NB, block_size) output in VMEM — fine for the block grains
the pipeline uses (words rows are ~bits/32 of the chunk). The tables are
int32 (not uint16/uint8) so the layout respects f32-class tiling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.huffman import DEFAULT_MAX_LEN
from ..dispatch import resolve_interpret

MAX_CODE_BITS = DEFAULT_MAX_LEN      # table depth the caller stages at
TBL = 1 << MAX_CODE_BITS


def _hufdec_kernel(cb_idx_ref, words_ref, nbits_ref, count_ref, sym_ref,
                   len_ref, out_ref):
    NB = nbits_ref.shape[1]
    bs = out_ref.shape[2]
    nbits = nbits_ref[...]                                   # (1, NB) i32
    ends = jnp.cumsum(nbits, axis=1)
    starts = (ends - nbits).astype(jnp.int32)                # block bit offs
    count = count_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, NB), 1)
    counts_b = jnp.clip(count - lane * bs, 0, bs)
    words = words_ref[0, :]                                  # (W,) u32
    sym_tbl = sym_ref[0, :]                                  # (TBL,) i32
    len_tbl = len_ref[0, :]

    def body(i, cursors):
        w = cursors >> 5
        b = (cursors & 31).astype(jnp.uint32)
        x0 = words[w]
        x1 = words[w + 1]
        win = (x0 << b) | jnp.where(
            b > 0, x1 >> (jnp.uint32(32) - jnp.maximum(b, jnp.uint32(1))),
            jnp.uint32(0))
        pk = (win >> jnp.uint32(32 - MAX_CODE_BITS)).astype(jnp.int32)
        sym = sym_tbl[pk]
        ln = len_tbl[pk]
        active = counts_b > i
        out_ref[0, :, i] = jnp.where(active, sym, 0)[0]
        return cursors + jnp.where(active, ln, 0)

    # tail-block early exit: the chunk's longest block holds
    # min(count, bs) symbols, so the walk stops there. Positions past
    # the bound keep the zero fill below — bit-identical to the
    # full-length loop, whose inactive lanes also wrote zeros.
    out_ref[...] = jnp.zeros_like(out_ref)
    upper = jnp.minimum(count, bs)
    jax.lax.fori_loop(0, upper, body, starts)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def hufdec(words2: jax.Array, nbits2: jax.Array, counts: jax.Array,
           sym2: jax.Array, len2: jax.Array, cb_idx: jax.Array,
           *, block_size: int, interpret: Optional[bool] = None):
    """words2 (C, W) u32; nbits2 (C, NB) i32; counts (C,) i32;
    sym2/len2 (K, 2^16) i32 stacked decode tables; cb_idx (C,) i32.

    Returns codes (C, NB, block_size) int32 (padding lanes decode to 0).
    """
    C, W = words2.shape
    NB = nbits2.shape[1]
    tbl = sym2.shape[1]
    counts2 = counts.reshape(C, 1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, W), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, NB), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, 1), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, tbl), lambda c, cb: (cb[c], 0)),
            pl.BlockSpec((1, tbl), lambda c, cb: (cb[c], 0)),
        ],
        out_specs=pl.BlockSpec((1, NB, block_size), lambda c, cb: (c, 0, 0)),
    )
    return pl.pallas_call(
        _hufdec_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, NB, block_size), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(cb_idx.astype(jnp.int32), words2, nbits2.astype(jnp.int32), counts2,
      sym2, len2)
