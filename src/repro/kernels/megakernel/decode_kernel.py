"""Pallas decode megakernel: one program per chunk for the read path.

The write-side `ceaz_chunk` megakernel (kernel.py) collapsed encode
into one VMEM residency per chunk; this module is its inverse. A
single program instance owns one chunk row and runs, entirely in VMEM:

  table walk     the canonical-Huffman bit-cursor walk of
                 kernels/hufdec (serial in-block, one vector lane per
                 block), against the chunk's scalar-prefetched decode
                 table row;
  outlier patch  the dual-quantizer's escape symbol IS code 0, and the
                 encoder stores outlier deltas in ascending position
                 order — so the patch is a rank gather (exclusive
                 prefix count of zero-codes), not a scatter;
  inverse        both inverse dual-quant forms in one pass: the
                 Lorenzo prefix reconstruction (two-level in-row
                 prefix sum + a cross-row segment carry held in a
                 revisited (1, 1) accumulator block, the encode
                 kernel's histogram-accumulation pattern) and the
                 value-direct centre add, selected per row at runtime
                 (`islor`) so mixed groups decode in one launch.

No intermediate (decoded codes, deltas, ranks) ever leaves VMEM; the
program's q row is the op's output. Chunks past `_DEC_FUSE_LIMIT`
values cannot hold a whole row per program — ops.py runs the word-tiled
walk below (`hufdec_tiles`, the hufenc tiling scheme: bounded word
windows placed by scalar-prefetched offsets) and the shared jnp
`ref.patch_and_inverse` tail instead; codes cross HBM exactly once
there, by physical necessity.

Garbage-bit termination contract: the walk is a `fori_loop` bounded by
min(count, block_size) and every cursor access is clamped into the
words window, so arbitrarily corrupted payload bits can decode to
nonsense but can neither hang the walk nor read out of bounds. (The
decoded VALUES on garbage are unspecified — stream CRCs reject
corrupted payloads before any decode path runs; the differential-fuzz
fence in tests/test_engine.py holds all impls to identical verdicts.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import dualquant as core_dq
from ..hufdec.kernel import MAX_CODE_BITS, TBL

RADIUS = core_dq.RADIUS

# one fused program holds the chunk's words row, a (2^16,) i32 table
# pair and the (NB, block_size) q row in VMEM: past this many values,
# ops.py switches to the word-tiled walk + shared jnp tail
_DEC_FUSE_LIMIT = 1 << 17
# values per word-tiled walk program (matches the encode TILE_SEG grain)
_DEC_TILE_VALUES = 1 << 15


def _walk_window(words, cursors, cmax):
    """One decode step's window peek, cursor-clamped into the resident
    words window — identical arithmetic to kernels/hufdec on valid
    streams (where the clamp never binds), bounded on garbage."""
    cur = jnp.clip(cursors, 0, cmax)
    w = cur >> 5
    b = (cur & 31).astype(jnp.uint32)
    x0 = words[w]
    x1 = words[w + 1]
    win = (x0 << b) | jnp.where(
        b > 0, x1 >> (jnp.uint32(32) - jnp.maximum(b, jnp.uint32(1))),
        jnp.uint32(0))
    return (win >> jnp.uint32(32 - MAX_CODE_BITS)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The fused single-program kernel (NB*block_size <= _DEC_FUSE_LIMIT)
# ---------------------------------------------------------------------------

def _dec_fused_kernel(cb_idx_ref, words_ref, nbits_ref, count_ref,
                      base_ref, islor_ref, reset_ref, odelta_ref,
                      sym_ref, len_ref, out_ref, carry_ref):
    NB = nbits_ref.shape[1]
    bs = out_ref.shape[2]
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    # -- stage 1: the bit-cursor table walk (kernels/hufdec body) --------
    nbits = nbits_ref[...]                                   # (1, NB) i32
    ends = jnp.cumsum(nbits, axis=1)
    starts = (ends - nbits).astype(jnp.int32)
    count = count_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, NB), 1)
    counts_b = jnp.clip(count - lane * bs, 0, bs)
    words = words_ref[0, :]                                  # (W,) u32
    sym_tbl = sym_ref[0, :]
    len_tbl = len_ref[0, :]
    cmax = (words.shape[0] - 2) * 32 + 31

    def body(i, cursors):
        pk = _walk_window(words, cursors, cmax)
        sym = sym_tbl[pk]
        ln = len_tbl[pk]
        active = counts_b > i
        out_ref[0, :, i] = jnp.where(active, sym, 0)[0]
        return cursors + jnp.where(active, ln, 0)

    out_ref[...] = jnp.zeros_like(out_ref)
    upper = jnp.minimum(count, bs)
    jax.lax.fori_loop(0, upper, body, starts)

    # -- stage 2: rank-gather outlier patch ------------------------------
    codes = out_ref[0, :, :]                                 # (NB, bs) i32
    bidx = jax.lax.broadcasted_iota(jnp.int32, (NB, bs), 0)
    iidx = jax.lax.broadcasted_iota(jnp.int32, (NB, bs), 1)
    valid = bidx * bs + iidx < count
    is_out = valid & (codes == 0)
    io32 = is_out.astype(jnp.int32)
    # flat-order exclusive zero-count: in-row prefix + block offsets
    row_c = jnp.cumsum(io32, axis=1)
    blk_tot = row_c[:, -1:]
    blk_off = jnp.cumsum(blk_tot, axis=0) - blk_tot
    rank = blk_off + row_c - io32
    odelta = odelta_ref[0, :]
    Ko = odelta.shape[0]
    dval = odelta[jnp.clip(rank, 0, Ko - 1)]
    delta = jnp.where(is_out, dval, codes - RADIUS)
    delta = jnp.where(valid, delta, 0)

    # -- stage 3: inverse dual-quant, both forms -------------------------
    loc = jnp.cumsum(delta, axis=1, dtype=jnp.int32)
    row_sum = loc[:, -1:]
    row_off = jnp.cumsum(row_sum, axis=0) - row_sum
    carry_in = jnp.where(reset_ref[0, 0] != 0, 0, carry_ref[0, 0])
    q_lor = loc + row_off + carry_in
    q_val = delta + base_ref[0, 0]
    q = jnp.where(islor_ref[0, 0] != 0, q_lor, q_val)
    out_ref[0, :, :] = jnp.where(valid, q, 0)
    carry_ref[0, 0] = carry_in + row_off[-1, 0] + row_sum[-1, 0]


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def ceaz_chunk_dec_fused(words2, nbits2, counts, sym2, len2, cb_idx,
                         odelta2, base, seg0, islor, *, block_size: int,
                         interpret: bool):
    """Grid (C,): one fused decode program per chunk row. Returns
    q (C, NB*block_size) i32, bit-identical to ref.ceaz_chunk_dec.

    The Lorenzo segment carry is a revisited (1, 1) output block with a
    constant index map: the sequential TPU grid keeps it VMEM-resident
    across programs, each row resetting it where `seg0[c] == c` —
    which is why a segment's rows must be contiguous ascending.
    """
    C, W = words2.shape
    NB = nbits2.shape[1]
    tbl = sym2.shape[1]
    Ko = odelta2.shape[1]
    counts2 = counts.reshape(C, 1).astype(jnp.int32)
    base2 = base.reshape(C, 1).astype(jnp.int32)
    islor2 = islor.reshape(C, 1).astype(jnp.int32)
    reset2 = (seg0.astype(jnp.int32)
              == jnp.arange(C, dtype=jnp.int32)).astype(
                  jnp.int32).reshape(C, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, W), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, NB), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, 1), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, 1), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, 1), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, 1), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, Ko), lambda c, cb: (c, 0)),
            pl.BlockSpec((1, tbl), lambda c, cb: (cb[c], 0)),
            pl.BlockSpec((1, tbl), lambda c, cb: (cb[c], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, NB, block_size), lambda c, cb: (c, 0, 0)),
            pl.BlockSpec((1, 1), lambda c, cb: (0, 0)),
        ],
    )
    q3, _carry = pl.pallas_call(
        _dec_fused_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((C, NB, block_size), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(cb_idx.astype(jnp.int32), words2, nbits2.astype(jnp.int32),
      counts2, base2, islor2, reset2, odelta2.astype(jnp.int32),
      sym2, len2)
    return q3.reshape(C, NB * block_size)


# ---------------------------------------------------------------------------
# Word-tiled walk (NB*block_size > _DEC_FUSE_LIMIT)
# ---------------------------------------------------------------------------
#
# The hufenc tiling scheme, read-side: each program owns a bounded run
# of blocks and ONE word window placed by scalar-prefetched offsets.
# The window offsets come from the cumulative per-block bit counts —
# known BEFORE any decoding, which is exactly what makes the tiles
# independent. Window-coverage bound: a tile of tb blocks spans at most
# tb*block_size*MAX_CODE_BITS payload bits, so a window of that many
# words (+3 slack: start-bit skew, the x1 peek, rounding) always covers
# the tile's walk — staged words rows carry >= 2 words of tail slack
# (runtime/fused_decode staging), so the clamped window stays in range.

def _dec_tile_kernel(cb_idx_ref, foff_ref, tbit_ref, words_ref,
                     nbits_ref, count_ref, sym_ref, len_ref, out_ref):
    c = pl.program_id(0)
    t = pl.program_id(1)
    tb = nbits_ref.shape[1]
    bs = out_ref.shape[2]
    nbits = nbits_ref[...]                                   # (1, tb) i32
    ends = jnp.cumsum(nbits, axis=1)
    starts = (tbit_ref[c, t] + ends - nbits).astype(jnp.int32)
    count = count_ref[0, 0]
    lane = t * tb + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    counts_b = jnp.clip(count - lane * bs, 0, bs)
    words = words_ref[0, :]
    sym_tbl = sym_ref[0, :]
    len_tbl = len_ref[0, :]
    cmax = (words.shape[0] - 2) * 32 + 31

    def body(i, cursors):
        pk = _walk_window(words, cursors, cmax)
        sym = sym_tbl[pk]
        ln = len_tbl[pk]
        active = counts_b > i
        out_ref[0, :, i] = jnp.where(active, sym, 0)[0]
        return cursors + jnp.where(active, ln, 0)

    out_ref[...] = jnp.zeros_like(out_ref)
    # the tile's fullest block is its first lane
    upper = jnp.clip(count - t * tb * bs, 0, bs)
    jax.lax.fori_loop(0, upper, body, starts)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def hufdec_tiles(words2, nbits2, counts, sym2, len2, cb_idx, *,
                 block_size: int, interpret: bool):
    """Word-tiled twin of the fused kernel's walk stage: same decoded
    codes (C, NB*block_size) i32, VMEM per program bounded by
    (_DEC_TILE_VALUES, block_size) instead of the whole chunk row."""
    C, W = words2.shape
    NB = nbits2.shape[1]
    tbl = sym2.shape[1]
    tb = max(1, _DEC_TILE_VALUES // block_size)
    nt = -(-NB // tb)
    nbp = nt * tb
    nbits_p = jnp.zeros((C, nbp), jnp.int32).at[:, :NB].set(
        nbits2.astype(jnp.int32))
    ends = jnp.cumsum(nbits_p, axis=1, dtype=jnp.int32)
    g0 = (ends - nbits_p).reshape(C, nt, tb)[:, :, 0]        # tile head bit
    win = (tb * block_size * MAX_CODE_BITS) // 32 + 3
    Wp = max(W, win)
    words_p = jnp.zeros((C, Wp), jnp.uint32).at[:, :W].set(words2)
    foff = jnp.clip(g0 >> 5, 0, Wp - win).astype(jnp.int32)
    tbit = (g0 - foff * 32).astype(jnp.int32)
    counts2 = counts.reshape(C, 1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(C, nt),
        in_specs=[
            pl.BlockSpec((1, pl.Element(win)),
                         lambda c, t, cb, foff, tbit: (c, foff[c, t])),
            pl.BlockSpec((1, tb), lambda c, t, cb, foff, tbit: (c, t)),
            pl.BlockSpec((1, 1), lambda c, t, cb, foff, tbit: (c, 0)),
            pl.BlockSpec((1, tbl),
                         lambda c, t, cb, foff, tbit: (cb[c], 0)),
            pl.BlockSpec((1, tbl),
                         lambda c, t, cb, foff, tbit: (cb[c], 0)),
        ],
        out_specs=pl.BlockSpec((1, tb, block_size),
                               lambda c, t, cb, foff, tbit: (c, t, 0)),
    )
    codes = pl.pallas_call(
        _dec_tile_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, nbp, block_size), jnp.int32),
        interpret=interpret,
    )(cb_idx.astype(jnp.int32), foff, tbit, words_p, nbits_p, counts2,
      sym2, len2)
    return codes.reshape(C, nbp * block_size)[:, :NB * block_size]
