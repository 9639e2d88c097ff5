"""Pallas kernel: Huffman encode (codebook gather + in-block bit packing).

This is the stage the paper identifies as the post-dual-quant bottleneck
(§2.4) and solves on FPGA with a streaming encoder. TPU adaptation:

  * the 1024-entry canonical codebook (codeword values + lengths) is a
    small operand every grid step maps to block (0, 0) — on real TPU it
    lives in VMEM and is scalar-gathered (SMEM would also fit it);
  * each program instance packs ONE block of `BLOCK` symbols into its own
    bitstream via a fori_loop carrying (word index, bits-in-word,
    accumulator) — serial per block, parallel ACROSS blocks. This is
    exactly the FPGA structure: one pipeline = one serial bit packer, N
    pipelines in parallel. Per-block bit counts come out alongside so
    decode is block-parallel.

Packing layout: MSB-first u32 words, one padded (BLOCK/2)-word row per
block (worst case 16 bits/symbol); `nbits[b]` gives the valid bit count.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import resolve_interpret

BLOCK = 4096                   # symbols per block (bitstream unit)
MAX_CODE_LEN = 16
WORDS = BLOCK * MAX_CODE_LEN // 32   # 2048 u32 words, worst case
_M32 = np.uint32(0xFFFFFFFF)         # numpy scalar => inlined literal


def _hufenc_kernel(codes_ref, cw_ref, ln_ref, words_ref, nbits_ref):
    words_ref[...] = jnp.zeros_like(words_ref)

    def body(k, carry):
        wi, bits, acc = carry
        sym = codes_ref[0, k]
        v = cw_ref[0, sym].astype(jnp.uint32)
        ln = ln_ref[0, sym].astype(jnp.int32)
        space = 32 - bits
        fits = ln <= space
        # path A (fits): append to accumulator
        sh_fit = jnp.clip(space - ln, 0, 31).astype(jnp.uint32)
        acc_fit = acc | ((v << sh_fit) & _M32)
        full_fit = bits + ln == 32
        # path B (split): top bits complete word wi, rest starts new acc
        over = jnp.clip(ln - space, 1, 31).astype(jnp.uint32)
        acc_split_done = acc | (v >> over)
        acc_split_new = (v << (jnp.uint32(32) - over)) & _M32
        # one store per iteration: the (possibly still partial) word at wi.
        # Partial stores are overwritten on later iterations at the same wi;
        # completed words are never revisited (wi strictly advances).
        store_val = jnp.where(fits, acc_fit, acc_split_done)
        words_ref[0, wi] = store_val
        new_wi = wi + jnp.where(fits, full_fit.astype(jnp.int32), 1)
        new_acc = jnp.where(fits, jnp.where(full_fit, jnp.uint32(0), acc_fit),
                            acc_split_new)
        new_bits = jnp.where(fits, jnp.where(full_fit, 0, bits + ln),
                             ln - space)
        return new_wi, new_bits, new_acc

    wi, bits, acc = jax.lax.fori_loop(
        0, BLOCK, body, (jnp.int32(0), jnp.int32(0), jnp.uint32(0)))
    words_ref[0, wi] = acc                     # flush trailing partial word
    nbits_ref[0, 0] = wi * 32 + bits


# ---------------------------------------------------------------------------
# Gather-pack variant: the fused pipeline's pass-2 inner loop
# ---------------------------------------------------------------------------
#
# The serial kernel above emits one padded word row PER BLOCK; the fused
# pipeline (runtime/fused.py) needs the chunk's bitstream CONTIGUOUS
# across block boundaries — the staged huffman.encode wire layout. The
# gather-pack formulation inverts the parallelism: instead of one serial
# packer per block, every OUTPUT word is computed independently by
# gathering the <=`cands` codewords that overlap it (a 16-bit-max code
# means at most 32 symbols start inside a 32-bit word, plus one spilling
# in from the left). The per-symbol bit offsets come from one in-kernel
# prefix sum; the first overlapping symbol of each word from a vectorized
# binary search over those offsets. All gathers and VPU ops — the scatter
# the naive formulation needs never appears.
#
# One program = one chunk: codes row, its codebook row and the output
# words row live in VMEM for the whole pack. w32 is provisioned by the
# caller from the exact payload bits (hist . lengths on the host), so
# VMEM holds ~the real bit-rate, not the 16-bit worst case. Chunks past
# a few hundred KB of codes per program go through the word-tiled grid
# (`gather_pack_tiled` below), which bounds VMEM per program.

def _compose_words(ends, starts, lens, vals, w_bit, cands: int):
    """Shared gather-pack core: OR-compose each output word from the
    <= `cands` codewords overlapping it.

    `ends`/`starts`/`lens`/`vals` are per-symbol GLOBAL bit offsets and
    gathered codewords (any window of the stream, as long as every
    symbol overlapping a requested word is present); `w_bit` the global
    bit offset of each requested u32 word. A vectorized binary search
    replays searchsorted(ends, w_bit, side='right') — #(ends <= w_bit),
    the first symbol covering each word — then the candidate window is
    gathered and summed (bit-disjoint => sum == or). Bit-identical to
    ref.encode_pack.
    """
    n = ends.shape[0]
    nw = w_bit.shape[0]
    lo = jnp.zeros((nw,), jnp.int32)
    hi = jnp.full((nw,), n, jnp.int32)
    for _ in range(max(int(n).bit_length(), 1)):
        active = lo < hi
        mid = (lo + hi) >> 1
        e = ends[jnp.clip(mid, 0, n - 1)]
        go = active & (e <= w_bit)
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(active & ~go, mid, hi)

    cand = lo[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (nw, cands), 1)
    in_range = cand < n
    ci = jnp.clip(cand, 0, n - 1)
    off = starts[ci] - w_bit[:, None]
    ln = lens[ci]
    v = vals[ci]
    left = 32 - off - ln
    live = in_range & (off < 32) & (off + ln > 0)
    ls = jnp.clip(left, 0, 31).astype(jnp.uint32)
    rs = jnp.clip(-left, 0, 31).astype(jnp.uint32)
    shifted = jnp.where(left >= 0, v << ls, v >> rs)
    return jnp.where(live, shifted, jnp.uint32(0)).sum(
        axis=1, dtype=jnp.uint32)


def _gather_symbols(codes, valid, ln_tbl, cw_tbl):
    """(lens i32, vals u32) for a window of symbols (invalid -> 0/0)."""
    lens = jnp.where(valid, ln_tbl[codes], 0)
    vals = jnp.where(valid, cw_tbl[codes],
                     jnp.uint32(0)).astype(jnp.uint32)
    return lens, vals


def _gather_pack_kernel(codes_ref, valid_ref, ln_ref, cw_ref, words_ref,
                        nbits_ref, *, block_size: int, cands: int):
    cv = codes_ref.shape[1]
    w32 = words_ref.shape[1]
    nblocks = nbits_ref.shape[1]
    codes = codes_ref[0, :]                                  # (cv,)
    valid = valid_ref[0, :] != 0
    lens, vals = _gather_symbols(codes, valid, ln_ref[0, :], cw_ref[0, :])
    ends = jnp.cumsum(lens)                                  # prefix sum
    starts = (ends - lens).astype(jnp.int32)
    w_bit = jax.lax.broadcasted_iota(jnp.int32, (1, w32), 1)[0] * 32
    words_ref[0, :] = _compose_words(ends, starts, lens, vals, w_bit,
                                     cands)
    lens_p = jnp.pad(lens, (0, nblocks * block_size - cv))
    nbits_ref[...] = lens_p.reshape(nblocks, block_size).sum(
        axis=1, dtype=jnp.int32)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_size", "w32", "cands",
                                    "interpret"))
def gather_pack(codes2: jax.Array, valid2: jax.Array, lengths_tbl: jax.Array,
                cwords_tbl: jax.Array, *, block_size: int, w32: int,
                cands: int = 33, interpret: Optional[bool] = None):
    """codes2/valid2 (C, cv); lengths_tbl (C, 1024) i32; cwords_tbl
    (C, 1024) u32 — one codebook row per chunk.

    Returns (words (C, w32) u32, block_nbits (C, nblocks) i32) in the
    fused pipeline's contiguous per-chunk wire layout (bit-identical to
    the staged ``core.huffman.encode`` stream cut at u32 grain).
    """
    C, cv = codes2.shape
    nblocks = max(1, -(-cv // block_size))
    kern = functools.partial(_gather_pack_kernel, block_size=block_size,
                             cands=min(cands, cv + 1))
    words, nbits = pl.pallas_call(
        kern,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, lengths_tbl.shape[1]), lambda c: (c, 0)),
            pl.BlockSpec((1, cwords_tbl.shape[1]), lambda c: (c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, w32), lambda c: (c, 0)),
            pl.BlockSpec((1, nblocks), lambda c: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, w32), jnp.uint32),
            jax.ShapeDtypeStruct((C, nblocks), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(codes2.astype(jnp.int32), valid2.astype(jnp.int32),
      lengths_tbl.astype(jnp.int32), cwords_tbl.astype(jnp.uint32))
    return words, nbits


# ---------------------------------------------------------------------------
# Word-tiled gather-pack: bounded VMEM for unbounded chunk sizes
# ---------------------------------------------------------------------------
#
# The one-program-per-chunk kernel above holds the whole codes row (and
# the whole provisioned words row) in VMEM — fine to ~128k values per
# program, a non-starter for paper-scale 32 MB chunks. The tiled layout
# inverts the decomposition around OUTPUT words:
#
#   pre-pass — a blocked Pallas grid reduces per-block bit counts
#              (lens gathered per symbol, summed per `block_size` group);
#   glue     — tiny per-(chunk, tile) host-free jnp: cumsum the block
#              counts, searchsorted each tile's first bit into them, and
#              derive (symbol window offset, exact base bit offset) —
#              O(nblocks + tiles) work, never O(values);
#   pack     — a (C, tiles) Pallas grid. Each program owns TILE_WORDS
#              u32 words and reads ONE bounded symbol window placed by
#              scalar-prefetched element offsets (pl.Element indexing).
#              `base` makes the window's local prefix sum globally
#              exact, so words compose bit-identically to the untiled
#              kernel.
#
# Window-coverage bound: a window of WB = ceil(TILE_WORDS*32/block_size)
# + 2 blocks always contains every symbol overlapping its tile, PROVIDED
# valid2 rows are PREFIX masks (all invalid symbols trail the valid
# ones) and every valid symbol has a code length >= 1 bit: then each
# non-tail block carries >= block_size bits, so WB-1 blocks cover
# TILE_WORDS*32 bits past the tile's first symbol — or the stream ends
# inside the window. Both hold for every fused-pipeline caller (padding
# is a suffix; canonical codebooks assign >= 1 bit to occurring
# symbols); the contract is asserted by the bit-identity fences in
# tests/test_kernels.py.

TILE_WORDS = 512               # u32 words per pack program (16 kbit)
_SB_SYMBOLS = 1 << 16          # symbols per block-sums program


def _block_sums_kernel(codes_ref, valid_ref, ln_ref, nbits_ref,
                       *, block_size: int):
    codes = codes_ref[0, :]
    valid = valid_ref[0, :] != 0
    lens, _ = _gather_symbols(codes, valid, ln_ref[0, :], ln_ref[0, :]
                              .astype(jnp.uint32))
    nbits_ref[0, :] = lens.reshape(-1, block_size).sum(
        axis=1, dtype=jnp.int32)


def _tiled_pack_kernel(foff_ref, base_ref, codes_ref, valid_ref, ln_ref,
                       cw_ref, words_ref, *, tile: int, cands: int):
    c = pl.program_id(0)
    t = pl.program_id(1)
    codes = codes_ref[0, :]                                  # (WB*bs,)
    valid = valid_ref[0, :] != 0
    lens, vals = _gather_symbols(codes, valid, ln_ref[0, :], cw_ref[0, :])
    base = base_ref[c, t]
    ends = base + jnp.cumsum(lens)     # window-local cumsum, globally exact
    starts = (ends - lens).astype(jnp.int32)
    w_bit = (t * tile + jax.lax.broadcasted_iota(
        jnp.int32, (1, tile), 1)[0]) * 32
    words_ref[0, :] = _compose_words(ends, starts, lens, vals, w_bit,
                                     cands)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "w32", "cands", "tile",
                                    "interpret"))
def gather_pack_tiled(codes2: jax.Array, valid2: jax.Array,
                      lengths_tbl: jax.Array, cwords_tbl: jax.Array, *,
                      block_size: int, w32: int, cands: int = 33,
                      tile: int = TILE_WORDS, interpret: Optional[bool] = None):
    """Word-tiled twin of :func:`gather_pack`: same signature and
    bit-exact output, VMEM per program bounded by (tile, block_size)
    instead of (cv, w32). Requires prefix-valid rows (see module note).
    """
    C, cv = codes2.shape
    nblocks = max(1, -(-cv // block_size))
    # pad the symbol stream to the block-sums grid grain; padded symbols
    # are invalid => 0 bits, so every derived offset is unchanged
    sbb = max(1, _SB_SYMBOLS // block_size)      # blocks per sums program
    nsb = -(-nblocks // sbb)
    nbp = nsb * sbb                              # padded block count
    cvp = nbp * block_size
    codes_p = jnp.zeros((C, cvp), jnp.int32).at[:, :cv].set(
        codes2.astype(jnp.int32))
    valid_p = jnp.zeros((C, cvp), jnp.int32).at[:, :cv].set(
        valid2.astype(jnp.int32))
    ln = lengths_tbl.astype(jnp.int32)
    cw = cwords_tbl.astype(jnp.uint32)

    nbits_p = pl.pallas_call(
        functools.partial(_block_sums_kernel, block_size=block_size),
        grid=(C, nsb),
        in_specs=[
            pl.BlockSpec((1, sbb * block_size), lambda c, s: (c, s)),
            pl.BlockSpec((1, sbb * block_size), lambda c, s: (c, s)),
            pl.BlockSpec((1, ln.shape[1]), lambda c, s: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, sbb), lambda c, s: (c, s)),
        out_shape=jax.ShapeDtypeStruct((C, nbp), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes_p, valid_p, ln)

    # glue: O(nblocks) prefix sums place each tile's symbol window
    ends_b = jnp.cumsum(nbits_p, axis=1, dtype=jnp.int32)    # (C, nbp)
    wt = max(1, -(-w32 // tile))
    wb = min(nbp, -(-(tile * 32) // block_size) + 2)         # window blocks
    w0 = jnp.arange(wt, dtype=jnp.int32) * (tile * 32)
    fbk = jax.vmap(
        lambda e: jnp.searchsorted(e, w0, side="right"))(ends_b)
    fbk = jnp.clip(fbk, 0, nbp - wb).astype(jnp.int32)
    ends0 = jnp.concatenate(
        [jnp.zeros((C, 1), jnp.int32), ends_b], axis=1)
    base = jnp.take_along_axis(ends0, fbk, axis=1)           # (C, wt) i32
    foff = fbk * block_size                                  # element offs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(C, wt),
        in_specs=[
            pl.BlockSpec((1, pl.Element(wb * block_size)),
                         lambda c, t, foff, base: (c, foff[c, t])),
            pl.BlockSpec((1, pl.Element(wb * block_size)),
                         lambda c, t, foff, base: (c, foff[c, t])),
            pl.BlockSpec((1, ln.shape[1]),
                         lambda c, t, foff, base: (c, 0)),
            pl.BlockSpec((1, cw.shape[1]),
                         lambda c, t, foff, base: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda c, t, foff, base: (c, t)),
    )
    words = pl.pallas_call(
        functools.partial(_tiled_pack_kernel, tile=tile,
                          cands=min(cands, wb * block_size + 1)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, wt * tile), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(foff, base, codes_p, valid_p, ln, cw)
    return words[:, :w32], nbits_p[:, :nblocks]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hufenc(codes: jax.Array, codewords: jax.Array, lengths: jax.Array,
           *, interpret: Optional[bool] = None):
    """codes: (nblocks, BLOCK) i32; codewords/lengths: (1024,) u32/i32.

    Returns (words (nblocks, WORDS) u32, nbits (nblocks,) i32).
    """
    nblocks = codes.shape[0]
    cw = codewords.reshape(1, -1).astype(jnp.uint32)
    ln = lengths.reshape(1, -1).astype(jnp.int32)
    words, nbits = pl.pallas_call(
        _hufenc_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, BLOCK), lambda b: (b, 0)),
            pl.BlockSpec((1, cw.shape[1]), lambda b: (0, 0)),
            pl.BlockSpec((1, ln.shape[1]), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, WORDS), lambda b: (b, 0)),
            pl.BlockSpec((1, 1), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((nblocks, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(codes, cw, ln)
    return words, nbits[:, 0]
