"""Pure-jnp oracles / reference implementations for the hufenc kernels.

Two entry points, one per packing layout:

  * ``hufenc``      — oracle for the serial per-block kernel: same
    padded-row output layout, but built with cumsum offsets + segment
    sums instead of a serial loop — the two implementations are
    completely independent, which is what makes the allclose sweep
    meaningful.
  * ``encode_pack`` — the `hufenc` dispatch op's 'jnp' implementation
    (contiguous per-chunk wire layout, the fused pipeline's pass 2): a
    symbol-side prefix-sum pack, dense per-symbol work plus one placement
    per output word, no search and no candidate window. It doubles as
    the bit-identity reference for the Pallas gather-pack kernel; the
    staged ``core.huffman.encode`` remains the ground-truth oracle for
    both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as K


@jax.jit
def hufenc(codes: jax.Array, codewords: jax.Array, lengths: jax.Array):
    nblocks = codes.shape[0]
    cw = codewords.astype(jnp.uint32)
    ln = lengths.astype(jnp.int32)

    def one_block(block_codes):
        v = cw[block_codes]                          # (BLOCK,) u32
        l = ln[block_codes]                          # (BLOCK,) i32
        ends = jnp.cumsum(l)
        starts = ends - l
        total = ends[-1]
        word = starts // 32
        bitin = starts % 32
        left = 32 - bitin - l                        # may be negative
        ls = jnp.clip(left, 0, 31).astype(jnp.uint32)
        rs = jnp.clip(-left, 0, 31).astype(jnp.uint32)
        hi = jnp.where(left >= 0, (v << ls) & K._M32, v >> rs)
        lo_sh = jnp.clip(32 + left, 0, 31).astype(jnp.uint32)
        lo = jnp.where(left < 0, (v << lo_sh) & K._M32, jnp.uint32(0))
        words = jnp.zeros(K.WORDS + 1, jnp.uint32)
        # non-overlapping bits => add == or
        words = words.at[word].add(hi)
        words = words.at[word + 1].add(lo)
        return words[:K.WORDS], total

    words, nbits = jax.vmap(one_block)(codes)
    return words, nbits.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Prefix-sum pack (fused-pipeline wire layout): the `hufenc` op's 'jnp' impl
# ---------------------------------------------------------------------------

def _encode_one(codes, valid, lengths, cwords, block_size, w32):
    """One chunk: symbol codes -> packed u32 bitstream (host-layout).

    Replicates core.huffman.encode bit-for-bit, cut at u32 grain and
    truncated to `w32` words. Symbol-side: every symbol splits its
    codeword into `hi`, the bits in the word it starts in, and `lo`, the
    bits that spill into the next word. A codeword of <= 32 bits leaves
    no word up to the last one without a symbol starting in it, and only
    a word's last symbol can spill, into the word its successor starts
    in: so symbol i contributes hi[i] + lo[i-1] to word[i] and nothing
    elsewhere. Contributions are bit-disjoint, so a word's OR is its sum
    and the wrapping u32 prefix sum P of those is exact. One placement
    of P per word, at its last symbol, leaves word k = P[k] - P[k-1];
    the word after the last one holds only the last symbol's spill.
    """
    lens = jnp.where(valid, lengths[codes], 0)
    vals = jnp.where(valid, cwords[codes], 0).astype(jnp.uint32)
    ends = jnp.cumsum(lens)
    starts = (ends - lens).astype(jnp.int32)

    word = starts >> 5
    left = 32 - (starts & 31) - lens                     # < 0: spills
    ls = jnp.clip(left, 0, 31).astype(jnp.uint32)
    rs = jnp.clip(-left, 0, 31).astype(jnp.uint32)
    hi = jnp.where(left >= 0, vals << ls, vals >> rs)
    lo_sh = jnp.clip(32 + left, 0, 31).astype(jnp.uint32)
    lo = jnp.where(left < 0, vals << lo_sh, jnp.uint32(0))
    psum = jnp.cumsum(hi + jnp.pad(lo[:-1], (1, 0)),    # wraps: exact
                      dtype=jnp.uint32)

    last = jnp.append(word[1:] != word[:-1], True)      # word's last symbol
    tgt = jnp.where(last, word, w32)                    # w32: dropped
    placed = jnp.zeros((w32,), jnp.uint32).at[tgt].set(psum, mode="drop")
    k = jnp.arange(w32, dtype=jnp.int32)
    words = jnp.where(k <= word[-1],
                      placed - jnp.pad(placed[:-1], (1, 0)),
                      jnp.where(k == word[-1] + 1, lo[-1], jnp.uint32(0)))

    cv = codes.shape[0]
    nblocks = -(-cv // block_size)
    lens_p = jnp.pad(lens, (0, nblocks * block_size - cv))
    block_nbits = lens_p.reshape(nblocks, block_size).sum(axis=1)
    return words, block_nbits


@functools.partial(jax.jit, static_argnames=("block_size", "w32", "cands"))
def encode_pack(codes2, valid2, lengths_tbl, cwords_tbl, block_size, w32,
                cands=33):
    """Encode every chunk against its own codebook row, in one trace.

    The `hufenc` dispatch op: (codes2, valid2 (C, cv); per-chunk
    codebook tables (C, 1024)) -> (words (C, w32) u32, block_nbits
    (C, nblocks) i32) in the contiguous per-chunk wire layout. w32 is
    sized by the caller from the EXACT per-chunk payload bits
    (hist . lengths, free on the host), bucketed. `cands`, the
    candidate-window size of the Pallas gather-pack, is part of the op's
    calling convention; the prefix-sum pack needs no window and ignores
    it.
    """
    del cands
    return jax.vmap(
        lambda c, v, ln, cw: _encode_one(c, v, ln, cw, block_size, w32))(
        codes2, valid2, lengths_tbl, cwords_tbl)
