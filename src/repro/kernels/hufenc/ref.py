"""Pure-jnp oracles / reference implementations for the hufenc kernels.

Two entry points, one per packing layout:

  * ``hufenc``      — oracle for the serial per-block kernel: same
    padded-row output layout, but built with cumsum offsets + segment
    sums instead of a serial loop — the two implementations are
    completely independent, which is what makes the allclose sweep
    meaningful.
  * ``encode_pack`` — the `hufenc` dispatch op's 'jnp' implementation
    (contiguous per-chunk wire layout, the fused pipeline's pass 2). It
    doubles as the bit-identity reference for the Pallas gather-pack
    kernel; the staged ``core.huffman.encode`` remains the ground-truth
    oracle for both.
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
# Gather-pack (fused-pipeline wire layout): the `hufenc` op's 'jnp' impl
# ---------------------------------------------------------------------------

def _encode_one(codes, valid, lengths, cwords, block_size, w32, cands):
    """One chunk: symbol codes -> packed u32 bitstream (host-layout).

    Replicates core.huffman.encode bit-for-bit, but scatter-free: for
    each OUTPUT word, searchsorted on the cumulative bit offsets finds
    the first overlapping symbol and the `cands`-candidate window is
    gathered and OR-composed. Gathers vectorize on every backend; the
    scatter formulation serializes on CPU XLA. The window is walked one
    candidate at a time, so temporaries stay O(w32) instead of
    O(w32 * cands) — a paper-scale 2^23-value chunk fits one chip.
    """
    cv = codes.shape[0]
    lens = jnp.where(valid, lengths[codes], 0)
    vals = jnp.where(valid, cwords[codes], 0).astype(jnp.uint32)
    ends = jnp.cumsum(lens)
    starts = (ends - lens).astype(jnp.int32)

    w_bit = jnp.arange(w32, dtype=jnp.int32) * 32
    first = jnp.searchsorted(ends, w_bit, side="right")   # covers bit w_bit

    def add_candidate(j, words):
        cand = first + j
        ci = jnp.clip(cand, 0, cv - 1)
        off = starts[ci] - w_bit
        ln = lens[ci]
        v = vals[ci]
        left = 32 - off - ln
        live = (cand < cv) & (off < 32) & (off + ln > 0)
        ls = jnp.clip(left, 0, 31).astype(jnp.uint32)
        rs = jnp.clip(-left, 0, 31).astype(jnp.uint32)
        shifted = jnp.where(left >= 0, v << ls, v >> rs)
        # live contributions are bit-disjoint => sum == or
        return words + jnp.where(live, shifted, jnp.uint32(0))

    words = jax.lax.fori_loop(0, cands, add_candidate,
                              jnp.zeros((w32,), jnp.uint32))

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
    (hist . lengths, free on the host), bucketed — the gather work
    tracks the real bit-rate instead of the 16-bit worst case.
    """
    return jax.vmap(
        lambda c, v, ln, cw: _encode_one(c, v, ln, cw, block_size, w32,
                                         cands))(
        codes2, valid2, lengths_tbl, cwords_tbl)
