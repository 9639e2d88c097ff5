"""jnp references for the `ceaz_chunk` / `ceaz_chunk_dec` megakernel ops.

Composed from the EXISTING stage implementations — core.dualquant for
the quantizers, the dualquant `chunk_center` reduction, and the bank
back end below (`chunk_histograms` -> `select_bank` -> `bank_encode`
with the hufenc jnp pack) — so its outputs are bitwise-identical to the
staged fused pipeline by construction, and serve as the bit-identity
fence for the Pallas megakernel. The back end is written once here: the
fused runtime's bank pass (runtime/fused.py `_bank_pass_fn`) runs the
same `bank_encode` after its N-D quantize pass, and `_device_stats` the
same `chunk_histograms`.

The decode twin (`ceaz_chunk_dec`, bottom of this module) composes the
hufdec lockstep walk with `patch_and_inverse`, the shared outlier-patch
+ inverse-dual-quant tail the word-tiled Pallas regime also uses.

Op contract (`ceaz_chunk`):

    ceaz_chunk(work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
               block_size, w32, cands, predictor)
      -> (q2, codes2, outl2, delta2, centers, hists, sel, totals,
          words, block_nbits)

  work2  (C, cv) f32   chunk rows (padded tail rows zero-filled)
  prev2  (C, 1)  f32   Lorenzo halo: the RAW value preceding each row
                       (0.0 for a stream head / independent row — the
                       exact zero-pad semantics of global Lorenzo)
  valid2 (C, cv) bool  PREFIX masks (all padding trails the data)
  ebs    (C,)    f32   per-row error bounds (fixed-ratio rows differ)
  bank_lengths (K, 1024) i32 / bank_cwords (K, 1024) u32: the offline
                       codebook bank tables

  q2/codes2/delta2 (C, cv) i32 and outl2 (C, cv) bool are masked to
  zero/False past the valid prefix; centers (C,) i32 (zero under
  Lorenzo); hists (C, 1024) i32; sel (C,) i32 the argmin_k of
  hist . lengths_k (first-occurrence ties, replayed bitwise by the host
  BankCoder); totals (C,) i32 the selected payload bits; words
  (C, w32) u32 + block_nbits (C, nblocks) i32 the packed payload in
  the fused pipeline's contiguous wire layout.

With prev2 supplied per the contract, a batch of rows quantizes
bitwise-identically to one global 1-D Lorenzo pass over the
concatenated stream: prequantization is elementwise, so re-quantizing
the predecessor value in the halo reproduces exactly the q[i-1] the
global pass used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import dualquant as core_dq
from ..dualquant import ops as dq_ops
from ..hufdec import ref as hufdec_ref
from ..hufenc import ref as hufenc_ref

NUM_SYMBOLS = core_dq.NUM_SYMBOLS
RADIUS = core_dq.RADIUS


def _quantize_rows(work2, prev2, valid2, ebs, predictor):
    """Shared quantize front-end: (q2, codes2, outl2, delta2, centers),
    all masked past the valid prefix."""
    eb2 = ebs.reshape(-1, 1).astype(jnp.float32)
    if predictor == "lorenzo":
        xrow = jnp.concatenate(
            [prev2.astype(jnp.float32), work2.astype(jnp.float32)], axis=1)
        qrow = core_dq.prequantize(xrow, eb2)          # (C, cv+1)
        q2 = qrow[:, 1:]
        pred = qrow[:, :-1]
        delta2 = q2 - pred
        codes_u16, outl2 = core_dq.postquantize(q2, pred)
        centers = jnp.zeros((work2.shape[0],), jnp.int32)
    else:
        q2 = core_dq.prequantize(work2.astype(jnp.float32), eb2)
        centers = dq_ops.chunk_center(q2, valid2)
        codes_u16, outl2, delta2 = core_dq.value_postquantize(
            q2, centers[:, None])
    codes2 = jnp.where(valid2, codes_u16,
                       jnp.uint16(0)).astype(jnp.int32)
    outl2 = outl2 & valid2
    delta2 = jnp.where(valid2, delta2, 0)
    q2 = jnp.where(valid2, q2, 0)
    return q2, codes2, outl2, delta2, centers


def chunk_histograms(codes2, valid2):
    """(C, NUM_SYMBOLS) i32 per-row histograms of the valid codes, one
    scatter-add over the (C, cv) rows."""
    cidx = jnp.broadcast_to(
        jnp.arange(codes2.shape[0], dtype=jnp.int32)[:, None], codes2.shape)
    return jnp.zeros((codes2.shape[0], NUM_SYMBOLS), jnp.int32) \
        .at[cidx, codes2].add(valid2.astype(jnp.int32))


def select_bank(hists, bank_lengths):
    """(sel, totals): exact-integer argmin_k of hist . lengths_k. The
    statistic is small (<= 16 * cv) so int32 is exact; first-occurrence
    ties match the host replay in core.codebook.BankCoder."""
    costs = jnp.einsum("cs,ks->ck", hists,
                       bank_lengths.astype(jnp.int32))
    sel = jnp.argmin(costs, axis=1).astype(jnp.int32)
    totals = jnp.take_along_axis(costs, sel[:, None], axis=1)[:, 0]
    return sel, totals


def bank_encode(codes2, valid2, bank_lengths, bank_cwords, block_size: int,
                w32: int, cands: int, encode_pack=hufenc_ref.encode_pack):
    """The bank back end over quantized (C, cv) code rows: histogram ->
    bank selection -> gather the selected rows -> Huffman pack through
    `encode_pack` (a `hufenc` implementation). -> (hists, sel, totals,
    words, block_nbits)."""
    hists = chunk_histograms(codes2, valid2)
    sel, totals = select_bank(hists, bank_lengths)
    words, block_nbits = encode_pack(
        codes2, valid2, bank_lengths[sel], bank_cwords[sel],
        block_size, w32, cands)
    return hists, sel, totals, words, block_nbits


@functools.partial(jax.jit,
                   static_argnames=("block_size", "w32", "cands",
                                    "predictor"))
def ceaz_chunk(work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
               block_size: int, w32: int, cands: int = 33,
               predictor: str = "lorenzo"):
    """The `ceaz_chunk` dispatch op's 'jnp' implementation."""
    valid2 = jnp.asarray(valid2).astype(bool)
    q2, codes2, outl2, delta2, centers = _quantize_rows(
        jnp.asarray(work2), jnp.asarray(prev2), valid2,
        jnp.asarray(ebs), predictor)
    hists, sel, totals, words, block_nbits = bank_encode(
        codes2, valid2, jnp.asarray(bank_lengths, jnp.int32),
        jnp.asarray(bank_cwords, jnp.uint32), block_size, w32, cands)
    return (q2, codes2, outl2, delta2, centers, hists, sel, totals,
            words, block_nbits)


# ---------------------------------------------------------------------------
# Decode twin: ceaz_chunk_dec
# ---------------------------------------------------------------------------
#
# Op contract (`ceaz_chunk_dec`):
#
#     ceaz_chunk_dec(words2, nbits2, counts, sym_flat, len_flat, cb_idx,
#                    odelta2, base, seg0, islor, block_size)
#       -> q2 (C, NB*block_size) i32
#
#   words2  (C, W)  u32   wire bitstream (u64 words split MSB-first)
#   nbits2  (C, NB) i32   per-block bit counts (zero-padded)
#   counts  (C,)    i32   valid symbols per chunk row
#   sym/len_flat (K*2^16,) stacked decode tables; cb_idx (C,) selects
#   odelta2 (C, Ko) i32   the row's outlier deltas IN ASCENDING POSITION
#                         ORDER (the encoder's flatnonzero order),
#                         zero-padded
#   base    (C,)    i32   additive base: the value-direct centre code,
#                         0 for Lorenzo / delta-passthrough rows
#   seg0    (C,)    i32   index of the first row of the row's Lorenzo
#                         carry segment (seg0[c] == c: no carry-in);
#                         rows of one segment must be contiguous and
#                         ascending in the batch
#   islor   (C,)    i32   1: inverse-Lorenzo rows (segmented prefix
#                         sum); 0: value/delta rows (q = delta + base)
#
# The outlier patch needs no index array: the dual-quantizer's escape
# symbol IS code 0 (core.dualquant.postquantize maps exactly the
# outliers there — every in-range code lands in [1, 1023]), and the
# encoder stores outlier deltas in ascending position order, so the
# r-th zero-code in a row's valid prefix pairs with odelta2[r] by an
# exclusive prefix count — a rank gather, no scatter.
#
# The per-row arithmetic is int32 WRAP throughout, matching the staged
# inverse exactly: a Lorenzo segment's carry is the difference of two
# wrapped prefix sums, which is exact mod 2^32.


@jax.jit
def patch_and_inverse(codes2, counts, odelta2, base, seg0, islor):
    """codes -> reconstruction codes q, one pass over (C, N) rows.

    Shared by the jnp twin below and the word-tiled Pallas regime
    (megakernel/ops.py): past the one-program ceiling the decoded codes
    cross HBM once and this tail runs as ONE jitted pass.
    """
    codes2 = codes2.astype(jnp.int32)
    C, N = codes2.shape
    Ko = odelta2.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (C, N), 1)
    valid = pos < counts.astype(jnp.int32)[:, None]
    is_out = valid & (codes2 == 0)
    io32 = is_out.astype(jnp.int32)
    rank = jnp.cumsum(io32, axis=1) - io32         # exclusive zero-count
    dval = jnp.take_along_axis(odelta2.astype(jnp.int32),
                               jnp.clip(rank, 0, Ko - 1), axis=1)
    delta = jnp.where(is_out, dval, codes2 - RADIUS)
    delta = jnp.where(valid, delta, 0)
    local = jnp.cumsum(delta, axis=1, dtype=jnp.int32)
    dsum = local[:, -1]
    carry_all = jnp.cumsum(dsum, dtype=jnp.int32) - dsum     # exclusive
    carry = carry_all - carry_all[seg0.astype(jnp.int32)]
    q_lor = local + carry[:, None]
    q_val = delta + base.astype(jnp.int32)[:, None]
    q = jnp.where(islor.astype(bool)[:, None], q_lor, q_val)
    return jnp.where(valid, q, 0)


@functools.partial(jax.jit, static_argnames=("block_size",))
def ceaz_chunk_dec(words2, nbits2, counts, sym_flat, len_flat, cb_idx,
                   odelta2, base, seg0, islor, block_size: int):
    """The `ceaz_chunk_dec` dispatch op's 'jnp' implementation: the
    hufdec lockstep table walk composed with the shared patch/inverse
    tail — bitwise-identical to the staged decode chain by
    construction, and the oracle the Pallas decode megakernel's
    bit-identity sweeps compare against."""
    codes = hufdec_ref.decode_blocks(words2, nbits2, counts, sym_flat,
                                     len_flat, cb_idx, block_size)
    return patch_and_inverse(codes, counts, odelta2, base, seg0, islor)
