"""Public wrappers for the megakernel ops ('pallas' impls).

`ceaz_chunk` (encode) and `ceaz_chunk_dec` (decode) each run two
regimes behind one signature (both bit-identical to their ref twins):

  * rows <= the per-program VMEM limit — ONE fused Pallas program per
    chunk (kernel.ceaz_chunk_fused / decode_kernel.ceaz_chunk_dec_fused):
    no intermediate leaves VMEM.
  * larger chunks — the word-tiled composition. Encode: tiled
    quantize+histogram kernels (bounded TILE_SEG windows, halo
    BlockSpecs), the radix-select `dq_center` kernel, a tiny jnp
    bank-select, and the shared kernels/hufenc word-tiled gather-pack.
    Decode: the word-tiled walk (decode_kernel.hufdec_tiles) + the
    shared jnp `ref.patch_and_inverse` tail. Codes cross HBM exactly
    once in either direction — physically necessary once a chunk row
    outgrows VMEM.

``interpret=None`` resolves per backend (compiled on TPU, interpreter
everywhere else so CI exercises both regimes on CPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..dispatch import resolve_interpret
from ..dualquant import ops as dq_ops
from ..hufenc import kernel as hufenc_k
from . import decode_kernel as DK
from . import kernel as K
from . import ref as R


@functools.partial(jax.jit,
                   static_argnames=("block_size", "w32", "cands",
                                    "predictor", "interpret"))
def _ceaz_chunk_tiled(work2, prev2, valid2, ebs, bank_lengths,
                      bank_cwords, *, block_size: int, w32: int,
                      cands: int, predictor: str, interpret: bool):
    C, cv = work2.shape
    seg = K.TILE_SEG
    ns = -(-cv // seg)
    cvp = ns * seg
    ebs2 = ebs.reshape(C, 1).astype(jnp.float32)
    valid_p = jnp.zeros((C, cvp), jnp.int32).at[:, :cv].set(
        valid2.astype(jnp.int32))
    bank_lengths = bank_lengths.astype(jnp.int32)
    bank_cwords = bank_cwords.astype(jnp.uint32)

    if predictor == "lorenzo":
        work_p = jnp.zeros((C, cvp + 1), jnp.float32).at[:, :cv].set(
            work2.astype(jnp.float32))
        q2p, codes2p, outl2p, delta2p, hists = K.lorenzo_tiles(
            work_p, prev2.astype(jnp.float32), valid_p, ebs2, seg=seg,
            interpret=interpret)
        centers = jnp.zeros((C,), jnp.int32)
    else:
        work_p = jnp.zeros((C, cvp), jnp.float32).at[:, :cv].set(
            work2.astype(jnp.float32))
        q2p = K.value_quant_tiles(work_p, ebs2, seg=seg,
                                  interpret=interpret)
        # global reduction between the tiled passes (padding is invalid,
        # so the padded rows centre identically to unpadded ones)
        centers = dq_ops.dq_center(q2p, valid_p, interpret=interpret)
        codes2p, outl2p, delta2p, hists = K.value_finalize_tiles(
            q2p, valid_p, centers, seg=seg, interpret=interpret)
        q2p = jnp.where(valid_p != 0, q2p, 0)

    sel, totals = R.select_bank(hists, bank_lengths)
    words, block_nbits = hufenc_k.gather_pack_tiled(
        codes2p[:, :cv], valid2.astype(jnp.int32),
        bank_lengths[sel], bank_cwords[sel], block_size=block_size,
        w32=w32, cands=cands, interpret=interpret)
    return (q2p[:, :cv], codes2p[:, :cv], outl2p[:, :cv], delta2p[:, :cv],
            centers, hists, sel, totals, words, block_nbits)


def ceaz_chunk(work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
               block_size: int, w32: int, cands: int = 33,
               predictor: str = "lorenzo", *,
               interpret: Optional[bool] = None):
    """Same signature and bit-exact outputs as ``ref.ceaz_chunk``."""
    interpret = resolve_interpret(interpret)
    work2 = jnp.asarray(work2, jnp.float32)
    prev2 = jnp.asarray(prev2, jnp.float32)
    valid2 = jnp.asarray(valid2)
    ebs = jnp.asarray(ebs, jnp.float32)
    bank_lengths = jnp.asarray(bank_lengths)
    bank_cwords = jnp.asarray(bank_cwords)
    if work2.shape[1] <= K._FUSE_ROW_LIMIT:
        out = K.ceaz_chunk_fused(
            work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
            block_size=block_size, w32=w32, cands=cands,
            predictor=predictor, interpret=interpret)
    else:
        out = _ceaz_chunk_tiled(
            work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
            block_size=block_size, w32=w32, cands=cands,
            predictor=predictor, interpret=interpret)
    (q2, codes2, outl2, delta2, centers, hists, sel, totals, words,
     nbits) = out
    return (q2, codes2, outl2.astype(bool), delta2, centers, hists, sel,
            totals, words, nbits)


def ceaz_chunk_dec(words2, nbits2, counts, sym_flat, len_flat, cb_idx,
                   odelta2, base, seg0, islor, block_size: int, *,
                   interpret: Optional[bool] = None):
    """Same signature and bit-exact output as ``ref.ceaz_chunk_dec``.

    The flat stacked decode tables widen to (K, 2^16) int32 rows so the
    layout respects f32-class tiling (the kernels/hufdec convention);
    row counts past `decode_kernel._DEC_FUSE_LIMIT` switch to the
    word-tiled walk + the shared jnp patch/inverse tail.
    """
    interpret = resolve_interpret(interpret)
    words2 = jnp.asarray(words2, jnp.uint32)
    nbits2 = jnp.asarray(nbits2, jnp.int32)
    counts = jnp.asarray(counts, jnp.int32)
    cb_idx = jnp.asarray(cb_idx, jnp.int32)
    odelta2 = jnp.asarray(odelta2, jnp.int32)
    base = jnp.asarray(base, jnp.int32)
    seg0 = jnp.asarray(seg0, jnp.int32)
    islor = jnp.asarray(islor, jnp.int32)
    sym2 = jnp.asarray(sym_flat).reshape(-1, DK.TBL).astype(jnp.int32)
    len2 = jnp.asarray(len_flat).reshape(-1, DK.TBL).astype(jnp.int32)
    if nbits2.shape[1] * block_size <= DK._DEC_FUSE_LIMIT:
        return DK.ceaz_chunk_dec_fused(
            words2, nbits2, counts, sym2, len2, cb_idx, odelta2, base,
            seg0, islor, block_size=block_size,
            interpret=interpret)
    codes = DK.hufdec_tiles(words2, nbits2, counts, sym2, len2, cb_idx,
                            block_size=block_size,
                            interpret=interpret)
    return R.patch_and_inverse(codes, counts, odelta2, base, seg0, islor)
