"""Pallas megakernel: one program per chunk for the bank encode path.

The paper's FPGA core (Fig 5 + §3) streams each chunk through
quantize -> code lookup -> bit-pack in one hardware pass; this kernel
is the TPU analogue. A single program instance owns one chunk row and
runs, entirely in VMEM:

  dual-quantize   prequantize (rint/clip/bound-tighten, the exact
                  core.dualquant formula) + Lorenzo prediction from a
                  1-value raw halo, or value-direct centring via the
                  dualquant radix-select median;
  histogram       1024-bin one-hot partial sums (sentinel key 1024
                  keeps padding out of bin 0);
  bank-select     argmin_k of hist . lengths_k over the (K, 1024) bank
                  tables — exact int32, first-occurrence ties;
  gather-pack     the selected codebook row feeds the shared
                  `_compose_words` prefix-sum pack from kernels/hufenc.

No intermediate (q, codes, histogram, selected row) ever leaves VMEM;
the program's outputs are the op's outputs. Chunks past
`_FUSE_ROW_LIMIT` values cannot hold a whole row per program — ops.py
composes the word-tiled kernels below (same halo/hist bodies on
bounded windows + kernels/hufenc.gather_pack_tiled) instead, the only
regime where codes round-trip HBM once by physical necessity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import dualquant as core_dq
from ..dualquant.kernel import _center_from_q
from ..hufenc.kernel import _compose_words, _gather_symbols

NUM_SYMBOLS = core_dq.NUM_SYMBOLS
RADIUS = core_dq.RADIUS

# one fused program holds ~6 cv-length i32/f32 rows (+ the one-hot hist
# slices) in VMEM: past this, ops.py switches to the tiled composition
_FUSE_ROW_LIMIT = 1 << 17
# symbols per tiled quantize program
TILE_SEG = 1 << 15
# one-hot histogram granularity (value segment x bin slice)
_HIST_SEG = 8192
_BIN_SLICE = 128


def _hist1024(keys):
    """1024-bin histogram of int32 keys by one-hot partial sums (keys
    outside [0, 1024) — the invalid-entry sentinel — count nowhere)."""
    n = keys.shape[0]
    total = jnp.zeros((NUM_SYMBOLS,), jnp.int32)
    for s0 in range(0, n, _HIST_SEG):
        ks = keys[s0:min(s0 + _HIST_SEG, n)]
        parts = []
        for b0 in range(0, NUM_SYMBOLS, _BIN_SLICE):
            oh = ks[:, None] == (b0 + jax.lax.broadcasted_iota(
                jnp.int32, (ks.shape[0], _BIN_SLICE), 1))
            parts.append(jnp.sum(oh, axis=0, dtype=jnp.int32))
        total = total + jnp.concatenate(parts)
    return total


def _postquant(q, pred_or_center, valid):
    """delta/codes/outlier from q and its prediction, masked past the
    valid prefix (int32 throughout — same wrap semantics as the staged
    postquantize/value_postquantize)."""
    delta = q - pred_or_center
    code = delta + RADIUS
    outlier = (code < 1) | (code >= NUM_SYMBOLS)
    codes = jnp.where(valid & ~outlier, code, 0)
    return (jnp.where(valid, delta, 0), codes, outlier & valid)


# ---------------------------------------------------------------------------
# The fused single-program kernel (cv <= _FUSE_ROW_LIMIT)
# ---------------------------------------------------------------------------

def _ceaz_chunk_kernel(work_ref, prev_ref, valid_ref, eb_ref, ln_ref,
                       cw_ref, q_ref, codes_ref, outl_ref, delta_ref,
                       center_ref, hist_ref, sel_ref, total_ref,
                       words_ref, nbits_ref, *, block_size: int,
                       cands: int, predictor: str):
    cv = work_ref.shape[1]
    w32 = words_ref.shape[1]
    nblocks = nbits_ref.shape[1]
    eb = eb_ref[0, 0]
    x = work_ref[0, :]
    valid = valid_ref[0, :] != 0

    if predictor == "lorenzo":
        xr = jnp.concatenate([prev_ref[0, :], x])      # (cv+1,) halo row
        qr = core_dq.prequantize(xr, eb)
        q = qr[1:]
        pred = qr[:-1]
        center = jnp.int32(0)
    else:
        q = core_dq.prequantize(x, eb)
        center = _center_from_q(q, valid)
        pred = center
    delta, codes, outlier = _postquant(q, pred, valid)

    keys = jnp.where(valid, codes, NUM_SYMBOLS)        # sentinel: no bin
    hist = _hist1024(keys)

    ln_all = ln_ref[...]                               # (K, 1024)
    cw_all = cw_ref[...]
    costs = jnp.sum(hist[None, :] * ln_all, axis=1, dtype=jnp.int32)
    sel = jnp.argmin(costs).astype(jnp.int32)
    total = costs[sel]

    lens, vals = _gather_symbols(codes, valid, ln_all[sel], cw_all[sel])
    ends = jnp.cumsum(lens)
    starts = (ends - lens).astype(jnp.int32)
    w_bit = jax.lax.broadcasted_iota(jnp.int32, (1, w32), 1)[0] * 32
    words_ref[0, :] = _compose_words(ends, starts, lens, vals, w_bit,
                                     cands)
    lens_p = jnp.pad(lens, (0, nblocks * block_size - cv))
    nbits_ref[0, :] = lens_p.reshape(nblocks, block_size).sum(
        axis=1, dtype=jnp.int32)

    q_ref[0, :] = jnp.where(valid, q, 0)
    codes_ref[0, :] = codes
    outl_ref[0, :] = outlier.astype(jnp.int32)
    delta_ref[0, :] = delta
    center_ref[0, 0] = center
    hist_ref[0, :] = hist
    sel_ref[0, 0] = sel
    total_ref[0, 0] = total


@functools.partial(jax.jit,
                   static_argnames=("block_size", "w32", "cands",
                                    "predictor", "interpret"))
def ceaz_chunk_fused(work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
                     *, block_size: int, w32: int, cands: int,
                     predictor: str, interpret: bool):
    """Grid (C,): one fused program per chunk row. Same outputs as
    ref.ceaz_chunk (outl2 as i32 for the store; ops casts to bool)."""
    C, cv = work2.shape
    nblocks = max(1, -(-cv // block_size))
    nbooks, nsym = bank_lengths.shape
    kern = functools.partial(_ceaz_chunk_kernel, block_size=block_size,
                             cands=min(cands, cv + 1),
                             predictor=predictor)
    outs = pl.pallas_call(
        kern,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, 1), lambda c: (c, 0)),
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, 1), lambda c: (c, 0)),
            pl.BlockSpec((nbooks, nsym), lambda c: (0, 0)),
            pl.BlockSpec((nbooks, nsym), lambda c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, cv), lambda c: (c, 0)),
            pl.BlockSpec((1, 1), lambda c: (c, 0)),
            pl.BlockSpec((1, NUM_SYMBOLS), lambda c: (c, 0)),
            pl.BlockSpec((1, 1), lambda c: (c, 0)),
            pl.BlockSpec((1, 1), lambda c: (c, 0)),
            pl.BlockSpec((1, w32), lambda c: (c, 0)),
            pl.BlockSpec((1, nblocks), lambda c: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, cv), jnp.int32),      # q2
            jax.ShapeDtypeStruct((C, cv), jnp.int32),      # codes2
            jax.ShapeDtypeStruct((C, cv), jnp.int32),      # outl2
            jax.ShapeDtypeStruct((C, cv), jnp.int32),      # delta2
            jax.ShapeDtypeStruct((C, 1), jnp.int32),       # centers
            jax.ShapeDtypeStruct((C, NUM_SYMBOLS), jnp.int32),
            jax.ShapeDtypeStruct((C, 1), jnp.int32),       # sel
            jax.ShapeDtypeStruct((C, 1), jnp.int32),       # totals
            jax.ShapeDtypeStruct((C, w32), jnp.uint32),
            jax.ShapeDtypeStruct((C, nblocks), jnp.int32),
        ],
        interpret=interpret,
    )(work2.astype(jnp.float32), prev2.astype(jnp.float32),
      valid2.astype(jnp.int32), ebs.reshape(C, 1).astype(jnp.float32),
      bank_lengths.astype(jnp.int32), bank_cwords.astype(jnp.uint32))
    (q2, codes2, outl2, delta2, centers, hists, sel, totals, words,
     nbits) = outs
    return (q2, codes2, outl2, delta2, centers[:, 0], hists, sel[:, 0],
            totals[:, 0], words, nbits)


# ---------------------------------------------------------------------------
# Word-tiled quantize kernels (cv > _FUSE_ROW_LIMIT)
# ---------------------------------------------------------------------------
#
# Same quantize/hist bodies as the fused kernel, on TILE_SEG windows.
# The Lorenzo kernel reads a (SEG+1)-value raw window whose first
# element is the segment's predecessor (element-indexed shifted
# BlockSpec, the dq1d line-buffer trick); the chunk head instead
# substitutes the chunk's prev halo, so the tiled rows quantize
# bitwise-identically to the fused kernel. Histograms accumulate into
# one (1, 1024) block per chunk across the sequential segment grid.

def _lorenzo_tile_kernel(eb_ref, prev_ref, work_ref, valid_ref, q_ref,
                         codes_ref, outl_ref, delta_ref, hist_ref):
    s = pl.program_id(1)
    eb = eb_ref[0, 0]
    win = work_ref[0, :]                               # (SEG+1,)
    valid = valid_ref[0, :] != 0
    head = jnp.concatenate([prev_ref[0, :], win[:-1]])
    xr = jnp.where(s == 0, head, win)
    qr = core_dq.prequantize(xr, eb)
    q = qr[1:]
    pred = qr[:-1]
    delta, codes, outlier = _postquant(q, pred, valid)

    q_ref[0, :] = jnp.where(valid, q, 0)
    codes_ref[0, :] = codes
    outl_ref[0, :] = outlier.astype(jnp.int32)
    delta_ref[0, :] = delta

    @pl.when(s == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    keys = jnp.where(valid, codes, NUM_SYMBOLS)
    hist_ref[0, :] += _hist1024(keys)


def _value_quant_tile_kernel(eb_ref, work_ref, q_ref):
    q_ref[0, :] = core_dq.prequantize(work_ref[0, :], eb_ref[0, 0])


def _value_finalize_tile_kernel(center_ref, q_ref, valid_ref, codes_ref,
                                outl_ref, delta_ref, hist_ref):
    s = pl.program_id(1)
    valid = valid_ref[0, :] != 0
    delta, codes, outlier = _postquant(q_ref[0, :], center_ref[0, 0],
                                       valid)
    codes_ref[0, :] = codes
    outl_ref[0, :] = outlier.astype(jnp.int32)
    delta_ref[0, :] = delta

    @pl.when(s == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    keys = jnp.where(valid, codes, NUM_SYMBOLS)
    hist_ref[0, :] += _hist1024(keys)


def lorenzo_tiles(work_p, prev2, valid_p, ebs2, *, seg: int,
                  interpret: bool):
    """work_p (C, ns*seg + 1) f32 (one-value halo margin), valid_p
    (C, ns*seg) i32 -> (q2, codes2, outl2 i32, delta2, hists)."""
    C = work_p.shape[0]
    cvp = valid_p.shape[1]
    ns = cvp // seg
    return pl.pallas_call(
        _lorenzo_tile_kernel,
        grid=(C, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda c, s: (c, 0)),
            pl.BlockSpec((1, 1), lambda c, s: (c, 0)),
            pl.BlockSpec((1, pl.Element(seg + 1)),
                         lambda c, s: (c, jnp.maximum(s * seg - 1, 0))),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
        ],
        out_specs=[
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, NUM_SYMBOLS), lambda c, s: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, NUM_SYMBOLS), jnp.int32),
        ],
        interpret=interpret,
    )(ebs2, prev2, work_p, valid_p)


def value_quant_tiles(work_p, ebs2, *, seg: int, interpret: bool):
    C, cvp = work_p.shape
    ns = cvp // seg
    return pl.pallas_call(
        _value_quant_tile_kernel,
        grid=(C, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda c, s: (c, 0)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
        ],
        out_specs=pl.BlockSpec((1, seg), lambda c, s: (c, s)),
        out_shape=jax.ShapeDtypeStruct((C, cvp), jnp.int32),
        interpret=interpret,
    )(ebs2, work_p)


def value_finalize_tiles(q2p, valid_p, centers, *, seg: int,
                         interpret: bool):
    C, cvp = q2p.shape
    ns = cvp // seg
    return pl.pallas_call(
        _value_finalize_tile_kernel,
        grid=(C, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda c, s: (c, 0)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
        ],
        out_specs=[
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, seg), lambda c, s: (c, s)),
            pl.BlockSpec((1, NUM_SYMBOLS), lambda c, s: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, cvp), jnp.int32),
            jax.ShapeDtypeStruct((C, NUM_SYMBOLS), jnp.int32),
        ],
        interpret=interpret,
    )(centers.reshape(C, 1).astype(jnp.int32), q2p, valid_p)
