"""Pallas TPU kernels for CEAZ's compute hot spots.

Six kernel packages, each a subpackage with kernel.py (pl.pallas_call +
explicit BlockSpec VMEM tiling), ops.py (jit'd public wrapper), ref.py
(pure-jnp oracle used by the allclose test sweeps):

  dualquant  — fused prequantization + Lorenzo + postquantization
               (+ the radix-select per-chunk centre reduction)
  histogram  — 1024-bin quant-code histogram (one-hot partial sums)
  hufenc     — Huffman encode: serial per-block packer + the fused
               pipeline's gather-pack (contiguous wire layout)
  hufdec     — canonical-Huffman table decode (block-parallel bit walk)
  bitpack    — fixed-width b-bit pack/unpack (fixed-ratio collective path)
  megakernel — the bank-mode encode hot path as ONE program per chunk
               (quantize -> histogram -> bank-select -> pack)

Off-TPU every kernel runs in the Pallas interpreter (its `interpret`
argument defaults to None, resolved by dispatch.resolve_interpret). None
of them compiles for a TPU yet, so the dispatch auto table names the
jnp twins there (see the table at the bottom of dispatch.py).

``dispatch`` is the backend-dispatch registry the fused runtime resolves
its inner loops through: (op, impl) -> callable with an (op, backend)
auto table, selected by ``CEAZConfig(kernel_impl=...)``.
"""
from . import (bitpack, dispatch, dualquant, histogram, hufdec,  # noqa: F401
               hufenc, megakernel)

__all__ = ["bitpack", "dispatch", "dualquant", "histogram", "hufdec",
           "hufenc", "megakernel"]
