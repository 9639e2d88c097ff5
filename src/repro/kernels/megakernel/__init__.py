"""`ceaz_chunk` megakernel: the bank-mode encode hot path as ONE Pallas
program per chunk (quantize -> histogram -> bank-select -> pack).

The FPGA pipeline of the paper compresses each chunk in a single
hardware pass — quantization, code lookup and bit-packing never leave
the datapath. This package is the TPU analogue for codebook='bank'
compression, where selection is a pure argmin over precomputed tables
(no host tree-build between quantize and pack):

  kernel.py — the fused Pallas program (and the word-tiled composition
              for chunks past the single-program VMEM limit)
  ref.py    — the jnp twin composed from the existing stage ops
              (bit-identity reference), and the bank back end
              (histogram, selection, pack) the fused bank pass shares
  ops.py    — the `ceaz_chunk` dispatch-op wrapper

See docs/ARCHITECTURE.md ("Encode megakernel") for the dataflow.
"""
from . import ops, ref  # noqa: F401

__all__ = ["ops", "ref"]
