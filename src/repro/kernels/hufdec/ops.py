"""Public wrapper: the `hufdec` op's 'pallas' implementation.

Adapts the dispatch-layer calling convention (flat stacked uint16/uint8
decode tables, exactly what ``runtime/fused_decode`` stages on the host)
to the kernel's layout: tables widened to int32 rows — uint8/uint16
operands would force sub-f32 tile shapes the (1, 2^16) row cannot
satisfy — and the (C, NB, bs) kernel output reshaped to the op's
(C, NB*bs) uint16 contract. ``interpret=None`` resolves per backend:
compiled on TPU, interpreter everywhere else so CI exercises the kernel
on CPU.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..dispatch import resolve_interpret
from . import kernel as K


def decode_blocks(words2, nbits2, counts, sym_flat, len_flat, cb_idx,
                  block_size: int, *, interpret: Optional[bool] = None):
    """Same signature and bit-exact output as ``ref.decode_blocks``."""
    interpret = resolve_interpret(interpret)
    sym2 = jnp.asarray(sym_flat).reshape(-1, K.TBL).astype(jnp.int32)
    len2 = jnp.asarray(len_flat).reshape(-1, K.TBL).astype(jnp.int32)
    out = K.hufdec(jnp.asarray(words2), jnp.asarray(nbits2),
                   jnp.asarray(counts), sym2, len2, jnp.asarray(cb_idx),
                   block_size=block_size, interpret=interpret)
    C = out.shape[0]
    return out.reshape(C, -1).astype(jnp.uint16)
