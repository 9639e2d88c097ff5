"""Pallas kernel: fixed-width b-bit pack/unpack.

The fixed-RATIO mode's wire format (and the compressed-collective payload)
uses fixed-width codes so the packed size is static under jit — the same
reason the paper's fixed-ratio mode exists (consistent FPGA throughput).
Packing b-bit values (b in {2,4,8,16}) into u32 words is fully
vectorizable: reshape so each output word's 32/b source values sit in the
sublane dim, then shift-and-OR reduce. No serial carry at all — this path
is VPU-parallel, unlike variable-length Huffman.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..dispatch import resolve_interpret

LANES = 128
SUBLANES = 8
_M32 = jnp.uint32(0xFFFFFFFF)


def _pack_kernel(vals_ref, out_ref, *, bits: int):
    per = 32 // bits
    v = vals_ref[...].astype(jnp.uint32)          # (SUBLANES, per, LANES)
    acc = jnp.zeros((v.shape[0], v.shape[2]), jnp.uint32)
    for k in range(per):                          # static unroll (<= 16)
        sh = jnp.uint32(32 - bits * (k + 1))      # MSB-first
        acc = acc | ((v[:, k, :] & jnp.uint32((1 << bits) - 1)) << sh)
    out_ref[...] = acc


def _unpack_kernel(words_ref, out_ref, *, bits: int):
    per = 32 // bits
    w = words_ref[...].astype(jnp.uint32)         # (SUBLANES, LANES)
    mask = jnp.uint32((1 << bits) - 1)
    parts = []
    for k in range(per):
        sh = jnp.uint32(32 - bits * (k + 1))
        parts.append(((w >> sh) & mask).astype(jnp.int32))
    out_ref[...] = jnp.stack(parts, axis=1)       # (SUBLANES, per, LANES)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def pack(vals: jax.Array, bits: int, *, interpret: Optional[bool] = None) -> jax.Array:
    """vals: (n_words, 32//bits, LANES)-collapsible i32 in [0, 2^bits).

    Input shape (R, 32//bits, LANES) with R % SUBLANES == 0;
    returns (R, LANES) u32.
    """
    r, per, lanes = vals.shape
    assert per == 32 // bits and lanes == LANES and r % SUBLANES == 0
    return pl.pallas_call(
        functools.partial(_pack_kernel, bits=bits),
        grid=(r // SUBLANES,),
        in_specs=[pl.BlockSpec((SUBLANES, per, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(vals)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def unpack(words: jax.Array, bits: int, *, interpret: Optional[bool] = None) -> jax.Array:
    """words: (R, LANES) u32 -> (R, 32//bits, LANES) i32."""
    r, lanes = words.shape
    assert lanes == LANES and r % SUBLANES == 0
    per = 32 // bits
    return pl.pallas_call(
        functools.partial(_unpack_kernel, bits=bits),
        grid=(r // SUBLANES,),
        in_specs=[pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, per, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, per, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(words)
