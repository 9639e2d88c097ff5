"""Public wrappers for the hufenc kernels.

``hufenc_flat`` drives the serial per-block kernel (pads the tail block
with symbol `pad_sym` — callers pass the most frequent symbol so the pad
costs ~1 bit/value of the <1-block tail — and returns per-block packed
words + bit counts + true symbol count for host trim/concatenate).

``encode_pack`` is the `hufenc` dispatch op's 'pallas' implementation:
the gather-pack kernel in the fused pipeline's contiguous wire layout,
with ``interpret=None`` resolving per backend (compiled on TPU,
interpreter everywhere else so CI exercises the kernel on CPU).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dispatch import resolve_interpret
from . import kernel as K


def encode_pack(codes2, valid2, lengths_tbl, cwords_tbl, block_size: int,
                w32: int, cands: int = 33, *,
                interpret: Optional[bool] = None):
    """Same signature and bit-exact output as ``ref.encode_pack``.

    Runs the word-tiled grid (``K.gather_pack_tiled``): VMEM per program
    is bounded by (TILE_WORDS, block_size) regardless of chunk size, so
    the same kernel covers test-size chunks and paper-scale 32 MB ones.
    The untiled one-program-per-chunk ``K.gather_pack`` stays available
    as the small-chunk comparison point (kernel microbench, tests).
    """
    interpret = resolve_interpret(interpret)
    return K.gather_pack_tiled(
        jnp.asarray(codes2), jnp.asarray(valid2), jnp.asarray(lengths_tbl),
        jnp.asarray(cwords_tbl), block_size=block_size, w32=w32,
        cands=cands, interpret=interpret)


def hufenc_flat(codes: jax.Array, codewords, lengths, pad_sym: int = 512,
                *, interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array, int]:
    flat = jnp.asarray(codes, jnp.int32).reshape(-1)
    n = int(flat.shape[0])
    nblocks = max(-(-n // K.BLOCK), 1)
    padded = jnp.full((nblocks * K.BLOCK,), pad_sym, jnp.int32)
    padded = padded.at[:n].set(flat).reshape(nblocks, K.BLOCK)
    words, nbits = K.hufenc(padded, jnp.asarray(codewords),
                            jnp.asarray(lengths), interpret=interpret)
    return words, nbits, n


def to_host_stream(words, nbits, n: int, lengths) -> Tuple[np.ndarray, int]:
    """Concatenate per-block padded words into one contiguous u64 bitstream
    compatible with core.huffman.decode (host path)."""
    from ...core import huffman as H
    words = np.asarray(words)
    nbits = np.asarray(nbits)
    # expand each block's valid bits into a bit array (host-side utility —
    # used by tests and the checkpoint writer, not a hot path)
    bits = []
    for b in range(words.shape[0]):
        nb = int(nbits[b])
        w = words[b][: (nb + 31) // 32]
        bb = np.unpackbits(w.astype(">u4").view(np.uint8))[:nb]
        bits.append(bb)
    allbits = np.concatenate(bits) if bits else np.zeros(0, np.uint8)
    pad = (-len(allbits)) % 64
    allbits = np.pad(allbits, (0, pad))
    u64 = np.packbits(allbits).view(">u8").astype(np.uint64)
    return np.concatenate([u64, np.zeros(1, np.uint64)]), int(nbits.sum())
