"""Public wrapper: pack/unpack arbitrary-shape int arrays at fixed width.

`pack_flat(x, bits)` zero-pads to the (R, 32/bits, 128) tile layout and
returns (words (R,128) u32, n) — a static-shape payload given a static
input shape, which is what the compressed collectives need.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import kernel as K


def _layout(n: int, bits: int) -> Tuple[int, int]:
    per = 32 // bits
    vals_per_row = per * K.LANES
    rows = max(-(-n // vals_per_row), 1)
    rows = -(-rows // K.SUBLANES) * K.SUBLANES
    return rows, per


def packed_rows(n: int, bits: int) -> int:
    return _layout(n, bits)[0]


def pack_flat(x: jax.Array, bits: int, *, interpret: Optional[bool] = None) -> jax.Array:
    flat = jnp.asarray(x, jnp.int32).reshape(-1)
    n = flat.shape[0]
    rows, per = _layout(n, bits)
    padded = jnp.zeros((rows * per * K.LANES,), jnp.int32).at[:n].set(flat)
    vals = padded.reshape(rows, per, K.LANES)
    return K.pack(vals, bits, interpret=interpret)


def unpack_flat(words: jax.Array, n: int, bits: int,
                *, interpret: Optional[bool] = None) -> jax.Array:
    vals = K.unpack(words, bits, interpret=interpret)
    return vals.reshape(-1)[:n]
