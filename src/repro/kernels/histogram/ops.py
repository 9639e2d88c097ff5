"""Public wrapper: histogram of arbitrary-shape code arrays (with padding).

Padding uses the outlier escape code 0? No — padding must not perturb the
histogram, so we pad with a sentinel OUTSIDE [0, 1024) and the kernel's
one-hot compare naturally drops it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import kernel as K

_SENTINEL = -1


def histogram(codes: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    flat = jnp.asarray(codes, jnp.int32).reshape(-1)
    n = flat.shape[0]
    cols = K.COLS
    rows = max(-(-n // cols), 1)
    rows = -(-rows // K.ROWS) * K.ROWS
    padded = jnp.full((rows * cols,), _SENTINEL, jnp.int32).at[:n].set(flat)
    return K.histogram(padded.reshape(rows, cols), interpret=interpret)
