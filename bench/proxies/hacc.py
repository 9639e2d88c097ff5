"""HACC (EXASKY cosmology) particle coordinate or velocity: coarse
locality plus white jitter, the least Lorenzo-friendly histogram (paper
Figs 7, 10; ratio about 8 at a range-relative bound of 1e-4)."""
import jax
import jax.numpy as jnp

from lib import fields

TARGET_SIGMA = 3.9


def make(key, shape):
    (n,) = shape
    k1, k2 = jax.random.split(key)
    # the smooth base is drawn on a power-of-two grid, whose FFT is cheap
    # on any device, and cut to the rank's share
    smooth = fields.normalize(fields.smooth_base(
        k1, (fields.fft_len(n),))[:n])
    fine = jax.random.normal(k2, shape, jnp.float32)
    return fields.calibrated(smooth, fine, TARGET_SIGMA) * 256.0
