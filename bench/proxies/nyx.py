"""NYX cosmology baryon density, 3-D: log-normal-ish, mid
compressibility (paper Table 8: ratio 8.5 at a range-relative bound of
1e-4)."""
import jax
import jax.numpy as jnp

from lib import fields

TARGET_SIGMA = 3.4


def make(key, shape):
    k1, k2 = jax.random.split(key)
    smooth = fields.normalize(jnp.exp(2.0 * fields.smooth_base(k1, shape)))
    fine = fields.spectral_field(k2, shape, 1.4) - 0.5
    return fields.calibrated(smooth, fine, TARGET_SIGMA)
