"""SDRBench field proxies, made on the device from the seed.

A `jax.numpy` port of the generators of the program's `data/fields.py`,
kept here so that the yardstick's data cannot change with the program.
A configuration names its proxy, `bench/proxies/<name>.py`, which
defines `make(key, shape)` and its `TARGET_SIGMA`. Each proxy mixes a
normalized smooth structure with a fine-scale component whose amplitude
is solved so that the std of the Lorenzo delta, in quantization units at
a range-relative bound of 1e-4, hits the dataset's target (the anchor
that gives the paper's Table 4 and Fig 10 ratios). Lorenzo is linear,
so the delta variances add.

The random numbers come from `jax.random`, so a field is not the one
the numpy original draws for the same seed; the calibration is the
same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import named

REF_REL_EB = 1e-4


def proxy(name: str):
    """The proxy module `bench/proxies/<name>.py`."""
    return named.module("proxies", name)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative whole number, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def fft_len(n: int) -> int:
    """The least power of two >= n."""
    return 1 << max(n - 1, 0).bit_length()


def _freq_radius(shape):
    """|k| on the rfftn grid of `shape` (cycles per sample)."""
    axes = [jnp.fft.fftfreq(n) for n in shape[:-1]] \
        + [jnp.fft.rfftfreq(shape[-1])]
    grids = jnp.meshgrid(*axes, indexing="ij")
    return jnp.sqrt(sum(g ** 2 for g in grids))


def normalize(x):
    x = x - jnp.min(x)
    return x / jnp.maximum(jnp.max(x), 1e-30)


def spectral_field(key, shape, beta: float):
    """Gaussian random field with isotropic power spectrum ~ k^-beta,
    normalized to [0, 1]."""
    white = jax.random.normal(key, shape, jnp.float32)
    f = jnp.fft.rfftn(white)
    k = _freq_radius(shape)
    k = k.at[(0,) * len(shape)].set(1.0)
    f = f * k ** (-beta / 2.0)
    return normalize(jnp.fft.irfftn(f, s=shape).astype(jnp.float32))


def smooth_base(key, shape, keep_frac: float = 0.02):
    """Very-low-frequency structure: a k^-3.5 field cut to its lowest
    modes, so its own Lorenzo delta is tiny."""
    f = spectral_field(key, shape, 3.5)
    ft = jnp.fft.rfftn(f)
    k_keep = max(keep_frac * 0.5, 3.0 / min(shape))
    ft = jnp.where(_freq_radius(shape) > k_keep, 0, ft)
    return normalize(jnp.fft.irfftn(ft, s=shape).astype(jnp.float32))


def lorenzo_delta_std(x):
    """Std of the n-D Lorenzo delta of `x`, boundary faces left out."""
    d = x
    for ax in range(x.ndim):
        d = jnp.diff(d, axis=ax, prepend=0)
    return jnp.std(d[(slice(1, None),) * x.ndim])


def calibrated(smooth, fine, target_sigma: float):
    """smooth + a * fine, with `a` solved so that the Lorenzo delta's std
    is `target_sigma` quantization units at REF_REL_EB (range ~1)."""
    target = target_sigma * 2.0 * REF_REL_EB
    s_smooth = lorenzo_delta_std(smooth)
    s_fine = lorenzo_delta_std(fine)
    a = jnp.sqrt(jnp.maximum(target ** 2 - s_smooth ** 2, 0.0)) \
        / jnp.maximum(s_fine, 1e-30)
    return (smooth + a * fine).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("name", "shape", "count"))
def _make(key, name: str, shape: tuple, count: int):
    gen = proxy(name).make
    return [gen(jax.random.fold_in(key, i), shape) for i in range(count)]


def make_fields(seed: int, name: str, shape, count: int):
    """`count` fields of one snapshot from the proxy `name`, made on the
    default device in one jitted call; field i is the same for a given
    seed whatever `count`."""
    return _make(seed_key(seed), name, tuple(shape), count)
