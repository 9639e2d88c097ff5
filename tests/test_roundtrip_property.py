"""Property-based round-trip over the FULL compression matrix.

Hypothesis draws jointly from mode x dtype(f32/f64) x predictor
(lorenzo/none/auto) x kernel_impl(jnp/pallas-interpret) x data kind,
asserting for every example:

  * round-trip honors the error bound (staged reference decode);
  * staged and fused compression are bit-identical, field by field;
  * fused decode is bit-identical to the staged decoder;
  * fixed-ratio mode tracks the target ratio within tolerance on
    streams with enough chunks and entropy for the law to apply;
  * speculative fixed-ratio output is byte-identical to the
    sequential oracle (speculation='off').

The deterministic twin (tests/test_full_grid.py) pins the same grid
with fixed seeds; this suite explores random data around it. The 'ci'
profile below is derandomized so CI failures reproduce exactly.
"""
import os

import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need the 'test' extra")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from conftest import assert_streams_bit_identical  # noqa: E402
from repro.core import CEAZ, CEAZConfig, default_offline_codebook  # noqa: E402

# deterministic CI profile: derandomized so every run draws the same
# examples and a red CI run reproduces locally with no shrink lottery
settings.register_profile("ci", derandomize=True, max_examples=25,
                          deadline=None)
settings.register_profile("dev", max_examples=25, deadline=None)
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))

OFFLINE = default_offline_codebook()

# fixed shape menu bounds the number of jit variants the suite compiles
SHAPES = [(611,), (96, 67), (9, 24, 31)]


def _arrays(draw):
    shape = draw(st.sampled_from(SHAPES))
    n = int(np.prod(shape))
    kind = draw(st.sampled_from(["smooth", "noise", "const", "mixed"]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    if kind == "smooth":
        x = np.cumsum(rng.standard_normal(n)) / 10
    elif kind == "noise":
        x = rng.standard_normal(n) * draw(st.sampled_from([1e-3, 1.0, 50.0]))
    elif kind == "const":
        x = np.full(n, draw(st.sampled_from([0.0, -3.5, 17.0])))
    else:
        x = np.where(rng.random(n) < 0.05, rng.standard_normal(n) * 100,
                     np.cumsum(rng.standard_normal(n)) / 10)
    return x.reshape(shape), kind


@st.composite
def cases(draw):
    x, kind = _arrays(draw)
    dtype = draw(st.sampled_from([np.float32, np.float64]))
    mode = draw(st.sampled_from(["abs", "rel", "fixed_ratio"]))
    predictor = draw(st.sampled_from(["lorenzo", "none", "auto"]))
    kernel_impl = draw(st.sampled_from(["jnp", "pallas"]))
    speculation = draw(st.sampled_from(["off", 2, "auto"]))
    kw = dict(mode=mode, predictor=predictor, chunk_bytes=1 << 12,
              block_size=512, backend="jax", kernel_impl=kernel_impl,
              speculation=speculation)
    if mode == "fixed_ratio":
        kw["target_ratio"] = draw(st.sampled_from([6.0, 10.0]))
    else:
        kw["eb"] = draw(st.sampled_from([1e-2, 1e-4]))
    return x.astype(dtype), kind, kw


def _abs_bound(x, cfg: CEAZConfig) -> float:
    if cfg.mode == "abs":
        return cfg.eb
    vrange = float(np.max(x) - np.min(x)) or 1.0
    # fixed_ratio adapts eb per chunk; bound by the loosest chunk below
    return cfg.eb * vrange if cfg.mode == "rel" else float("inf")


@given(cases())
@settings(max_examples=25, deadline=None)
def test_roundtrip_bound_and_fused_parity(case):
    x, kind, kw = case
    staged = CEAZ(CEAZConfig(use_fused=False, **kw),
                  offline_codebook=OFFLINE)
    fused = CEAZ(CEAZConfig(use_fused=True, **kw),
                 offline_codebook=OFFLINE)
    cs, cf = staged.compress(x), fused.compress(x)

    # staged and fused streams are bit-identical across the whole grid
    assert_streams_bit_identical(cs, cf)

    rec = staged._decompress_staged(cs)            # reference decode
    assert rec.shape == x.shape and rec.dtype == x.dtype
    bound = _abs_bound(x, staged.cfg)
    if np.isfinite(bound):
        err = np.abs(rec.astype(np.float64) - x.astype(np.float64))
        assert err.max() <= bound
    else:                                          # fixed_ratio per-chunk ebs
        errs = np.abs(rec.reshape(-1).astype(np.float64)
                      - x.reshape(-1).astype(np.float64))
        ebs = np.repeat([ch.eb for ch in cs.chunks],
                        [ch.n_values for ch in cs.chunks])
        assert np.all(errs <= ebs)
    # fused decode must be bit-exact vs the staged reference
    rec_fused = fused.decompress(cf)
    assert rec_fused.dtype == rec.dtype
    assert np.array_equal(rec_fused, rec)


@given(cases())
@settings(max_examples=15, deadline=None)
def test_speculative_fixed_ratio_is_byte_identical(case):
    """For every drawn grid point, the fixed-ratio stream must not
    depend on the speculation window (kw's own speculation draw is
    overridden on both sides to make the comparison explicit)."""
    x, kind, kw = case
    kw = dict(kw, mode="fixed_ratio")
    kw.setdefault("target_ratio", 8.0)
    kw.pop("eb", None)
    mk = lambda spec: CEAZ(CEAZConfig(use_fused=True,
                                      **dict(kw, speculation=spec)),
                           offline_codebook=OFFLINE)
    c_off = mk("off").compress(x)
    c_spec = mk(4).compress(x)
    assert_streams_bit_identical(c_off, c_spec)


@given(st.integers(0, 2**31 - 1), st.sampled_from(["smooth", "noise"]),
       st.sampled_from([6.0, 10.0]), st.sampled_from(["off", "auto"]))
@settings(max_examples=10, deadline=None)
def test_fixed_ratio_tracks_target(seed, kind, target, speculation):
    """Achieved-vs-target ratio tolerance where the rate law applies:
    a stream with enough chunks for the feedback loop to settle and
    enough entropy that the target bit-rate is reachable at all
    (constant arrays saturate at ~0 bits however small eb gets)."""
    rng = np.random.default_rng(seed)
    n = 16 * 2048
    x = (np.cumsum(rng.standard_normal(n)) / 10 if kind == "smooth"
         else rng.standard_normal(n)).astype(np.float32)
    comp = CEAZ(CEAZConfig(mode="fixed_ratio", target_ratio=target,
                           chunk_bytes=1 << 13, block_size=512,
                           use_fused=True, speculation=speculation),
                offline_codebook=OFFLINE)
    c = comp.compress(x)
    # exclude the calibration transient: judge the controlled tail
    tail = c.chunks[4:]
    bits = sum(ch.total_bits() for ch in tail)
    vals = sum(ch.n_values for ch in tail)
    target_bitrate = c.word_bits / target
    assert abs(bits / vals - target_bitrate) <= max(0.35 * target_bitrate,
                                                    0.6)
