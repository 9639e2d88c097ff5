"""Edge-case pass over the fused pipeline: empty arrays, size-1 chunks,
non-finite inputs, ragged tails, and an adversarial speculation workload
— every case asserting the fused/speculative paths stay bit-identical
to their oracles (staged reference, speculation='off')."""
import numpy as np
import pytest

from conftest import assert_streams_bit_identical
from repro.core import CEAZ, CEAZConfig, default_offline_codebook

OFFLINE = default_offline_codebook()


def _pair(**kw):
    mk = lambda uf: CEAZ(CEAZConfig(backend="jax", use_fused=uf, **kw),
                         offline_codebook=OFFLINE)
    return mk(False), mk(True)


def _check_pair(x, **kw):
    staged, fused = _pair(**kw)
    cs, cf = staged.compress(x), fused.compress(x)
    assert_streams_bit_identical(cs, cf)
    rs = staged._decompress_staged(cs)
    rf = fused.decompress(cf)
    assert rs.dtype == rf.dtype == x.dtype and rs.shape == x.shape
    assert np.array_equal(rs, rf, equal_nan=True)
    return cs, rs


# -- empty arrays ------------------------------------------------------------

@pytest.mark.parametrize("mode,kw", [("rel", dict(eb=1e-4)),
                                     ("fixed_ratio",
                                      dict(target_ratio=8.0))])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("shape", [(0,), (0, 7)])
def test_empty_arrays(mode, kw, dtype, shape):
    x = np.zeros(shape, dtype)
    cs, rec = _check_pair(x, mode=mode, **kw)
    assert cs.chunks == [] and cs.nbytes() == 0
    assert rec.shape == shape


def test_empty_member_in_batch():
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True),
                offline_codebook=OFFLINE)
    rng = np.random.default_rng(0)
    shards = [rng.standard_normal(5000).astype(np.float32),
              np.zeros(0, np.float32),
              rng.standard_normal(5000).astype(np.float32)]
    outs = comp.compress_batch(shards)
    recs = comp.decompress_batch(outs)
    for r, s in zip(recs, shards):
        assert r.shape == s.shape and r.dtype == s.dtype


# -- size-1 chunks -----------------------------------------------------------

@pytest.mark.parametrize("mode,kw", [("abs", dict(eb=1e-3)),
                                     ("fixed_ratio",
                                      dict(target_ratio=8.0))])
def test_size_one_chunks(mode, kw):
    """chunk_bytes=4, block_size=1 => every chunk holds ONE value; the
    whole policy/feedback machinery runs per value."""
    rng = np.random.default_rng(3)
    x = np.cumsum(rng.standard_normal(17)).astype(np.float32)
    cs, rec = _check_pair(x, mode=mode, chunk_bytes=4, block_size=1, **kw)
    assert len(cs.chunks) == 17
    assert all(ch.n_values == 1 for ch in cs.chunks)


def test_single_value_stream():
    x = np.asarray([1.25], np.float32)
    cs, rec = _check_pair(x, mode="rel", eb=1e-4)
    assert len(cs.chunks) == 1 and cs.chunks[0].n_values == 1


# -- non-finite inputs -------------------------------------------------------

@pytest.mark.parametrize("fill", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "-inf"])
@pytest.mark.parametrize("mode,kw", [("abs", dict(eb=1e-3)),
                                     ("fixed_ratio",
                                      dict(target_ratio=8.0))])
def test_all_nonfinite_inputs(fill, mode, kw):
    """All-NaN / all-Inf arrays must compress deterministically and
    bit-identically on both paths (NaN disables the bound — comparisons
    against NaN are false — while +-Inf round-trips exactly through the
    literal channel)."""
    x = np.full(5000, fill, np.float32)
    cs, rec = _check_pair(x, mode=mode, chunk_bytes=1 << 12,
                          block_size=512, **kw)
    if np.isinf(fill):
        assert np.array_equal(rec, x)     # literals restore the infs


def test_speculation_off_identity_on_nonfinite_mix():
    rng = np.random.default_rng(9)
    x = np.cumsum(rng.standard_normal(6 * 1024)).astype(np.float32)
    x[::97] = np.inf
    x[5::131] = np.nan
    mk = lambda spec: CEAZ(
        CEAZConfig(mode="fixed_ratio", target_ratio=8.0, use_fused=True,
                   chunk_bytes=1 << 12, speculation=spec),
        offline_codebook=OFFLINE)
    assert_streams_bit_identical(mk("off").compress(x),
                                 mk(4).compress(x))


# -- ragged tails ------------------------------------------------------------

@pytest.mark.parametrize("mode,kw", [("rel", dict(eb=1e-4)),
                                     ("fixed_ratio",
                                      dict(target_ratio=8.0))])
@pytest.mark.parametrize("tail", [1, 300, 511])
def test_last_chunk_shorter_than_block(mode, kw, tail):
    """A stream whose last chunk is SHORTER than the block grain: the
    tail chunk's only block is partial, exercising the hufdec
    early-exit bound end-to-end on both decode paths."""
    rng = np.random.default_rng(5)
    n = 2 * 4096 + tail                  # cv=4096, block=512, tail<block
    x = np.cumsum(rng.standard_normal(n)).astype(np.float32)
    cs, rec = _check_pair(x, mode=mode, chunk_bytes=1 << 14,
                          block_size=512, **kw)
    assert cs.chunks[-1].n_values == tail
    assert len(cs.chunks[-1].block_nbits) == 1


# -- decode megakernel edges (PR 9) ------------------------------------------
# `_check_pair` above already routes fused decode through the megakernel;
# this section pins it against the staged decoder exactly at the
# megakernel's own seams: degenerate chunk grains, word-tile boundaries
# of the tiled walk regime, and all-outlier chunks where every code is
# the escape symbol.

def _check_decode_edges(x, kernel_impl="jnp", **kw):
    """One stream, staged and fused decode, byte-equal outputs."""
    staged, fused = _pair(kernel_impl=kernel_impl, **kw)
    c = fused.compress(x)
    want = staged._decompress_staged(c)
    comp = CEAZ(CEAZConfig(backend="jax", use_fused=True,
                           kernel_impl=kernel_impl, **kw),
                offline_codebook=OFFLINE)
    got = comp.decompress(c)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(want, got, equal_nan=True)
    return c, want


@pytest.mark.parametrize("kernel_impl", ["jnp", "pallas"])
def test_decode_megakernel_degenerate_grains(kernel_impl):
    """Empty streams, a single-value stream and size-1 chunks
    (chunk_bytes=4, block_size=1: one value per program) through every
    decode route."""
    rng = np.random.default_rng(3)
    for shape in [(0,), (0, 7)]:
        _check_decode_edges(np.zeros(shape, np.float32),
                            kernel_impl=kernel_impl, mode="rel", eb=1e-4)
    _check_decode_edges(np.asarray([1.25], np.float32),
                        kernel_impl=kernel_impl, mode="rel", eb=1e-4)
    x = np.cumsum(rng.standard_normal(17)).astype(np.float32)
    c, _ = _check_decode_edges(x, kernel_impl=kernel_impl, mode="abs",
                               eb=1e-3, chunk_bytes=4, block_size=1)
    assert all(ch.n_values == 1 for ch in c.chunks)


def test_decode_megakernel_tails_at_word_tile_boundaries():
    """Chunks past the one-program limit (2^18 values) decode through
    the word-tiled walk; sweep the ragged tail across a tile seam of
    the tiled grid — one short of a full tile, exactly full, one value
    into a fresh tile, and a lone value."""
    from repro.kernels.megakernel import decode_kernel as DK
    rng = np.random.default_rng(8)
    cv = 1 << 18
    bs = 512
    assert cv > DK._DEC_FUSE_LIMIT
    tile = (DK._DEC_TILE_VALUES // bs) * bs      # values per walk tile
    for tail in (tile - 1, tile, tile + 1, 1):
        x = np.cumsum(rng.standard_normal(cv + tail)).astype(np.float32)
        c, _ = _check_decode_edges(x, mode="abs", eb=1e-3,
                                   chunk_bytes=4 * cv, block_size=bs)
        assert c.chunks[0].n_values == cv and c.chunks[-1].n_values == tail
    # the same seam through the Pallas tiled kernel (interpret on CPU)
    x = np.cumsum(rng.standard_normal(cv + tile + 1)).astype(np.float32)
    _check_decode_edges(x, kernel_impl="pallas", mode="abs", eb=1e-3,
                        chunk_bytes=4 * cv, block_size=bs)


@pytest.mark.parametrize("kernel_impl", ["jnp", "pallas"])
@pytest.mark.parametrize("predictor", ["lorenzo", "none"])
def test_decode_megakernel_all_outlier_chunks(predictor, kernel_impl):
    """Every quantized delta escapes the code range (code 0 for all
    values): the rank-gather patch must reconstruct the whole chunk
    from the outlier channel alone, on both inverse forms."""
    n = 3000
    if predictor == "lorenzo":
        x = (np.arange(n) * 5.0).astype(np.float32)   # step >> 2*eb*511
    else:
        rng = np.random.default_rng(2)
        x = (rng.standard_normal(n) * 1e4).astype(np.float32)
    c, _ = _check_decode_edges(x, kernel_impl=kernel_impl, mode="abs",
                               eb=1e-3, predictor=predictor,
                               chunk_bytes=1 << 12, block_size=512)
    # everything escapes except the handful of values that anchor the
    # predictor itself (the stream head / the centre code)
    assert sum(len(ch.outlier_idx) for ch in c.chunks) >= c.n_values - 2
    assert any(len(ch.outlier_idx) == ch.n_values for ch in c.chunks)


# -- adversarial speculation workload ---------------------------------------

def test_speculation_miss_every_chunk_monotone_ramp():
    """A monotone per-chunk rate ramp (noise sigma doubling every
    chunk) defeats the rate-law forecast on EVERY chunk: the bound
    moves each feedback step by more than the prediction can see. The
    speculative pipeline must repair every miss and still emit the
    sequential loop's exact bytes."""
    from repro.runtime import fused as F
    rng = np.random.default_rng(13)
    cv = 2048
    n_chunks = 12
    # sigma x4 per chunk = +2 bits/chunk, scaled so eb never saturates
    # at the controller clamps (a clamped bound predicts trivially)
    parts = [rng.standard_normal(cv) * (1e-3 * 4.0 ** j)
             for j in range(n_chunks)]
    x = np.concatenate(parts).astype(np.float32)
    mk = lambda spec: CEAZ(
        CEAZConfig(mode="fixed_ratio", target_ratio=8.0, use_fused=True,
                   chunk_bytes=cv * 4, block_size=512, speculation=spec),
        offline_codebook=OFFLINE)
    c_off = mk("off").compress(x)
    repairs = []
    orig = F._run_pass1
    F._run_pass1 = lambda *a, **k: repairs.append(1) or orig(*a, **k)
    try:
        c_spec = mk(6).compress(x)
    finally:
        F._run_pass1 = orig
    assert_streams_bit_identical(c_off, c_spec)
    # the ramp must actually defeat the forecast: every speculated
    # chunk except each window's (always-exact) head needed a repair
    windows = -(-n_chunks // 6)
    assert len(repairs) >= n_chunks - windows


def test_speculation_window_one_equals_off():
    rng = np.random.default_rng(21)
    x = np.cumsum(rng.standard_normal(8 * 1024)).astype(np.float32)
    mk = lambda spec: CEAZ(
        CEAZConfig(mode="fixed_ratio", target_ratio=8.0, use_fused=True,
                   chunk_bytes=1 << 12, speculation=spec),
        offline_codebook=OFFLINE)
    assert_streams_bit_identical(mk("off").compress(x), mk(1).compress(x))


# -- telemetry at the degenerate points --------------------------------------

def test_empty_stream_produces_valid_all_zero_manifest(tmp_path):
    """An engine closed with zero submissions must still embed a valid,
    all-zero telemetry manifest — and the report renderer must handle it
    without division by zero."""
    from repro.io import engine as E
    from repro.obs import manifest as M
    from repro.obs import report

    path = str(tmp_path / "empty.ceazs")
    eng = E.AsyncCompressWriteEngine(
        path, lambda keys, items: [np.asarray(i).tobytes() for i in items],
        fsync=False)
    eng.close()
    assert eng.manifest["summary"] == {
        "n_records": 0, "raw_bytes": 0, "stored_bytes": 0,
        "ratio": 0.0, "overlap_efficiency": 0.0}
    assert all(r["share"] == 0.0 for r in M.stage_rows(eng.manifest))
    with E.StreamReader(path) as r:
        assert len(r) == 0
        assert r.telemetry() == eng.manifest
    assert report.main([path]) == 0


def test_zero_chunk_array_keeps_metrics_summary_finite():
    """Compressing a zero-size array routes through the facade without
    producing chunks; every derived ratio in the metrics summary must
    stay finite (guarded division) on a registry that saw only that."""
    from repro.obs import metrics as om

    reg = om.MetricsRegistry()
    s = reg.summary()
    assert all(np.isfinite(v) for v in s.values())

    _, fused = _pair(mode="rel", eb=1e-4)
    before = om.snapshot()
    c = fused.compress(np.zeros((0,), np.float32))
    assert len(c.chunks) == 0
    d = om.diff(om.snapshot(), before)
    assert d.get(om.CHUNKS, 0) == 0
    s = om.summary()
    assert all(np.isfinite(v) for v in s.values())
