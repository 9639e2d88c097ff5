"""Fused device-resident decode vs staged reference: bit-exactness.

The contract (runtime/fused_decode.py): for float32 Lorenzo streams the
fused decode — batched jit Huffman table decode, device outlier scatter
and inverse dual-quant, host float64 finish — produces output
BIT-IDENTICAL to the host-staged reference decompressor in every mode,
for chunk sizes that do and do not divide the block size. Ineligible
streams (float64, value-direct) fall back to the staged path inside the
``CEAZ.decompress_batch`` facade.
"""
import numpy as np
import pytest

from repro.core import CEAZ, CEAZConfig, default_offline_codebook
from repro.core import huffman as H
from repro.data import fields as F


@pytest.fixture(scope="module")
def offline_cb():
    return default_offline_codebook()


@pytest.fixture(scope="module")
def field():
    return F.cesm_proxy(seed=3).astype(np.float32)


def _pair(offline_cb, mode, chunk_bytes, block_size, **kw):
    mk = lambda uf: CEAZ(
        CEAZConfig(mode=mode, chunk_bytes=chunk_bytes,
                   block_size=block_size, backend="jax",
                   predictor="lorenzo", use_fused=uf, **kw),
        offline_codebook=offline_cb)
    return mk(False), mk(True)


def _assert_same(a: np.ndarray, b: np.ndarray):
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.array_equal(a, b)


@pytest.mark.parametrize("mode,kw", [
    ("abs", dict(eb=1e-3)),
    ("rel", dict(eb=1e-4)),
    ("fixed_ratio", dict(target_ratio=10.0)),
])
@pytest.mark.parametrize("chunk_bytes,block_size", [
    (1 << 17, 4096),
    (30000, 4096),          # chunk does not divide block: partial tails
])
def test_decode_bit_exact(offline_cb, field, mode, kw, chunk_bytes,
                          block_size):
    staged, fusedc = _pair(offline_cb, mode, chunk_bytes, block_size, **kw)
    c = staged.compress(field)
    _assert_same(staged._decompress_staged(c), fusedc.decompress(c))


def test_decode_3d_and_tiny(offline_cb, rng):
    for shape in [(12, 40, 40), (7,), (100, 100), (4, 5, 6, 7)]:
        x = (np.cumsum(rng.standard_normal(int(np.prod(shape))))
             .reshape(shape).astype(np.float32) / 10)
        staged, fusedc = _pair(offline_cb, "rel", 1 << 16, 4096, eb=1e-4)
        c = staged.compress(x)
        _assert_same(staged._decompress_staged(c), fusedc.decompress(c))


def test_decode_outlier_heavy(offline_cb, rng):
    """White noise at a tight bound: nearly every delta is an escape —
    exercises the dense outlier scatter and the literal patch."""
    noise = (rng.standard_normal(20000) * 100).astype(np.float32)
    staged, fusedc = _pair(offline_cb, "abs", 1 << 14, 4096, eb=1e-4)
    c = staged.compress(noise)
    rec = fusedc.decompress(c)
    _assert_same(staged._decompress_staged(c), rec)
    assert np.abs(rec.astype(np.float64) - noise).max() <= 1e-4


def test_decompress_batch_heterogeneous_fallback(offline_cb, field, rng):
    """One batch mixing fused-eligible float32 streams with float64 and
    value-direct streams: the facade decodes the eligible ones in one
    batched pass and routes the rest to the staged path — output order
    and bits both preserved."""
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           chunk_bytes=1 << 17),
                offline_codebook=offline_cb)
    x64 = np.cumsum(rng.standard_normal(30000))
    direct = CEAZ(CEAZConfig(mode="rel", eb=1e-4, predictor="none"),
                  offline_codebook=offline_cb)
    noise = rng.standard_normal(20000).astype(np.float32)
    comps = [comp.compress(field), comp.compress(x64),
             direct.compress(noise),
             comp.compress(F.nyx_proxy(seed=1).astype(np.float32))]
    outs = comp.decompress_batch(comps)
    assert len(outs) == len(comps)
    for o, c in zip(outs, comps):
        _assert_same(comp._decompress_staged(c), o)


def test_batch_shares_one_decode_pass(offline_cb, monkeypatch):
    """decompress_batch must stage all eligible arrays' chunks through a
    single batched Huffman-decode launch."""
    from repro.runtime import fused_decode as FD
    calls = []
    orig = FD._ChunkBatch.run

    def spy(self):
        calls.append(len(self.counts))
        return orig(self)
    monkeypatch.setattr(FD._ChunkBatch, "run", spy)
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           chunk_bytes=1 << 15),
                offline_codebook=offline_cb)
    shards = [F.nyx_proxy(seed=s).astype(np.float32) for s in range(3)]
    comps = [comp.compress(s) for s in shards]
    comp.decompress_batch(comps)
    assert len(calls) == 1                 # one pass for the whole group
    assert calls[0] == sum(len(c.chunks) for c in comps)


def test_megakernel_decode_accounts_kernel_pass(offline_cb, field):
    """A megakernel decompress is ONE accounted ceaz_chunk_dec pass:
    the per-(op, impl) kernel counter moves by exactly one (the same
    dispatch.measure contract as the encode megakernel)."""
    from repro.kernels import dispatch
    from repro.obs import metrics as om
    impl = dispatch.resolve_name("ceaz_chunk_dec", "auto")
    key = om.KERNEL_CALLS + f'{{impl="{impl}",op="ceaz_chunk_dec"}}'
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           chunk_bytes=1 << 15),
                offline_codebook=offline_cb)
    c = comp.compress(field)
    before = om.snapshot().get(key, 0)
    comp.decompress(c)
    assert om.snapshot().get(key, 0) == before + 1


def test_codebook_memoization(offline_cb, field):
    """Satellite: decode tables are built once per distinct codebook —
    the same lengths array returns the SAME cached Codebook instance, so
    its lazily-built tables are shared across chunks and calls."""
    lengths = H.Codebook.from_freqs(
        np.arange(H.NUM_SYMBOLS) % 97).lengths
    a = H.codebook_from_lengths(lengths)
    b = H.codebook_from_lengths(np.array(lengths, copy=True))
    assert a is b
    sym, ln = a.tables()
    assert sym is a.tables()[0]            # instance-cached tables
    # and the staged decompressor goes through the cache
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, chunk_bytes=1 << 15,
                           adaptive=False),    # rebuild every chunk
                offline_codebook=offline_cb)
    c = comp.compress(field)
    assert sum(ch.codebook_lengths is not None for ch in c.chunks) > 1
    H._codebook_from_lengths_cached.cache_clear()
    comp._decompress_staged(c)
    info = H._codebook_from_lengths_cached.cache_info()
    assert info.misses == len({ch.codebook_id for ch in c.chunks
                               if ch.codebook_lengths is not None})


def test_block_size_mismatch_fails_loudly(offline_cb, field):
    """The wire format carries per-block bit counts but not the block
    grain; decoding with the wrong block_size would pass every checksum
    and return garbage — both decode paths must refuse instead."""
    enc = CEAZ(CEAZConfig(mode="rel", eb=1e-4, chunk_bytes=1 << 17,
                          block_size=1024), offline_codebook=offline_cb)
    c = enc.compress(field)
    for uf in (False, True):
        dec = CEAZ(CEAZConfig(mode="rel", eb=1e-4, block_size=4096,
                              use_fused=uf), offline_codebook=offline_cb)
        with pytest.raises(ValueError, match="block_size"):
            dec.decompress(c)
        with pytest.raises(ValueError, match="block_size"):
            dec.decompress_batch([c])
    ok = CEAZ(CEAZConfig(mode="rel", eb=1e-4, block_size=1024,
                         use_fused=True), offline_codebook=offline_cb)
    _assert_same(enc._decompress_staged(c), ok.decompress(c))


def test_fused_decode_respects_error_bound(offline_cb, field):
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           chunk_bytes=1 << 17),
                offline_codebook=offline_cb)
    c = comp.compress(field)
    rec = comp.decompress(c)
    bound = 1e-4 * float(field.max() - field.min())
    assert np.abs(rec.astype(np.float64) - field).max() <= bound
