"""Fused device-resident pipeline vs staged reference: bit-exactness.

The contract (runtime/fused.py): with the same quantization backend, the
fused path's CompressedChunk payloads — words, block_nbits, outliers —
and the literal channel are BIT-IDENTICAL to the staged path
(use_fused=False, backend='jax') in every mode, for chunk sizes that do
and do not divide the block size, on both stats paths (host snapshot and
device scatter summaries).
"""
import numpy as np
import pytest

from repro.core import CEAZ, CEAZConfig, default_offline_codebook
from repro.core import huffman as H
from repro.data import fields as F
from repro.runtime import fused


@pytest.fixture(scope="module")
def offline_cb():
    return default_offline_codebook()


@pytest.fixture(scope="module")
def field():
    return F.cesm_proxy(seed=3).astype(np.float32)


@pytest.fixture(params=[False, True], ids=["host_stats", "device_stats"])
def stats_on_device(request, monkeypatch):
    monkeypatch.setattr(fused, "_default_stats_on_device",
                        lambda: request.param)
    return request.param


def _pair(offline_cb, mode, chunk_bytes, block_size, **kw):
    mk = lambda uf: CEAZ(
        CEAZConfig(mode=mode, chunk_bytes=chunk_bytes,
                   block_size=block_size, backend="jax",
                   predictor="lorenzo", use_fused=uf, **kw),
        offline_codebook=offline_cb)
    return mk(False), mk(True)


def _assert_bit_identical(cs, cf):
    assert len(cs.chunks) == len(cf.chunks)
    for a, b in zip(cs.chunks, cf.chunks):
        assert np.array_equal(a.words, b.words)
        assert np.array_equal(a.block_nbits, b.block_nbits)
        assert np.array_equal(a.outlier_idx, b.outlier_idx)
        assert np.array_equal(a.outlier_delta, b.outlier_delta)
        assert a.action == b.action and a.eb == b.eb
        assert a.n_values == b.n_values
        assert a.codebook_id == b.codebook_id
        la, lb = a.codebook_lengths, b.codebook_lengths
        assert (la is None) == (lb is None)
        if la is not None:
            assert np.array_equal(la, lb)
    assert np.array_equal(cs.literal_idx, cf.literal_idx)
    assert np.array_equal(cs.literal_val, cf.literal_val)


@pytest.mark.parametrize("mode,kw", [
    ("abs", dict(eb=1e-3)),
    ("rel", dict(eb=1e-4)),
    ("fixed_ratio", dict(target_ratio=10.0)),
])
# 2^17 bytes -> 32768 values (divides 4096); 30000 bytes -> 7500 values
# (does NOT divide 4096: tests the partial tail block per chunk)
@pytest.mark.parametrize("chunk_bytes,block_size", [
    (1 << 17, 4096),
    (30000, 4096),
])
def test_payload_parity(offline_cb, field, stats_on_device, mode, kw,
                        chunk_bytes, block_size):
    staged, fusedc = _pair(offline_cb, mode, chunk_bytes, block_size, **kw)
    cs, cf = staged.compress(field), fusedc.compress(field)
    _assert_bit_identical(cs, cf)
    # decompression is therefore identical too
    assert np.array_equal(staged.decompress(cs), fusedc.decompress(cf))


def test_parity_on_outlier_heavy_stream(offline_cb, stats_on_device, rng):
    """White noise at a tight bound makes nearly every delta an escape —
    exercises the fixed-capacity compaction overflow fallback."""
    noise = (rng.standard_normal(20000) * 100).astype(np.float32)
    staged, fusedc = _pair(offline_cb, "abs", 1 << 14, 4096, eb=1e-4)
    cs, cf = staged.compress(noise), fusedc.compress(noise)
    _assert_bit_identical(cs, cf)
    rec = fusedc.decompress(cf)
    assert np.abs(rec.astype(np.float64) - noise).max() <= 1e-4


def test_parity_3d_and_tiny(offline_cb, stats_on_device, rng):
    for shape in [(12, 40, 40), (7,), (100, 100)]:
        x = (np.cumsum(rng.standard_normal(int(np.prod(shape))))
             .reshape(shape).astype(np.float32) / 10)
        staged, fusedc = _pair(offline_cb, "rel", 1 << 16, 4096, eb=1e-4)
        cs, cf = staged.compress(x), fusedc.compress(x)
        _assert_bit_identical(cs, cf)


def test_roundtrip_through_huffman_decode(offline_cb, field):
    """Decode the fused wire format directly with core.huffman.decode:
    per-block bit counts + packed words must reproduce the symbol stream
    the staged encoder would have produced."""
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, chunk_bytes=1 << 17,
                           backend="jax", predictor="lorenzo",
                           use_fused=True),
                offline_codebook=offline_cb)
    c = comp.compress(field)
    # replay the codebook sequence exactly as the decompressor does
    current = offline_cb
    import repro.core.dualquant as dq
    for ch in c.chunks:
        if ch.codebook_lengths is not None:
            lengths = ch.codebook_lengths.astype(np.int64)
            current = H.Codebook(lengths=ch.codebook_lengths,
                                 codes=H._canonize(lengths))
        elif ch.action == "offline":
            current = offline_cb
        syms = H.decode(ch.words, ch.block_nbits, ch.n_values,
                        comp.cfg.block_size, current)
        assert len(syms) == ch.n_values
        # non-escape symbols must invert exactly through the codebook
        again, _, _ = H.encode(syms, current, comp.cfg.block_size)
        assert np.array_equal(again, ch.words)
    rec = comp.decompress(c)
    bound = 1e-4 * float(field.max() - field.min())
    assert np.abs(rec.astype(np.float64) - field).max() <= bound


def test_fixed_ratio_controller_sequence_matches(offline_cb, field):
    """The eb feedback sequence (policy state) must be identical, chunk
    for chunk, between fused and staged fixed-ratio compression."""
    staged, fusedc = _pair(offline_cb, "fixed_ratio", 1 << 16, 4096,
                           target_ratio=8.0)
    cs, cf = staged.compress(field), fusedc.compress(field)
    assert [c.eb for c in cs.chunks] == [c.eb for c in cf.chunks]
    assert [c.action for c in cs.chunks] == [c.action for c in cf.chunks]


def test_batch_compress_matches_per_shard(offline_cb):
    shards = [F.nyx_proxy(seed=s).astype(np.float32) for s in range(3)]
    outs = fused.batch_compress(shards, 1e-4, 1 << 15, 4096,
                                offline=offline_cb)
    staged = CEAZ(CEAZConfig(mode="rel", eb=1e-4, chunk_bytes=1 << 17,
                             backend="jax", predictor="lorenzo",
                             use_fused=False),
                  offline_codebook=offline_cb)
    for sh, cf in zip(shards, outs):
        cs = staged.compress(sh)
        _assert_bit_identical(cs, cf)


def test_float64_falls_back_to_staged(offline_cb, rng):
    x64 = np.cumsum(rng.standard_normal(50000)).astype(np.float64)
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-5, use_fused=True),
                offline_codebook=offline_cb)
    c = comp.compress(x64)
    rec = comp.decompress(c)
    assert c.word_bits == 64
    assert np.abs(rec - x64).max() <= 1e-5 * (x64.max() - x64.min())


def _encode_counter(name):
    from repro.obs import metrics as om
    return om.counter(name, side="encode").value()


@pytest.fixture(scope="module")
def proxy_fields():
    """Proxy fields normalized as the SDRBench fields are: at rel 1e-4
    their guard band holds 1-3% of the values, over the 1/256 floor."""
    return {"hacc": F.hacc_proxy(seed=0), "nyx": F.nyx_proxy(seed=5)}


@pytest.mark.parametrize("codebook", ["exact", "bank"])
@pytest.mark.parametrize("name", ["hacc", "nyx"])
@pytest.mark.parametrize("capacity", ["sized", "floor"])
def test_literal_candidates_sized_from_the_band(offline_cb, proxy_fields,
                                                stats_on_device, monkeypatch,
                                                codebook, name, capacity):
    """At rel 1e-4 the band yields more candidates than the 1/256 floor
    and fewer than the capacity sized from it: the literal check replays
    the candidates only. Held to the floor, the candidates overflow and
    the check runs densely on the host, once. Either way the literals,
    and the whole stream, equal the staged reference's."""
    from repro.obs import metrics as om
    x = proxy_fields[name]
    n = x.size
    used = []
    sized = fused.literal_capacity

    def spy(n_, eb=None, amax=None):
        k = sized(n_, eb, amax) if capacity == "sized" \
            else sized(n_)
        used.append(k)
        return k
    monkeypatch.setattr(fused, "literal_capacity", spy)
    staged, fusedc = _pair(offline_cb, "rel", 1 << 20, 4096, eb=1e-4,
                           codebook=codebook)
    cs = staged.compress(x)
    checks = _encode_counter(om.LITERAL_CHECKS)
    dense = _encode_counter(om.LITERAL_DENSE_CHECKS)
    cf = fusedc.compress(x)
    _assert_bit_identical(cs, cf)
    assert len(cs.literal_idx) > 0
    assert _encode_counter(om.LITERAL_CHECKS) == checks + 1
    overflows = stats_on_device and capacity == "floor"
    assert _encode_counter(om.LITERAL_DENSE_CHECKS) == dense + overflows
    if stats_on_device:
        assert used == [n // 16 if capacity == "sized" else n // 256]


def test_literal_candidates_bunched_at_the_bound_overflow(offline_cb,
                                                          stats_on_device):
    """Values on the half grid of the quantizer err by the bound itself,
    so every one is a candidate, past any capacity the band's spread
    predicts: the dense host check still gives the staged literals."""
    from repro.obs import metrics as om
    eb = 1e-3
    x = ((np.arange(1 << 16) % 1000 + 0.5) * (2 * eb)).astype(np.float32)
    staged, fusedc = _pair(offline_cb, "abs", 1 << 18, 4096, eb=eb)
    cs = staged.compress(x)
    dense = _encode_counter(om.LITERAL_DENSE_CHECKS)
    cf = fusedc.compress(x)
    _assert_bit_identical(cs, cf)
    assert len(cs.literal_idx) > x.size // 16
    assert _encode_counter(om.LITERAL_DENSE_CHECKS) \
        == dense + stats_on_device
    assert np.abs(fusedc.decompress(cf).astype(np.float64) - x).max() <= eb


def test_literal_capacity_rule():
    cap = fused.literal_capacity
    # the cells' fields at rel 1e-4, max |x| about their range: n // 16
    for n, rng_ in [(8779809, 256.0), (256 ** 3, 1.0)]:
        assert cap(n, 1e-4 * rng_, 0.998 * rng_) == n // 16
    # a 256x256x128 brick at rel 1e-3: the floor, n // 256
    n = 256 * 256 * 128
    assert cap(n, 1e-3, 0.9999) == cap(n, 1e-3, 1.0) == n // 256
    # never under the floor, never over n; unknown extrema: the floor
    for n in (1, 7, 255, 256, 4096, 65537, 8779809):
        floor = min(n, max(256, n // 256))
        assert cap(n) == cap(n, 1.0, None) == floor
        assert cap(n, 0.0, 1.0) == cap(n, 1e-3, float("nan")) == floor
        for eb, amax in [(1.0, 0.0), (1.0, 1.0), (1e-4, 1.0), (1e-7, 1.0),
                         (1e-30, 1e30), (1e-4, 3e3)]:
            assert floor <= cap(n, eb, amax) <= n
    assert cap(4096, 1e-7, 1.0) == 4096
    # six fields of one proxy: one bucket, so one compiled pass
    for make, n in [(F.hacc_proxy, 8779809), (F.nyx_proxy, 256 ** 3)]:
        caps = set()
        for seed in range(6):
            x = make(seed=seed)
            hi, lo = float(x.max()), float(x.min())
            caps.add(cap(n, 1e-4 * (hi - lo), max(abs(hi), abs(lo))))
        assert caps == {n // 16}, (make.__name__, caps)
