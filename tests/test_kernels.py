"""Per-kernel sweeps: Pallas (interpret=True) vs pure-jnp ref oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import huffman as H
from repro.kernels.bitpack import kernel as BK, ops as BO, ref as BR
from repro.kernels.dualquant import kernel as DK, ops as DO, ref as DR
from repro.kernels.histogram import ops as HO
from repro.kernels.hufdec import ops as HDO, ref as HDR
from repro.kernels.hufenc import kernel as EK, ops as EO, ref as ER


def _smooth(rng, shape):
    x = rng.standard_normal(shape).astype(np.float32)
    return np.cumsum(x, axis=-1).astype(np.float32) / 20


@pytest.mark.parametrize("shape", [(8, 512), (16, 1024), (32, 1536)])
@pytest.mark.parametrize("eb", [1e-2, 1e-3, 1e-4])
def test_dq1d_kernel_vs_ref(shape, eb, rng):
    x = _smooth(rng, shape)
    k = DK.dq1d(jnp.asarray(x), eb)
    r = DR.dq1d(jnp.asarray(x), eb)
    for a, b in zip(k, r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", [(8, 512), (24, 1024)])
@pytest.mark.parametrize("eb", [1e-2, 1e-4])
def test_dq2d_kernel_vs_ref_and_core(shape, eb, rng):
    from repro.core import dualquant as CDQ
    x = np.cumsum(_smooth(rng, shape), axis=0)
    k = DK.dq2d(jnp.asarray(x), eb)
    r = DR.dq2d(jnp.asarray(x), eb)
    c = CDQ.dual_quantize(jnp.asarray(x), eb, 2)
    for a, b in zip(k, r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(k[0]), np.asarray(c[0]))


@pytest.mark.parametrize("n", [100, 4096, 100001])
def test_stream_roundtrip(n, rng):
    x = np.cumsum(rng.standard_normal(n)).astype(np.float32) / 10
    eb = 1e-3
    codes, outl, delta = DO.stream_quantize(jnp.asarray(x), eb)
    rec = DO.stream_dequantize(delta, eb)
    # raw-layer bound: eb + 0.5 ulp (f32 midpoints; facade patches these)
    ulp = float(np.spacing(np.abs(x).max()))
    assert float(jnp.abs(rec - x).max()) <= eb + ulp


@pytest.mark.parametrize("n", [1, 1000, 65536])
def test_histogram_kernel(n, rng):
    codes = rng.integers(0, 1024, n).astype(np.int32)
    h = np.asarray(HO.histogram(jnp.asarray(codes)))
    np.testing.assert_array_equal(h, np.bincount(codes, minlength=1024))


@pytest.mark.parametrize("sigma", [3, 30, 300])
def test_hufenc_kernel_vs_ref_and_host_decode(sigma, rng):
    x = np.clip(rng.normal(512, sigma, 8192), 0, 1023).astype(np.int64)
    cb = H.Codebook.from_freqs(np.bincount(x, minlength=1024))
    codes = x.reshape(2, 4096).astype(np.int32)
    wk, nk = EK.hufenc(jnp.asarray(codes), jnp.asarray(cb.codes),
                       jnp.asarray(cb.lengths))
    wr, nr = ER.hufenc(jnp.asarray(codes), jnp.asarray(cb.codes),
                       jnp.asarray(cb.lengths))
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))
    stream, _ = EO.to_host_stream(wk, nk, len(x), cb.lengths)
    dec = H.decode(stream, np.asarray(nk, np.int64), len(x), 4096, cb)
    assert np.array_equal(dec, x.astype(np.uint16))


@pytest.mark.parametrize("sigma", [5, 80])
def test_gather_pack_kernel_vs_ref(sigma, rng):
    """Fused-wire-layout encode: Pallas gather-pack vs the jnp ref."""
    cv = 6000
    codes = np.clip(rng.normal(512, sigma, (3, cv)), 0, 1023) \
        .astype(np.int32)
    valid = np.ones((3, cv), bool)
    valid[2, 5000:] = False
    cb = H.Codebook.from_freqs(
        np.bincount(codes.reshape(-1), minlength=1024))
    lengths = np.broadcast_to(cb.lengths.astype(np.int32), (3, 1024))
    cwords = np.broadcast_to(cb.codes.astype(np.uint32), (3, 1024))
    args = (jnp.asarray(codes), jnp.asarray(valid), jnp.asarray(lengths),
            jnp.asarray(cwords), 1024, 4096, 33)
    wr, nr = ER.encode_pack(*args)
    wk, nk = EO.encode_pack(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))


def test_hufdec_kernel_vs_ref_roundtrip(rng):
    """Table-decode kernel vs jnp ref, through a real encoded stream."""
    from repro.runtime.fused_decode import _u64_to_u32
    bs = 512
    syms = np.clip(rng.normal(512, 25, 3000), 0, 1023).astype(np.int64)
    cb = H.Codebook.from_freqs(np.bincount(syms, minlength=1024))
    w64, bnb, _ = H.encode(syms, cb, bs)
    u32 = _u64_to_u32(w64)
    words2 = np.zeros((1, len(u32) + 2), np.uint32)
    words2[0, :len(u32)] = u32
    nbits2 = bnb.astype(np.int32)[None, :]
    counts = np.array([len(syms)], np.int32)
    sym_flat, len_flat = cb.tables()
    cb_idx = np.zeros(1, np.int32)
    args = (jnp.asarray(words2), jnp.asarray(nbits2), jnp.asarray(counts),
            jnp.asarray(sym_flat), jnp.asarray(len_flat),
            jnp.asarray(cb_idx), bs)
    out_r = np.asarray(HDR.decode_blocks(*args))
    out_k = np.asarray(HDO.decode_blocks(*args, interpret=True))
    np.testing.assert_array_equal(out_k, out_r)
    np.testing.assert_array_equal(out_k[0][:len(syms)],
                                  syms.astype(np.uint16))


@pytest.mark.parametrize("counts", [
    [3], [1], [511],                      # single chunk shorter than a block
    [512, 100], [700, 5], [37, 1, 512],   # mixed full/ragged tail blocks
])
def test_hufdec_tail_block_early_exit_bit_identity(counts, rng):
    """Regression for the counts-aware fori upper bound: chunks whose
    blocks are ALL shorter than the block grain (the early-exit case)
    must decode bit-identically to the staged decoder in both impls,
    including the zero padding beyond each chunk's count."""
    bs = 512
    rows_w, rows_nb, books, all_syms = [], [], [], []
    for k, n in enumerate(counts):
        syms = np.clip(rng.normal(512, 10 + 40 * k, n), 0,
                       1023).astype(np.int64)
        cb = H.Codebook.from_freqs(np.bincount(syms, minlength=1024))
        w64, bnb, _ = H.encode(syms, cb, bs)
        from repro.runtime.fused_decode import _u64_to_u32
        rows_w.append(_u64_to_u32(w64))
        rows_nb.append(bnb)
        books.append(cb)
        all_syms.append(syms)
    C = len(counts)
    W = max(len(w) for w in rows_w) + 2
    NB = max(len(nb) for nb in rows_nb)
    words2 = np.zeros((C, W), np.uint32)
    nbits2 = np.zeros((C, NB), np.int32)
    for i in range(C):
        words2[i, :len(rows_w[i])] = rows_w[i]
        nbits2[i, :len(rows_nb[i])] = rows_nb[i]
    sym_flat = np.concatenate([b.tables()[0] for b in books])
    len_flat = np.concatenate([b.tables()[1] for b in books])
    args = (jnp.asarray(words2), jnp.asarray(nbits2),
            jnp.asarray(np.asarray(counts, np.int32)),
            jnp.asarray(sym_flat), jnp.asarray(len_flat),
            jnp.asarray(np.arange(C, dtype=np.int32)), bs)
    out_r = np.asarray(HDR.decode_blocks(*args))
    out_k = np.asarray(HDO.decode_blocks(*args, interpret=True))
    np.testing.assert_array_equal(out_r, out_k)
    for i, (n, syms) in enumerate(zip(counts, all_syms)):
        np.testing.assert_array_equal(out_r[i][:n], syms.astype(np.uint16))
        assert not out_r[i][n:].any()     # padding stays zero past count


@pytest.mark.parametrize("bits", [2, 4, 8, 16])
@pytest.mark.parametrize("n", [7, 4096, 50000])
def test_bitpack_roundtrip_and_ref(bits, n, rng):
    v = rng.integers(0, 1 << bits, n).astype(np.int32)
    w = BO.pack_flat(jnp.asarray(v), bits)
    u = BO.unpack_flat(w, n, bits)
    np.testing.assert_array_equal(np.asarray(u), v)
    rows = BO.packed_rows(n, bits)
    vals = np.zeros(rows * (32 // bits) * BK.LANES, np.int32)
    vals[:n] = v
    wref = BR.pack(jnp.asarray(vals.reshape(rows, 32 // bits, BK.LANES)),
                   bits)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(wref))


def test_bitpack_jnp_twin_matches_kernel(rng):
    """grad_compress's in-SPMD pack must agree with the Pallas kernel."""
    from repro.optim.grad_compress import pack_jnp, unpack_jnp
    v = rng.integers(0, 256, 13000).astype(np.int32)
    w_jnp = np.asarray(pack_jnp(jnp.asarray(v), 8))
    u = np.asarray(unpack_jnp(jnp.asarray(w_jnp), len(v), 8))
    np.testing.assert_array_equal(u, v)


# -- word-tiled gather-pack (unbounded chunk sizes) ---------------------------

def _pack_case(rng, C, cv, sigma=40):
    """Codes + per-chunk codebook rows with full symbol support (every
    valid symbol gets >= 1 bit, the tiled coverage contract)."""
    codes = np.clip(rng.normal(512, sigma, (C, cv)), 0, 1023) \
        .astype(np.int32)
    cb = H.Codebook.from_freqs(
        np.bincount(codes.reshape(-1), minlength=1024) + 1)
    lengths = np.broadcast_to(cb.lengths.astype(np.int32), (C, 1024))
    cwords = np.broadcast_to(cb.codes.astype(np.uint32), (C, 1024))
    return codes, np.array(lengths), np.array(cwords)


def _tiled_vs_ref(codes, valid, lengths, cwords, block_size, w32):
    args = (jnp.asarray(codes), jnp.asarray(valid), jnp.asarray(lengths),
            jnp.asarray(cwords), block_size, w32, 33)
    wr, nr = ER.encode_pack(*args[:4], *args[4:])
    wk, nk = EK.gather_pack_tiled(*args[:4], block_size=block_size,
                                  w32=w32, interpret=True)
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))


@pytest.mark.parametrize("w32", [512, 1024, 1200, 8192])
def test_gather_pack_tiled_word_tile_boundaries(w32, rng):
    """The payload is tiled in 512-word output tiles: exact one- and
    two-tile capacities, a ragged tail tile, and an over-provisioned
    capacity whose trailing tiles are all past the payload must all be
    bit-identical to the untiled reference (truncation included)."""
    codes, lengths, cwords = _pack_case(rng, 3, 5000)
    valid = np.ones((3, 5000), bool)
    valid[-1, 4321:] = False
    _tiled_vs_ref(codes, valid, lengths, cwords, 1024, w32)


def test_gather_pack_tiled_zero_length_tail(rng):
    """An all-invalid row (zero payload bits) and a row whose payload
    ends exactly on a word-tile boundary both pack to zeros / exact
    prefixes, matching the reference."""
    codes, lengths, cwords = _pack_case(rng, 2, 4096)
    valid = np.ones((2, 4096), bool)
    valid[1, :] = False                 # zero-length row
    _tiled_vs_ref(codes, valid, lengths, cwords, 1024, 2048)


def test_gather_pack_tiled_past_single_program_limit(rng):
    """Chunks far beyond the old one-program-per-chunk VMEM ceiling
    (~128k values) pack bit-identically through the word-tiled grid."""
    cv = 200_000
    codes, lengths, cwords = _pack_case(rng, 2, cv)
    valid = np.ones((2, cv), bool)
    valid[-1, cv - 77:] = False
    need = int(np.sum(lengths[0][codes[0]]))
    w32 = -(-2 * ((need + 63) // 64 + 1) // 128) * 128
    _tiled_vs_ref(codes, valid, lengths, cwords, 4096, w32)


def test_encode_pack_routes_through_tiled(rng):
    """The public hufenc op wrapper feeds the word-tiled kernel (the
    untiled gather-pack stays only as a microbench/test subject)."""
    codes, lengths, cwords = _pack_case(rng, 2, 3000)
    valid = np.ones((2, 3000), bool)
    args = (jnp.asarray(codes), jnp.asarray(valid), jnp.asarray(lengths),
            jnp.asarray(cwords), 1024, 2048, 33)
    wo, no = EO.encode_pack(*args, interpret=True)
    wr, nr = ER.encode_pack(*args)
    np.testing.assert_array_equal(np.asarray(wo), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(no), np.asarray(nr))


# -- jnp prefix-sum pack vs the staged huffman.encode ground truth -----------

def _book(lengths):
    lengths = np.asarray(lengths, np.uint8)
    return H.Codebook(lengths=lengths, codes=H._canonize(lengths))


def _book_of(lens):
    """A canonical book of the {symbol: length} in `lens`; every other
    symbol is unused."""
    L = np.zeros(1024, int)
    L[list(lens)] = list(lens.values())
    return _book(L)


def _staged_u32(codes, valid, cb, block_size, w32):
    """huffman.encode of the valid symbols as MSB-first u32 words cut
    to w32, and its block bit counts over the whole (padded) row."""
    lens = np.where(valid, cb.lengths[codes], 0).astype(np.int64)
    nblocks = -(-codes.size // block_size)
    nbits = np.pad(lens, (0, nblocks * block_size - codes.size)) \
        .reshape(nblocks, block_size).sum(axis=1)
    out = np.zeros(w32, np.uint32)
    if valid.any():
        w64, _, _ = H.encode(codes[valid], cb, block_size)
        u32 = np.stack([w64 >> np.uint64(32),
                        w64 & np.uint64(0xFFFFFFFF)], 1).reshape(-1)
        m = min(w32, u32.size)
        out[:m] = u32[:m]
    return out, nbits


def _words_for(codes, valid, books, spare=1):
    need = max(int(np.where(v, b.lengths[c], 0).sum())
               for c, v, b in zip(codes, valid, books))
    return max(1, -(-need // 32) + spare)


def _pack_spill_only_last_word(rng):
    # seven 5-bit symbols: the last starts at bit 30 and spills 3 bits
    # into word 1, which no symbol starts in
    codes = np.full((1, 7), 3, np.int32)
    books = [_book_of({3: 5, 4: 5})]
    return codes, np.ones_like(codes, bool), books, 4, 4


def _pack_word_boundary_end(rng):
    # 4-bit symbols: the 8th ends exactly on the first word boundary;
    # rows end there, carry on past it, and stop there under a mask
    codes = rng.choice([1, 2], (3, 24)).astype(np.int32)
    valid = np.ones((3, 24), bool)
    valid[0, 8:] = False
    valid[2, 16:] = False
    books = [_book_of({1: 4, 2: 4})] * 3
    return codes, valid, books, 8, 4


def _pack_one_bit_book(rng):
    codes = rng.integers(0, 2, (2, 5000)).astype(np.int32)
    valid = np.ones_like(codes, bool)
    valid[1, 4321:] = False
    books = [_book_of({0: 1, 1: 1})] * 2
    return codes, valid, books, 1024, _words_for(codes, valid, books)


def _pack_sixteen_bit_book(rng):
    # row 0 on the word grid; row 1 shifted off it by one 1-bit symbol,
    # so a codeword spills into every word after the first
    L = np.full(1024, 16)
    L[0] = 1
    codes = rng.integers(1, 1024, (2, 3000)).astype(np.int32)
    codes[1, 0] = 0
    valid = np.ones_like(codes, bool)
    books = [_book(L)] * 2
    return codes, valid, books, 1024, _words_for(codes, valid, books)


def _pack_prefix_masks(rng):
    codes, lengths, _ = _pack_case(rng, 3, 3000)
    valid = np.arange(3000)[None, :] < np.array([[0], [1], [3000]])
    books = [_book(lengths[0])] * 3
    return codes, valid, books, 1024, _words_for(codes, valid, books)


def _pack_rows_own_books(rng):
    books, rows = [], []
    for sigma in (2, 30, 300):
        row = np.clip(rng.normal(512, sigma, 4000), 0, 1023) \
            .astype(np.int32)
        books.append(_book(H.Codebook.from_freqs(
            np.bincount(row, minlength=1024) + 1).lengths))
        rows.append(row)
    codes = np.stack(rows)
    valid = np.ones_like(codes, bool)
    valid[0, 3333:] = False
    return codes, valid, books, 512, _words_for(codes, valid, books)


def _pack_fills_w32(rng):
    # 2-bit symbols: 16 per word, 64 words exactly, no spare word
    codes = rng.integers(0, 4, (2, 1024)).astype(np.int32)
    valid = np.ones_like(codes, bool)
    books = [_book_of({0: 2, 1: 2, 2: 2, 3: 2})] * 2
    assert _words_for(codes, valid, books, spare=0) == 64
    return codes, valid, books, 256, 64


_PACK_CASES = {
    "spill_only_last_word": _pack_spill_only_last_word,
    "word_boundary_end": _pack_word_boundary_end,
    "one_bit_book": _pack_one_bit_book,
    "sixteen_bit_book": _pack_sixteen_bit_book,
    "prefix_masks_0_1_all": _pack_prefix_masks,
    "rows_own_books": _pack_rows_own_books,
    "fills_w32": _pack_fills_w32,
}


@pytest.mark.parametrize("case", sorted(_PACK_CASES))
def test_encode_pack_vs_staged_encode(case, rng):
    """The jnp `hufenc` op (prefix-sum pack) is bit-identical to the
    staged huffman.encode, row by row, at the wire's u32 grain."""
    codes, valid, books, block_size, w32 = _PACK_CASES[case](rng)
    words, nbits = ER.encode_pack(
        jnp.asarray(codes), jnp.asarray(valid),
        jnp.asarray(np.stack([b.lengths.astype(np.int32) for b in books])),
        jnp.asarray(np.stack([b.codes.astype(np.uint32) for b in books])),
        block_size, w32, 33)
    for i, cb in enumerate(books):
        want_w, want_n = _staged_u32(codes[i], valid[i], cb, block_size,
                                     w32)
        np.testing.assert_array_equal(np.asarray(words)[i], want_w)
        np.testing.assert_array_equal(np.asarray(nbits)[i], want_n)


def test_encode_pack_bank_overflow_repack_vs_staged_encode(rng):
    """A row past the bank's 8-bit pack provision re-packs at full
    capacity through the runtime's retry, bit-identical to the staged
    encode."""
    from repro.runtime import fused
    cv, block_size = 6000, 1024
    codes = rng.integers(0, 1024, (2, cv)).astype(np.int32)  # 10 bits
    valid = np.ones_like(codes, bool)
    valid[1, 5555:] = False
    cb = _book(np.full(1024, 10))
    totals = np.array([int(cb.lengths[r[v]].sum())
                       for r, v in zip(codes, valid)])
    w32_full = fused._bank_w32(int(cb.lengths.max()), cv)
    assert not fused._bank_fits(
        totals, fused._bank_w32(fused.BANK_PROVISION_BITS, cv))
    words, nbits = fused._bank_repack_fn("jnp", block_size, w32_full, 33)(
        jnp.asarray(codes), jnp.asarray(valid),
        jnp.asarray(np.broadcast_to(cb.lengths.astype(np.int32), (2, 1024))),
        jnp.asarray(np.broadcast_to(cb.codes.astype(np.uint32), (2, 1024))))
    for i in range(2):
        want_w, want_n = _staged_u32(codes[i], valid[i], cb, block_size,
                                     w32_full)
        np.testing.assert_array_equal(np.asarray(words)[i], want_w)
        np.testing.assert_array_equal(np.asarray(nbits)[i], want_n)


# -- dq_center radix-select kernel -------------------------------------------

def test_dq_center_kernel_vs_ref(rng):
    """Count-aware median via in-VMEM radix-select vs the sort-based
    jnp reference: ragged valid prefixes, heavy duplicates, an
    all-invalid row, and a spread whose (hi - lo) wraps int32."""
    V = 5000
    rows = [rng.integers(-2**31, 2**31 - 1, V),
            np.repeat(rng.integers(-50, 50, 10), V // 10),
            rng.integers(-5, 5, V),
            np.zeros(V, np.int64),
            np.concatenate([[-2**31 + 1, 2**31 - 1], np.zeros(V - 2)])]
    q2 = np.stack(rows).astype(np.int32)
    valid2 = np.ones_like(q2, bool)
    valid2[0, 3000:] = False
    valid2[1, 1:] = False               # single-value row
    valid2[3, :] = False                # zero-valid row -> centre 0
    valid2[4, 2:] = False               # int32-wrap midpoint pair
    ck = DK.dq_center(jnp.asarray(q2), jnp.asarray(valid2.astype(np.int32)),
                      interpret=True)
    cr = DO.chunk_center(jnp.asarray(q2), jnp.asarray(valid2))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    assert int(np.asarray(ck)[3]) == 0
    co = DO.dq_center(jnp.asarray(q2), jnp.asarray(valid2))
    np.testing.assert_array_equal(np.asarray(co), np.asarray(cr))


# -- ceaz_chunk megakernel ----------------------------------------------------

def _bank_tables(rng):
    lens, cws = [], []
    for sigma in (5, 20, 80, 300):
        codes = np.clip(rng.normal(512, sigma, 20000), 0, 1023) \
            .astype(np.int32)
        cb = H.Codebook.from_freqs(np.bincount(codes, minlength=1024) + 1)
        lens.append(cb.lengths.astype(np.int32))
        cws.append(cb.codes.astype(np.uint32))
    return np.stack(lens), np.stack(cws)


@pytest.mark.parametrize("predictor", ["lorenzo", "value"])
@pytest.mark.parametrize("cv", [4096, 140_000],
                         ids=["fused", "tiled"])
def test_ceaz_chunk_megakernel_vs_ref(predictor, cv, rng):
    """The one-program-per-chunk megakernel (and its word-tiled
    composition past the VMEM limit) is bit-identical to the jnp twin
    composed from the stage ops, on chained-halo Lorenzo and
    value-direct rows with a ragged tail."""
    from repro.kernels.megakernel import kernel as MK
    from repro.kernels.megakernel import ops as MO
    from repro.kernels.megakernel import ref as MR
    assert (cv <= MK._FUSE_ROW_LIMIT) == (cv == 4096)
    C = 2
    flat = np.cumsum(rng.standard_normal(C * cv)).astype(np.float32) / 10
    work2 = flat.reshape(C, cv)
    prev2 = (np.concatenate([[0.0], work2[:-1, -1]])
             .astype(np.float32).reshape(C, 1)
             if predictor == "lorenzo" else np.zeros((C, 1), np.float32))
    valid2 = np.ones((C, cv), bool)
    valid2[-1, cv - 13:] = False
    ebs = np.array([1e-3, 2e-3], np.float32)
    bl, bc = _bank_tables(rng)
    w32 = -(-2 * ((int(bl.max()) * cv + 63) // 64 + 1) // 128) * 128
    args = (work2, prev2, valid2, ebs, bl, bc, 1024, w32, 33, predictor)
    ro = MR.ceaz_chunk(*args)
    po = MO.ceaz_chunk(*args, interpret=True)
    for name, a, b in zip(("q2", "codes2", "outl2", "delta2", "centers",
                           "hists", "sel", "totals", "words", "nbits"),
                          ro, po):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_ceaz_chunk_dispatch_registration():
    """Both impls resolve through the registry; 'auto' picks the jnp
    twin on every backend (the Pallas megakernel does not compile for
    the TPU yet)."""
    from repro.kernels import dispatch as D
    from repro.kernels.megakernel import ops as MO
    from repro.kernels.megakernel import ref as MR
    assert D.resolve("ceaz_chunk", "jnp") is MR.ceaz_chunk
    assert D.resolve("ceaz_chunk", "pallas") is MO.ceaz_chunk
    assert D.auto_impl("ceaz_chunk", "cpu") == "jnp"
    assert D.auto_impl("ceaz_chunk", "tpu") == "jnp"
    assert D.auto_impl("dq_center", "tpu") == "jnp"
    from repro.kernels.dualquant import ops as DQO
    assert D.resolve("dq_center", "pallas") is DQO.dq_center
