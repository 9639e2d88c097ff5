"""Kernel-dispatch layer: registry behavior + jnp/pallas bit-identity.

The contract (kernels/dispatch.py): both registered implementations of
each inner-loop op produce BIT-IDENTICAL outputs for any valid staging
(random codebooks included); 'auto' resolves per backend through the
(op, backend) table; unknown names fail loudly at resolve time — a
typo'd CEAZConfig(kernel_impl=...) must never silently fall back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CEAZ, CEAZConfig, default_offline_codebook
from repro.core import huffman as H
from repro.data import fields as F
from repro.kernels import dispatch
from repro.runtime.fused_decode import _u64_to_u32


@pytest.fixture(scope="module")
def offline_cb():
    return default_offline_codebook()


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------

def test_unknown_impl_raises_with_choices():
    with pytest.raises(ValueError, match="unknown kernel_impl"):
        dispatch.resolve("hufenc", "cuda")
    with pytest.raises(ValueError, match="pallas"):
        dispatch.resolve("hufdec", "typo")


def test_unknown_op_raises():
    with pytest.raises(ValueError, match="unknown kernel op"):
        dispatch.resolve("matmul", "jnp")


def test_auto_resolves_per_backend():
    for op in ("hufenc", "hufdec", "dq_center", "ceaz_chunk",
               "ceaz_chunk_dec"):
        assert dispatch.auto_impl(op, "cpu") == "jnp"
        assert dispatch.auto_impl(op, "gpu") == "jnp"
        # the Pallas kernels do not compile for the TPU yet (see the
        # table in kernels/dispatch.py): auto runs the XLA twin there
        assert dispatch.auto_impl(op, "tpu") == "jnp"
        # unknown backends get the safe default
        assert dispatch.auto_impl(op, "warp_drive") == "jnp"
        # and 'auto' resolves to the same callable as the table says
        assert dispatch.resolve(op, "auto") is dispatch.resolve(
            op, dispatch.auto_impl(op, jax.default_backend()))


def test_available_lists_registered_impls():
    assert set(dispatch.available("hufenc")) == {"jnp", "pallas"}
    assert set(dispatch.available("hufdec")) == {"jnp", "pallas"}


def test_register_and_override_auto():
    calls = []
    dispatch.register("hufenc", "_test_impl", lambda: calls.append(1) or
                      (lambda *a: "sentinel"))
    try:
        fn = dispatch.resolve("hufenc", "_test_impl")
        assert fn() == "sentinel"
        assert calls == [1]
        dispatch.resolve("hufenc", "_test_impl")   # loader memoized
        assert calls == [1]
    finally:
        dispatch._LOADERS.pop(("hufenc", "_test_impl"), None)
        dispatch._RESOLVED.pop(("hufenc", "_test_impl"), None)


def test_facade_rejects_unknown_kernel_impl():
    x = np.cumsum(np.ones(4096, np.float32))
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           kernel_impl="nope"))
    with pytest.raises(ValueError, match="kernel_impl"):
        comp.compress(x)


# ---------------------------------------------------------------------------
# jnp vs pallas(interpret) bit-identity on random codebooks
# ---------------------------------------------------------------------------

def _random_chunks(rng, n_chunks, cv, sigma):
    codes2 = np.clip(rng.normal(512, sigma, (n_chunks, cv)), 0,
                     1023).astype(np.int32)
    valid2 = np.ones((n_chunks, cv), bool)
    valid2[-1, rng.integers(1, cv):] = False     # ragged tail
    books = [H.Codebook.from_freqs(
        np.bincount(codes2[i][valid2[i]], minlength=H.NUM_SYMBOLS),
        exact=bool(i % 2)) for i in range(n_chunks)]
    return codes2, valid2, books


@pytest.mark.parametrize("n_chunks,cv,sigma", [
    (1, 700, 3),                       # single short chunk, tight book
    (3, 5000, 30),                     # partial tail blocks
    (2, 8192, 300),                    # wide symbol spread, long codes
])
def test_hufenc_impls_bit_identical(rng, n_chunks, cv, sigma):
    codes2, valid2, books = _random_chunks(rng, n_chunks, cv, sigma)
    lengths = np.stack([b.lengths for b in books]).astype(np.int32)
    cwords = np.stack([b.codes for b in books]).astype(np.uint32)
    bits = max(int(lengths[i][codes2[i][valid2[i]]].sum())
               for i in range(n_chunks))
    w32 = 2 * ((bits + 63) // 64 + 2)
    args = (jnp.asarray(codes2), jnp.asarray(valid2), jnp.asarray(lengths),
            jnp.asarray(cwords), 1024, w32, 33)
    wj, nj = dispatch.resolve("hufenc", "jnp")(*args)
    wp, npk = dispatch.resolve("hufenc", "pallas")(*args)
    np.testing.assert_array_equal(np.asarray(wj), np.asarray(wp))
    np.testing.assert_array_equal(np.asarray(nj), np.asarray(npk))
    # ground truth: the staged host encoder's wire words
    for i in range(n_chunks):
        syms = codes2[i][valid2[i]]
        w64, bnb, _ = H.encode(syms, books[i], 1024)
        u32 = _u64_to_u32(w64)
        np.testing.assert_array_equal(np.asarray(wp)[i][:len(u32) - 2],
                                      u32[:-2])
        np.testing.assert_array_equal(
            np.asarray(npk)[i][:len(bnb)], bnb.astype(np.int32))


@pytest.mark.parametrize("n_chunks,cv,sigma", [
    (1, 700, 3),
    (3, 5000, 30),
    (2, 8192, 300),
])
def test_hufdec_impls_bit_identical(rng, n_chunks, cv, sigma):
    codes2, valid2, books = _random_chunks(rng, n_chunks, cv, sigma)
    bs = 512
    rows_w, rows_nb, counts = [], [], []
    for i in range(n_chunks):
        syms = codes2[i][valid2[i]]
        w64, bnb, _ = H.encode(syms, books[i], bs)
        rows_w.append(_u64_to_u32(w64))
        rows_nb.append(bnb)
        counts.append(len(syms))
    C = n_chunks
    W = max(len(w) for w in rows_w) + 2
    NB = max(len(nb) for nb in rows_nb)
    words2 = np.zeros((C, W), np.uint32)
    nbits2 = np.zeros((C, NB), np.int32)
    for i in range(C):
        words2[i, :len(rows_w[i])] = rows_w[i]
        nbits2[i, :len(rows_nb[i])] = rows_nb[i]
    sym_flat = np.concatenate([b.tables()[0] for b in books])
    len_flat = np.concatenate([b.tables()[1] for b in books])
    cb_idx = np.arange(C, dtype=np.int32)
    args = (jnp.asarray(words2), jnp.asarray(nbits2),
            jnp.asarray(np.asarray(counts, np.int32)),
            jnp.asarray(sym_flat), jnp.asarray(len_flat),
            jnp.asarray(cb_idx), bs)
    out_j = np.asarray(dispatch.resolve("hufdec", "jnp")(*args))
    out_p = np.asarray(dispatch.resolve("hufdec", "pallas")(*args))
    assert out_p.dtype == out_j.dtype == np.uint16
    np.testing.assert_array_equal(out_j, out_p)
    for i in range(C):
        np.testing.assert_array_equal(
            out_p[i][:counts[i]], codes2[i][valid2[i]].astype(np.uint16))


# ---------------------------------------------------------------------------
# Facade: kernel_impl='pallas' end-to-end vs the staged reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,kw", [
    ("abs", dict(eb=1e-3)),
    ("rel", dict(eb=1e-4)),
    ("fixed_ratio", dict(target_ratio=10.0)),
])
def test_facade_pallas_bit_identical_to_staged(offline_cb, mode, kw):
    field = F.cesm_proxy(seed=3).astype(np.float32)
    staged = CEAZ(CEAZConfig(mode=mode, chunk_bytes=1 << 16,
                             block_size=1024, backend="jax",
                             predictor="lorenzo", use_fused=False, **kw),
                  offline_codebook=offline_cb)
    pallas = CEAZ(CEAZConfig(mode=mode, chunk_bytes=1 << 16,
                             block_size=1024, predictor="lorenzo",
                             use_fused=True, kernel_impl="pallas", **kw),
                  offline_codebook=offline_cb)
    cs, cp = staged.compress(field), pallas.compress(field)
    assert len(cs.chunks) == len(cp.chunks)
    for a, b in zip(cs.chunks, cp.chunks):
        assert np.array_equal(a.words, b.words)
        assert np.array_equal(a.block_nbits, b.block_nbits)
    # decode side: the pallas table walk must reproduce the staged bytes
    rec_s = staged._decompress_staged(cs)
    rec_p = pallas.decompress(cp)
    assert rec_s.dtype == rec_p.dtype
    np.testing.assert_array_equal(rec_s, rec_p)
