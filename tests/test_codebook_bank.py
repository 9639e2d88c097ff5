"""The codebook-bank contract (docs/CODEBOOK_BANK.md): single-pass
bank encode bit-identical to the staged BankCoder reference across the
full mode x dtype x predictor grid, ONE traced pass (no two-pass
machinery, no host tree build), drift fallback byte-identical to
``codebook='exact'``, versioned artifact rules, and stream integration
(footer-meta bank resolution + corruption fuzzing)."""
import json
import zlib

import numpy as np
import pytest

from conftest import assert_streams_bit_identical
from repro.core import (CEAZ, CEAZConfig, CodebookBank,
                        default_offline_codebook, train_codebook_bank)

OFFLINE = default_offline_codebook()


def _data(kind: str, n: int = 30000) -> np.ndarray:
    rng = np.random.default_rng(11)
    if kind == "smooth":
        return np.cumsum(rng.standard_normal(n)) / 10
    return rng.standard_normal(n)               # noise: value-direct's case


def _toy_bank() -> CodebookBank:
    # smooth-walk-only training corpus: in-envelope for the grid's
    # smooth data, OUT of envelope for i.i.d. noise (the fallback case)
    rng = np.random.default_rng(7)
    fields = [np.cumsum(rng.standard_normal(40000)).astype(np.float32) / 10,
              np.cumsum(rng.standard_normal(40000)).astype(np.float32) / 50]
    return train_codebook_bank(fields, n_books=4)


BANK = _toy_bank()

MODES = [("abs", dict(eb=1e-3)), ("rel", dict(eb=1e-4)),
         ("fixed_ratio", dict(target_ratio=10.0))]


def _pair(mode, predictor, **kw):
    # drift tolerance off: the grid verifies the BANK path itself on
    # every cell (incl. data far outside the toy bank's envelope), not
    # the fallback — test_drift_fallback_* covers the guard
    mk = lambda uf: CEAZ(
        CEAZConfig(mode=mode, predictor=predictor, chunk_bytes=1 << 14,
                   block_size=1024, backend="jax", use_fused=uf,
                   codebook="bank", bank_drift_tol=float("inf"), **kw),
        offline_codebook=OFFLINE, bank=BANK)
    return mk(False), mk(True)


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("predictor", ["lorenzo", "none", "auto"])
@pytest.mark.parametrize("mode,kw", MODES, ids=[m for m, _ in MODES])
def test_bank_grid(mode, kw, predictor, dtype):
    """Single-pass fused bank encode is bit-identical to the staged
    jax-backend reference running the same BankCoder policy, and the
    decoded stream honours the error bound — cell by cell on the same
    grid the exact-codebook paths are fenced with."""
    kind = "noise" if predictor == "none" else "smooth"
    x = _data(kind).astype(dtype)
    staged, fused = _pair(mode, predictor, **kw)
    cs, cf = staged.compress(x), fused.compress(x)
    assert_streams_bit_identical(cs, cf)
    if mode in ("abs", "rel"):
        assert {ch.action for ch in cf.chunks} == {"bank"}
        assert all(ch.bank_ref == BANK.id and 0 <= ch.bank_index <
                   BANK.n_books for ch in cf.chunks)
    rs = staged._decompress_staged(cs)
    rf = fused.decompress(cf)
    assert rf.dtype == rs.dtype == x.dtype and rf.shape == x.shape
    assert np.array_equal(rs, rf)
    if mode == "abs":
        assert np.abs(rs.astype(np.float64)
                      - x.astype(np.float64)).max() <= kw["eb"]
    elif mode == "rel":
        bound = kw["eb"] * float(x.max() - x.min())
        assert np.abs(rs.astype(np.float64)
                      - x.astype(np.float64)).max() <= bound
    else:
        errs = np.abs(rs.reshape(-1).astype(np.float64)
                      - x.reshape(-1).astype(np.float64))
        ebs = np.repeat([ch.eb for ch in cs.chunks],
                        [ch.n_values for ch in cs.chunks])
        assert np.all(errs <= ebs)


def test_bank_encode_is_one_pass(monkeypatch):
    """codebook='bank' on the fused path runs ONE traced pass: the bank
    pass executes exactly once per array and none of the two-pass
    machinery — pass-1 stats, host codebook builds, host-side row
    encode — runs at all."""
    from repro.core import huffman
    from repro.runtime import fused
    x = _data("smooth").astype(np.float32)
    comp = CEAZ(CEAZConfig(mode="abs", eb=1e-3, use_fused=True,
                           chunk_bytes=1 << 14, block_size=1024,
                           codebook="bank",
                           bank_drift_tol=float("inf")),
                offline_codebook=OFFLINE, bank=BANK)
    ref = comp.compress(x)          # warm: bank tables + traces built
    runs, forbidden = [], []

    def spy(orig_pass):             # bypass the lru cache
        def spying_pass(*a, **kw):
            run = orig_pass(*a, **kw)
            def counted(*ra, **rkw):
                runs.append(1)
                return run(*ra, **rkw)
            return counted
        return spying_pass
    # the one bank pass builder, whichever quantize front end the
    # layout picks (ceaz_chunk rows or the global N-D pass)
    monkeypatch.setattr(fused, "_bank_pass_fn",
                        spy(fused._bank_pass_fn.__wrapped__))
    monkeypatch.setattr(fused, "_run_pass1",
                        lambda *a, **kw: forbidden.append("_run_pass1"))
    monkeypatch.setattr(fused, "_run_value_pass1",
                        lambda *a, **kw:
                        forbidden.append("_run_value_pass1"))
    monkeypatch.setattr(fused, "_encode_rows",
                        lambda *a, **kw: forbidden.append("_encode_rows"))
    monkeypatch.setattr(
        huffman.Codebook, "from_freqs",
        classmethod(lambda cls, *a, **kw: forbidden.append("from_freqs")))
    c = comp.compress(x)
    assert len(runs) == 1, runs     # exactly one device pass
    assert forbidden == []          # no two-pass / host-build machinery
    assert_streams_bit_identical(ref, c)


def test_drift_fallback_byte_identical_to_exact():
    """Out-of-envelope input trips the drift guard: the whole array
    re-encodes on the exact two-pass path, byte-identical to
    ``codebook='exact'`` — never a mixed stream."""
    noise = _data("noise").astype(np.float32)
    cfg = dict(mode="abs", eb=1e-3, use_fused=True, chunk_bytes=1 << 14,
               block_size=1024)
    banked = CEAZ(CEAZConfig(codebook="bank", **cfg),
                  offline_codebook=OFFLINE, bank=BANK)
    exact = CEAZ(CEAZConfig(codebook="exact", **cfg),
                 offline_codebook=OFFLINE)
    cb = banked.compress(noise)
    assert "bank" not in {ch.action for ch in cb.chunks}
    assert all(ch.bank_index == -1 and ch.bank_ref == ""
               for ch in cb.chunks)
    assert_streams_bit_identical(cb, exact.compress(noise))
    # in-envelope input stays on the bank path under the same tolerance
    smooth = _data("smooth").astype(np.float32)
    assert {ch.action
            for ch in banked.compress(smooth).chunks} == {"bank"}


def test_provision_overflow_repacks_bit_identically(monkeypatch):
    """Chunks whose exact payload exceeds the static
    BANK_PROVISION_BITS provisioning re-run ONLY the pack at full
    capacity — and the resulting stream is still bit-identical to the
    staged reference (which never provisions)."""
    from repro.runtime import fused
    rng = np.random.default_rng(3)
    # deltas spread over ~900 symbols -> ~10 bits/value, well past the
    # 8-bit provision
    x = np.cumsum(rng.uniform(-0.45, 0.45, 40000)).astype(np.float32)
    wide_bank = train_codebook_bank([x], n_books=2,
                                    target_bitrates=(10.0,))
    mk = lambda uf: CEAZ(
        CEAZConfig(mode="abs", eb=1e-3, use_fused=uf, chunk_bytes=1 << 14,
                   block_size=1024, backend="jax", codebook="bank",
                   bank_drift_tol=float("inf")),
        offline_codebook=OFFLINE, bank=wide_bank)
    staged, fus = mk(False), mk(True)
    repacks = []
    orig = fused._bank_repack_fn
    monkeypatch.setattr(fused, "_bank_repack_fn",
                        lambda *a: repacks.append(a) or orig(*a))
    cf = fus.compress(x)
    assert repacks, "workload did not overflow the pack provision"
    cs = staged.compress(x)
    assert_streams_bit_identical(cs, cf)
    rec = fus.decompress(cf)
    assert np.abs(rec.astype(np.float64)
                  - x.astype(np.float64)).max() <= 1e-3


# -- artifact rules (docs/CODEBOOK_BANK.md "Versioning rules") --------------

def test_bank_artifact_save_load_roundtrip(tmp_path):
    p = str(tmp_path / "bank.npz")
    BANK.save(p)
    b2 = CodebookBank.load(p)
    assert b2.id == BANK.id
    assert np.array_equal(b2.lengths, BANK.lengths)
    assert b2.version == BANK.version


def test_bank_refuses_unknown_version():
    with pytest.raises(ValueError, match="version"):
        CodebookBank(lengths=BANK.lengths, version=2)


def test_bank_meta_roundtrip_and_id_self_validation():
    m = BANK.to_meta()
    b2 = CodebookBank.from_meta(m)
    assert b2.id == BANK.id
    forged = dict(m, id="0" * 12)
    with pytest.raises(ValueError, match="id mismatch"):
        CodebookBank.from_meta(forged)


# -- stream integration (docs/STREAM_FORMAT.md bank keys) -------------------

def _bank_stream(tmp_path, name="bank.ceazs"):
    from repro.io import engine as E
    rng = np.random.default_rng(5)
    shards = [np.cumsum(rng.standard_normal(30000)).astype(np.float32) / 10,
              np.cumsum(rng.standard_normal(30000)).astype(np.float32) / 20]
    comp = CEAZ(CEAZConfig(mode="abs", eb=1e-3, use_fused=True,
                           chunk_bytes=1 << 14, block_size=1024,
                           codebook="bank",
                           bank_drift_tol=float("inf")),
                offline_codebook=OFFLINE, bank=BANK)
    path = str(tmp_path / name)
    E.write_stream(path, shards, comp, fsync=False)
    return path, shards


def _rewrite_footer(path, mutate):
    """Apply ``mutate(footer_dict)`` and re-finalize the stream with a
    consistent footer length / crc32 / trailer, so ONLY the mutated
    field is wrong — the structural checks all still pass."""
    from repro.io import engine as E
    blob = bytearray(open(path, "rb").read())
    foot_off, foot_len, _, magic = E.TRAILER.unpack(
        bytes(blob[-E.TRAILER.size:]))
    footer = json.loads(bytes(blob[foot_off:foot_off + foot_len]).decode())
    mutate(footer)
    fb = json.dumps(footer, sort_keys=True,
                    separators=(",", ":")).encode()
    with open(path, "wb") as f:
        f.write(bytes(blob[:foot_off]) + fb
                + E.TRAILER.pack(foot_off, len(fb),
                                 zlib.crc32(fb) & 0xFFFFFFFF, magic))


def test_stream_carries_bank_and_reader_resolves_it(tmp_path, monkeypatch):
    """Bank streams are self-contained: footer meta embeds the artifact
    and index rows carry (bank_id, bank_delta); a reader in a process
    that has NEVER seen the trained bank decodes through them alone."""
    from repro.core import codebook as CB
    from repro.io import engine as E
    path, shards = _bank_stream(tmp_path)
    with E.StreamReader(path) as r:
        assert r.meta["codebook_bank"]["id"] == BANK.id
        for rec in r.records:
            assert rec["bank_id"] == BANK.id
            assert all(0 <= int(d) < BANK.n_books
                       for d in rec["bank_delta"])
    monkeypatch.setattr(CB, "_BANKS", {})     # fresh-process simulation
    back = E.read_stream_arrays(path)
    for b, s in zip(back, shards):
        assert np.abs(b.astype(np.float64)
                      - s.astype(np.float64)).max() <= 1e-3


def test_fuzz_unresolvable_bank_id_is_corruption(tmp_path, monkeypatch):
    from repro.core import codebook as CB
    from repro.io import engine as E
    path, _ = _bank_stream(tmp_path)
    _rewrite_footer(path, lambda f:
                    f["records"][0].update(bank_id="deadbeefcafe"))
    monkeypatch.setattr(CB, "_BANKS", {})
    with pytest.raises(E.StreamCorruptionError, match="bank id"):
        E.read_stream_arrays(path)


def test_fuzz_mismatched_bank_delta_is_corruption(tmp_path):
    from repro.io import engine as E
    path, _ = _bank_stream(tmp_path)
    def flip_delta(f):
        d = f["records"][0]["bank_delta"]
        d[0] = (int(d[0]) + 1) % BANK.n_books
    _rewrite_footer(path, flip_delta)
    # the error names the failing record (seq attribution, PR 9)
    with pytest.raises(E.StreamCorruptionError,
                       match=r"record seq=0 .*bank_delta"):
        E.read_stream_arrays(path)


def test_fuzz_forged_bank_meta_is_corruption(tmp_path):
    from repro.io import engine as E
    path, _ = _bank_stream(tmp_path)
    _rewrite_footer(path, lambda f:
                    f["meta"]["codebook_bank"].update(id="0" * 12))
    with pytest.raises(E.StreamCorruptionError, match="codebook_bank"):
        E.read_stream_arrays(path)
