"""Deterministic full-matrix sweep: staged vs fused bit-identity over
mode x dtype x predictor x kernel_impl.

The hypothesis property suite (tests/test_roundtrip_property.py) draws
from the same grid with random data; this file pins the grid down with
fixed seeds so the acceptance contract — the fused pipeline covers the
WHOLE compression matrix bit-identically to the staged jax-backend
reference, encode and decode — is verified even where hypothesis is not
installed, combination by combination.
"""
import numpy as np
import pytest

from conftest import assert_streams_bit_identical
from repro.core import CEAZ, CEAZConfig, default_offline_codebook

OFFLINE = default_offline_codebook()

MODES = [("abs", dict(eb=1e-3)), ("rel", dict(eb=1e-4)),
         ("fixed_ratio", dict(target_ratio=10.0))]


def _data(kind: str, n: int = 30000) -> np.ndarray:
    rng = np.random.default_rng(11)
    if kind == "smooth":
        return np.cumsum(rng.standard_normal(n)) / 10
    return rng.standard_normal(n)               # noise: value-direct's case


def _pair(mode, predictor, kernel_impl, **kw):
    mk = lambda uf: CEAZ(
        CEAZConfig(mode=mode, predictor=predictor, chunk_bytes=1 << 14,
                   block_size=1024, backend="jax", use_fused=uf,
                   kernel_impl=kernel_impl, **kw),
        offline_codebook=OFFLINE)
    return mk(False), mk(True)


def _check_combo(x, mode, kw, predictor, kernel_impl):
    staged, fused = _pair(mode, predictor, kernel_impl, **kw)
    cs, cf = staged.compress(x), fused.compress(x)
    assert_streams_bit_identical(cs, cf)
    # decode: fused must be bit-identical to the staged oracle, for the
    # stream from either encoder
    rs = staged._decompress_staged(cs)
    rf = fused.decompress(cf)
    assert rf.dtype == rs.dtype == x.dtype and rf.shape == x.shape
    assert np.array_equal(rs, rf)
    # error bound (abs / rel; fixed_ratio bounds are per-chunk)
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


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("predictor", ["lorenzo", "none", "auto"])
@pytest.mark.parametrize("mode,kw", MODES, ids=[m for m, _ in MODES])
def test_grid_jnp(mode, kw, predictor, dtype):
    kind = "noise" if predictor == "none" else "smooth"
    _check_combo(_data(kind).astype(dtype), mode, kw, predictor, "jnp")


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("predictor", ["lorenzo", "none"])
@pytest.mark.parametrize("mode,kw", MODES, ids=[m for m, _ in MODES])
def test_grid_pallas_interpret(mode, kw, predictor, dtype):
    """Same grid through the Pallas kernels (interpret=True on CPU);
    smaller arrays keep the interpreter inside the fast-lane budget."""
    kind = "noise" if predictor == "none" else "smooth"
    _check_combo(_data(kind, n=6000).astype(dtype), mode, kw, predictor,
                 "pallas")


def test_fixed_ratio_tracks_target_ratio():
    """Achieved-vs-target accuracy on a multi-chunk stream: the
    quantized-step controller must stay inside the paper's 15%
    acceptance envelope (Fig 13), on both the staged and fused paths."""
    x = _data("smooth", n=32 * 8192).astype(np.float32)
    for target in (6.0, 10.5):
        for uf in (False, True):
            comp = CEAZ(CEAZConfig(mode="fixed_ratio", target_ratio=target,
                                   chunk_bytes=1 << 15, use_fused=uf),
                        offline_codebook=OFFLINE)
            c = comp.compress(x)
            assert abs(c.ratio() / target - 1) <= 0.15, (target, uf,
                                                         c.ratio())


def test_compress_batch_never_splits_to_staged(monkeypatch):
    """float64 and value-direct groups run through fused.batch_compress
    (one batched device pass per group), and singleton/ragged leftovers
    still take the per-stream FUSED path — the staged encoder must not
    run at all under use_fused=True."""
    from repro.runtime import fused as F
    batch_calls, staged_calls = [], []
    orig_batch = F.batch_compress
    monkeypatch.setattr(F, "batch_compress",
                        lambda shards, *a, **kw:
                        batch_calls.append((len(shards),
                                            kw.get("predictor")))
                        or orig_batch(shards, *a, **kw))
    monkeypatch.setattr(
        CEAZ, "_compress_eb",
        lambda self, x, wb: staged_calls.append("eb") or None)
    monkeypatch.setattr(
        CEAZ, "_compress_eb_direct",
        lambda self, x, wb: staged_calls.append("direct") or None)
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           predictor="auto", chunk_bytes=1 << 14),
                offline_codebook=OFFLINE)
    rng = np.random.default_rng(5)
    smooth64 = np.cumsum(rng.standard_normal(20000))
    noise32 = rng.standard_normal(20000).astype(np.float32)
    shards = [smooth64, smooth64 * 2, noise32, noise32 * 3,
              rng.standard_normal(777).astype(np.float32)]   # ragged
    outs = comp.compress_batch(shards)
    assert staged_calls == []                   # staged encoder never ran
    assert sorted(batch_calls) == [(2, "lorenzo"), (2, "none")]
    # grouping must not change bytes vs per-shard compress
    for c, s in zip(outs, shards):
        assert_streams_bit_identical(comp.compress(s), c)


def test_speculation_is_byte_invariant():
    """The emitted fixed-ratio stream must not depend on the speculation
    window at all."""
    x = _data("smooth", n=20 * 4096).astype(np.float32)
    streams = []
    for spec in ("off", 2, 8):
        comp = CEAZ(CEAZConfig(mode="fixed_ratio", target_ratio=8.0,
                               use_fused=True, chunk_bytes=1 << 14,
                               speculation=spec), offline_codebook=OFFLINE)
        streams.append(comp.compress(x))
    assert_streams_bit_identical(streams[0], streams[1])
    assert_streams_bit_identical(streams[0], streams[2])


def test_unknown_speculation_raises():
    comp = CEAZ(CEAZConfig(mode="fixed_ratio", use_fused=True,
                           speculation="warp"), offline_codebook=OFFLINE)
    with pytest.raises(ValueError, match="speculation"):
        comp.compress(np.ones(4096, np.float32))


# -- encode megakernel column -------------------------------------------------
# Bank-mode 1-D encode routes through the ceaz_chunk megakernel (one
# program per chunk); the staged BankCoder reference is the oracle.

def _toy_bank():
    from repro.core import train_codebook_bank
    rng = np.random.default_rng(7)
    fields = [np.cumsum(rng.standard_normal(40000)).astype(np.float32) / 10,
              np.cumsum(rng.standard_normal(40000)).astype(np.float32) / 50]
    return train_codebook_bank(fields, n_books=4)


BANK = _toy_bank()


def _check_bank_combo(x, mode, kw, predictor, kernel_impl,
                      chunk_bytes=1 << 14):
    mk = lambda uf: CEAZ(
        CEAZConfig(mode=mode, predictor=predictor, chunk_bytes=chunk_bytes,
                   block_size=1024, backend="jax", use_fused=uf,
                   kernel_impl=kernel_impl, codebook="bank",
                   bank_drift_tol=float("inf"), **kw),
        offline_codebook=OFFLINE, bank=BANK)
    staged, fused = mk(False), mk(True)
    cs, cf = staged.compress(x), fused.compress(x)
    assert_streams_bit_identical(cs, cf)
    assert np.array_equal(staged._decompress_staged(cs),
                          fused.decompress(cf))


@pytest.mark.parametrize("kernel_impl", ["jnp", "pallas"])
@pytest.mark.parametrize("predictor", ["lorenzo", "none"])
@pytest.mark.parametrize("mode,kw", MODES, ids=[m for m, _ in MODES])
def test_bank_megakernel_grid(mode, kw, predictor, kernel_impl):
    """The single-program ceaz_chunk path (jnp twin and Pallas
    interpret) is byte-identical to the staged BankCoder reference."""
    kind = "noise" if predictor == "none" else "smooth"
    n = 6000 if kernel_impl == "pallas" else 30000
    x = _data(kind, n=n).astype(np.float32)
    _check_bank_combo(x, mode, kw, predictor, kernel_impl)


def test_bank_megakernel_past_program_limit():
    """Chunks larger than the fused megakernel's one-program VMEM limit
    (2^17 values) take the word-tiled composition and stay
    byte-identical to the staged reference."""
    from repro.kernels.megakernel import kernel as MK
    cv = 1 << 18                                 # 2 x _FUSE_ROW_LIMIT
    assert cv > MK._FUSE_ROW_LIMIT
    x = _data("smooth", n=cv + cv // 2).astype(np.float32)
    _check_bank_combo(x, "abs", dict(eb=1e-3), "lorenzo", "jnp",
                      chunk_bytes=4 * cv)
    _check_bank_combo(x, "fixed_ratio", dict(target_ratio=10.0),
                      "lorenzo", "jnp", chunk_bytes=4 * cv)


# -- decode megakernel column -------------------------------------------------
# The read side has two routes — staged (the oracle) and the fused
# ceaz_chunk_dec megakernel (jnp twin / Pallas interpret). Every grid
# cell must decode to the SAME BYTES through both, from the same stream.

DECODE_ROUTES = ["jnp", "pallas"]


def _check_decode_routes(x, mode, kw, predictor, want, c):
    for kernel_impl in DECODE_ROUTES:
        comp = CEAZ(CEAZConfig(mode=mode, predictor=predictor,
                               chunk_bytes=1 << 14, block_size=1024,
                               backend="jax", use_fused=True,
                               kernel_impl=kernel_impl, **kw),
                    offline_codebook=OFFLINE)
        got = comp.decompress(c)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(want, got), (mode, predictor, kernel_impl)


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("predictor", ["lorenzo", "none"])
@pytest.mark.parametrize("mode,kw", MODES, ids=[m for m, _ in MODES])
def test_decode_impl_grid(mode, kw, predictor, dtype):
    kind = "noise" if predictor == "none" else "smooth"
    x = _data(kind, n=6000).astype(dtype)
    staged, enc = _pair(mode, predictor, "jnp", **kw)
    c = enc.compress(x)
    _check_decode_routes(x, mode, kw, predictor,
                         staged._decompress_staged(c), c)


@pytest.mark.parametrize("mode,kw", MODES, ids=[m for m, _ in MODES])
def test_decode_impl_grid_2d_lorenzo(mode, kw):
    """Higher-rank Lorenzo decodes through the megakernel's delta
    passthrough + the host-side multi-axis cumsum — same bytes as the
    staged oracle on every mode."""
    x = (_data("smooth", n=96 * 64).astype(np.float32)).reshape(96, 64)
    staged, enc = _pair(mode, "lorenzo", "jnp", **kw)
    c = enc.compress(x)
    _check_decode_routes(x, mode, kw, "lorenzo",
                         staged._decompress_staged(c), c)


# -- adaptive speculation -----------------------------------------------------

def test_speculation_auto_is_byte_invariant():
    """speculation='auto' (adaptive window) emits the same bytes as any
    fixed window — depth only moves latency, never the stream."""
    x = _data("smooth", n=20 * 4096).astype(np.float32)
    mk = lambda spec: CEAZ(
        CEAZConfig(mode="fixed_ratio", target_ratio=8.0, use_fused=True,
                   chunk_bytes=1 << 14, speculation=spec),
        offline_codebook=OFFLINE)
    ref = mk("off").compress(x)
    for spec in ("auto", 64):
        assert_streams_bit_identical(ref, mk(spec).compress(x))


def test_next_window_policy_and_gauge():
    """Hit streaks double the speculation depth (capped), any miss
    halves it (floored); a fused auto run publishes the final depth as
    the ceaz_speculation_window gauge."""
    from repro.obs import metrics as om
    from repro.runtime import fused as F
    assert F._next_window(8, 0) == 16
    assert F._next_window(F._SPEC_WINDOW_MAX, 0) == F._SPEC_WINDOW_MAX
    assert F._next_window(8, 3) == 4
    assert F._next_window(F._SPEC_WINDOW_MIN, 1) == F._SPEC_WINDOW_MIN
    x = _data("smooth", n=12 * 4096).astype(np.float32)
    comp = CEAZ(CEAZConfig(mode="fixed_ratio", target_ratio=8.0,
                           use_fused=True, chunk_bytes=1 << 14,
                           speculation="auto"), offline_codebook=OFFLINE)
    comp.compress(x)
    depth = om.snapshot().get(om.SPEC_WINDOW)
    assert depth is not None
    assert F._SPEC_WINDOW_MIN <= depth <= F._SPEC_WINDOW_MAX
