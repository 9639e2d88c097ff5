"""Device-resident fused CEAZ chunk pipeline (the paper's Fig-4 engine).

The staged reference path in ``core.ceaz`` orchestrates dual-quant ->
histogram -> Huffman encode -> bit-pack from host numpy, with a device<->
host round-trip between every stage and a Python loop over chunks. This
module keeps the whole per-value pipeline on device, mirroring the FPGA's
streaming structure (and cuSZ's fused GPU kernels):

  pass 1  — one traced computation quantizes the WHOLE batch of chunks
            (global-Lorenzo dual-quant) and computes the integer
            reconstruction the literal check replays. Codes/deltas stay
            in device memory; only per-chunk histogram summaries cross
            to the host.
  host    — the chi / codebook-update policy (AdaptiveCoder) and, in
            fixed-ratio mode, the eb controller run per super-chunk on
            the tiny histogram summaries — exactly the split the paper
            uses (codeword generation is the slow serial path, §3.2).
  pass 2  — one traced computation Huffman-encodes and bit-packs every
            chunk against its per-chunk codebook. The packed payload +
            per-block bit counts come back in a single transfer. The
            gather-pack inner loop resolves through the kernel-dispatch
            layer (kernels/dispatch.py op 'hufenc': 'jnp' scatter-free
            formulation or the Pallas VMEM-resident kernel, selected by
            CEAZConfig(kernel_impl=...)).

Bit-exactness contract: given the same quantization backend, the fused
path produces payloads (words, block_nbits, outliers, literals)
BIT-IDENTICAL to ``core.ceaz.CEAZ`` with ``use_fused=False,
backend='jax'`` — enforced by tests/test_fused.py and the full-grid
property suite. The device bitstream is packed in uint32 words (jax
runs without 64-bit types by default); ``_u32_to_u64`` folds pairs into
the uint64 MSB-first wire layout of ``core.huffman.encode``.

Scope: the whole compression matrix — float32 AND float64 inputs,
Lorenzo and value-direct (predictor='none') prediction, abs/rel/
fixed_ratio modes. Float64 inputs quantize through the same f32 device
pass the jax staged backend uses; the float64 error-bound guarantee is
restored by the literal escape channel, whose check replays the exact
float64 formula on the host. Value-direct centres each chunk on a
device median (the `dq_center` dispatch op). In fixed-ratio mode the
eb feedback loop runs speculatively: windows of W chunks quantize in
one vmapped device pass against rate-law-predicted bounds, the exact
feedback chain is replayed on the host from pass-1 summaries alone,
and only chunks whose predicted eb matched bitwise are committed —
``speculation='off'`` keeps the sequential loop as the byte-identical
oracle. Only ragged-shape batches remain outside the fused path (see
docs/ARCHITECTURE.md).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import dualquant as core_dq
from ..core.codebook import AdaptiveCoder, BankCoder
from ..core.huffman import DEFAULT_MAX_LEN, NUM_SYMBOLS, Codebook
from ..kernels import dispatch
from ..obs import metrics as om
from ..obs import trace as ot

# Device bitstreams are packed at the codebook's length limit; the wire
# format (and the candidate window below) assumes codes never exceed 16
# bits.
MAX_CODE_BITS = DEFAULT_MAX_LEN
_EPS32 = float(np.finfo(np.float32).eps)
# The device passes have only the f32 reconstruction: a value is a
# literal CANDIDATE when its f32 error lies within GUARD_ULPS ulp of
# |rec| + |x| under the bound, and the host replays the float64 check on
# the candidates alone (see _literals).
GUARD_ULPS = 16


def chunk_layout(n: int, chunk_values: int) -> Tuple[int, int]:
    """(n_chunks, n_last) for an n-value stream cut into chunk_values."""
    n_chunks = max(1, -(-n // chunk_values))
    n_last = n - (n_chunks - 1) * chunk_values
    return n_chunks, n_last


def words_capacity(chunk_values: int) -> int:
    """Static uint32 words per chunk: worst case MAX_CODE_BITS/value,
    rounded so the valid prefix always trims to whole uint64 words."""
    max_w64 = (chunk_values * MAX_CODE_BITS + 63) // 64
    return 2 * (max_w64 + 1)


# On hosts where the jax "device" shares the CPU's memory, XLA scatters
# (histogram, sparse compaction) serialize at ~10M values/s while a bulk
# snapshot is a memcpy and numpy bincount/flatnonzero run at memory
# bandwidth — so summaries are computed host-side from one snapshot per
# array. On real accelerators the device-side scatter paths keep the data
# resident. Overridable for testing via the stats_on_device arguments.
def _default_stats_on_device() -> bool:
    return jax.default_backend() != "cpu"


# ---------------------------------------------------------------------------
# Pass 1: batched dual-quant (+ the integer reconstruction for literals)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("ndim", "n_chunks", "chunk_values"))
def _quantize_pass(work, eb, ndim, n_chunks, chunk_values):
    """work (f32, rank=ndim) -> device-resident chunked state.

    Returns (codes2, outl2, delta2, valid2, q) where the 2-D arrays are
    (n_chunks, chunk_values) and q is the flat inverse-Lorenzo integer
    field the literal check replays. Scatter-free by construction.
    """
    codes, outl, delta = core_dq.dual_quantize(work, eb, ndim)
    n = codes.size
    flat_codes = codes.reshape(-1).astype(jnp.int32)
    flat_outl = outl.reshape(-1)
    flat_delta = delta.reshape(-1)
    pad = n_chunks * chunk_values - n
    valid = jnp.arange(n_chunks * chunk_values, dtype=jnp.int32) < n
    codes2 = jnp.pad(flat_codes, (0, pad)).reshape(n_chunks, chunk_values)
    outl2 = jnp.pad(flat_outl, (0, pad)).reshape(n_chunks, chunk_values)
    delta2 = jnp.pad(flat_delta, (0, pad)).reshape(n_chunks, chunk_values)
    valid2 = valid.reshape(n_chunks, chunk_values)
    q = core_dq.inverse_lorenzo(delta, ndim).reshape(-1)
    return codes2, outl2, delta2, valid2, q


@functools.partial(jax.jit, static_argnames=("k_literal",))
def _device_stats(codes2, valid2, q, work_flat, eb, k_literal):
    """Accelerator path: per-chunk histograms + literal candidates as
    device scatters; only these summaries cross to the host.

    The decompressor reconstructs through a float64 multiply; on device
    we only have the float32 formula, so we collect a conservative
    CANDIDATE set (few-ulp guard band) together with the exact integer q
    at each candidate — the host replays the float64 formula on just
    those to recover the staged path's exact literal set.
    """
    n_chunks = codes2.shape[0]
    cidx = jnp.broadcast_to(jnp.arange(n_chunks, dtype=jnp.int32)[:, None],
                            codes2.shape)
    hists = jnp.zeros((n_chunks, NUM_SYMBOLS), jnp.int32) \
        .at[cidx, codes2].add(valid2.astype(jnp.int32))
    lit_idx, lit_q, lit_count = _extract_sparse(
        _literal_candidates(q, work_flat, eb), q, k_literal)
    return hists, lit_idx, lit_q, lit_count


def _literal_candidates(q, x_flat, eb):
    """Mask of the values whose f32 reconstruction q * 2eb lies within
    the guard band (GUARD_ULPS ulp of |rec| + |x|) under the bound `eb`,
    or past it: a superset of the float64 literal set."""
    rec = q.astype(jnp.float32) * (2.0 * eb)
    band = GUARD_ULPS * _EPS32 * (jnp.abs(rec) + jnp.abs(x_flat)) + 1e-38
    return jnp.abs(rec - x_flat) > (eb - band)


def literal_capacity(n: int, eb: Optional[float] = None,
                     amax: Optional[float] = None) -> int:
    """Static capacity of the literal-candidate compaction of an
    n-value pass at absolute bound `eb` over data with max |x| `amax`.

    The quantization errors spread over [0, eb], and the guard band is
    about 2 * GUARD_ULPS * eps32 * |x| wide, so at most a share
    2 * GUARD_ULPS * eps32 * amax / eb of the values are candidates. The
    capacity is n >> s for the largest s <= 8 whose 2^-s covers that
    share (few buckets, so few compiled passes), never under the floor
    min(n, max(256, n // 256)) and never over n. Without `eb` and `amax`
    (or with eb <= 0, or a non-finite share) it is the floor. Data whose
    errors bunch near the bound can still overflow: :func:`_literals`
    then checks densely."""
    floor = min(n, max(256, n // 256))
    if eb is None or amax is None or not eb > 0:
        return floor
    share = 2 * GUARD_ULPS * _EPS32 * float(amax) / float(eb)
    if not np.isfinite(share):
        return floor
    s = 8
    while s > 0 and 2.0 ** -s < share:
        s -= 1
    return min(n, max(floor, n >> s))


def _extract_sparse(mask, values, k):
    """Deterministic fixed-capacity compaction of a sparse mask.

    -> (idx (k,) int32 ascending, vals (k,), count). Entries past the
    first k survivors are dropped; callers compare count against k and
    fall back to a dense host pass on overflow. For literal candidates
    `k` is :func:`literal_capacity`, sized from the guard band's yield.
    """
    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask, pos, k)                 # k => out of range, dropped
    idx = jnp.zeros(k, jnp.int32).at[tgt].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    vals = jnp.zeros(k, values.dtype).at[tgt].set(values, mode="drop")
    return idx, vals, mask.sum(dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Pass 2: batched Huffman encode + bit-pack + outlier compaction
# ---------------------------------------------------------------------------

# The pack lives behind the kernel-dispatch layer (kernels/dispatch.py,
# op 'hufenc'): 'jnp' is the symbol-side prefix-sum pack
# (kernels/hufenc/ref.py), 'pallas' the explicit VMEM-resident
# gather-pack kernel (kernels/hufenc/kernel.py); both are bit-identical
# and selected via CEAZConfig(kernel_impl=...). The gather-pack composes
# each output word from a window of candidate symbols: a Huffman
# codeword is at most MAX_CODE_BITS=16 bits, so every real symbol
# occupies >= 1 bit, at most 32 symbols START inside one 32-bit output
# word, plus one that spills in from the left — 33 candidates in the
# worst case. The host shrinks the window when the batch's codebooks
# have a larger minimum code length (bucketed to bound recompiles); the
# prefix-sum pack takes the window as part of the op's calling
# convention and ignores it.
_CANDS = 33
_CAND_BUCKETS = (9, 17, 33)          # min code length >= 4 / >= 2 / >= 1


def _cand_window(min_len: int) -> int:
    need = -(-32 // max(int(min_len), 1)) + 1
    for b in _CAND_BUCKETS:
        if need <= b:
            return b
    return _CANDS


@functools.partial(jax.jit, static_argnames=("k_outlier",))
def _extract_outliers(outl2, delta2, valid2, k_outlier):
    """Accelerator path: per-chunk fixed-capacity outlier compaction."""
    return jax.vmap(lambda m, d: _extract_sparse(m, d, k_outlier))(
        outl2 & valid2, delta2)


# ---------------------------------------------------------------------------
# Host assembly
# ---------------------------------------------------------------------------

def _u32_to_u64(u32: np.ndarray) -> np.ndarray:
    """Fold MSB-first u32 pairs into the u64 wire words of huffman.encode."""
    return ((u32[0::2].astype(np.uint64) << np.uint64(32))
            | u32[1::2].astype(np.uint64))


def to_device(side: str, site: str, *arrays):
    """`arrays` (already handed to the device) once they are there: the
    blocking wait makes the enclosing span cover the copy, not only its
    enqueue. Their bytes count under ceaz_h2d_bytes_total."""
    jax.block_until_ready(arrays)
    om.add(om.H2D_BYTES, sum(int(a.nbytes) for a in arrays),
           side=side, site=site)
    return arrays


def to_host(side: str, site: str, *arrays):
    """numpy copies of device `arrays` (None passes through); their bytes
    count under ceaz_d2h_bytes_total."""
    out = [None if a is None else np.asarray(a) for a in arrays]
    om.add(om.D2H_BYTES, sum(int(a.nbytes) for a in out if a is not None),
           side=side, site=site)
    return out


@dataclasses.dataclass
class _Pass1:
    """State between the two fused passes.

    The 2-D chunked arrays stay device-resident; which summaries exist
    depends on the stats path (device scatters vs host snapshot).
    """
    codes2: jax.Array
    outl2: jax.Array
    delta2: jax.Array
    valid2: jax.Array
    q: jax.Array
    hists: np.ndarray
    n: int
    n_chunks: int
    chunk_values: int
    stats_on_device: bool
    # device-stats path: fixed-capacity literal candidates
    lit_idx: Optional[jax.Array] = None
    lit_q: Optional[jax.Array] = None
    lit_count: Optional[jax.Array] = None
    # host-stats path: bulk snapshots shared by hist/outlier/literal code
    codes_host: Optional[np.ndarray] = None
    outl_host: Optional[np.ndarray] = None
    delta_host: Optional[np.ndarray] = None
    q_host: Optional[np.ndarray] = None
    # value-direct (predictor='none'): per-chunk centre codes
    predictor: str = "lorenzo"
    centers: Optional[np.ndarray] = None


def _host_hists(codes_host: np.ndarray, n: int) -> np.ndarray:
    """Per-chunk histograms in ONE bincount pass (runs at memory speed)."""
    nc, cv = codes_host.shape
    flat = codes_host.reshape(-1)[:n].astype(np.int64)
    keys = flat + (np.arange(n, dtype=np.int64) // cv) * NUM_SYMBOLS
    return np.bincount(keys, minlength=nc * NUM_SYMBOLS) \
        .reshape(nc, NUM_SYMBOLS)


def _run_pass1(work: jnp.ndarray, eb: float, ndim: int, chunk_values: int,
               stats_on_device: Optional[bool] = None,
               amax: Optional[float] = None) -> _Pass1:
    """Pass 1 of one array; `amax` (max |x|, where the caller has it)
    sizes the literal candidates (:func:`literal_capacity`)."""
    if stats_on_device is None:
        stats_on_device = _default_stats_on_device()
    n = int(work.size)
    n_chunks, _ = chunk_layout(n, chunk_values)
    codes2, outl2, delta2, valid2, q = _quantize_pass(
        work, eb, ndim, n_chunks, chunk_values)
    if stats_on_device:
        hists, lit_idx, lit_q, lit_count = _device_stats(
            codes2, valid2, q, work.reshape(-1), eb,
            literal_capacity(n, eb, amax))
        return _Pass1(codes2, outl2, delta2, valid2, q, np.asarray(hists),
                      n, n_chunks, chunk_values, True,
                      lit_idx=lit_idx, lit_q=lit_q, lit_count=lit_count)
    codes_host = np.asarray(codes2)
    return _Pass1(codes2, outl2, delta2, valid2, q,
                  _host_hists(codes_host, n), n, n_chunks, chunk_values,
                  False, codes_host=codes_host, q_host=np.asarray(q))


# ---------------------------------------------------------------------------
# Pass 1, value-direct flavour (predictor='none')
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_chunks", "chunk_values"))
def _value_prequantize(work, eb, n_chunks, chunk_values):
    """flat work (f32) -> (q2, valid2) padded chunk rows (elementwise
    quantization only; centring happens after the `dq_center` op)."""
    flat = work.reshape(-1)
    n = flat.shape[0]
    q = core_dq.prequantize(flat, eb)
    pad = n_chunks * chunk_values - n
    valid = jnp.arange(n_chunks * chunk_values, dtype=jnp.int32) < n
    q2 = jnp.pad(q, (0, pad)).reshape(n_chunks, chunk_values)
    return q2, valid.reshape(n_chunks, chunk_values)


@jax.jit
def _value_finalize(q2, centers, valid2):
    """centre-relative codes/outliers/deltas; padded entries code to 0
    so the histogram scatter stays in range."""
    codes2, outl2, delta2 = core_dq.value_postquantize(q2, centers[:, None])
    codes2 = jnp.where(valid2, codes2, jnp.uint16(0)).astype(jnp.int32)
    return codes2, outl2, delta2


def _run_value_pass1(work: jnp.ndarray, eb: float, chunk_values: int,
                     stats_on_device: Optional[bool] = None,
                     kernel_impl: str = "auto",
                     amax: Optional[float] = None) -> _Pass1:
    """Value-direct twin of :func:`_run_pass1`: same _Pass1 contract,
    with per-chunk device centre codes instead of Lorenzo prediction.
    The integer field the literal check replays is q itself (the
    reconstruction is q * 2eb, no prefix sum)."""
    if stats_on_device is None:
        stats_on_device = _default_stats_on_device()
    n = int(work.size)
    n_chunks, _ = chunk_layout(n, chunk_values)
    q2, valid2 = _value_prequantize(work, eb, n_chunks, chunk_values)
    with dispatch.measure("dq_center", kernel_impl):
        centers = dispatch.resolve("dq_center", kernel_impl)(q2, valid2)
    codes2, outl2, delta2 = _value_finalize(q2, centers, valid2)
    q = q2.reshape(-1)[:n]
    centers_np = np.asarray(centers).astype(np.int64)
    if stats_on_device:
        hists, lit_idx, lit_q, lit_count = _device_stats(
            codes2, valid2, q, work.reshape(-1), eb,
            literal_capacity(n, eb, amax))
        return _Pass1(codes2, outl2, delta2, valid2, q, np.asarray(hists),
                      n, n_chunks, chunk_values, True,
                      lit_idx=lit_idx, lit_q=lit_q, lit_count=lit_count,
                      predictor="none", centers=centers_np)
    codes_host = np.asarray(codes2)
    return _Pass1(codes2, outl2, delta2, valid2, q,
                  _host_hists(codes_host, n), n, n_chunks, chunk_values,
                  False, codes_host=codes_host, q_host=np.asarray(q),
                  predictor="none", centers=centers_np)


def _literals(p1: _Pass1, x_flat: np.ndarray, eb: float, ndim: int,
              work_shape) -> Tuple[np.ndarray, np.ndarray]:
    """Exact literal set (identical to the staged float64 check).

    Host-stats path: direct dense check on the snapshot. Device-stats
    path: replay the float64 formula on the device's candidate positions
    only. The candidate buffer is sized from the guard band's expected
    yield (:func:`literal_capacity`); when the candidates overflow it
    anyway, the check runs densely over every value on the host, which
    is exact too and counts under ceaz_literal_dense_checks_total. Values
    are gathered from the caller's ORIGINAL array, and the
    reconstruction is rounded through the ORIGINAL dtype (f32 or f64)
    exactly as the staged reference's dequantize does."""
    out_dtype = x_flat.dtype
    om.add(om.LITERAL_CHECKS, side="encode")
    if not p1.stats_on_device:
        q = p1.q_host.astype(np.int64)
        rec = (q.astype(np.float64) * (2.0 * eb)).astype(out_dtype)
        idx = np.flatnonzero(
            np.abs(rec.astype(np.float64) - x_flat.astype(np.float64)) > eb
        ).astype(np.int64)
        return idx, x_flat[idx].copy()
    count = int(p1.lit_count)
    if count <= p1.lit_idx.shape[0]:
        idx = np.asarray(p1.lit_idx[:count]).astype(np.int64)
        q = np.asarray(p1.lit_q[:count]).astype(np.int64)
        rec = (q.astype(np.float64) * (2.0 * eb)).astype(out_dtype)
        viol = (np.abs(rec.astype(np.float64)
                       - x_flat[idx].astype(np.float64)) > eb)
        idx = idx[viol]
    else:       # candidate capacity overflow: exact dense pass on the host
        om.add(om.LITERAL_DENSE_CHECKS, side="encode")
        if p1.predictor == "none":
            delta = np.asarray(p1.delta2).astype(np.int64)
            q = (delta + p1.centers[:, None]).reshape(-1)[:p1.n]
            rec = (q.astype(np.float64) * (2.0 * eb)).astype(out_dtype)
        else:
            delta = np.asarray(p1.delta2).reshape(-1)[:p1.n]
            rec = core_dq.np_dequantize(delta.reshape(work_shape), eb, ndim,
                                        dtype=out_dtype).reshape(-1)
        idx = np.flatnonzero(
            np.abs(rec.astype(np.float64) - x_flat.astype(np.float64)) > eb
        ).astype(np.int64)
    return idx, x_flat[idx].copy()


def _chunk_len(p1: _Pass1, i: int) -> int:
    return (p1.chunk_values if i < p1.n_chunks - 1
            else p1.n - (p1.n_chunks - 1) * p1.chunk_values)


def _outliers(p1: _Pass1) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per-chunk (idx, delta) outlier escapes, path-appropriate."""
    out = []
    if p1.stats_on_device:
        ext = _extract_outliers(p1.outl2, p1.delta2, p1.valid2,
                                _k_outlier(p1.chunk_values))
        oidx_np, odelta_np, ocount = (np.asarray(a) for a in ext)
        k = oidx_np.shape[1]
        for i in range(p1.n_chunks):
            c = int(ocount[i])
            if c <= k:
                out.append((oidx_np[i, :c].astype(np.int64),
                            odelta_np[i, :c].astype(np.int32)))
            else:   # overflow: dense host fallback for this chunk
                m = np.asarray(p1.outl2[i] & p1.valid2[i])
                oi = np.flatnonzero(m).astype(np.int64)
                out.append((oi, np.asarray(p1.delta2[i])[oi]
                            .astype(np.int32)))
        return out
    if p1.outl_host is None:
        p1.outl_host = np.asarray(p1.outl2)
        p1.delta_host = np.asarray(p1.delta2)
    for i in range(p1.n_chunks):
        n_i = _chunk_len(p1, i)
        oi = np.flatnonzero(p1.outl_host[i, :n_i]).astype(np.int64)
        out.append((oi, p1.delta_host[i][oi].astype(np.int32)))
    return out


def _codebook_tables(decisions) -> Tuple[np.ndarray, np.ndarray]:
    lengths = np.stack([d.codebook.lengths for d in decisions]) \
        .astype(np.int32)
    cwords = np.stack([d.codebook.codes for d in decisions]) \
        .astype(np.uint32)
    return lengths, cwords


def _w32_bucket(totals: np.ndarray, chunk_values: int) -> int:
    """Bucketed u32 capacity covering the exact payload bits: powers of
    two up to a page, then page multiples (few jit variants, little
    over-provisioning)."""
    need = 2 * ((int(totals.max()) + 63) // 64 + 1)
    cap = words_capacity(chunk_values)
    if need <= 4096:
        w32 = 4
        while w32 < need:
            w32 *= 2
    else:
        w32 = -(-need // 4096) * 4096
    return min(w32, cap)


def _k_outlier(chunk_values: int) -> int:
    return min(chunk_values, max(1024, chunk_values // 8))


def _encode_rows(hists: np.ndarray, codes2, valid2, chunk_values: int,
                 decisions, block_size: int, kernel_impl: str):
    """The shared pass-2 core: provision the traced pack for the exact
    bit-rate (per-chunk payload size is hist . lengths — free on the
    host) and run the gather-pack through the kernel-dispatch registry.
    One chunk row per decision; every pass-2 caller (single array,
    speculative window, shard batch) funnels through here so the
    w32/cands provisioning policy cannot diverge between paths.
    Returns (words_np, block_nbits_np, totals)."""
    lengths_np, cwords_np = _codebook_tables(decisions)
    totals = np.einsum("cs,cs->c", hists.astype(np.int64),
                       lengths_np.astype(np.int64))
    w32 = _w32_bucket(totals, chunk_values)
    cands = _cand_window(lengths_np[lengths_np > 0].min())
    encode_pack = dispatch.resolve("hufenc", kernel_impl)
    with dispatch.measure("hufenc", kernel_impl):
        words, block_nbits = encode_pack(
            codes2, valid2, jnp.asarray(lengths_np),
            jnp.asarray(cwords_np), block_size, w32, cands)
    return np.asarray(words), np.asarray(block_nbits), totals


def _encode_all(p1: _Pass1, decisions, block_size: int,
                kernel_impl: str = "auto"):
    """Pass 2 for one array: batched encode+pack plus outlier escapes.
    Returns (words_np, block_nbits_np, totals, outliers)."""
    words_np, nbits_np, totals = _encode_rows(
        p1.hists, p1.codes2, p1.valid2, p1.chunk_values, decisions,
        block_size, kernel_impl)
    return words_np, nbits_np, totals, _outliers(p1)


def _assemble_chunks(p1: _Pass1, words_np, nbits_np, totals, outliers,
                     eb: float, decisions, block_size: int) -> List:
    """Build host CompressedChunk records from the batched transfers."""
    from ..core.ceaz import CompressedChunk
    chunks = []
    for i, decision in enumerate(decisions):
        n_i = _chunk_len(p1, i)
        nw64 = (int(totals[i]) + 63) // 64
        words = _u32_to_u64(words_np[i, :2 * (nw64 + 1)])
        nblocks = max(1, -(-n_i // block_size))
        oi, od = outliers[i]
        chunks.append(CompressedChunk(
            words=words, block_nbits=nbits_np[i, :nblocks].astype(np.int64),
            n_values=n_i, eb=eb,
            action=decision.action, chi=decision.chi,
            codebook_lengths=(decision.codebook.lengths.copy()
                              if decision.stored_codebook else None),
            codebook_id=decision.codebook.id,
            outlier_idx=oi, outlier_delta=od,
            center=(int(p1.centers[i]) if p1.centers is not None else 0),
            bank_ref=getattr(decision, "bank_ref", ""),
            bank_index=getattr(decision, "bank_index", -1)))
    return chunks


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def compress_error_bounded(x: np.ndarray, eb: float, mode: str,
                           coder: AdaptiveCoder, chunk_values: int,
                           block_size: int, adaptive: bool = True,
                           exact_build: bool = False,
                           stats_on_device: Optional[bool] = None,
                           kernel_impl: str = "auto",
                           predictor: str = "lorenzo",
                           amax: Optional[float] = None):
    """Fused abs/rel compression of a float array (any dtype/predictor).

    Returns a CEAZCompressed bit-compatible with the staged jax-backend
    reference. With the Lorenzo predictor the array is quantized ONCE
    (native-rank Lorenzo) and the code stream is then cut into chunks
    for the adaptive coder; value-direct (predictor='none') quantizes
    each value against its chunk's device-computed centre code. Float64
    inputs quantize through the same f32 device pass (the staged jax
    backend's semantics); the float64 bound is restored by the literal
    channel. `amax`, max |x| where the caller has it, sizes the literal
    candidates (:func:`literal_capacity`); it never changes the bytes.
    """
    from ..core.ceaz import CEAZCompressed
    # capping at the stream length keeps chunk boundaries identical and
    # avoids padding the whole pipeline up to a chunk nothing fills
    chunk_values = max(1, min(chunk_values, int(x.size)))
    if predictor == "none":
        ndim = 1
        work = jnp.asarray(x.reshape(-1), jnp.float32)
        p1 = _run_value_pass1(work, eb, chunk_values, stats_on_device,
                              kernel_impl, amax)
    else:
        ndim = min(x.ndim, 3)
        work_shape = x.shape if x.ndim <= 3 else (-1,) + x.shape[-2:]
        work = jnp.asarray(x.reshape(work_shape), jnp.float32)
        p1 = _run_pass1(work, eb, ndim, chunk_values, stats_on_device,
                        amax)
    decisions = _policy(p1.hists, coder, adaptive, exact_build)
    with ot.span("fused.encode_pass2", n_chunks=p1.n_chunks):
        enc = _encode_all(p1, decisions, block_size, kernel_impl)
    chunks = _assemble_chunks(p1, *enc, eb, decisions, block_size)
    lit_idx, lit_val = _literals(p1, x.reshape(-1), eb, ndim, work.shape)
    return CEAZCompressed(shape=x.shape, dtype=str(x.dtype), ndim=ndim,
                          mode=mode, chunks=chunks,
                          word_bits=x.dtype.itemsize * 8,
                          predictor=predictor,
                          literal_idx=lit_idx, literal_val=lit_val)


# ---------------------------------------------------------------------------
# Single-pass bank mode (codebook='bank'): quantize -> select -> encode ->
# pack in ONE traced computation, no host tree-build between the passes
# ---------------------------------------------------------------------------

# The provisioned pack grain: the single-pass trace cannot size its
# output buffer from the data (that would be the host sync it exists to
# delete), so it provisions for BANK_PROVISION_BITS bits/value — double
# the capacity the shipped bank's books ever need on in-distribution
# data — and the host re-packs (pack only: codes stay device-resident)
# through _bank_repack_fn in the rare case a chunk's exact payload
# (hist . lengths, known from the one transfer) exceeds it.
BANK_PROVISION_BITS = 8


def _bank_w32(bits_per_value: int, chunk_values: int) -> int:
    """Static u32 provisioning for bits_per_value, trimmed like
    words_capacity so the valid prefix cuts to whole uint64 words."""
    need = 2 * ((chunk_values * int(bits_per_value) + 63) // 64 + 1)
    return min(need, words_capacity(chunk_values))


def _bank_fits(totals: np.ndarray, w32: int) -> bool:
    """Whether every chunk's exact payload fits the provisioned pack."""
    return 2 * ((int(totals.max()) + 63) // 64 + 1) <= w32


@functools.lru_cache(maxsize=None)
def _bank_pass_fn(kernel_impl: str, predictor: str, ndim: int,
                  n_chunks: int, chunk_values: int, block_size: int,
                  w32: int, cands: int, k_outlier: int, k_literal: int,
                  stats_on_device: bool):
    """Build (and cache) the fused single-pass trace for one work shape.

    The returned jitted function runs quantize -> per-chunk histogram ->
    bank selection (argmin over hist . lengths_k) -> gather the selected
    rows -> Huffman encode + bit-pack as ONE traced computation. Nothing
    crosses to the host between quantize and pack; the caller snapshots
    the whole result tuple in a single transfer. The selection statistic
    is integer and small (<= 16 * chunk_values per entry), so the host
    drift replay in ``core.codebook.BankCoder`` reproduces the device
    argmin bitwise. Outlier / literal-candidate compaction joins the
    trace only on real accelerators (``stats_on_device``); on CPU hosts
    the dense snapshots are cheaper than XLA scatters, exactly as in
    :func:`_run_pass1`.
    """
    encode_pack = dispatch.resolve("hufenc", kernel_impl)
    center_fn = (dispatch.resolve("dq_center", kernel_impl)
                 if predictor == "none" else None)

    @jax.jit
    def run(work, eb, bank_lengths, bank_cwords):
        if predictor == "none":
            q2, valid2 = _value_prequantize(work, eb, n_chunks,
                                            chunk_values)
            centers = center_fn(q2, valid2)
            codes2, outl2, delta2 = _value_finalize(q2, centers, valid2)
            q = q2.reshape(-1)[:work.size]
        else:
            codes2, outl2, delta2, valid2, q = _quantize_pass(
                work, eb, ndim, n_chunks, chunk_values)
            centers = None
        cidx = jnp.broadcast_to(
            jnp.arange(n_chunks, dtype=jnp.int32)[:, None], codes2.shape)
        hists = jnp.zeros((n_chunks, NUM_SYMBOLS), jnp.int32) \
            .at[cidx, codes2].add(valid2.astype(jnp.int32))
        costs = jnp.einsum("cs,ks->ck", hists, bank_lengths)
        sel = jnp.argmin(costs, axis=1).astype(jnp.int32)
        totals = jnp.take_along_axis(costs, sel[:, None], axis=1)[:, 0]
        words, block_nbits = encode_pack(
            codes2, valid2, bank_lengths[sel], bank_cwords[sel],
            block_size, w32, cands)
        if not stats_on_device:
            return (hists, sel, totals, words, block_nbits,
                    None, None, None, None, None, None,
                    codes2, outl2, delta2, valid2, q, centers)
        oidx, odelta, ocount = jax.vmap(
            lambda m, d: _extract_sparse(m, d, k_outlier))(
            outl2 & valid2, delta2)
        lit_idx, lit_q, lit_count = _extract_sparse(
            _literal_candidates(q, work.reshape(-1), eb), q, k_literal)
        return (hists, sel, totals, words, block_nbits,
                oidx, odelta, ocount, lit_idx, lit_q, lit_count,
                codes2, outl2, delta2, valid2, q, centers)

    return run


@functools.lru_cache(maxsize=None)
def _mega_pass_fn(kernel_impl: str, predictor: str, n_chunks: int,
                  chunk_values: int, block_size: int, w32: int,
                  cands: int, k_outlier: int, k_literal: int,
                  stats_on_device: bool):
    """:func:`_bank_pass_fn` twin built on the `ceaz_chunk` megakernel
    dispatch op: quantize -> histogram -> bank-select -> pack run as ONE
    op (one Pallas program per chunk under 'pallas') instead of a trace
    composed from the stage ops. Same return contract, bit-identical
    outputs. Only the shapes whose Lorenzo halo is a single raw value
    qualify — 1-D streams and value-direct — because the op quantizes
    each chunk row from a one-value halo, which reproduces global
    Lorenzo bitwise only in 1-D (the halo re-quantizes exactly the
    q[i-1] the global pass used; see kernels/megakernel/ref.py).
    Higher-rank Lorenzo keeps using `_bank_pass_fn`.
    """
    ceaz_op = dispatch.resolve("ceaz_chunk", kernel_impl)
    op_pred = "value" if predictor == "none" else "lorenzo"

    @jax.jit
    def run(work, eb, bank_lengths, bank_cwords):
        flat = work.reshape(-1)
        n = flat.shape[0]
        pad = n_chunks * chunk_values - n
        work2 = jnp.pad(flat, (0, pad)).reshape(n_chunks, chunk_values)
        valid2 = (jnp.arange(n_chunks * chunk_values, dtype=jnp.int32)
                  < n).reshape(n_chunks, chunk_values)
        ci = jnp.arange(n_chunks, dtype=jnp.int32)
        if op_pred == "lorenzo":
            # row i's halo: the RAW predecessor of its first value
            # (row 0 gets the stream head's zero-pad)
            prev2 = jnp.where(
                ci == 0, jnp.float32(0),
                flat[jnp.maximum(ci * chunk_values - 1, 0)])[:, None]
        else:
            prev2 = jnp.zeros((n_chunks, 1), jnp.float32)
        ebs = jnp.broadcast_to(jnp.asarray(eb, jnp.float32), (n_chunks,))
        (q2, codes2, outl2, delta2, centers, hists, sel, totals, words,
         block_nbits) = ceaz_op(work2, prev2, valid2, ebs, bank_lengths,
                                bank_cwords, block_size, w32, cands,
                                op_pred)
        q = q2.reshape(-1)[:n]
        centers_out = centers if op_pred == "value" else None
        if not stats_on_device:
            return (hists, sel, totals, words, block_nbits,
                    None, None, None, None, None, None,
                    codes2, outl2, delta2, valid2, q, centers_out)
        oidx, odelta, ocount = jax.vmap(
            lambda m, d: _extract_sparse(m, d, k_outlier))(
            outl2 & valid2, delta2)
        lit_idx, lit_q, lit_count = _extract_sparse(
            _literal_candidates(q, flat, eb), q, k_literal)
        return (hists, sel, totals, words, block_nbits,
                oidx, odelta, ocount, lit_idx, lit_q, lit_count,
                codes2, outl2, delta2, valid2, q, centers_out)

    return run


@functools.lru_cache(maxsize=None)
def _bank_repack_fn(kernel_impl: str, block_size: int, w32: int,
                    cands: int):
    """Pack-only retry at full bank capacity for provisioning overflow:
    quantized codes never leave the device, only the pack re-runs."""
    encode_pack = dispatch.resolve("hufenc", kernel_impl)

    @jax.jit
    def run(codes2, valid2, lengths_sel, cwords_sel):
        return encode_pack(codes2, valid2, lengths_sel, cwords_sel,
                           block_size, w32, cands)

    return run


@dataclasses.dataclass(frozen=True)
class _BankLayout:
    """The static sizing of one bank pass over one array (or one rank's
    brick): what selects and builds its traced program."""
    kernel_impl: str
    predictor: str
    ndim: int
    work_shape: tuple
    n: int
    n_chunks: int
    chunk_values: int
    block_size: int
    w32: int
    w32_full: int
    cands: int
    k_outlier: int
    k_literal: int
    stats_on_device: bool

    @property
    def mega(self) -> bool:
        # the megakernel op covers exactly the shapes whose Lorenzo halo
        # is one raw value — 1-D streams and value-direct; higher-rank
        # Lorenzo keeps the stage-composed trace (same outputs either way)
        return self.predictor == "none" or self.ndim == 1

    @property
    def op(self) -> str:
        return "ceaz_chunk" if self.mega else "hufenc"

    def pass_fn(self):
        """The cached jitted bank pass `run(work, eb, lengths, cwords)`."""
        if self.mega:
            return _mega_pass_fn(
                self.kernel_impl, self.predictor, self.n_chunks,
                self.chunk_values, self.block_size, self.w32, self.cands,
                self.k_outlier, self.k_literal, self.stats_on_device)
        return _bank_pass_fn(
            self.kernel_impl, self.predictor, self.ndim, self.n_chunks,
            self.chunk_values, self.block_size, self.w32, self.cands,
            self.k_outlier, self.k_literal, self.stats_on_device)


def _bank_layout(bank, shape, chunk_values: int, block_size: int,
                 kernel_impl: str, predictor: str, stats_on_device: bool,
                 bounds: Sequence[Tuple[float, float]] = ()) -> _BankLayout:
    """The static sizing of a bank pass over arrays of `shape`.

    `bounds` holds an (eb, max |x|) pair for each array the one program
    compresses (one, or one a rank); the literal-candidate capacity is
    the largest :func:`literal_capacity` over them, and its floor
    without them."""
    n = int(np.prod(shape))
    chunk_values = max(1, min(chunk_values, n))
    n_chunks, _ = chunk_layout(n, chunk_values)
    if predictor == "none":
        ndim, work_shape = 1, (-1,)
    else:
        ndim = min(len(shape), 3)
        work_shape = _work_shape(shape)
    return _BankLayout(
        kernel_impl, predictor, ndim, work_shape, n, n_chunks, chunk_values,
        block_size,
        _bank_w32(min(int(bank.lengths.max()), BANK_PROVISION_BITS),
                  chunk_values),
        _bank_w32(int(bank.lengths.max()), chunk_values),
        _cand_window(int(bank.lengths.min())), _k_outlier(chunk_values),
        max(literal_capacity(n, eb, amax) for eb, amax in bounds)
        if bounds else literal_capacity(n), stats_on_device)


def _count_pass(lay: _BankLayout, passes: int = 1) -> None:
    om.add(om.PASS_VALUES, passes * lay.n_chunks * lay.chunk_values,
           side="encode", op=lay.op)
    om.add(om.PASS_LIVE_VALUES, passes * lay.n, side="encode", op=lay.op)


def compress_error_bounded_bank(x: np.ndarray, eb: float, mode: str,
                                coder: BankCoder, chunk_values: int,
                                block_size: int,
                                stats_on_device: Optional[bool] = None,
                                kernel_impl: str = "auto",
                                predictor: str = "lorenzo",
                                amax: Optional[float] = None):
    """Single-pass fused compression against an offline codebook bank.

    Unlike :func:`compress_error_bounded`, the per-chunk codebook comes
    from the coder's pre-trained :class:`~repro.core.codebook.
    CodebookBank` instead of a host tree-build, so the WHOLE encode —
    quantize, histogram, bank selection, Huffman pack — runs as one
    traced device pass with a single transfer at the end. The host then
    replays the selection from the histogram summaries (``coder.step``)
    to record per-chunk decisions and the drift statistic the ``CEAZ``
    facade's fallback check consumes; the replay must agree with the
    device argmin bitwise (asserted). When a chunk's exact payload
    exceeds the BANK_PROVISION_BITS pack provisioning, only the pack
    re-runs at full capacity (the quantized codes stay device-resident).
    `amax`, max |x| where the caller has it, sizes the literal candidates
    (:func:`literal_capacity`); it never changes the bytes.
    """
    bank = coder.bank
    if stats_on_device is None:
        stats_on_device = _default_stats_on_device()
    lay = _bank_layout(bank, x.shape, chunk_values, block_size,
                       kernel_impl, predictor, stats_on_device,
                       () if amax is None else ((eb, amax),))
    run = lay.pass_fn()
    with ot.span("fused.h2d"):
        work, lengths_d, cwords_d = to_device(
            "encode", "fused.h2d",
            jnp.asarray(x.reshape(lay.work_shape), jnp.float32),
            jnp.asarray(bank.lengths, jnp.int32),
            jnp.asarray(bank.code_table(), jnp.uint32))
    with dispatch.measure(lay.op, kernel_impl):
        out = run(work, eb, lengths_d, cwords_d)
    _count_pass(lay)
    with ot.span("fused.device_wait"):
        out = jax.block_until_ready(out)
    (hists, sel, totals, words, block_nbits, oidx, odelta, ocount,
     lit_idx, lit_q, lit_count, codes2, outl2, delta2, valid2, q,
     centers) = out
    # --- everything below is host work on the pulled results ---
    dense = None
    with ot.span("fused.d2h"):
        hists_np, sel_np, totals_np, words_np, nbits_np, centers_np = \
            to_host("encode", "fused.d2h", hists, sel, totals, words,
                     block_nbits, centers)
        if stats_on_device:
            stats = to_host("encode", "fused.d2h", oidx, odelta, ocount,
                            lit_idx, lit_q, lit_count)
            if int(stats[-1]) > stats[3].shape[0]:
                # more literal candidates than the pass kept: the check
                # runs dense over the deltas (see _literals)
                delta2, = to_host("encode", "fused.d2h", delta2)
        else:
            stats = (None,) * 6
            dense = to_host("encode", "fused.d2h", outl2, delta2, q)
    return _bank_finish(
        x, eb, mode, coder, lay,
        (hists_np, sel_np, totals_np, words_np, nbits_np, *stats),
        centers_np, (codes2, outl2, delta2, valid2), dense)


def _bank_finish(x: np.ndarray, eb: float, mode: str, coder: BankCoder,
                 lay: _BankLayout, pulled, centers_np, dev, dense=None):
    """Host half of a bank pass: selection replay, the provisioning
    repack, chunk records and the literal check, from the pass's pulled
    results `pulled` = (hists, sel, totals, words, block_nbits, oidx,
    odelta, ocount, lit_idx, lit_q, lit_count) (the last six None on the
    host-stats path, which brings `dense` = (outl2, delta2, q) snapshots
    instead). `dev` = (codes2, outl2, delta2, valid2) are the pass's
    device arrays, read only on an overflow; delta2 is already on the
    host where the literal candidates overflowed."""
    from ..core.ceaz import CEAZCompressed
    (hists_np, sel_np, totals_np, words_np, nbits_np, oidx_np, odelta_np,
     ocount_np, lit_idx_np, lit_q_np, lit_count_np) = pulled
    codes2, outl2, delta2, valid2 = dev
    n, n_chunks = lay.n, lay.n_chunks
    with ot.span("fused.host_select"):
        hists_np = hists_np.astype(np.int64)
        totals_np = totals_np.astype(np.int64)
        decisions = [coder.step(h) for h in hists_np]
        for i, d in enumerate(decisions):
            # the host replay of the selection statistic must land on
            # the same bank row the device argmin picked (integer-exact)
            assert d.bank_index == int(sel_np[i])
    if lay.w32 < lay.w32_full and not _bank_fits(totals_np, lay.w32):
        om.add(om.BANK_REPACKS)
        lengths_np, cwords_np = _codebook_tables(decisions)
        with ot.span("fused.bank_overflow_repack"):
            repacked = _bank_repack_fn(
                lay.kernel_impl, lay.block_size, lay.w32_full, lay.cands)(
                codes2, valid2, jnp.asarray(lengths_np),
                jnp.asarray(cwords_np))
            with ot.span("fused.d2h"):
                words_np, nbits_np = to_host("encode", "fused.d2h",
                                              *repacked)
    with ot.span("fused.assemble"):
        if centers_np is not None:
            centers_np = centers_np.astype(np.int64)
        if lay.stats_on_device:
            p1 = _Pass1(None, outl2, delta2, valid2, None, hists_np, n,
                        n_chunks, lay.chunk_values, True,
                        lit_idx=lit_idx_np, lit_q=lit_q_np,
                        lit_count=lit_count_np, predictor=lay.predictor,
                        centers=centers_np)
            k = oidx_np.shape[1]
            outliers = []
            for i in range(n_chunks):
                c = int(ocount_np[i])
                if c <= k:
                    outliers.append((oidx_np[i, :c].astype(np.int64),
                                     odelta_np[i, :c].astype(np.int32)))
                else:   # overflow: dense host fallback for this chunk
                    m, d = to_host("encode", "fused.d2h",
                                    outl2[i] & valid2[i], delta2[i])
                    oi = np.flatnonzero(m).astype(np.int64)
                    outliers.append((oi, d[oi].astype(np.int32)))
        else:
            outl_np, delta_np, q_np = dense
            p1 = _Pass1(None, None, None, None, None, hists_np, n,
                        n_chunks, lay.chunk_values, False,
                        outl_host=outl_np, delta_host=delta_np, q_host=q_np,
                        predictor=lay.predictor, centers=centers_np)
            outliers = _outliers(p1)
        chunks = _assemble_chunks(p1, words_np, nbits_np, totals_np,
                                  outliers, eb, decisions, lay.block_size)
        lit_i, lit_v = _literals(p1, x.reshape(-1), eb, lay.ndim,
                                 lay.work_shape)
    return CEAZCompressed(shape=x.shape, dtype=str(x.dtype), ndim=lay.ndim,
                          mode=mode, chunks=chunks,
                          word_bits=x.dtype.itemsize * 8,
                          predictor=lay.predictor,
                          literal_idx=lit_i, literal_val=lit_v)


# ---------------------------------------------------------------------------
# Rank-sharded bank mode: one brick per device along a mesh axis, each
# device running the bank pass on its own brick; the packed payloads are
# gathered to the aggregator device (io/collectives.py)
# ---------------------------------------------------------------------------

def rank_mesh_axis(x):
    """(mesh, axis) where `x` is a `jax.Array` whose leading axis is split
    one row per device along one axis of a mesh and no other axis is
    split; None otherwise."""
    from jax.sharding import NamedSharding
    sh = getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding) or x.ndim < 2:
        return None
    spec = tuple(sh.spec) + (None,) * (x.ndim - len(sh.spec))
    axis = spec[0]
    if not isinstance(axis, str) or any(s is not None for s in spec[1:]):
        return None
    if sh.mesh.shape[axis] != x.shape[0] or x.shape[0] < 2 \
            or len(sh.device_set) != x.shape[0]:
        return None
    return sh.mesh, axis


def rank_shards(a) -> List[jax.Array]:
    """The per-device pieces of a rank-sharded array, in rank order."""
    shards = sorted(a.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    return [s.data for s in shards]


@functools.lru_cache(maxsize=None)
def _rank_range_fn(mesh, axis: str):
    spec = P(axis)

    def per_rank(x):
        return jnp.stack([jnp.max(x), jnp.min(x)])[None]

    def ceaz_rank_range(x):
        return jax.shard_map(per_rank, mesh=mesh, in_specs=spec,
                             out_specs=spec, axis_names={axis})(x)

    return jax.jit(ceaz_rank_range)


def rank_extrema(x) -> np.ndarray:
    """(R, 2) host float64 (max, min) of each rank's brick, each reduced
    on its own device: only these scalars cross to the host."""
    mesh, axis = rank_mesh_axis(x)
    out, = to_host("encode", "fused.d2h", _rank_range_fn(mesh, axis)(x))
    return out.astype(np.float64)


@functools.lru_cache(maxsize=None)
def _sharded_pass_fn(lay: _BankLayout, mesh, axis: str):
    """The bank pass of `lay` over every rank's brick at once: one
    program, each device running `lay.pass_fn()` on its own brick with
    its own bound. Outputs are rank-sharded along `axis` (rank r's rows
    on rank r's device); a scalar output becomes one row per rank."""
    run = lay.pass_fn()
    spec = P(axis)

    def per_rank(x, eb, lengths, cwords):
        out = run(x[0].reshape(lay.work_shape), eb[0], lengths, cwords)
        return tuple(None if a is None else (a if a.ndim else a[None])
                     for a in out)

    def ceaz_gather_pass(x, ebs, lengths, cwords):
        return jax.shard_map(per_rank, mesh=mesh,
                             in_specs=(spec, spec, P(), P()),
                             out_specs=spec, axis_names={axis})(
            x, ebs, lengths, cwords)

    return jax.jit(ceaz_gather_pass)


def compress_bank_sharded(x, ebs: Sequence[float], mode: str,
                          coders: Sequence[BankCoder], chunk_values: int,
                          block_size: int, kernel_impl: str = "auto",
                          amaxes: Sequence[float] = ()):
    """Lorenzo bank compression of every rank's brick of a rank-sharded
    float32 array (see :func:`rank_mesh_axis`), each on its own device.

    One program runs the bank pass of :func:`compress_error_bounded_bank`
    on every device at once, rank r at absolute bound ``ebs[r]`` with
    ``coders[r]``; the packed payloads (summaries, words, block bit
    counts, outlier and literal-candidate buffers) are then gathered over
    the mesh axis to the aggregator, rank 0's device, and pulled from it
    in one transfer (:func:`repro.io.collectives.gather_to_aggregator`).
    No field value crosses between devices. Each rank's record is
    finished on the host exactly as the single-array path finishes it,
    so rank r's record equals ``compress_error_bounded_bank`` of its
    brick. `amaxes`, each brick's max |x|, size the one program's
    literal candidates for the rank that needs the most. The host's
    literal check reads each brick's values, so the raw bricks are
    pulled too (each from its own device). Returns (records, the raw
    bricks on the host as one (R, ...) array)."""
    from jax.sharding import NamedSharding
    from ..io import collectives
    mesh, axis = rank_mesh_axis(x)
    ranks = x.shape[0]
    bank = coders[0].bank
    lay = _bank_layout(bank, x.shape[1:], chunk_values, block_size,
                       kernel_impl, "lorenzo", True, tuple(zip(ebs, amaxes)))
    with ot.span("fused.h2d"):
        ebs_d, lengths_d, cwords_d = to_device(
            "encode", "fused.h2d",
            jax.device_put(np.asarray(ebs, np.float32),
                           NamedSharding(mesh, P(axis))),
            jax.device_put(np.asarray(bank.lengths, np.int32),
                           NamedSharding(mesh, P())),
            jax.device_put(np.asarray(bank.code_table(), np.uint32),
                           NamedSharding(mesh, P())))
    with dispatch.measure(lay.op, kernel_impl):
        out = _sharded_pass_fn(lay, mesh, axis)(x, ebs_d, lengths_d,
                                                cwords_d)
    _count_pass(lay, ranks)
    (hists, sel, totals, words, block_nbits, oidx, odelta, ocount,
     lit_idx, lit_q, lit_count, codes2, outl2, delta2, valid2, _q,
     _centers) = out
    payload = (hists, sel, totals, words, block_nbits, oidx, odelta,
               ocount, lit_idx, lit_q, lit_count)
    rows = collectives.gather_to_aggregator(
        payload, mesh, axis, rank_shards(x)[0].devices().pop())
    om.add(om.GATHER_RANKS, ranks)
    for r in rows:
        live = _payload_live_bytes(r)
        om.add(om.GATHER_PAYLOAD_BYTES, live)
        om.add(om.GATHER_PAD_BYTES, sum(a.nbytes for a in r) - live)
    with ot.span("fused.d2h"):
        x_host, = to_host("encode", "fused.d2h", x)
    dev = [rank_shards(a) for a in (codes2, outl2, delta2, valid2)]
    comps = []
    for r, (coder, row) in enumerate(zip(coders, rows)):
        row = list(row)
        row[-1] = row[-1][0]                    # lit_count: one per rank
        codes_r, outl_r, delta_r, valid_r = (d[r] for d in dev)
        if int(row[-1]) > row[-3].shape[0]:
            with ot.span("fused.d2h"):
                delta_r, = to_host("encode", "fused.d2h", delta_r)
        comps.append(_bank_finish(
            x_host[r], float(ebs[r]), mode, coder, lay, tuple(row), None,
            (codes_r, outl_r, delta_r, valid_r)))
    return comps, x_host


def _payload_live_bytes(row) -> int:
    """Bytes of one rank's gathered payload that its record reads: the
    summaries and block bit counts whole, each chunk's words up to its
    payload (the kept prefix of :func:`_assemble_chunks`), the outlier
    and literal-candidate buffers up to their counts."""
    (hists, sel, totals, words, nbits, oidx, odelta, ocount, lit_idx,
     lit_q, lit_count) = row
    w32 = words.shape[1]
    kept = np.minimum(2 * ((totals.astype(np.int64) + 63) // 64 + 1), w32)
    n_out = np.minimum(ocount, oidx.shape[1]).astype(np.int64)
    n_lit = min(int(lit_count[0]), lit_idx.shape[0])
    return 4 * int(hists.size + sel.size + totals.size + kept.sum()
                   + nbits.size + 2 * n_out.sum() + ocount.size
                   + 2 * n_lit + lit_count.size)


def _spec_window(speculation) -> int:
    """Resolve the speculation knob: 'off' -> 1 (the sequential oracle
    loop), 'auto' -> 8 (then adapted per window, see `_next_window`),
    an int >= 1 -> that fixed window size."""
    if speculation == "off":
        return 1
    if speculation == "auto":
        return 8
    if isinstance(speculation, int) and not isinstance(speculation, bool) \
            and speculation >= 1:
        return int(speculation)
    raise ValueError(
        f"speculation must be 'off', 'auto' or an int >= 1, "
        f"got {speculation!r}")


# adaptive depth bounds ('auto' only): the floor keeps speculation from
# silently degrading into the sequential loop, the cap bounds how much
# speculative quantization one eb shift can discard
_SPEC_WINDOW_MIN = 2
_SPEC_WINDOW_MAX = 64


def _next_window(window: int, misses: int) -> int:
    """Adaptive speculation depth: a fully-hit window doubles the next
    one (the controller is sitting on its quantized update grid, so
    deeper speculation is free), any miss halves it (the eb is moving;
    keep the mispredicted work small). The depth NEVER changes the
    emitted bytes — every committed chunk's eb is replayed exactly —
    only how much speculative work a miss throws away. Exposed as the
    ceaz_speculation_window gauge."""
    if misses == 0:
        return min(window * 2, _SPEC_WINDOW_MAX)
    return max(window // 2, _SPEC_WINDOW_MIN)


@jax.jit
def _outlier_counts(outl3, valid3):
    """Exact per-chunk escape counts (the feedback replay needs them
    before pass 2 runs)."""
    return jnp.sum(outl3 & valid3, axis=(1, 2), dtype=jnp.int32)


def _chunk_total_bits(hist: np.ndarray, decision, n_outliers: int,
                      nblocks: int) -> int:
    """CompressedChunk.total_bits() computed from pass-1 summaries alone
    — the payload is exactly hist . lengths, so the eb feedback chain
    can be replayed BEFORE any chunk is actually encoded."""
    from ..core.ceaz import BLOCK_COUNT_BITS, CHUNK_HEADER_BITS, OUTLIER_BITS
    bits = int(np.dot(hist.astype(np.int64),
                      decision.codebook.lengths.astype(np.int64)))
    bits += CHUNK_HEADER_BITS + BLOCK_COUNT_BITS * nblocks
    bits += OUTLIER_BITS * n_outliers
    if decision.stored_codebook:
        bits += 5 * NUM_SYMBOLS
    return bits


def _window_pass1(seg2: np.ndarray, ebs, stats_on_device: bool):
    """Vmapped pass 1 over a window of full-size fixed-ratio chunks,
    each row an independent 1-D stream with its own (speculative) eb.

    Returns (p1s, ocounts, codes_all, valid_all): one _Pass1 per chunk,
    the exact per-chunk outlier counts the feedback replay needs, and
    the stacked (w, cv) device code/valid arrays pass 2 consumes. On
    the host-stats path the per-chunk _Pass1 records carry only numpy
    snapshot rows (no device fields): eager per-row device slicing is
    pure dispatch overhead there, and everything downstream reads the
    snapshots or the stacked arrays."""
    w, cv = seg2.shape
    work = jnp.asarray(seg2)
    ebs_j = jnp.asarray(ebs, jnp.float32)
    qp = jax.vmap(lambda wk, e: _quantize_pass(wk, e, 1, 1, cv))(work, ebs_j)
    codes3, outl3, delta3, valid3, q2 = qp
    ocounts = np.array(_outlier_counts(outl3, valid3))   # writable: repairs
    codes_all = codes3.reshape(w, cv)
    valid_all = valid3.reshape(w, cv)
    p1s: List[_Pass1] = []
    if stats_on_device:
        # each chunk has its own speculative bound and no extrema: the
        # capacity's floor
        k_lit = literal_capacity(cv)
        st = jax.vmap(lambda c, v, q, wk, e: _device_stats(
            c, v, q, wk, e, k_lit))(codes3, valid3, q2, work, ebs_j)
        hists = np.asarray(st[0])
        for j in range(w):
            p1s.append(_Pass1(codes3[j], outl3[j], delta3[j], valid3[j],
                              q2[j], hists[j], cv, 1, cv, True,
                              lit_idx=st[1][j], lit_q=st[2][j],
                              lit_count=st[3][j]))
    else:
        codes_host = np.asarray(codes3)
        outl_host = np.asarray(outl3)
        delta_host = np.asarray(delta3)
        q_host = np.asarray(q2)
        for j in range(w):
            p1s.append(_Pass1(None, None, None, None, None,
                              _host_hists(codes_host[j], cv), cv, 1,
                              cv, False, codes_host=codes_host[j],
                              outl_host=outl_host[j],
                              delta_host=delta_host[j], q_host=q_host[j]))
    return p1s, ocounts, codes_all, valid_all


def _encode_window(hists: Sequence[np.ndarray], codes_all, valid_all,
                   decisions, block_size: int, kernel_impl: str,
                   chunk_values: int):
    """One batched pass 2 over a window's chunks (stacked rows)."""
    return _encode_rows(np.concatenate(hists), codes_all, valid_all,
                        chunk_values, decisions, block_size, kernel_impl)


@functools.lru_cache(maxsize=None)
def _mega_window_fn(kernel_impl: str, w: int, chunk_values: int,
                    block_size: int, w32: int, cands: int,
                    k_literal: int, stats_on_device: bool):
    """One `ceaz_chunk` op call over a speculation window: each row is
    an independent 1-D stream (zero halo — exactly the per-chunk
    zero-pad the sequential fixed-ratio loop uses) at its own
    speculative eb. The packed words come back WITH the histograms, so
    a fully-hit window needs no second pass at all; only repaired rows
    rerun."""
    ceaz_op = dispatch.resolve("ceaz_chunk", kernel_impl)

    @jax.jit
    def run(seg2, ebs, bank_lengths, bank_cwords):
        valid2 = jnp.ones((w, chunk_values), bool)
        prev2 = jnp.zeros((w, 1), jnp.float32)
        (q2, codes2, outl2, delta2, _centers, hists, sel, totals, words,
         block_nbits) = ceaz_op(seg2, prev2, valid2, ebs, bank_lengths,
                                bank_cwords, block_size, w32, cands,
                                "lorenzo")
        ocounts = jnp.sum(outl2, axis=1, dtype=jnp.int32)
        if not stats_on_device:
            return (hists, sel, totals, words, block_nbits, ocounts,
                    codes2, outl2, delta2, q2, None, None, None)
        st = jax.vmap(lambda c, v, q, wk, e: _device_stats(
            c[None], v[None], q, wk, e, k_literal))(
            codes2, valid2, q2, seg2, ebs)
        return (hists, sel, totals, words, block_nbits, ocounts,
                codes2, outl2, delta2, q2, st[1], st[2], st[3])

    return run


def _mega_window(seg2: np.ndarray, ebs, bank, block_size: int,
                 kernel_impl: str, stats_on_device: bool):
    """Bank-mode window pass via the megakernel op.

    Returns (p1s, ocounts, hists, sel, totals, words, block_nbits) with
    the array results as writable numpy rows so the repair path can
    replace a mispredicted row in place. Provisioned at the bank's full
    bit-rate (no repack path needed): `_assemble_chunks` trims every
    row to its exact payload, so provisioning never changes bytes.
    """
    w, cv = seg2.shape
    w32 = _bank_w32(int(bank.lengths.max()), cv)
    cands = _cand_window(int(bank.lengths.min()))
    run = _mega_window_fn(kernel_impl, w, cv, block_size, w32, cands,
                          literal_capacity(cv), stats_on_device)
    with dispatch.measure("ceaz_chunk", kernel_impl):
        out = run(jnp.asarray(seg2, jnp.float32),
                  jnp.asarray(ebs, jnp.float32),
                  jnp.asarray(bank.lengths, jnp.int32),
                  jnp.asarray(bank.code_table(), jnp.uint32))
    (hists, sel, totals, words, nbits, ocounts, codes2, outl2, delta2,
     q2, lit_idx, lit_q, lit_count) = out
    # np.array (not asarray): the repair path overwrites rows in place
    hists_np = np.array(hists)
    p1s: List[_Pass1] = []
    if stats_on_device:
        for j in range(w):
            p1s.append(_Pass1(codes2[j][None], outl2[j][None],
                              delta2[j][None], jnp.ones((1, cv), bool),
                              q2[j], hists_np[j:j + 1], cv, 1, cv, True,
                              lit_idx=lit_idx[j], lit_q=lit_q[j],
                              lit_count=lit_count[j]))
    else:
        outl_host = np.asarray(outl2)
        delta_host = np.asarray(delta2)
        q_host = np.asarray(q2)
        for j in range(w):
            p1s.append(_Pass1(None, None, None, None, None,
                              hists_np[j:j + 1], cv, 1, cv, False,
                              outl_host=outl_host[j:j + 1],
                              delta_host=delta_host[j:j + 1],
                              q_host=q_host[j]))
    return (p1s, np.array(ocounts), hists_np, np.array(sel),
            np.array(totals).astype(np.int64), np.array(words),
            np.array(nbits))


def compress_fixed_ratio(x: np.ndarray, ctrl, coder: AdaptiveCoder,
                         chunk_values: int, block_size: int,
                         adaptive: bool = True, exact_build: bool = False,
                         stats_on_device: Optional[bool] = None,
                         kernel_impl: str = "auto",
                         speculation="auto"):
    """Fused fixed-ratio compression (1-D stream of chunks).

    The eb feedback loop is sequential across chunks (chunk i's bound
    depends on chunk i-1's achieved bit-rate), but the loop state can
    be replayed from pass-1 summaries alone: a chunk's total bits are
    exactly ``hist . lengths`` plus per-chunk overheads, all known
    before pass 2 runs. So the pipeline SPECULATES: it forecasts the
    next W-1 bounds with the controller's rate-law predictor, runs one
    vmapped pass 1 over the whole window, then replays the exact
    feedback chain on the host — every chunk whose forecast landed on
    the exact sequential eb (the controller's quantized update grid
    makes that the common case) keeps its speculative quantization; a
    mispredicted chunk is requantized ALONE at its exact bound, so only
    the misses re-encode and the rest of the window's speculative work
    survives. The whole window then runs one batched pass 2. The
    emitted stream is byte-identical to the sequential loop
    (``speculation='off'``) on EVERY input — a miss costs one extra
    single-chunk device pass, never different bytes.

    `speculation`: 'off' (sequential oracle), 'auto' (start at window
    8, then adapt: double after a fully-hit window, halve on any miss
    — see `_next_window`; the depth is visible as the
    ceaz_speculation_window gauge), or an explicit fixed window size
    >= 1. With a BankCoder the window runs through the `ceaz_chunk`
    megakernel op — packed payloads come back with the pass-1
    histograms, so a fully-hit window needs no second encode pass.
    """
    from ..core.ceaz import CEAZCompressed
    flat = x.reshape(-1)
    n = len(flat)
    if stats_on_device is None:
        stats_on_device = _default_stats_on_device()
    window = _spec_window(speculation)
    adaptive_window = speculation == "auto"
    use_mega = isinstance(coder, BankCoder)
    chunks, lit_idx_parts, lit_val_parts = [], [], []
    pos = 0                              # position in full-size chunks
    n_full = n // chunk_values
    while window > 1 and n_full - pos >= 2:
        w = min(window, n_full - pos)
        ebs = [float(ctrl.eb)]           # window head is always exact
        for _ in range(w - 1):
            ebs.append(ctrl.predict_next(ebs[-1]))
        seg2 = np.asarray(flat[pos * chunk_values:(pos + w) * chunk_values],
                          np.float32).reshape(w, chunk_values)
        with ot.span("fused.spec_window_pass1", window=w):
            if use_mega:
                (p1s, ocounts, m_hists, m_sel, m_totals, m_words,
                 m_nbits) = _mega_window(seg2, ebs, coder.bank,
                                         block_size, kernel_impl,
                                         stats_on_device)
            else:
                p1s, ocounts, codes_all, valid_all = _window_pass1(
                    seg2, ebs, stats_on_device)
        # replay the exact sequential feedback chain from the summaries;
        # a mispredicted chunk requantizes alone at its exact bound
        decisions, fed_bits, repaired = [], [], {}
        for j in range(w):
            if j > 0 and ebs[j] != float(ctrl.eb):
                ebs[j] = float(ctrl.eb)
                with ot.span("fused.spec_repair", chunk=pos + j):
                    if use_mega:
                        # one-row megakernel rerun at the exact bound
                        # replaces the row's packed payload in place
                        r = _mega_window(seg2[j:j + 1], [ebs[j]],
                                         coder.bank, block_size,
                                         kernel_impl, stats_on_device)
                        p1s[j] = r[0][0]
                        ocounts[j] = int(r[1][0])
                        m_hists[j] = r[2][0]
                        m_sel[j] = r[3][0]
                        m_totals[j] = r[4][0]
                        m_words[j] = r[5][0]
                        m_nbits[j] = r[6][0]
                        repaired[j] = True
                    else:
                        p1s[j] = _run_pass1(jnp.asarray(seg2[j]), ebs[j],
                                            1, chunk_values,
                                            stats_on_device)
                        # exact escape count from the (cached) outlier
                        # extraction
                        ocounts[j] = len(_outliers(p1s[j])[0][0])
                        repaired[j] = p1s[j].codes2
            d = _policy(p1s[j].hists, coder, adaptive, exact_build)[0]
            if use_mega:
                # the host bank replay must land on the same row the
                # device argmin picked (integer-exact statistic)
                assert d.bank_index == int(m_sel[j])
            nblocks = max(1, -(-chunk_values // block_size))
            bits = _chunk_total_bits(p1s[j].hists[0], d, int(ocounts[j]),
                                     nblocks)
            ctrl.feedback(bits / chunk_values)
            decisions.append(d)
            fed_bits.append(bits)
        # window head is exact by construction: w-1 chunks were
        # speculated, the repaired ones mispredicted
        om.add(om.SPEC_MISSES, len(repaired))
        om.add(om.SPEC_HITS, (w - 1) - len(repaired))
        if use_mega:
            # the packed payload came back with pass 1 (and repairs
            # replaced their rows above) — no second encode pass
            words_np, nbits_np, totals = m_words, m_nbits, m_totals
        else:
            if repaired:    # one batched row replacement, not per miss
                codes_all = codes_all.at[jnp.asarray(list(repaired))].set(
                    jnp.concatenate(list(repaired.values())))
            words_np, nbits_np, totals = _encode_window(
                [p.hists for p in p1s], codes_all, valid_all, decisions,
                block_size, kernel_impl, chunk_values)
        for j in range(w):
            ch = _assemble_chunks(p1s[j], words_np[j:j + 1],
                                  nbits_np[j:j + 1], totals[j:j + 1],
                                  _outliers(p1s[j]), ebs[j],
                                  [decisions[j]], block_size)[0]
            # the replayed feedback must mirror the emitted chunk exactly
            assert ch.total_bits() == fed_bits[j]
            s = (pos + j) * chunk_values
            li, lv = _literals(p1s[j], flat[s:s + chunk_values], ebs[j], 1,
                               (chunk_values,))
            lit_idx_parts.append(li + s)
            lit_val_parts.append(lv)
            chunks.append(ch)
        pos += w
        if adaptive_window:
            window = _next_window(window, len(repaired))
            om.set_gauge(om.SPEC_WINDOW, window)
    # sequential tail: remaining full chunks (speculation off, or one
    # full chunk left) plus the final partial chunk
    for s in range(pos * chunk_values, n, chunk_values):
        e = min(s + chunk_values, n)
        eb = float(ctrl.eb)
        seg = jnp.asarray(flat[s:e], jnp.float32)
        p1 = _run_pass1(seg, eb, 1, e - s, stats_on_device)
        decisions = _policy(p1.hists, coder, adaptive, exact_build)
        enc = _encode_all(p1, decisions, block_size, kernel_impl)
        ch = _assemble_chunks(p1, *enc, eb, decisions, block_size)[0]
        li, lv = _literals(p1, flat[s:e], eb, 1, (e - s,))
        lit_idx_parts.append(li + s)
        lit_val_parts.append(lv)
        chunks.append(ch)
        ctrl.feedback(ch.total_bits() / ch.n_values)
    return CEAZCompressed(shape=x.shape, dtype=str(x.dtype), ndim=1,
                          mode="fixed_ratio", chunks=chunks,
                          word_bits=x.dtype.itemsize * 8,
                          literal_idx=np.concatenate(lit_idx_parts)
                          .astype(np.int64),
                          literal_val=np.concatenate(lit_val_parts))


def _policy(hists: np.ndarray, coder: AdaptiveCoder, adaptive: bool,
            exact_build: bool):
    """Host chi policy over the per-chunk histogram summaries."""
    from ..core.codebook import AdaptiveDecision
    decisions = []
    for freqs in hists.astype(np.int64):
        if isinstance(coder, BankCoder) or adaptive:
            decisions.append(coder.step(freqs))
        else:
            cb = Codebook.from_freqs(freqs, exact=exact_build)
            decisions.append(AdaptiveDecision("rebuild", 0.0, cb, True))
    return decisions


# ---------------------------------------------------------------------------
# Shard-parallel batched compression (mesh-aware)
# ---------------------------------------------------------------------------

def batch_compress(shards: Sequence[np.ndarray], eb_rel: float,
                   chunk_values: int, block_size: int,
                   offline: Optional[Codebook] = None,
                   plan=None, mode: str = "rel",
                   stats_on_device: Optional[bool] = None,
                   tau0: Optional[float] = None,
                   tau1: Optional[float] = None,
                   adaptive: bool = True, exact_build: bool = False,
                   kernel_impl: str = "auto",
                   predictor: str = "lorenzo"):
    """Compress many same-shape, same-dtype shards through ONE pair of
    fused device passes, optionally sharded over the mesh's batch axes.

    Each shard keeps its own AdaptiveCoder stream (policy sequences match
    per-shard staged compression); the per-value work for all shards runs
    as a single stacked trace, which GSPMD splits across devices when
    `plan` carries a mesh — the paper's N independent pipelines realized
    over a device mesh instead of FPGA lanes. Float64 shards quantize
    through the f32 device pass (literal channel restores the f64
    bound); `predictor='none'` runs the batched value-direct pass with
    per-chunk device centres.
    """
    from ..core.ceaz import CEAZCompressed
    from ..core.codebook import default_offline_codebook
    if stats_on_device is None:
        stats_on_device = _default_stats_on_device()
    if offline is None:
        offline = default_offline_codebook()
    if len({s.shape for s in shards}) != 1:
        raise ValueError("batch_compress requires same-shape shards")
    if len({s.dtype for s in shards}) != 1:
        raise ValueError("batch_compress requires same-dtype shards")
    word_bits = shards[0].dtype.itemsize * 8
    stack_np = np.stack([np.asarray(s, np.float32) for s in shards])
    dp = 1
    if plan is not None and getattr(plan, "mesh", None) is not None:
        dp = int(np.prod([plan.axis_size(a) for a in plan.batch_axes]))
    if dp > 1 and len(shards) % dp == 0:
        stacked = jax.device_put(stack_np, plan.named(plan.batch))
    else:
        stacked = jnp.asarray(stack_np)
    nshards = stacked.shape[0]
    ndim = 1 if predictor == "none" else min(stacked.ndim - 1, 3)
    extrema = [(float(np.max(s)), float(np.min(s))) for s in shards]
    ebs = [eb_rel * core_dq.extrema_range(hi, lo) if mode == "rel"
           else eb_rel for hi, lo in extrema]

    # pass 1 vmapped over the shard axis (per-shard eb)
    n = int(stacked[0].size)
    chunk_values = max(1, min(chunk_values, n))
    n_chunks, _ = chunk_layout(n, chunk_values)
    ebs_j = jnp.asarray(ebs, jnp.float32)
    centers2 = None
    if predictor == "none":
        work = stacked.reshape(nshards, -1)
        q3, valid3 = jax.vmap(
            lambda w, e: _value_prequantize(w, e, n_chunks, chunk_values)
        )(work, ebs_j)
        center_fn = dispatch.resolve("dq_center", kernel_impl)
        with dispatch.measure("dq_center", kernel_impl):
            centers2 = jax.vmap(center_fn)(q3, valid3)
        codes3, outl3, delta3 = jax.vmap(_value_finalize)(q3, centers2,
                                                          valid3)
        q2 = q3.reshape(nshards, -1)[:, :n]
        centers_np = np.asarray(centers2).astype(np.int64)
    else:
        work = stacked.reshape((nshards,) + _work_shape(stacked.shape[1:]))
        qp = jax.vmap(lambda w, e: _quantize_pass(w, e, ndim, n_chunks,
                                                  chunk_values))(work, ebs_j)
        codes3, outl3, delta3, valid3, q2 = qp

    def _p1_extra(si):
        if predictor == "none":
            return dict(predictor="none", centers=centers_np[si])
        return {}

    p1s: List[_Pass1] = []
    if stats_on_device:
        # one traced pass for every shard: the largest shard's capacity
        k_lit = max(literal_capacity(n, eb, max(abs(hi), abs(lo)))
                    for eb, (hi, lo) in zip(ebs, extrema))
        st = jax.vmap(lambda c, v, q, w, e: _device_stats(
            c, v, q, w.reshape(-1), e, k_lit))(
            codes3, valid3, q2, work, ebs_j)
        hists = np.asarray(st[0])
        for si in range(nshards):
            p1s.append(_Pass1(codes3[si], outl3[si], delta3[si],
                              valid3[si], q2[si], hists[si], n, n_chunks,
                              chunk_values, True, lit_idx=st[1][si],
                              lit_q=st[2][si], lit_count=st[3][si],
                              **_p1_extra(si)))
    else:
        codes_host = np.asarray(codes3)
        outl_host = np.asarray(outl3)
        delta_host = np.asarray(delta3)
        q_host = np.asarray(q2)
        for si in range(nshards):
            p1s.append(_Pass1(codes3[si], outl3[si], delta3[si],
                              valid3[si], q2[si],
                              _host_hists(codes_host[si], n), n, n_chunks,
                              chunk_values, False,
                              codes_host=codes_host[si],
                              outl_host=outl_host[si],
                              delta_host=delta_host[si],
                              q_host=q_host[si], **_p1_extra(si)))

    # host policy per shard, then ONE batched pass-2 over shards*chunks
    from ..core.codebook import DEFAULT_TAU0, DEFAULT_TAU1
    all_dec = []
    for si in range(nshards):
        coder = AdaptiveCoder(
            offline, DEFAULT_TAU0 if tau0 is None else tau0,
            DEFAULT_TAU1 if tau1 is None else tau1, exact_build)
        all_dec.append(_policy(p1s[si].hists, coder, adaptive=adaptive,
                               exact_build=exact_build))
    flat2 = lambda a: a.reshape((nshards * n_chunks,) + a.shape[2:])
    words_np, nbits_np, totals = _encode_rows(
        np.concatenate([p.hists for p in p1s]), flat2(codes3),
        flat2(valid3), chunk_values,
        [d for ds in all_dec for d in ds], block_size, kernel_impl)

    outs = []
    for si, s in enumerate(shards):
        sl = slice(si * n_chunks, (si + 1) * n_chunks)
        chunks = _assemble_chunks(p1s[si], words_np[sl], nbits_np[sl],
                                  totals[sl], _outliers(p1s[si]), ebs[si],
                                  all_dec[si], block_size)
        x_flat = np.asarray(s).reshape(-1)
        lit_idx, lit_val = _literals(p1s[si], x_flat, ebs[si], ndim,
                                     _work_shape(stacked.shape[1:]))
        outs.append(CEAZCompressed(
            shape=s.shape, dtype=str(s.dtype), ndim=ndim, mode=mode,
            chunks=chunks, word_bits=word_bits, predictor=predictor,
            literal_idx=lit_idx, literal_val=lit_val))
    return outs


def _work_shape(shape) -> tuple:
    return tuple(shape) if len(shape) <= 3 else (-1,) + tuple(shape[-2:])
