"""Device-resident fused CEAZ decode pipeline (the read-side of Fig 4).

``runtime.fused`` keeps the whole compression pipeline on device; this
module is its symmetric inverse. The staged reference decompressor
(``core.ceaz.CEAZ.decompress``) walks chunks in a host python loop and
runs the canonical-Huffman table decode in numpy, one chunk at a time —
exactly the chunk-sequential host bounce cuSZ/FZ-GPU show the read path
cannot afford. Here the per-value stages run as ONE batched device
pass, the `ceaz_chunk_dec` dispatch op (kernels/megakernel):

  device  — over EVERY chunk in the batch (across arrays: the batch
            dimension is the union of all chunks of all arrays in the
            group): the canonical-Huffman table walk, each chunk's
            blocks in parallel lanes (the multi-pipeline FPGA decoder
            with (n_chunks x n_blocks) lanes instead of n_blocks), the
            outlier patch (code 0 escapes -> stored deltas) and the 1-D
            inverse dual-quant. Higher-rank abs/rel fields take their
            multi-axis cumsum in a follow-up jit (``_nd_cumsum``).
  host    — ONLY the final scale multiply (the staged reference computes
            it through float64, which jax does not carry by default) and
            the literal patch: one vectorized elementwise op each, at
            memory bandwidth. Everything bit-width-heavy (table walk,
            patch, prefix sums) never touches host numpy.

Bit-exactness contract: for float32 Lorenzo streams the output is
BIT-IDENTICAL to the staged reference in every mode (abs/rel/
fixed_ratio) — enforced by tests/test_fused_decode.py. The device walk
reproduces the staged decoder's integer state exactly (same tables, same
cursor arithmetic on the u32 reinterpretation of the u64 wire words);
the host multiply then replays the staged float64 formula on the exact
integer field.

Scope mirrors the fused encoder: float32 AND float64 streams, Lorenzo
and value-direct (predictor='none') prediction. Value-direct chunks add
their per-chunk centre code on device (no prefix sum); float64 streams
differ only in the host multiply's output dtype. The integer envelope
is the encoder's: reconstruction codes |q| <= ~2e9 fit the device's
int32 walk (the f32 quantize pass clips there) — a hypothetical stream
quantized outside that envelope (host-numpy encode at an absurdly tight
bound) is the one case the staged decoder must handle instead.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.huffman import DEFAULT_MAX_LEN, Codebook, replay_codebooks
from ..kernels import dispatch
from ..obs import metrics as om
from ..obs import trace as ot
from .fused import to_device, to_host

MAX_CODE_BITS = DEFAULT_MAX_LEN
_TBL = 1 << MAX_CODE_BITS

# The pass lives behind the kernel-dispatch layer (kernels/dispatch.py,
# op 'ceaz_chunk_dec'): 'jnp' composes the lockstep hufdec walk with
# the shared patch/inverse tail (kernels/megakernel/ref.py), 'pallas'
# is the one-program-per-chunk kernel (kernels/megakernel/
# decode_kernel.py). Both are bit-exact; CEAZConfig(kernel_impl=...)
# selects, 'auto' resolves per backend.


# ---------------------------------------------------------------------------
# Host assembly
# ---------------------------------------------------------------------------

def _u64_to_u32(w64: np.ndarray) -> np.ndarray:
    """Split the u64 wire words into the device's MSB-first u32 pairs."""
    out = np.empty(2 * len(w64), np.uint32)
    out[0::2] = (w64 >> np.uint64(32)).astype(np.uint32)
    out[1::2] = (w64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return out


def _bucket_pow2(n: int, floor: int = 1) -> int:
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


def _bucket_words(n: int) -> int:
    """u32 capacity buckets: powers of two up to a page, then pages."""
    if n <= 4096:
        return _bucket_pow2(n, 4)
    return -(-n // 4096) * 4096


def fused_decode_ok(c, offline: Codebook) -> bool:
    """Scope mirrors the fused encoder: float32/float64 streams with
    Lorenzo or value-direct prediction, codebooks packed at the
    standard length limit. Empty streams (no chunks) decode trivially
    on the staged path."""
    return (getattr(c, "predictor", "lorenzo") in ("lorenzo", "none")
            and np.dtype(c.dtype) in (np.float32, np.float64)
            and c.mode in ("abs", "rel", "fixed_ratio")
            and len(c.chunks) > 0
            and offline.max_len == MAX_CODE_BITS)


class _ChunkBatch:
    """Host staging of one group's chunks for the batched decode pass.

    ``run()`` runs the `ceaz_chunk_dec` decode megakernel: walk,
    rank-gather outlier patch and inverse dual-quant in ONE dispatched
    pass over the whole group; only the float64 scale multiply +
    literal patch remain (``_finish``).
    """

    def __init__(self, block_size: int, kernel_impl: str = "auto"):
        self.block_size = block_size
        self.kernel_impl = kernel_impl
        self.words: List[np.ndarray] = []          # u32 per chunk
        self.nbits: List[np.ndarray] = []
        self.counts: List[int] = []
        self.books: List[Codebook] = []
        self.spans: List[Tuple[int, int]] = []     # comp -> row range
        # per-row megakernel metadata (see kernels/megakernel/ref.py):
        # outlier deltas (ascending position order), value-direct centre
        # base, Lorenzo-row flag, carry-segment head row
        self.odelta: List[np.ndarray] = []
        self.base: List[int] = []
        self.islor: List[int] = []
        self.seg0: List[int] = []

    def add_comp(self, c, offline: Codebook, bank=None):
        row0 = len(self.counts)
        value = getattr(c, "predictor", "lorenzo") == "none"
        # one flat Lorenzo chain across the comp's rows (the encoder's
        # single whole-array pass) only when the work shape IS flat;
        # higher-rank fields decode per-row deltas here and run the
        # multi-axis cumsum in _mega_q
        chained = (not value and c.mode in ("abs", "rel")
                   and len(c.shape) == 1)
        lor1d = not value and (c.mode == "fixed_ratio" or chained)
        for j, (ch, book) in enumerate(
                zip(c.chunks,
                    replay_codebooks(c.chunks, offline, bank=bank))):
            self.words.append(_u64_to_u32(ch.words))
            self.nbits.append(np.asarray(ch.block_nbits, np.int64))
            self.counts.append(int(ch.n_values))
            self.books.append(book)
            self.odelta.append(ch.outlier_delta)
            self.base.append(int(ch.center) if value else 0)
            self.islor.append(1 if lor1d else 0)
            self.seg0.append(row0 if chained else row0 + j)
        self.spans.append((row0, len(self.counts)))

    def _stage(self):
        """Pad the staged chunks to capacity buckets, stack the unique
        decode tables and pad the megakernel's per-row metadata."""
        C = len(self.counts)
        c_cap = _bucket_pow2(C)
        nb_cap = _bucket_pow2(max(len(b) for b in self.nbits))
        w_need = max(len(w) for w in self.words) + 2
        w_cap = _bucket_words(w_need)
        words2 = np.zeros((c_cap, w_cap), np.uint32)
        nbits2 = np.zeros((c_cap, nb_cap), np.int32)
        counts = np.zeros(c_cap, np.int32)
        for i, (w, nb) in enumerate(zip(self.words, self.nbits)):
            words2[i, :len(w)] = w
            nbits2[i, :len(nb)] = nb
            counts[i] = self.counts[i]
        # unique codebooks -> stacked decode tables + per-chunk row index
        uniq: Dict[str, int] = {}
        tables_sym, tables_len = [], []
        cb_idx = np.zeros(c_cap, np.int32)
        for i, book in enumerate(self.books):
            k = uniq.get(book.id)
            if k is None:
                k = uniq[book.id] = len(tables_sym)
                sym, ln = book.tables()
                tables_sym.append(sym)
                tables_len.append(ln)
            cb_idx[i] = k
        k_cap = _bucket_pow2(len(tables_sym))
        while len(tables_sym) < k_cap:
            tables_sym.append(np.zeros(_TBL, np.uint16))
            tables_len.append(np.zeros(_TBL, np.uint8))
        k_out = _bucket_pow2(max(1, max(len(d) for d in self.odelta)))
        odelta2 = np.zeros((c_cap, k_out), np.int32)
        for i, d in enumerate(self.odelta):
            odelta2[i, :len(d)] = d.astype(np.int32)
        base = np.zeros(c_cap, np.int32)
        base[:C] = np.asarray(self.base, np.int64).astype(np.int32)
        islor = np.zeros(c_cap, np.int32)
        islor[:C] = self.islor
        seg0 = np.arange(c_cap, dtype=np.int32)    # padding: own segment
        seg0[:C] = self.seg0
        return (words2, nbits2, counts, np.concatenate(tables_sym),
                np.concatenate(tables_len), cb_idx, odelta2, base, seg0,
                islor)

    def run(self):
        """-> device q (C_cap, NB_cap*block_size) int32 (padded): the
        `ceaz_chunk_dec` megakernel over the whole group."""
        with ot.span("fused_decode.stage"):
            staged = self._stage()
        with ot.span("fused_decode.h2d"):
            dev = to_device("decode", "fused_decode.h2d",
                            *(jnp.asarray(a) for a in staged))
        fn = dispatch.resolve("ceaz_chunk_dec", self.kernel_impl)
        with dispatch.measure("ceaz_chunk_dec", self.kernel_impl):
            out = fn(*dev, self.block_size)
        # values the pass was sized for (C_cap x NB_cap blocks) and the
        # real values it carried
        c_cap, nb_cap = staged[1].shape
        om.add(om.PASS_VALUES, c_cap * nb_cap * self.block_size,
               side="decode", op="ceaz_chunk_dec")
        om.add(om.PASS_LIVE_VALUES, sum(self.counts), side="decode",
               op="ceaz_chunk_dec")
        return out


def _finish_host(c, q: np.ndarray, eb_per_value: np.ndarray) -> np.ndarray:
    """The staged float64 formula + literal patch — the ONLY host math."""
    out_dtype = np.dtype(c.dtype)
    rec = (q.astype(np.float64) * eb_per_value).astype(out_dtype)
    rec[c.literal_idx] = c.literal_val.astype(out_dtype)
    return rec.reshape(c.shape)


def _work_shape(c) -> tuple:
    if len(c.shape) <= 3:
        return tuple(int(s) for s in c.shape)
    tail = tuple(int(s) for s in c.shape[-2:])
    lead = int(np.prod(c.shape[:-2]))
    return (lead,) + tail


def _per_chunk(c) -> bool:
    """Whether the array's chunks are independent rows with their own eb
    (value-direct and fixed_ratio) rather than one flat field."""
    return (getattr(c, "predictor", "lorenzo") == "none"
            or c.mode == "fixed_ratio")


def _finish(c, q: np.ndarray) -> np.ndarray:
    """Host finish for one array from its pulled integer field: per-chunk
    rows (value-direct, fixed_ratio) or the flat field (a 1-D chain may
    still be in chunk rows)."""
    if _per_chunk(c):
        parts = [q[i, :ch.n_values] for i, ch in enumerate(c.chunks)]
        ebs = np.repeat([2.0 * ch.eb for ch in c.chunks],
                        [ch.n_values for ch in c.chunks])
        return _finish_host(c, np.concatenate(parts), ebs)
    return _finish_host(c, q.reshape(-1)[:int(c.n_values)],
                        np.float64(2.0 * c.chunks[0].eb))


@functools.partial(jax.jit, static_argnames=("ndim", "n", "work_shape"))
def _nd_cumsum(delta2, ndim, n, work_shape):
    """Multi-axis inverse-Lorenzo for megakernel delta-passthrough rows
    (higher-rank abs/rel fields): the cumsum crosses chunk boundaries
    exactly as the encoder's single whole-array quantization pass did;
    the outlier patch is already applied in-kernel."""
    q = delta2.reshape(-1)[:n].reshape(work_shape)
    for ax in range(ndim):
        q = jnp.cumsum(q, axis=ax, dtype=jnp.int32)
    return q.reshape(-1)


def _mega_q(q_rows, c):
    """The device rest for one array: its q rows
    (outliers patched and 1-D inverses applied in-kernel), cut to the
    array's chunk width; higher-rank abs/rel rows arrive as deltas and
    take the multi-axis cumsum here."""
    cv = int(c.chunks[0].n_values)
    rows = q_rows[:, :cv]
    if _per_chunk(c) or len(c.shape) == 1:
        # per-chunk rows are final q; a flat Lorenzo chain's segment
        # carry already crossed the chunk boundaries in the kernel
        return rows
    return _nd_cumsum(rows, c.ndim, int(c.n_values), _work_shape(c))


def decompress_batch(comps: Sequence, block_size: int,
                     offline: Codebook,
                     kernel_impl: str = "auto",
                     bank=None) -> List[np.ndarray]:
    """Fused decode of a group of CEAZCompressed streams.

    All chunks of all arrays share ONE batched device pass, the
    `ceaz_chunk_dec` decode megakernel (walk + outlier patch + inverse
    dual-quant in one kernel residency). `kernel_impl` selects the pass
    implementation through the dispatch registry. Bank-mode chunks
    resolve their codebooks through `bank` / the process bank registry
    (see ``core.huffman.replay_codebooks``). Callers must pre-filter
    eligibility with ``fused_decode_ok`` — the ``CEAZ.decompress_batch``
    facade does. The output is bit-identical to the staged decoder on
    everything ``fused_decode_ok`` admits (tests/test_full_grid.py).
    """
    batch = _ChunkBatch(block_size, kernel_impl)
    with ot.span("fused_decode.stage"):
        for c in comps:
            batch.add_comp(c, offline, bank=bank)
    if not batch.counts:
        return []
    q_all = batch.run()
    with ot.span("fused_decode.device_wait"):
        qs = jax.block_until_ready(
            [_mega_q(q_all[r0:r1], c)
             for c, (r0, r1) in zip(comps, batch.spans)])
    with ot.span("fused_decode.d2h"):
        qs = to_host("decode", "fused_decode.d2h", *qs)
    with ot.span("fused_decode.finish"):
        return [_finish(c, q) for c, q in zip(comps, qs)]
