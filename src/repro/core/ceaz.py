"""CEAZ compressor facade: error-bounded + fixed-ratio streaming modes.

Mirrors the engine of CEAZ Fig 4:

  top path    — dual-quantization (N independent "pipelines" = Pallas grid
                blocks / vectorized lanes) producing quant-code symbols;
  middle path — symbols encoded immediately with the CURRENT codewords
                (offline at stream start), packed into per-block bitstreams;
  bottom path — per-chunk histogram -> chi policy decides keep / rebuild /
                offline; in fixed-ratio mode the achieved bit-rate feeds the
                error-bound controller for the next chunk.

Two modes:
  * 'abs' / 'rel' (error-bounded): one eb for the whole array, native-rank
    Lorenzo prediction (best CR).
  * 'fixed_ratio': the array is treated as a 1-D stream of chunks (exactly
    what a NIC sees); eb adapts per chunk so the payload tracks the target
    bit-rate => consistent throughput / static buffer sizes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from . import dualquant as dq
from ..obs import metrics as om
from ..obs import trace as ot
from .codebook import (DEFAULT_BANK_DRIFT_TOL, DEFAULT_TAU0, DEFAULT_TAU1,
                       AdaptiveCoder, BankCoder, CodebookBank,
                       min_update_bytes, sigma_of)
from .huffman import NUM_SYMBOLS, Codebook, encode, decode, entropy_bits
from .metrics import compression_ratio
from .ratecontrol import FixedRatioController, bitrate_from_ratio

CHUNK_HEADER_BITS = 128
BLOCK_COUNT_BITS = 32
OUTLIER_BITS = 64          # 32-bit position + 32-bit delta


@dataclasses.dataclass
class CompressedChunk:
    words: np.ndarray            # uint64 bitstream
    block_nbits: np.ndarray      # int64 per block
    n_values: int
    eb: float
    action: str                  # which codebook path was taken
    chi: float
    codebook_lengths: Optional[np.ndarray]   # shipped only when rebuilt
    codebook_id: str
    outlier_idx: np.ndarray      # chunk-local positions (int64)
    outlier_delta: np.ndarray    # int32 deltas
    center: int = 0              # value-direct mode: per-chunk centre code
    # bank mode (action == 'bank'): which book of which codebook bank
    # encoded this chunk; decode resolves the book from the bank instead
    # of shipped lengths. Defaults keep pre-bank pickles deserializing
    # (decoders read these through getattr).
    bank_ref: str = ""
    bank_index: int = -1

    def payload_bits(self) -> int:
        return int(self.block_nbits.sum())

    def total_bits(self) -> int:
        bits = self.payload_bits()
        bits += CHUNK_HEADER_BITS
        bits += BLOCK_COUNT_BITS * len(self.block_nbits)
        bits += OUTLIER_BITS * len(self.outlier_idx)
        if self.codebook_lengths is not None:
            bits += 5 * NUM_SYMBOLS
        return bits


@dataclasses.dataclass
class CEAZCompressed:
    shape: tuple
    dtype: str
    ndim: int                    # Lorenzo rank used
    mode: str
    chunks: List[CompressedChunk]
    word_bits: int = 32
    predictor: str = "lorenzo"   # 'lorenzo' | 'none' (value-direct)
    # raw-literal channel: the rare points (~1e-5) where NO f32-rounded
    # reconstruction level lies within eb (x halfway between two levels,
    # both rounded outward). Patched after reconstruction; does not affect
    # the integer prediction chain. SZ stores unpredictable points raw for
    # the same reason.
    literal_idx: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    literal_val: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.float32))

    def total_bits(self) -> int:
        return (sum(c.total_bits() for c in self.chunks)
                + OUTLIER_BITS * len(self.literal_idx))

    @property
    def n_values(self) -> int:
        return int(np.prod(self.shape))

    def ratio(self) -> float:
        return compression_ratio(self.n_values * self.word_bits,
                                 self.total_bits())

    def bitrate(self) -> float:
        return self.total_bits() / max(self.n_values, 1)

    def nbytes(self) -> int:
        return (self.total_bits() + 7) // 8


@dataclasses.dataclass
class CEAZConfig:
    """Compression policy for the :class:`CEAZ` facade.

    The two switches that matter most in practice:

    * ``use_fused`` — route eligible work through the device-resident
      fused pipeline (``runtime/fused.py`` / ``runtime/fused_decode.py``)
      instead of the host-staged reference. Both paths are bit-identical
      for the streams the fused path covers (float32 + Lorenzo).
    * ``kernel_impl`` — which implementation of the fused pipeline's two
      inner loops (encode gather-pack, decode table walk) to resolve
      from the kernel-dispatch registry (``kernels/dispatch.py``).

    See ``docs/ARCHITECTURE.md`` for the full dtype x predictor x mode
    fallback matrix.
    """
    mode: str = "rel"                 # 'abs' | 'rel' | 'fixed_ratio'
    eb: float = 1e-4                  # absolute or range-relative bound
    target_ratio: float = 10.0        # fixed-ratio mode
    chunk_bytes: int = 1 << 25        # paper Fig 11 optimum: 32 MB
    block_size: int = 4096            # bitstream block (parallel decode unit)
    tau0: float = DEFAULT_TAU0
    tau1: float = DEFAULT_TAU1
    exact_build: bool = False         # True => oracle Huffman (non-FPGA path)
    adaptive: bool = True             # False => always rebuild ("online" bars)
    backend: str = "numpy"            # 'numpy' | 'jax' | 'pallas'
    predictor: str = "lorenzo"        # 'lorenzo' | 'none' | 'auto'
    # 'none' quantizes values directly (noise-like data: weights/moments);
    # 'auto' probes a sample chunk and picks the lower-entropy predictor
    # Device-resident fused pipeline (runtime/fused.py): per-value work
    # (dual-quant -> histogram -> Huffman -> bit-pack) runs as jitted
    # batched device passes; only histograms and the final payload cross
    # the host boundary. Covers the whole dtype x predictor x mode
    # matrix (float32/float64, lorenzo/none, abs/rel/fixed_ratio); the
    # staged path below remains the bit-exactness reference
    # (tests/test_fused.py, tests/test_full_grid.py).
    use_fused: bool = False
    # Fixed-ratio speculation window (runtime/fused.py): how many chunks
    # each fused device pass quantizes against rate-law-predicted error
    # bounds while the exact eb feedback chain is replayed on the host.
    # 'auto' (window 8), an explicit int >= 1, or 'off' to run the
    # sequential chunk loop — the byte-identical oracle the speculative
    # path is tested against. Output bytes NEVER depend on this knob;
    # a misprediction costs wasted device work, not different bits.
    speculation: int | str = "auto"
    # Inner-loop implementation for the fused pipeline's two hot loops,
    # resolved through kernels/dispatch.py: 'jnp' (XLA-compiled
    # jax.numpy), 'pallas' (explicit kernels; interpreted off-TPU) or
    # 'auto' (the per-backend table in kernels/dispatch.py: jnp on every
    # backend today). An unknown name raises ValueError at first
    # compress/decompress.
    kernel_impl: str = "auto"
    # Decode-side megakernel (kernels/megakernel/decode_kernel.py):
    # 'auto'/'mega' run eligible fused decodes through `ceaz_chunk_dec`
    # (Huffman walk + outlier patch + inverse dual-quant as ONE
    # dispatched pass per group); 'split' forces the three-stage PR 3
    # path (hufdec walk, then per-array scatter + inverse jits). Both
    # are bit-identical (tests/test_full_grid.py); 'split' exists as
    # the differential fence's second oracle and an escape hatch. An
    # unknown name raises ValueError at first decompress.
    decode_megakernel: str = "auto"
    # Codebook policy (docs/CODEBOOK_BANK.md): 'exact' keeps the
    # chi-driven adaptive coder (host tree builds between the fused
    # passes); 'bank' selects per chunk from an offline CodebookBank —
    # on the fused abs/rel path quantize -> select -> encode -> pack run
    # as ONE traced pass with no host work between quantize and pack.
    # 'auto' means 'bank' iff a bank was passed to the facade. An
    # unknown name raises ValueError at first compress.
    codebook: str = "exact"
    # Bank mode's safety valve: after a bank compress, if the aggregate
    # achieved/ideal bits drifted past this bound the whole array is
    # recompressed on the exact path (byte-identical to
    # codebook='exact'). The check replays from histogram summaries —
    # no second quantization unless it actually trips.
    bank_drift_tol: float = DEFAULT_BANK_DRIFT_TOL
    # Observability (docs/OBSERVABILITY.md): a path here turns on the
    # process span tracer at facade construction and saves a Chrome
    # trace_event JSON there at exit — same effect as CEAZ_TRACE=path.
    # Pipeline counters (repro.obs.metrics) are always on; tracing is
    # the only opt-in.
    trace: Optional[str] = None


class CEAZ:
    """The compressor facade: policy + eligibility routing.

    All compression/decompression enters through this class; the facade
    decides per array/stream whether the device-resident fused pipeline
    or the host-staged reference runs (see the fallback matrix in
    ``docs/ARCHITECTURE.md``) — callers never pre-split their inputs.

    Construct from a :class:`CEAZConfig` (keyword overrides are applied
    with ``dataclasses.replace``), optionally with a shared offline
    :class:`~repro.core.huffman.Codebook` (the adaptive policy's reset
    target; a default is built when omitted):

        comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True))
        comp = CEAZ(mode="abs", eb=1e-3)          # kwargs-only form
    """

    def __init__(self, config: CEAZConfig | None = None,
                 offline_codebook: Codebook | None = None,
                 bank: CodebookBank | None = None, **kw):
        if config is None:
            config = CEAZConfig(**kw)
        elif kw:
            config = dataclasses.replace(config, **kw)
        self.cfg = config
        if config.trace:
            ot.enable(config.trace)
        if offline_codebook is None:
            from .codebook import default_offline_codebook
            offline_codebook = default_offline_codebook()
        self.offline = offline_codebook
        if bank is None and config.codebook == "bank":
            from .codebook import default_codebook_bank
            bank = default_codebook_bank()
        self.bank = bank
        if self.bank is not None:
            from .codebook import register_bank
            register_bank(self.bank)   # decode-side bank_ref resolution

    # -- helpers -------------------------------------------------------------
    def _abs_eb(self, x: np.ndarray) -> float:
        return self._bound(x)[0]

    def _bound(self, x: np.ndarray) -> Tuple[float, Optional[float]]:
        """(absolute bound, max |x|) of `x`: in rel mode both come from
        the one reduction of its max and min; in abs mode max |x| is
        None (the fused route reduces it where it needs it)."""
        if self.cfg.mode == "abs":
            return self.cfg.eb, None
        hi, lo = float(np.max(x)), float(np.min(x))
        return (self.cfg.eb * dq.extrema_range(hi, lo),
                max(abs(hi), abs(lo)))

    def _dual_quantize(self, x: np.ndarray, eb: float, ndim: int):
        if self.cfg.backend == "pallas":
            from ..kernels.dualquant import ops as dqops
            import jax.numpy as jnp
            codes, outlier, delta = dqops.dual_quantize(
                jnp.asarray(x, jnp.float32), eb, ndim)
            return (np.asarray(codes), np.asarray(outlier), np.asarray(delta))
        if self.cfg.backend == "jax":
            import jax.numpy as jnp
            codes, outlier, delta = dq.dual_quantize(
                jnp.asarray(x, jnp.float32), eb, ndim)
            return (np.asarray(codes), np.asarray(outlier), np.asarray(delta))
        return dq.np_dual_quantize(x, eb, ndim)

    def _encode_chunk(self, codes_flat: np.ndarray, delta_flat: np.ndarray,
                      outlier_flat: np.ndarray, eb: float,
                      coder: AdaptiveCoder) -> CompressedChunk:
        freqs = np.bincount(codes_flat, minlength=NUM_SYMBOLS)
        if isinstance(coder, BankCoder) or self.cfg.adaptive:
            decision = coder.step(freqs)
        else:
            cb = Codebook.from_freqs(freqs, exact=self.cfg.exact_build)
            from .codebook import AdaptiveDecision
            decision = AdaptiveDecision("rebuild", 0.0, cb, True)
        words, block_nbits, _ = encode(codes_flat, decision.codebook,
                                       self.cfg.block_size)
        oidx = np.flatnonzero(outlier_flat)
        return CompressedChunk(
            words=words, block_nbits=block_nbits, n_values=len(codes_flat),
            eb=eb, action=decision.action, chi=decision.chi,
            codebook_lengths=(decision.codebook.lengths.copy()
                              if decision.stored_codebook else None),
            codebook_id=decision.codebook.id,
            outlier_idx=oidx.astype(np.int64),
            outlier_delta=delta_flat[oidx].astype(np.int32),
            bank_ref=decision.bank_ref, bank_index=decision.bank_index)

    # -- public API ------------------------------------------------------------
    def _pick_predictor(self, x: np.ndarray, eb: float) -> str:
        if self.cfg.predictor != "auto":
            return self.cfg.predictor
        from .huffman import entropy_bits as H
        sample = x.reshape(-1)[:1 << 16]
        c_l, o_l, _ = dq.np_dual_quantize(sample, eb, 1)
        c_v, o_v, _, _ = dq.np_value_quantize(sample, eb)
        cost_l = H(np.bincount(c_l, minlength=1024)) + 64 * o_l.mean()
        cost_v = H(np.bincount(c_v, minlength=1024)) + 64 * o_v.mean()
        return "lorenzo" if cost_l <= cost_v else "none"

    def compress(self, x: np.ndarray) -> CEAZCompressed:
        """Compress one array under this facade's policy.

        Args:
          x: float32 or float64 array, any shape (Lorenzo prediction
            uses up to rank 3; higher ranks fold leading axes). Empty
            arrays compress to a zero-chunk stream.

        Returns a :class:`CEAZCompressed` carrying the packed chunk
        payloads, the outlier/literal escape channels and everything a
        decoder needs except the block grain (``cfg.block_size`` —
        recorded in stream footers by the I/O layer).

        Routing: with ``cfg.use_fused``, every dtype x predictor x mode
        combination runs the fused device pipeline (float64 and
        value-direct included); ``use_fused=False`` keeps the
        host-staged reference. Output bits do not depend on the path
        taken. ``cfg.codebook='bank'`` swaps the chi policy for
        per-chunk bank selection (single-pass on the fused abs/rel
        path); when the achieved/ideal drift exceeds
        ``cfg.bank_drift_tol`` the array transparently recompresses on
        the exact path — byte-identical to ``codebook='exact'``.

        Raises:
          TypeError: non-float dtype.
          ValueError: unknown ``cfg.mode``, ``cfg.codebook`` or
            ``cfg.kernel_impl``.
        """
        x = np.asarray(x)
        if x.dtype not in (np.float32, np.float64):
            raise TypeError(f"CEAZ compresses float data, got {x.dtype}")
        if self.cfg.mode not in ("abs", "rel", "fixed_ratio"):
            raise ValueError(self.cfg.mode)
        word_bits = x.dtype.itemsize * 8
        if x.size == 0:
            return CEAZCompressed(
                shape=x.shape, dtype=str(x.dtype), ndim=1,
                mode=self.cfg.mode, chunks=[], word_bits=word_bits,
                predictor="none" if self.cfg.predictor == "none"
                else "lorenzo")
        fused_ok = self.cfg.use_fused
        with ot.span("ceaz.compress", shape=list(x.shape),
                     dtype=str(x.dtype), mode=self.cfg.mode):
            if not self._bank_mode():
                return self._note_compressed(
                    x, self._compress_routed(x, word_bits, fused_ok,
                                             self._coder()))
            coder = BankCoder(self.bank)
            c = self._compress_routed(x, word_bits, fused_ok, coder)
            return self._drift_checked(x, c, coder, word_bits, fused_ok)

    def _drift_checked(self, x: np.ndarray, c: CEAZCompressed, coder,
                       word_bits: int, fused_ok: bool) -> CEAZCompressed:
        """A bank compress `c` of `x`, or, where its coder drifted past
        ``cfg.bank_drift_tol`` (out-of-distribution input), the exact
        two-pass path's compress of the whole array (drift is replayed on
        the host from the histogram summaries the bank pass produced)."""
        om.set_gauge(om.BANK_DRIFT, coder.drift())
        if coder.drift() > self.cfg.bank_drift_tol:
            om.add(om.BANK_FALLBACKS)
            with ot.span("ceaz.bank_exact_fallback", drift=coder.drift()):
                c = self._compress_routed(x, word_bits, fused_ok,
                                          self._coder())
        return self._note_compressed(x, c)

    @staticmethod
    def _note_compressed(x: np.ndarray, c: CEAZCompressed) -> CEAZCompressed:
        """The one choke point every finished encode flows through:
        bumps the process-wide chunk/byte counters (repro.obs.metrics)."""
        om.add(om.CHUNKS, len(c.chunks))
        om.add(om.RAW_BYTES, int(x.nbytes))
        om.add(om.STORED_BYTES, c.nbytes())
        return c

    def _compress_routed(self, x: np.ndarray, word_bits: int,
                         use_fused: bool, coder) -> CEAZCompressed:
        """mode/predictor routing for one array, under a given coder."""
        if self.cfg.mode in ("abs", "rel"):
            with ot.span("ceaz.policy"):
                eb, amax = self._bound(x)
                pred = self._pick_predictor(x, eb)
            if use_fused:
                return self._compress_eb_fused(x, pred, coder=coder, eb=eb,
                                               amax=amax)
            if pred == "none":
                return self._compress_eb_direct(x, word_bits, coder=coder)
            return self._compress_eb(x, word_bits, coder=coder)
        return self._compress_fixed_ratio(x, word_bits, use_fused=use_fused,
                                          coder=coder)

    def compress_batch(self, shards, plan=None) -> List[CEAZCompressed]:
        """Compress a sequence of shards under this facade's policy.

        Args:
          shards: sequence of arrays. With ``cfg.use_fused``,
            error-bounded shards are grouped by (shape, dtype, resolved
            predictor) and every group of two or more runs as ONE
            batched fused device pass — float64 and value-direct
            groups included. Everything left over (ragged shapes,
            singleton groups, fixed-ratio mode, ``use_fused`` off)
            takes per-shard :meth:`compress`, which still routes
            through the fused pipeline when enabled — nothing is split
            out to per-array staged calls.
            A rank-sharded ``jax.Array`` (see :meth:`rank_sharded`)
            in bank mode is compressed where it lives instead: each
            device runs the bank pass on its own brick, at the bound of
            its own brick's range, and the packed payloads are gathered
            to the first rank's device (``fused.compress_bank_sharded``);
            a rank whose coder drifts recompresses alone on the exact
            path. Its rows compress as they would one by one.
          plan: optional ``ShardingPlan``; when it carries a mesh the
            batched exact-codebook pass is GSPMD-sharded over its batch
            axes. Bank mode compresses shard by shard and ignores it.

        Returns one :class:`CEAZCompressed` per shard, in order; each
        shard keeps its own adaptive-coder stream, so batching never
        changes the bytes. Raises as :meth:`compress`.
        """
        if self.rank_sharded(shards):
            return self._compress_rank_sharded(shards)
        shards = [np.asarray(s) for s in shards]
        out: List[Optional[CEAZCompressed]] = [None] * len(shards)
        preds: dict = {}               # probe once; leftovers reuse it
        if self.cfg.use_fused and self.cfg.mode in ("abs", "rel") \
                and not self._bank_mode():
            # bank mode routes per shard through compress() below: the
            # drift-fallback decision is per array, so the grouped pass
            # (shared trace, per-shard coders) does not apply
            groups: dict = {}
            for i, s in enumerate(shards):
                if s.dtype not in (np.float32, np.float64) or s.size == 0:
                    continue        # compress() raises/handles below
                preds[i] = self._pick_predictor(s, self._abs_eb(s))
                groups.setdefault((s.shape, s.dtype, preds[i]),
                                  []).append(i)
            from ..runtime import fused
            for (_, dtype, pred), idxs in groups.items():
                if len(idxs) < 2:
                    continue        # per-shard fused compress below
                with ot.span("ceaz.batch_fused_pass", n=len(idxs),
                             predictor=pred):
                        outs = fused.batch_compress(
                        [shards[i] for i in idxs], self.cfg.eb,
                        self._chunk_values(dtype.itemsize * 8),
                        self.cfg.block_size, offline=self.offline,
                        plan=plan, mode=self.cfg.mode, tau0=self.cfg.tau0,
                        tau1=self.cfg.tau1, adaptive=self.cfg.adaptive,
                        exact_build=self.cfg.exact_build,
                        kernel_impl=self.cfg.kernel_impl, predictor=pred)
                for i, c in zip(idxs, outs):
                    out[i] = c
        # counters: shards routed through compress() below count there;
        # batched / per-shard-fused results count here
        return [self._note_compressed(s, c) if c is not None
                else (self._note_compressed(
                          s, self._compress_eb_fused(s, preds[i]))
                      if i in preds else self.compress(s))
                for i, (c, s) in enumerate(zip(out, shards))]

    def rank_sharded(self, x) -> bool:
        """Whether :meth:`compress_batch` compresses `x` on its devices:
        a float32 ``jax.Array`` whose leading axis is split one row (a
        rank's brick) per device along one mesh axis, under fused
        error-bounded bank mode with the Lorenzo predictor."""
        if not (self.cfg.use_fused and self.cfg.mode in ("abs", "rel")
                and self.cfg.predictor == "lorenzo"
                and getattr(x, "dtype", None) == np.float32
                and hasattr(x, "addressable_shards") and self._bank_mode()):
            return False
        from ..runtime import fused
        return fused.rank_mesh_axis(x) is not None

    def _compress_rank_sharded(self, x) -> List[CEAZCompressed]:
        from ..runtime import fused
        ranks = x.shape[0]
        with ot.span("ceaz.compress", shape=list(x.shape), dtype="float32",
                     mode=self.cfg.mode, ranks=ranks):
            with ot.span("ceaz.policy"):
                extrema = fused.rank_extrema(x)
                ebs = [self.cfg.eb if self.cfg.mode == "abs"
                       else self.cfg.eb * dq.extrema_range(hi, lo)
                       for hi, lo in extrema]
            coders = [BankCoder(self.bank) for _ in range(ranks)]
            comps, bricks = fused.compress_bank_sharded(
                x, ebs, self.cfg.mode, coders, self._chunk_values(32),
                self.cfg.block_size, kernel_impl=self.cfg.kernel_impl,
                amaxes=[max(abs(hi), abs(lo)) for hi, lo in extrema])
            return [self._drift_checked(b, c, coder, 32, True)
                    for b, c, coder in zip(bricks, comps, coders)]

    def _coder(self) -> AdaptiveCoder:
        return AdaptiveCoder(self.offline, self.cfg.tau0, self.cfg.tau1,
                             self.cfg.exact_build)

    def _bank_mode(self) -> bool:
        """Resolve cfg.codebook: 'bank' always, 'auto' iff a bank was
        handed to the facade, 'exact' never."""
        cb = self.cfg.codebook
        if cb == "bank":
            return True
        if cb == "auto":
            return self.bank is not None
        if cb == "exact":
            return False
        raise ValueError(
            f"codebook must be 'exact', 'bank' or 'auto', got {cb!r}")

    def _chunk_values(self, word_bits: int) -> int:
        return max(self.cfg.chunk_bytes // (word_bits // 8),
                   self.cfg.block_size)

    def _compress_eb_fused(self, x: np.ndarray,
                           predictor: str = "lorenzo",
                           coder=None, eb: Optional[float] = None,
                           amax: Optional[float] = None) -> CEAZCompressed:
        """Policy stays here; all per-value work runs device-resident.
        With a BankCoder the whole encode runs as ONE traced pass
        (quantize -> select -> encode -> pack, no host tree build).
        `eb` and `amax` are the absolute bound and max |x| when the
        caller already has them (see :meth:`_bound`); max |x| sizes the
        pass's literal candidates and never changes the bytes."""
        from ..runtime import fused
        coder = coder if coder is not None else self._coder()
        if eb is None or amax is None:
            with ot.span("ceaz.policy"):
                if eb is None:
                    eb, amax = self._bound(x)
                if amax is None:
                    amax = max(abs(float(np.max(x))), abs(float(np.min(x))))
        if isinstance(coder, BankCoder):
            return fused.compress_error_bounded_bank(
                x, eb, self.cfg.mode, coder,
                self._chunk_values(x.dtype.itemsize * 8),
                self.cfg.block_size, kernel_impl=self.cfg.kernel_impl,
                predictor=predictor, amax=amax)
        return fused.compress_error_bounded(
            x, eb, self.cfg.mode, coder,
            self._chunk_values(x.dtype.itemsize * 8), self.cfg.block_size,
            adaptive=self.cfg.adaptive, exact_build=self.cfg.exact_build,
            kernel_impl=self.cfg.kernel_impl, predictor=predictor,
            amax=amax)

    def _value_quantize(self, chunk: np.ndarray, eb: float):
        """Per-chunk value-direct quantization, backend-selected: the
        numpy backend keeps the float64/int64 host reference; jax and
        pallas use the device twin (f32 quantize + `dq_center` op) the
        fused pipeline batches — so staged backend='jax' and fused
        value-direct outputs are bit-identical by construction."""
        if self.cfg.backend == "numpy":
            return dq.np_value_quantize(chunk, eb)
        return dq.value_quantize(chunk, eb,
                                 kernel_impl=self.cfg.kernel_impl)

    def _compress_eb_direct(self, x: np.ndarray, word_bits: int,
                            coder=None) -> CEAZCompressed:
        """predictor='none': per-chunk value-direct quantization."""
        flat = x.reshape(-1)
        eb = self._abs_eb(x)
        coder = coder if coder is not None else self._coder()
        cv = max(self.cfg.chunk_bytes // (word_bits // 8),
                 self.cfg.block_size)
        chunks, lit_idx, lit_val = [], [], []
        for s in range(0, len(flat), cv):
            e = min(s + cv, len(flat))
            codes, outlier, delta, center = self._value_quantize(flat[s:e],
                                                                 eb)
            ch = self._encode_chunk(codes.reshape(-1), delta.reshape(-1),
                                    outlier.reshape(-1), eb, coder)
            ch.center = center
            rec = dq.np_value_dequantize(delta, center, eb, dtype=x.dtype)
            viol = np.flatnonzero(
                np.abs(rec.astype(np.float64)
                       - flat[s:e].astype(np.float64)) > eb)
            lit_idx.append(viol + s)
            lit_val.append(flat[s:e][viol])
            chunks.append(ch)
        return CEAZCompressed(
            shape=x.shape, dtype=str(x.dtype), ndim=1, mode=self.cfg.mode,
            chunks=chunks, word_bits=word_bits, predictor="none",
            literal_idx=np.concatenate(lit_idx).astype(np.int64),
            literal_val=np.concatenate(lit_val))

    def _compress_eb(self, x: np.ndarray, word_bits: int,
                     coder=None) -> CEAZCompressed:
        ndim = min(x.ndim, 3)
        work = x if x.ndim <= 3 else x.reshape((-1,) + x.shape[-2:])
        eb = self._abs_eb(x)
        codes, outlier, delta = self._dual_quantize(work, eb, ndim)
        codes_f = codes.reshape(-1)
        delta_f = delta.reshape(-1)
        outl_f = outlier.reshape(-1)
        coder = coder if coder is not None else self._coder()
        cv = max(self.cfg.chunk_bytes // (word_bits // 8), self.cfg.block_size)
        chunks = []
        for s in range(0, len(codes_f), cv):
            e = min(s + cv, len(codes_f))
            chunks.append(self._encode_chunk(codes_f[s:e], delta_f[s:e],
                                             outl_f[s:e], eb, coder))
        rec = dq.np_dequantize(delta, eb, ndim, dtype=x.dtype).reshape(-1)
        viol = np.flatnonzero(np.abs(rec.astype(np.float64)
                                     - x.reshape(-1).astype(np.float64)) > eb)
        return CEAZCompressed(shape=x.shape, dtype=str(x.dtype), ndim=ndim,
                              mode=self.cfg.mode, chunks=chunks,
                              word_bits=word_bits,
                              literal_idx=viol.astype(np.int64),
                              literal_val=x.reshape(-1)[viol].copy())

    def _compress_fixed_ratio(self, x: np.ndarray, word_bits: int,
                              use_fused: bool = False,
                              coder=None) -> CEAZCompressed:
        flat = x.reshape(-1)
        target_b = bitrate_from_ratio(self.cfg.target_ratio, word_bits)
        # seed eb via one-shot rate law on the first chunk sample
        from .ratecontrol import calibrate_eb_for_bitrate
        cv = max(self.cfg.chunk_bytes // (word_bits // 8), self.cfg.block_size)
        sample = flat[:min(len(flat), cv)]
        eb = calibrate_eb_for_bitrate(sample, target_b, 1)
        ctrl = FixedRatioController(target_bitrate=target_b, eb=eb)
        coder = coder if coder is not None else self._coder()
        if use_fused:
            from ..runtime import fused
            return fused.compress_fixed_ratio(
                x, ctrl, coder, cv, self.cfg.block_size,
                adaptive=self.cfg.adaptive,
                exact_build=self.cfg.exact_build,
                kernel_impl=self.cfg.kernel_impl,
                speculation=self.cfg.speculation)
        chunks, lit_idx, lit_val = [], [], []
        for s in range(0, len(flat), cv):
            e = min(s + cv, len(flat))
            codes, outlier, delta = self._dual_quantize(flat[s:e], ctrl.eb, 1)
            ch = self._encode_chunk(codes, delta, outlier, ctrl.eb, coder)
            rec = dq.np_dequantize(delta, ctrl.eb, 1, dtype=x.dtype)
            viol = np.flatnonzero(np.abs(rec.astype(np.float64)
                                         - flat[s:e].astype(np.float64))
                                  > ctrl.eb)
            lit_idx.append(viol + s)
            lit_val.append(flat[s:e][viol])
            chunks.append(ch)
            achieved = ch.total_bits() / ch.n_values
            ctrl.feedback(achieved)
        return CEAZCompressed(shape=x.shape, dtype=str(x.dtype), ndim=1,
                              mode="fixed_ratio", chunks=chunks,
                              word_bits=word_bits,
                              literal_idx=np.concatenate(lit_idx).astype(np.int64),
                              literal_val=np.concatenate(lit_val))

    # -- decode side -----------------------------------------------------------
    def decompress(self, c: CEAZCompressed) -> np.ndarray:
        """Decode one stream under this facade's policy.

        With ``cfg.use_fused``, streams of every dtype (f32/f64),
        predictor (lorenzo/value-direct) and mode run the
        device-resident fused decode (runtime/fused_decode.py —
        bit-identical to the staged reference). Returns the
        reconstruction in the stream's original shape and dtype.

        Raises:
          ValueError: the stream's per-chunk block counts are
            inconsistent with ``cfg.block_size`` (decoding with the
            wrong block grain would pass every checksum and return
            garbage, so the facade refuses loudly — pass the grain the
            stream was compressed with; ``.ceazs`` footers record it).
        """
        return self.decompress_batch([c])[0]

    def decompress_batch(self, comps) -> List[np.ndarray]:
        """Decode a sequence of streams under this facade's policy.

        Eligible streams (any mix of shapes, dtypes, predictors and
        modes) share ONE batched fused Huffman-decode pass; the rest —
        empty streams, ``use_fused`` off — transparently take the
        host-staged reference path, mirroring ``compress_batch``:
        callers never need their own eligibility split. Returns arrays
        in input order; raises the block-grain ``ValueError`` described
        on :meth:`decompress`.
        """
        comps = list(comps)
        out: List[Optional[np.ndarray]] = [None] * len(comps)
        with ot.span("ceaz.decompress_batch", n=len(comps)):
            if self.cfg.use_fused:
                from ..runtime import fused_decode as FD
                fused_idx = [i for i, c in enumerate(comps)
                             if FD.fused_decode_ok(c, self.offline)]
                dmk = self.cfg.decode_megakernel
                if dmk not in ("auto", "mega", "split"):
                    raise ValueError(
                        f"unknown decode_megakernel {dmk!r}; choose "
                        "from ('auto', 'mega', 'split')")
                if fused_idx:
                    for i in fused_idx:
                        self._check_block_size(comps[i])
                    dec = FD.decompress_batch(
                        [comps[i] for i in fused_idx],
                        self.cfg.block_size, self.offline,
                        kernel_impl=self.cfg.kernel_impl, bank=self.bank,
                        megakernel=dmk != "split")
                    for i, a in zip(fused_idx, dec):
                        out[i] = a
            res = [a if a is not None else self._decompress_staged(c)
                   for a, c in zip(out, comps)]
        for c, a in zip(comps, res):
            om.add(om.DECODED_CHUNKS, len(c.chunks))
            om.add(om.DECODED_BYTES, int(a.nbytes))
        return res

    def _check_block_size(self, c: CEAZCompressed):
        """Decode needs the encoder's block_size: the wire format carries
        per-block bit counts but not the block grain itself. A mismatch
        would pass every checksum (the stored bytes are intact) and decode
        to garbage — so refuse loudly when the per-chunk block counts are
        inconsistent with this facade's block_size."""
        bs = self.cfg.block_size
        for i, ch in enumerate(c.chunks):
            expect = max(1, -(-ch.n_values // bs))
            if len(ch.block_nbits) != expect:
                raise ValueError(
                    f"decode block_size={bs} inconsistent with stream: "
                    f"chunk {i} has {len(ch.block_nbits)} blocks for "
                    f"{ch.n_values} values (expected {expect}); pass the "
                    "block_size the stream was compressed with")

    def _decompress_staged(self, c: CEAZCompressed) -> np.ndarray:
        """Host-staged reference decoder (the bit-exactness oracle)."""
        from .huffman import replay_codebooks
        self._check_block_size(c)
        out_dtype = np.dtype(c.dtype)
        if not c.chunks:                     # empty stream: zero values
            return np.zeros(c.shape, dtype=out_dtype)
        # decode tables are memoized per distinct codebook, not per chunk
        books: List[Codebook] = replay_codebooks(c.chunks, self.offline,
                                                 bank=self.bank)

        if c.predictor == "none":
            parts = []
            for ch, cb in zip(c.chunks, books):
                codes = decode(ch.words, ch.block_nbits, ch.n_values,
                               self.cfg.block_size, cb)
                d = codes.astype(np.int64) - dq.RADIUS
                d[ch.outlier_idx] = ch.outlier_delta
                parts.append(dq.np_value_dequantize(d, ch.center, ch.eb,
                                                    dtype=out_dtype))
            rec = np.concatenate(parts)
            rec[c.literal_idx] = c.literal_val.astype(out_dtype)
            return rec.reshape(c.shape)

        if c.mode in ("abs", "rel"):
            codes_parts, delta_parts = [], []
            for ch, cb in zip(c.chunks, books):
                codes = decode(ch.words, ch.block_nbits, ch.n_values,
                               self.cfg.block_size, cb)
                d = codes.astype(np.int64) - dq.RADIUS
                d[ch.outlier_idx] = ch.outlier_delta
                delta_parts.append(d)
            delta = np.concatenate(delta_parts)
            work_shape = (c.shape if len(c.shape) <= 3
                          else (-1,) + c.shape[-2:])
            delta = delta.reshape(work_shape)
            rec = dq.np_dequantize(delta, c.chunks[0].eb, c.ndim,
                                   dtype=out_dtype).reshape(-1)
            rec[c.literal_idx] = c.literal_val.astype(out_dtype)
            return rec.reshape(c.shape)

        parts = []
        for ch, cb in zip(c.chunks, books):
            codes = decode(ch.words, ch.block_nbits, ch.n_values,
                           self.cfg.block_size, cb)
            d = codes.astype(np.int64) - dq.RADIUS
            d[ch.outlier_idx] = ch.outlier_delta
            parts.append(dq.np_dequantize(d, ch.eb, 1, dtype=out_dtype))
        rec = np.concatenate(parts)
        rec[c.literal_idx] = c.literal_val.astype(out_dtype)
        return rec.reshape(c.shape)


def compress(x, **kw) -> CEAZCompressed:
    return CEAZ(**kw).compress(x)


def decompress(c: CEAZCompressed, **kw) -> np.ndarray:
    return CEAZ(**kw).decompress(c)
