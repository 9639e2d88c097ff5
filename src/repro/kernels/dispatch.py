"""Backend dispatch for the CEAZ inner-loop kernels.

The fused pipeline has exactly two per-value hot loops — the encode-side
bit-pack (`hufenc`: a prefix-sum pack under 'jnp', a gather-pack under
'pallas') and the decode-side canonical-table walk (`hufdec`). Each has
interchangeable implementations with one calling convention and a
bit-exact output contract:

  * ``'jnp'``    — pure jax.numpy, XLA-compiled; what ``'auto'`` runs on
    every backend today (and the reference the Pallas sweeps compare
    against);
  * ``'pallas'`` — explicit Pallas kernels (kernels/hufenc gather-pack,
    kernels/hufdec table decode); compiled on TPU, interpreted
    everywhere else so CI exercises the kernel path on CPU. None of
    them compiles for the TPU yet (see the auto table at the bottom).

Callers never import an implementation directly — they resolve through
the registry:

    fn = dispatch.resolve("hufenc", cfg.kernel_impl)

keyed on ``(op, impl)`` with an ``(op, backend) -> impl`` auto table, so
a future TPU/GPU-specialized variant (a Mosaic-GPU decode, a fully
tiled TPU pack) is one ``register(...)`` call — no caller changes. The
facade knob is ``CEAZConfig(kernel_impl='auto'|'jnp'|'pallas')``.

Implementations are registered as zero-arg loaders and imported on first
resolve: importing this module (or the facade) never pulls in the Pallas
machinery until a pallas impl is actually selected.

Op calling conventions (all array args jax-compatible):

  hufenc(codes2, valid2, lengths_tbl, cwords_tbl, block_size, w32,
         cands) -> (words (C, w32) u32, block_nbits (C, nblocks) i32)
  hufdec(words2, nbits2, counts, sym_flat, len_flat, cb_idx,
         block_size) -> codes (C, NB*block_size) u16
  dq_center(q2, valid2) -> centers (C,) i32   (value-direct per-chunk
         centre reduction: count-aware median of each row's valid set;
         'pallas' is the radix-select VMEM kernel, 'jnp' the sort)
  ceaz_chunk(work2, prev2, valid2, ebs, bank_lengths, bank_cwords,
         block_size, w32, cands, predictor)
      -> (q2, codes2, outl2, delta2, centers, hists, sel, totals,
          words, block_nbits)
         The bank-mode encode megakernel: dual-quantize (Lorenzo from a
         1-value raw halo, or value-direct centring), 1024-bin
         histogram, exact-integer bank selection (argmin hist .
         lengths_k) and prefix-sum gather-pack as ONE program per chunk
         ('pallas'; word-tiled past the per-program VMEM limit), or the
         jnp twin composed from the stage ops ('jnp'). valid2 rows must
         be prefix masks. See kernels/megakernel/ref.py for the full
         contract.
  ceaz_chunk_dec(words2, nbits2, counts, sym_flat, len_flat, cb_idx,
         odelta2, base, seg0, islor, block_size)
      -> q (C, NB*block_size) i32
         The decode megakernel: canonical-Huffman table walk, rank-
         gather outlier patch (code 0 is the escape symbol; deltas are
         stored in ascending position order) and inverse dual-quant
         (segmented Lorenzo prefix sum OR value-direct centre add,
         selected per row by `islor`) as ONE program per chunk
         ('pallas'; word-tiled walk + shared jnp tail past the
         per-program VMEM limit), or the jnp twin composed from the
         hufdec walk + patch/inverse tail ('jnp'). Lorenzo segments
         (`seg0`) must be contiguous ascending row runs. See
         kernels/megakernel/ref.py for the full contract.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional, Tuple

import jax

from ..obs import metrics as om
from ..obs import trace as ot

KNOWN_IMPLS = ("auto", "jnp", "pallas")


def default_interpret() -> bool:
    """Whether a Pallas impl should run in interpreter mode on the
    current backend: compiled on TPU, interpreted everywhere else (the
    kernels are written against TPU tiling; CPU CI exercises them
    through the interpreter). Shared by every */ops.py wrapper so the
    policy cannot drift between ops."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel's `interpret` argument: as given, or the backend policy
    of `default_interpret` when None (every kernel's default, so no
    kernel runs interpreted on a TPU unless a caller asks for it)."""
    return default_interpret() if interpret is None else bool(interpret)

_LOADERS: Dict[Tuple[str, str], Callable[[], Callable]] = {}
_RESOLVED: Dict[Tuple[str, str], Callable] = {}
_AUTO: Dict[Tuple[str, str], str] = {}


def register(op: str, impl: str, loader: Callable[[], Callable],
             *, auto_for: Tuple[str, ...] = ()) -> None:
    """Register `loader` (zero-arg, returns the impl fn) under
    ``(op, impl)``; `auto_for` lists backends for which ``'auto'``
    resolves to this impl."""
    _LOADERS[(op, impl)] = loader
    _RESOLVED.pop((op, impl), None)
    for backend in auto_for:
        _AUTO[(op, backend)] = impl


def available(op: str) -> Tuple[str, ...]:
    """Registered implementation names for `op` (excluding 'auto')."""
    return tuple(sorted(i for (o, i) in _LOADERS if o == op))


def auto_impl(op: str, backend: str | None = None) -> str:
    """The impl name ``'auto'`` resolves to for `op` on `backend`
    (default: the current ``jax.default_backend()``)."""
    if backend is None:
        backend = jax.default_backend()
    return _AUTO.get((op, backend), "jnp")


def resolve(op: str, impl: str = "auto",
            backend: str | None = None) -> Callable:
    """The implementation of `op` selected by `impl`.

    ``'auto'`` picks per backend (see ``auto_impl``); anything not
    registered raises ValueError naming the valid choices — a typo'd
    ``kernel_impl`` fails loudly instead of silently falling back.
    """
    if impl == "auto":
        impl = auto_impl(op, backend)
    key = (op, impl)
    fn = _RESOLVED.get(key)
    if fn is not None:
        return fn
    loader = _LOADERS.get(key)
    if loader is None:
        ops = sorted({o for (o, _) in _LOADERS})
        if op not in ops:
            raise ValueError(
                f"unknown kernel op {op!r}; registered ops: {ops}")
        raise ValueError(
            f"unknown kernel_impl {impl!r} for op {op!r}; choose from "
            f"{('auto',) + available(op)}")
    fn = _RESOLVED[key] = loader()
    return fn


def resolve_name(op: str, impl: str = "auto",
                 backend: str | None = None) -> str:
    """The concrete impl name `impl` resolves to for `op` — 'auto'
    goes through the per-backend table, anything else passes through
    unchanged (no loader is imported)."""
    return auto_impl(op, backend) if impl == "auto" else impl


# -- observability -----------------------------------------------------------
# The resolved fns execute INSIDE jit traces, so they run at trace time
# only — per-invocation accounting has to happen at the host-level pass
# call sites (runtime/fused.py, runtime/fused_decode.py). Those sites
# wrap each pass in `measure(op, impl)`, which bumps the per-(op, impl)
# ceaz_kernel_calls_total counter and opens a `kernel.<op>` span around
# the pass's (asynchronous) enqueue. Device time comes from a
# jax.profiler trace, which needs no sync.

@contextlib.contextmanager
def measure(op: str, impl: str = "auto", backend: str | None = None):
    """Account one host-level device-pass invocation of `op`: the
    per-(op, impl) call counter and a `kernel.<op>` trace span over the
    enqueue of the pass."""
    impl = resolve_name(op, impl, backend)
    om.add(om.KERNEL_CALLS, op=op, impl=impl)
    with ot.span("kernel." + op, impl=impl):
        yield


# -- default implementations -------------------------------------------------

def _hufenc_jnp() -> Callable:
    from .hufenc import ref
    return ref.encode_pack


def _hufenc_pallas() -> Callable:
    from .hufenc import ops
    return ops.encode_pack


def _hufdec_jnp() -> Callable:
    from .hufdec import ref
    return ref.decode_blocks


def _hufdec_pallas() -> Callable:
    from .hufdec import ops
    return ops.decode_blocks


def _dq_center_jnp() -> Callable:
    from .dualquant import ops
    return ops.chunk_center


def _dq_center_pallas() -> Callable:
    from .dualquant import ops
    return ops.dq_center


def _ceaz_chunk_jnp() -> Callable:
    from .megakernel import ref
    return ref.ceaz_chunk


def _ceaz_chunk_pallas() -> Callable:
    from .megakernel import ops
    return ops.ceaz_chunk


def _ceaz_chunk_dec_jnp() -> Callable:
    from .megakernel import ref
    return ref.ceaz_chunk_dec


def _ceaz_chunk_dec_pallas() -> Callable:
    from .megakernel import ops
    return ops.ceaz_chunk_dec


# auto policy: the XLA-compiled jnp twin on every backend. On CPU and GPU
# a Pallas kernel would run interpreted. On TPU no Pallas kernel
# compiles yet. Compiled for a v5e with interpret=False at the main
# path's shapes (one chunk row of 2^17 and of 2^23 values, block_size
# 4096, a 12-book bank), Mosaic refuses each one:
#   hufenc          "The Pallas TPU lowering currently requires that the
#                   last two dimensions of your block shape are
#                   divisible by 8 and 128 respectively, or be equal to
#                   the respective dimensions of the overall array"
#                   (the (1, 16) block-sum blocks of gather_pack_tiled)
#   hufdec          the same rule, for the (1, 2^16) decode-table rows
#   dq_center       "Unimplemented primitive in Pallas TPU lowering for
#                   KernelType.TC: cumsum" (rows past 2^20 values take
#                   the jnp sort inside the Pallas wrapper)
#   ceaz_chunk      2^17: "NotImplementedError: Only float32 is
#                   supported"; 2^23: the block-shape rule, for the
#                   (1, Element(32769)) window of lorenzo_tiles
#   ceaz_chunk_dec  the block-shape rule: the (1, 2^16) table rows at
#                   2^17, the (1, Element(16387)) word window of
#                   hufdec_tiles at 2^23
# A kernel returns to auto_for=("tpu",) once it compiles for the chip
# and is bit-identical to its jnp twin there; tests/test_tpu_compile.py
# then compiles it with interpret=False. GPU-specialized variants
# (Mosaic-GPU / Triton) slot in as register("hufdec", "pallas_gpu", ...,
# auto_for=("gpu",)).
register("hufenc", "jnp", _hufenc_jnp, auto_for=("cpu", "gpu", "tpu"))
register("hufenc", "pallas", _hufenc_pallas)
register("hufdec", "jnp", _hufdec_jnp, auto_for=("cpu", "gpu", "tpu"))
register("hufdec", "pallas", _hufdec_pallas)
register("dq_center", "jnp", _dq_center_jnp, auto_for=("cpu", "gpu", "tpu"))
register("dq_center", "pallas", _dq_center_pallas)
register("ceaz_chunk", "jnp", _ceaz_chunk_jnp, auto_for=("cpu", "gpu", "tpu"))
register("ceaz_chunk", "pallas", _ceaz_chunk_pallas)
register("ceaz_chunk_dec", "jnp", _ceaz_chunk_dec_jnp,
         auto_for=("cpu", "gpu", "tpu"))
register("ceaz_chunk_dec", "pallas", _ceaz_chunk_dec_pallas)
