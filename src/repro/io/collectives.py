"""Compressed collectives: the paper's MPI_Gather scenario on a device mesh.

`compressed_all_gather` moves fixed-ratio CEAZ payloads over a mesh axis
instead of raw floats: quantize (stream dual-quant) -> pack b-bit codes ->
all_gather(packed) -> unpack -> reconstruct. Static shapes throughout
(fixed-ratio mode is what makes this jittable — same co-design argument as
the paper's constant-throughput FPGA requirement), and uniform payload
sizes mean the gather has no size-stragglers.

`gather_with_deadline` is the host-level straggler-mitigation wrapper used
by the I/O examples: ranks that miss the deadline are excluded from the
round and their shards backfilled from the previous round (bounded
staleness), which is the standard trick for jittery storage paths.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..optim.grad_compress import pack_jnp, unpack_jnp


@dataclasses.dataclass(frozen=True)
class WireFormat:
    bits: int = 8
    use_lorenzo: bool = True     # stream dual-quant before quantization


def _encode_local(x_flat, bits: int, use_lorenzo: bool):
    """-> (packed u32, scale f32). Static output shapes."""
    half = (1 << (bits - 1)) - 1
    if use_lorenzo:
        # prediction-residual stream: deltas are small on smooth payloads,
        # so the same b bits buy a tighter effective error bound
        shifted = jnp.concatenate([x_flat[:1] * 0, x_flat[:-1]])
        resid = x_flat - shifted
    else:
        resid = x_flat
    scale = jnp.max(jnp.abs(resid)) / half + 1e-30
    q = jnp.clip(jnp.rint(resid / scale), -half, half).astype(jnp.int32)
    return pack_jnp(q + half, bits), scale


def _decode_local(packed, scale, n: int, bits: int, use_lorenzo: bool):
    half = (1 << (bits - 1)) - 1
    q = unpack_jnp(packed, n, bits) - half
    resid = q.astype(jnp.float32) * scale
    if use_lorenzo:
        return jnp.cumsum(resid)
    return resid


def compressed_all_gather(x, mesh: Mesh, axis: str,
                          wire: WireFormat = WireFormat()):
    """x: (n_local, ...) per-rank shard (sharded over `axis`).

    Returns the gathered (n_ranks, n_local, ...) array, having moved only
    packed payloads + scales over the wire. Wire bytes = bits/32 of f32.
    """
    shape = x.shape

    def per_rank(x_loc):
        flat = x_loc.reshape(-1)
        packed, scale = _encode_local(flat, wire.bits, wire.use_lorenzo)
        all_packed = jax.lax.all_gather(packed, axis)
        all_scale = jax.lax.all_gather(scale, axis)
        dec = jax.vmap(lambda p, s: _decode_local(
            p, s, flat.shape[0], wire.bits, wire.use_lorenzo))(
            all_packed, all_scale)
        return dec.reshape((-1,) + x_loc.shape)

    spec = P(axis, *([None] * (len(shape) - 1)))
    return jax.shard_map(per_rank, mesh=mesh, in_specs=spec,
                         out_specs=P(None, axis),
                         axis_names={axis})(x)


def ceaz_gather(shards, eb_rel: float = 1e-4, plan=None,
                chunk_values: int = 1 << 20, block_size: int = 4096):
    """Host-level compressed gather: the paper's MPI_Gather scenario.

    Every rank's shard is compressed through the device-resident fused
    pipeline in ONE batched trace (mesh-sharded when `plan` carries a
    mesh), then only the packed payloads are 'gathered' (returned with
    wire-size stats). Ranks with unequal shard shapes (the usual
    smaller-last-rank case) fall back to per-rank fused passes.
    Returns (compressed_list, stats) where stats reports raw vs wire
    bytes — the paper's Fig 17 quantity.
    """
    from ..core import CEAZ, CEAZConfig
    shards = [np.asarray(s) for s in shards]
    comp = CEAZ(CEAZConfig(mode="rel", eb=eb_rel, use_fused=True,
                           chunk_bytes=4 * chunk_values,
                           block_size=block_size))
    # facade routes: homogeneous f32 -> one batched fused pass; ragged/
    # float64 -> transparent per-shard staged fallback
    comps = comp.compress_batch(shards, plan=plan)
    raw = sum(int(s.nbytes) for s in shards)
    wire = sum(c.nbytes() for c in comps)
    return comps, dict(raw_bytes=raw, wire_bytes=wire,
                       ratio=raw / max(wire, 1), n_ranks=len(comps))


def ceaz_gather_decode(comps, block_size: int = 4096):
    """Aggregator-side inverse of `ceaz_gather`: reconstruct every
    rank's shard from the gathered payloads.

    All ranks' chunks share ONE batched fused Huffman-decode device
    pass (`CEAZ.decompress_batch`); ragged/float64/value-direct payloads
    transparently take the staged host path inside the facade. Returns
    the list of reconstructed arrays in rank order.
    """
    from ..core import CEAZ, CEAZConfig
    comp = CEAZ(CEAZConfig(mode="rel", use_fused=True,
                           block_size=block_size))
    return comp.decompress_batch(comps)


def read_gather_stream(path: str, block_size: Optional[int] = None,
                       group: int = 4):
    """Read an aggregated gather stream back to per-rank arrays.

    The read mirror of `ceaz_gather_stream`: the engine's prefetch
    thread pulls+deserializes rank records while groups decode as one
    batched fused device pass each. By default the decode block grain
    comes from the stream's own footer meta (the writer records it);
    passing `block_size` explicitly takes precedence — for streams
    written before the meta existed, or to force a grain (a mismatch
    with the stream raises rather than decoding garbage). Returns
    (arrays, stats) where stats carries the read/decode overlap
    accounting.
    """
    from ..core import CEAZ, CEAZConfig
    from . import engine as E
    comp = (CEAZ(CEAZConfig(mode="rel", use_fused=True,
                            block_size=block_size))
            if block_size is not None else None)
    with E.AsyncDecodeReadEngine(path, comp, group=group) as eng:
        arrays = [obj for _, obj in eng]
    return arrays, eng.stats.as_dict()


def ceaz_gather_stream(shards, path: str, eb_rel: float = 1e-4,
                       plan=None, chunk_values: int = 1 << 20,
                       block_size: int = 4096, group: int = 2,
                       overlap: bool = True):
    """Streaming gather: rank shards land in one indexed stream file.

    The aggregator's view of MPI_Gather + write: as each group of rank
    shards finishes its fused device compression, its payloads are
    already committing to the aggregated stream while the next group
    compresses (two-phase aggregation with the phases overlapped).
    `shards` may also contain callables — a rank "arriving" is its
    fetcher being called, so slow ranks overlap the commits of earlier
    ones. Returns gather stats incl. wire bytes (the Fig 17 quantity).
    """
    from ..core import CEAZ, CEAZConfig
    from . import engine as E
    comp = CEAZ(CEAZConfig(mode="rel", eb=eb_rel, use_fused=True,
                           chunk_bytes=4 * chunk_values,
                           block_size=block_size))
    eng = E.AsyncCompressWriteEngine(
        path, E.ceaz_compress_fn(comp, plan),
        sync=not overlap, meta={"kind": "gather", "eb_rel": eb_rel},
        block_size=block_size)
    with eng:
        shards = list(shards)
        for s in range(0, len(shards), max(1, group)):
            grp = [np.asarray(sh() if callable(sh) else sh)
                   for sh in shards[s:s + max(1, group)]]
            eng.submit_batch(
                [f"rank_{s + j:04d}" for j in range(len(grp))], grp,
                [{"shape": list(a.shape), "dtype": str(a.dtype),
                  "raw_nbytes": int(a.nbytes)} for a in grp])
    d = eng.stats.as_dict()
    return dict(raw_bytes=d["raw_bytes"], wire_bytes=d["stored_bytes"],
                ratio=d["raw_bytes"] / max(d["stored_bytes"], 1),
                n_ranks=d["n_records"], wall_s=d["wall_s"],
                overlap_efficiency=d["overlap_efficiency"], path=path)


@dataclasses.dataclass
class DeadlineGather:
    """Host-side straggler-tolerant gather (bounded staleness)."""
    deadline_s: float
    last_good: Optional[List[np.ndarray]] = None
    stats: dict = dataclasses.field(
        default_factory=lambda: {"rounds": 0, "dropped": 0})

    def gather(self, fetchers: List[Callable[[], np.ndarray]]):
        """fetchers: one callable per rank returning its (possibly slow)
        shard. Ranks exceeding the per-round deadline are backfilled."""
        out: List[Optional[np.ndarray]] = []
        t0 = time.perf_counter()
        dropped = 0
        for i, fetch in enumerate(fetchers):
            remaining = self.deadline_s - (time.perf_counter() - t0)
            if remaining <= 0 and self.last_good is not None:
                out.append(self.last_good[i])
                dropped += 1
                continue
            out.append(fetch())
        if self.last_good is None:
            self.last_good = list(out)
        else:
            self.last_good = [o if o is not None else lg
                              for o, lg in zip(out, self.last_good)]
        self.stats["rounds"] += 1
        self.stats["dropped"] += dropped
        return out, dropped
